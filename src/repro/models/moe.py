"""Mixture-of-Experts FFN: top-k routing, capacity-bounded sort-based
dispatch, expert parallelism over the ``model`` axis via explicit
all-to-all inside shard_map.

TPU adaptation: GShard's one-hot dispatch einsum is O(N·D·E·C) — infeasible
at 128 experts — so we use the sort/scatter formulation (tokens sorted by
expert id, capacity-clipped, scatter-add into [E, cap, D] slots).  The two
``all_to_all`` collectives over the model axis are exactly the transport the
HLO inspector must see for an EP workload; a silent fallback to all-gather
here is the TPU analogue of the paper's "container fell back to TCP".
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import params as P
from repro.parallel.ctx import _current


def moe_specs(cfg: ModelConfig, layers: int | None) -> dict:
    d, e, f = cfg.d_model, cfg.n_experts, cfg.d_ff
    lyr = (layers,) if layers is not None else ()
    lax_ = ("layers",) if layers is not None else ()
    return {
        "router": P.ParamSpec(lyr + (d, e), lax_ + ("embed", None), jnp.float32),
        "gate": P.ParamSpec(lyr + (e, d, f), lax_ + ("experts", "embed", "mlp")),
        "up": P.ParamSpec(lyr + (e, d, f), lax_ + ("experts", "embed", "mlp")),
        "down": P.ParamSpec(lyr + (e, f, d), lax_ + ("experts", "mlp", "embed")),
    }


def _capacity(n_tokens: int, cfg: ModelConfig) -> int:
    cap = int(n_tokens * cfg.top_k * cfg.capacity_factor / cfg.n_experts)
    return max(cap, 1)


def _moe_local(cfg: ModelConfig, p: dict, x: jax.Array, a2a_axis: str | None,
               tp: int) -> tuple[jax.Array, jax.Array]:
    """Per-shard MoE body.  x: [b_loc, s, d].  Returns (y, aux[b_loc, s])."""
    e, k = cfg.n_experts, cfg.top_k
    b, s, d = x.shape
    n = b * s
    xf = x.reshape(n, d)

    logits = (xf.astype(jnp.float32) @ p["router"])  # [n, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)            # [n, k]
    top_w = (top_p / jnp.sum(top_p, axis=-1, keepdims=True)).astype(x.dtype)

    # Load-balancing aux (Switch): E * sum_e f_e * p_e, per shard.
    assign = jnp.zeros((n, e), jnp.float32).at[
        jnp.arange(n)[:, None], top_i].add(1.0)
    f_e = jnp.mean(assign, axis=0) / k
    p_e = jnp.mean(probs, axis=0)
    aux_val = e * jnp.sum(f_e * p_e)
    aux = jnp.full((b, s), aux_val, jnp.float32)

    # Sort-based capacity dispatch.
    cap = _capacity(n, cfg)
    flat_e = top_i.reshape(-1)                        # [n*k]
    order = jnp.argsort(flat_e)
    sorted_e = flat_e[order]
    first = jnp.searchsorted(sorted_e, sorted_e, side="left")
    pos_in_e = jnp.arange(n * k) - first
    keep = pos_in_e < cap
    dest = jnp.where(keep, sorted_e * cap + pos_in_e, e * cap)
    token_of = order // k
    xs = xf[token_of] * keep[:, None].astype(xf.dtype)
    disp = jnp.zeros((e * cap + 1, d), x.dtype).at[dest].add(xs)
    disp = disp[:-1].reshape(e, cap, d)

    if a2a_axis is not None and tp > 1:
        disp = jax.lax.all_to_all(disp, a2a_axis, split_axis=0, concat_axis=1,
                                  tiled=True)        # [E/tp, cap*tp, d]

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", disp, p["gate"]))
    h = h * jnp.einsum("ecd,edf->ecf", disp, p["up"])
    y_e = jnp.einsum("ecf,efd->ecd", h, p["down"])    # [E_loc, cap*tp, d]

    if a2a_axis is not None and tp > 1:
        y_e = jax.lax.all_to_all(y_e, a2a_axis, split_axis=1, concat_axis=0,
                                 tiled=True)          # [E, cap, d]

    slots = jnp.concatenate(
        [y_e.reshape(e * cap, d), jnp.zeros((1, d), x.dtype)], axis=0)
    gathered = slots[dest] * top_w.reshape(-1)[order][:, None]
    y = jnp.zeros((n, d), x.dtype).at[token_of].add(gathered)
    return y.reshape(b, s, d), aux


def moe_ffn(cfg: ModelConfig, p: dict, x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE FFN.  x: [B,S,D] -> (y [B,S,D], aux [B,S])."""
    ctx = _current()
    tp = ctx.axis_sizes.get("model", 1) if ctx else 1
    if ctx is None or tp == 1:
        return _moe_local(cfg, p, x, None, 1)

    mesh = ctx.mesh
    # tokens arrive residual-sharded (batch × seq-SP over model); dispatch is
    # local per shard, the two all_to_alls over `model` carry tokens to their
    # expert owners — MoE sequence-parallel dispatch, no all-gather needed.
    x_spec = ctx.resolve(("act_batch", "act_res", None), x.shape)
    w_e = jax.sharding.PartitionSpec("model", None, None)
    p_specs = {
        "router": jax.sharding.PartitionSpec(None, None),
        "gate": w_e, "up": w_e, "down": w_e,
    }
    aux_spec = ctx.resolve(("act_batch", "act_res"), (x.shape[0], x.shape[1]))

    body = partial(_moe_local, cfg, a2a_axis="model", tp=tp)

    def wrapped(p_loc, x_loc):
        return body(p_loc, x_loc)

    return jax.shard_map(
        wrapped, mesh=mesh,
        in_specs=(p_specs, x_spec),
        out_specs=(x_spec, aux_spec), check_vma=False,
    )(p, x)
