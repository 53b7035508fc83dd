"""Mixture-of-Experts FFN: top-k routing, capacity-bounded sort-based
dispatch, expert parallelism over the ``model`` axis via explicit
all-to-all inside shard_map; and the serving step's dropless layer over
the experts one chip holds (:func:`moe_serve`).

TPU adaptation: GShard's one-hot dispatch einsum is O(N·D·E·C) — infeasible
at 128 experts — so we use the sort/scatter formulation (tokens sorted by
expert id, capacity-clipped, scatter-add into [E, cap, D] slots).  The two
``all_to_all`` collectives over the model axis are exactly the transport the
HLO inspector must see for an EP workload; a silent fallback to all-gather
here is the TPU analogue of the paper's "container fell back to TCP".
"""
from __future__ import annotations

import contextlib
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental.xla_metadata import set_xla_metadata

from repro.configs.base import ModelConfig
from repro.models import params as P
from repro.models.layers import swiglu, swiglu_specs
from repro.parallel.ctx import _current

#: rows of one expert matmul in the serving layer's grouped loop
#: (:func:`moe_serve`): a held expert's weights are read once for each
#: ``TILE`` rows routed to it
TILE = 128


def moe_specs(cfg: ModelConfig, layers: int | None) -> dict:
    """The router over all ``n_experts`` and the ``held_experts`` this
    chip holds; a sigmoid router adds its per-expert selection bias, and
    ``n_shared_experts`` one gated MLP of their summed width."""
    d, e, f = cfg.d_model, cfg.n_experts, cfg.d_ff
    eh = cfg.held_experts
    lyr = (layers,) if layers is not None else ()
    lax_ = ("layers",) if layers is not None else ()
    specs = {
        "router": P.ParamSpec(lyr + (d, e), lax_ + ("embed", None), jnp.float32),
        "gate": P.ParamSpec(lyr + (eh, d, f), lax_ + ("experts", "embed", "mlp")),
        "up": P.ParamSpec(lyr + (eh, d, f), lax_ + ("experts", "embed", "mlp")),
        "down": P.ParamSpec(lyr + (eh, f, d), lax_ + ("experts", "mlp", "embed")),
    }
    if cfg.router == "sigmoid":
        specs["bias"] = P.ParamSpec(lyr + (e,), lax_ + (None,), jnp.float32,
                                    init="zeros")
    if cfg.n_shared_experts:
        specs["shared"] = swiglu_specs(cfg, layers,
                                       cfg.n_shared_experts * f)
    return specs


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


def route(cfg: ModelConfig, p: dict, x: jax.Array
          ) -> tuple[jax.Array, jax.Array]:
    """Each row's ``top_k`` experts (global ids) and their weights, over
    rows x [N, d]: the router scores every one of ``n_experts`` in
    float32.  ``softmax``: the largest probabilities, renormalised.
    ``sigmoid``: chosen by score plus ``p["bias"]`` (selection only),
    weighted by score, renormalised when ``route_norm``; both times
    ``route_scale``."""
    logits = x.astype(jnp.float32) @ p["router"]
    if cfg.router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, top_i = jax.lax.top_k(scores + p["bias"], cfg.top_k)
        top_w = jnp.take_along_axis(scores, top_i, axis=1)
        if cfg.route_norm:
            top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    else:
        top_w, top_i = jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                                     cfg.top_k)
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    return top_i, top_w * cfg.route_scale


@contextlib.contextmanager
def scope(name: str):
    """A named scope that the device trace also shows: the operations'
    op_name carries ``name`` (HLO dumps), and their HLO instruction, the
    name a profiler gives a device operation, carries it as a frontend
    attribute."""
    with jax.named_scope(name), set_xla_metadata(scope=name):
        yield


def moe_serve(cfg: ModelConfig, p: dict, x: jax.Array, valid: jax.Array,
              stack: dict | None = None, layer: jax.Array | None = None
              ) -> tuple[jax.Array, jax.Array]:
    """The serving step's expert layer over the experts this chip holds:
    dropless, and a row's output depends on that row alone.

    x [B, C, d]; valid [B, C] marks the rows a lane feeds (the step's
    padding rows are routed nowhere).  Every (row, chosen expert) pair
    whose expert is held (``expert_offset`` .. + ``held_experts``) is
    computed, however many pick one expert: the pairs are sorted by held
    expert, cut into tiles of ``TILE`` rows of one expert each, and one
    loop runs the tiles in ascending expert order, its trip count the
    tiles the step needs (no capacity, nothing dropped, and an expert no
    row picks costs no read).  The weighted outputs add into the rows in
    that order, then the shared experts' output is added.  What the
    experts held elsewhere would add is not.

    ``stack`` with ``layer``: the experts' ``gate``/``up``/``down`` of
    every layer, ``[layers, held, ...]``, indexed at ``layer`` inside
    the loop (the layer scan passes them whole, so that no layer's
    experts are copied out before the loop reads them); else ``p``'s.

    Returns (y [B, C, d], [rows routed to held experts, the most that
    one held expert took]) int32.
    """
    b, c, d = x.shape
    n, k, eh = b * c, cfg.top_k, cfg.held_experts
    xf = x.reshape(n, d)

    def weight(name, e):
        return p[name][e] if stack is None else stack[name][layer, e]

    with scope("moe_route"):
        top_i, top_w = route(cfg, p, xf)
        local = top_i - cfg.expert_offset
        held = (local >= 0) & (local < eh) & valid.reshape(n, 1)
        # held pairs first, grouped by expert; the rest after them
        key = jnp.where(held, local, eh).reshape(-1)
        order = jnp.argsort(key, stable=True)
        counts = jnp.sum(key[None, :] == jnp.arange(eh)[:, None], axis=1,
                         dtype=jnp.int32)
        starts = jnp.cumsum(counts) - counts
        tiles = (counts + TILE - 1) // TILE
        first_tile = jnp.cumsum(tiles) - tiles
        # padded by a tile, so the last tile's slice stays in range
        row_of = jnp.pad(order // k, (0, TILE))
        w_of = jnp.pad(top_w.reshape(-1)[order], (0, TILE))

    def tile(t, acc):
        # the last expert whose tiles start at or before t (one with no
        # tiles starts where the next does)
        e = jnp.sum(first_tile <= t) - 1
        at = starts[e] + (t - first_tile[e]) * TILE
        rows = jax.lax.dynamic_slice(row_of, (at,), (TILE,))
        # rows past the expert's own are the next expert's: they add
        # zero here and are computed again under their own
        w = jnp.where(at + jnp.arange(TILE) < starts[e] + counts[e],
                      jax.lax.dynamic_slice(w_of, (at,), (TILE,)), 0.0)
        xt = xf[rows]
        h = jax.nn.silu(xt @ weight("gate", e)) * (xt @ weight("up", e))
        yt = jnp.matmul(h, weight("down", e),
                        preferred_element_type=jnp.float32)
        return acc.at[rows].add(w[:, None] * yt)

    with scope("moe_experts"):
        acc = jax.lax.fori_loop(0, jnp.sum(tiles), tile,
                                jnp.zeros((n, d), jnp.float32))
    if cfg.n_shared_experts:
        with scope("moe_shared"):
            acc = acc + swiglu(p["shared"], x).reshape(n, d).astype(
                jnp.float32)
    stats = jnp.stack([jnp.sum(counts), jnp.max(counts)])
    return acc.astype(x.dtype).reshape(b, c, d), stats
