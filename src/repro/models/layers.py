"""Shared layer primitives: norms, RoPE, MLPs, embeddings, loss.

All functions are pure; parameters arrive as pytrees declared by the
``*_specs`` constructors so the same declaration drives abstract lowering,
real initialization and partitioning (models/params.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import params as P
from repro.parallel.ctx import constrain


def ceil_mult(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# ---------------------------------------------------------------- norms


def rmsnorm_spec(d: int, layers: int | None = None) -> P.ParamSpec:
    return P.scale(d, layers)


def rmsnorm(w: jax.Array, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    h = x.astype(jnp.float32)
    var = jnp.mean(h * h, axis=-1, keepdims=True)
    return (h * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


# ---------------------------------------------------------------- rope


def rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding.  x: [..., seq, heads, head_dim], pos: [..., seq]."""
    if theta <= 0:
        return x
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = pos[..., :, None].astype(jnp.float32) * freqs  # [..., seq, half]
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)
    return out


def sinusoidal_positions(seq: int, d: int, offset: jax.Array | int = 0) -> jax.Array:
    """Whisper-style fixed sinusoidal embeddings (stub for learned tables)."""
    pos = jnp.arange(seq)[:, None] + offset
    dim = jnp.arange(d // 2)[None, :]
    inv = jnp.exp(-jnp.log(10000.0) * dim / max(d // 2 - 1, 1))
    ang = pos * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1).astype(jnp.bfloat16)


# ---------------------------------------------------------------- MLP


def swiglu_specs(cfg: ModelConfig, layers: int | None, d_ff: int | None = None) -> dict:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    return {
        "gate": P.dense(d, f, "embed", "mlp", layers),
        "up": P.dense(d, f, "embed", "mlp", layers),
        "down": P.dense(f, d, "mlp", "embed", layers),
    }


def swiglu(p: dict, x: jax.Array) -> jax.Array:
    g, u = sp_col_projects(x, (p["gate"], p["up"]), ("act_mlp", "act_mlp"))
    h = jax.nn.silu(g) * u
    h = constrain(h, ("act_batch", "act_seq", "act_mlp"))
    return rs_project(h, p["down"], "act_mlp")


def gelu_mlp_specs(cfg: ModelConfig, layers: int | None) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    return {
        "up": P.dense(d, f, "embed", "mlp", layers),
        "down": P.dense(f, d, "mlp", "embed", layers),
    }


def gelu_mlp(p: dict, x: jax.Array) -> jax.Array:
    (u,) = sp_col_projects(x, (p["up"],), ("act_mlp",))
    h = jax.nn.gelu(u)
    h = constrain(h, ("act_batch", "act_seq", "act_mlp"))
    return rs_project(h, p["down"], "act_mlp")


# ---------------------------------------------------------------- embed / head


def embed_specs(cfg: ModelConfig) -> dict:
    v, d = cfg.padded_vocab, cfg.d_model
    specs = {"tok": P.ParamSpec((v, d), ("vocab", "embed"), init="embed")}
    if not cfg.tie_embeddings:
        specs["head"] = P.dense(d, v, "embed", "vocab")
    return specs


def embed_tokens(p: dict, tokens: jax.Array) -> jax.Array:
    x = jnp.take(p["tok"], tokens, axis=0)
    return constrain(x, ("act_batch", "act_seq", None))


def logits_from(p: dict, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    w = p["tok"].T if cfg.tie_embeddings else p["head"]
    logits = (x @ w).astype(jnp.float32)
    return constrain(logits, ("act_batch", "act_seq", "act_vocab"))


# ---------------------------------------------------------------- loss


def cross_entropy(logits: jax.Array, labels: jax.Array, vocab_size: int,
                  z_loss: float = 0.0) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Mean next-token CE over all positions; padded vocab ids masked."""
    v_pad = logits.shape[-1]
    if v_pad > vocab_size:
        mask = jnp.arange(v_pad) < vocab_size
        logits = jnp.where(mask, logits, -1e9)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    loss = jnp.mean(nll)
    aux = {"nll": loss}
    if z_loss:
        zl = z_loss * jnp.mean(lse**2)
        aux["z_loss"] = zl
        loss = loss + zl
    return loss, aux


# ---------------------------------------------------------------- GQA geometry


@dataclasses.dataclass(frozen=True)
class HeadGeom:
    """Padding geometry making GQA shardable over a ``tp``-wide model axis.

    train/prefill compute: kv replicated over tp; q padded on the group dim
      to ``g_pad`` so that ``kv·g_pad % tp == 0``  (H_run = kv·g_pad).
    decode cache: kv zero-padded to ``kv_pad = ceil_mult(kv, tp)`` so the
      cache head dim itself shards     (H_dec = kv_pad·g).
    """

    n_heads: int
    n_kv: int
    head_dim: int
    tp: int

    @property
    def group(self) -> int:
        return self.n_heads // self.n_kv

    @property
    def g_pad(self) -> int:
        g = self.group
        while (self.n_kv * g) % self.tp:
            g += 1
        return g

    @property
    def h_run(self) -> int:
        return self.n_kv * self.g_pad

    @property
    def kv_pad(self) -> int:
        return ceil_mult(self.n_kv, self.tp)

    @property
    def h_dec(self) -> int:
        return self.kv_pad * self.group


def head_geom(cfg: ModelConfig, tp: int) -> HeadGeom:
    return HeadGeom(cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim, tp)


def pad_group_dim(w: jax.Array, geom: HeadGeom, axis_is_out: bool) -> jax.Array:
    """Zero-pad a [*, H·hd] (or [H·hd, *]) projection to the padded run
    layout [*, kv·g_pad·hd] keeping q heads grouped by their kv head."""
    if geom.g_pad == geom.group:
        return w
    hd, kv, g, gp = geom.head_dim, geom.n_kv, geom.group, geom.g_pad
    if axis_is_out:
        d = w.shape[0]
        w4 = w.reshape(d, kv, g, hd)
        w4 = jnp.pad(w4, ((0, 0), (0, 0), (0, gp - g), (0, 0)))
        return w4.reshape(d, kv * gp * hd)
    d = w.shape[1]
    w4 = w.reshape(kv, g, hd, d)
    w4 = jnp.pad(w4, ((0, 0), (0, gp - g), (0, 0), (0, 0)))
    return w4.reshape(kv * gp * hd, d)


# ------------------------------------------------ explicit SP transitions
#
# Megatron sequence parallelism needs exactly two collectives per
# block half: all-gather(seq) at entry, reduce-scatter(seq) after the
# row-parallel projection.  GSPMD (without the GPU pipeline's
# ReduceScatterCreator pass) instead emits fp32 full-activation
# all-reduces — measured 4–8x the wire bytes on the train cells
# (EXPERIMENTS.md §Perf).  These helpers make the transitions explicit
# and bf16 via shard_map; they are no-ops whenever the residual stream
# is not sequence-sharded (single device, serve rules, indivisible dims).


@jax.custom_vjp
def bf16_tangent(x):
    return x


def _bf16_tangent_fwd(x):
    return x, None


def _bf16_tangent_bwd(_, g):
    return (g.astype(jnp.bfloat16),)


bf16_tangent.defvjp(_bf16_tangent_fwd, _bf16_tangent_bwd)


def _sp_ctx(x_shape):
    from repro.parallel.ctx import _current

    ctx = _current()
    if ctx is None:
        return None
    tp = ctx.axis_sizes.get("model", 1)
    if tp <= 1 or ctx.rules.get("act_res") != "model":
        return None
    spec = ctx.resolve(("act_batch", "act_res", None), x_shape)
    if spec[1] != "model":
        return None
    return ctx, tp, spec


def sp_gather_seq(x: jax.Array) -> jax.Array:
    """[B, S(seq-sharded over model), D] -> [B, S, D] replicated over
    model (bf16 all-gather; transpose = reduce-scatter)."""
    c = _sp_ctx(x.shape)
    if c is None:
        return x
    ctx, tp, spec = c
    out_spec = jax.sharding.PartitionSpec(spec[0], None, None)

    def body(xl):
        return jax.lax.optimization_barrier(
            jax.lax.all_gather(xl, "model", axis=1, tiled=True))

    return bf16_tangent(jax.shard_map(
        body, mesh=ctx.mesh, in_specs=(spec,), out_specs=out_spec,
        check_vma=False)(x))


def sp_col_projects(x: jax.Array, ws: tuple, features: tuple):
    """Fused SP-entry + column-parallel projections.

    x [B, S(seq-sharded), D]; each w [D, F_i] column-sharded over model when
    features[i] names a sharded logical axis (None -> replicated output).
    One all-gather serves every projection, and — the point — the backward
    pass emits ONE bf16 psum_scatter for the summed dx instead of GSPMD's
    fp32 all-reduce tuple (measured 1.0 TB of the deepseek-coder train
    cell's 1.7 TB all-reduce traffic)."""
    c = _sp_ctx((x.shape[0], x.shape[1], x.shape[2]))
    if c is None:
        outs = []
        for w, f in zip(ws, features):
            h = x @ w
            if f:
                h = constrain(h, ("act_batch", "act_seq", f))
            outs.append(h)
        return tuple(outs)
    ctx, tp, res_spec = c
    PS = jax.sharding.PartitionSpec
    w_specs = tuple(PS(None, "model" if f else None) for f in features)
    out_specs = tuple(
        ctx.resolve(("act_batch", None, f), (x.shape[0], x.shape[1], w.shape[1]))
        for w, f in zip(ws, features))
    def body(xl, *wl):
        xf = jax.lax.all_gather(xl, "model", axis=1, tiled=True)
        # barrier: stops XLA:CPU's bf16->f32 dot-operand promotion from
        # hoisting the convert above the gather (which would double the
        # wire bytes; TPU has native bf16 dots and no such promotion)
        xf = jax.lax.optimization_barrier(xf)
        return tuple(xf @ w for w in wl)

    outs = jax.shard_map(body, mesh=ctx.mesh,
                         in_specs=(res_spec,) + w_specs,
                         out_specs=out_specs, check_vma=False)(x, *ws)
    return tuple(bf16_tangent(o) for o in outs)


def rs_project(h: jax.Array, w: jax.Array, feature: str) -> jax.Array:
    """Row-parallel projection with fused reduce-scatter: h [B, S, F]
    (F sharded over model as `feature`), w [F, D] -> [B, S(seq-sharded), D].
    psum_scatter replaces GSPMD's all-reduce(+later slice): half the wire
    bytes before even counting the fp32->bf16 saving."""
    c = _sp_ctx((h.shape[0], h.shape[1], w.shape[-1]))
    if c is None:
        from repro.parallel.ctx import constrain as _cons

        return _cons(h @ w, ("act_batch", "act_res", None))
    ctx, tp, out_spec = c
    h_spec = ctx.resolve(("act_batch", None, feature), h.shape)
    if h_spec[2] != "model" or h.shape[1] % tp:
        from repro.parallel.ctx import constrain as _cons

        return _cons(h @ w, ("act_batch", "act_res", None))
    w_spec = jax.sharding.PartitionSpec("model", None)

    def body(hl, wl):
        part = jax.lax.optimization_barrier(hl @ wl)
        return jax.lax.psum_scatter(part.astype(hl.dtype), "model",
                                    scatter_dimension=1, tiled=True)

    return bf16_tangent(jax.shard_map(
        body, mesh=ctx.mesh, in_specs=(h_spec, w_spec),
        out_specs=out_spec, check_vma=False)(h, w))
