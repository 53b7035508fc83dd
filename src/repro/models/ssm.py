"""Mamba2 (SSD — state-space duality, arXiv:2405.21060) block.

Chunked SSD: within a chunk the token-mixing is a masked quadratic form
(MXU-friendly); across chunks a linear state recurrence carries
[B, H, P, N] states via lax.scan.  All einsums are local per head shard —
the only collectives an SSM layer should emit are FSDP weight gathers,
which is exactly what the HLO inspector asserts for the ssm family.

Weight layout note: Mamba2 fuses z/xBC/dt into one in_proj; we keep three
projections with identical total parameter count so each output dim shards
cleanly over the model axis.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import params as P
from repro.models.layers import rmsnorm
from repro.parallel.ctx import constrain


def conv_channels(cfg: ModelConfig) -> int:
    return cfg.d_inner + 2 * cfg.ssm_groups * cfg.ssm_state


def ssm_specs(cfg: ModelConfig, layers: int | None) -> dict:
    d, di, h = cfg.d_model, cfg.d_inner, cfg.n_ssm_heads
    cc = conv_channels(cfg)
    lyr = (layers,) if layers is not None else ()
    lax_ = ("layers",) if layers is not None else ()

    def spec(shape, axes, **kw):
        return P.ParamSpec(lyr + shape, lax_ + axes, **kw)

    return {
        "wz": P.dense(d, di, "embed", "ssm_inner", layers),
        "wxbc": P.dense(d, cc, "embed", "ssm_inner", layers),
        "wdt": P.dense(d, h, "embed", "ssm_heads", layers),
        "conv_w": spec((cfg.conv_width, cc), (None, "ssm_inner")),
        "conv_b": spec((cc,), ("ssm_inner",), init="zeros"),
        "a_log": spec((h,), ("ssm_heads",), dtype=jnp.float32, init="zeros"),
        "d_skip": spec((h,), ("ssm_heads",), dtype=jnp.float32, init="ones"),
        "dt_bias": spec((h,), ("ssm_heads",), dtype=jnp.float32, init="zeros"),
        "norm": P.scale(di, layers),
        "out": P.dense(di, d, "ssm_inner", "embed", layers),
    }


def causal_conv(w: jax.Array, b: jax.Array, x: jax.Array) -> jax.Array:
    """Depthwise causal conv via tap shifts.  x: [B,S,C]; w: [W,C]."""
    width = w.shape[0]
    out = x * w[-1] + b
    for k in range(1, width):
        shifted = jnp.pad(x, ((0, 0), (k, 0), (0, 0)))[:, : x.shape[1]]
        out = out + shifted * w[-1 - k]
    return out


def ssd_chunked(x: jax.Array, dt: jax.Array, a: jax.Array, b_in: jax.Array,
                c_in: jax.Array, chunk: int,
                init_state: jax.Array | None = None):
    """Chunked SSD scan.

    x: [B,S,H,P]  dt: [B,S,H] (post-softplus)  a: [H] (negative)
    b_in/c_in: [B,S,G,N].  Returns (y [B,S,H,P], final_state [B,H,P,N]).
    """
    bsz, s, h, p = x.shape
    g, n = b_in.shape[2], b_in.shape[3]
    nc = s // chunk
    assert nc * chunk == s, (s, chunk)
    hg = h // g  # heads per group

    f32 = jnp.float32
    dtc = dt.reshape(bsz, nc, chunk, h).astype(f32)
    da = dtc * a  # [B,c,Q,H]
    seg = jnp.cumsum(da, axis=2)
    xc = x.reshape(bsz, nc, chunk, h, p)
    bc = b_in.reshape(bsz, nc, chunk, g, n)
    cc = c_in.reshape(bsz, nc, chunk, g, n)

    # --- intra-chunk (quadratic, masked) ---
    cb = jnp.einsum("bcqgn,bckgn->bcgqk", cc.astype(f32), bc.astype(f32))
    cb = jnp.repeat(cb, hg, axis=2)  # [B,c,H,Q,K]
    seg_t = seg.swapaxes(2, 3)  # [B,c,H,Q]
    decay = jnp.exp(seg_t[..., :, None] - seg_t[..., None, :])  # [B,c,H,Q,K]
    mask = jnp.tril(jnp.ones((chunk, chunk), bool))
    m = jnp.where(mask[None, None, None], cb * decay, 0.0)
    dt_k = dtc.swapaxes(2, 3)[..., None, :]  # [B,c,H,1,K]
    m = m * dt_k
    y_intra = jnp.einsum("bchqk,bckhp->bcqhp", m, xc.astype(f32))

    # --- chunk states ---
    last = seg[:, :, -1:, :]  # [B,c,1,H]
    w_k = jnp.exp(last - seg) * dtc  # decay from k to chunk end × dt_k
    bh_ = jnp.repeat(bc.astype(f32), hg, axis=3)  # [B,c,K,H,N] (group->head)
    states = jnp.einsum("bckhn,bckh,bckhp->bchpn",
                        bh_, w_k, xc.astype(f32))

    # --- inter-chunk recurrence ---
    chunk_decay = jnp.exp(last[:, :, 0, :])  # [B,c,H]
    s0 = (jnp.zeros((bsz, h, p, n), f32) if init_state is None
          else init_state.astype(f32))

    def step(carry, inp):
        st_local, dec = inp
        new = carry * dec[:, :, None, None] + st_local
        return new, carry  # emit the *incoming* state for this chunk

    final, prev_states = jax.lax.scan(
        step, s0, (states.swapaxes(0, 1), chunk_decay.swapaxes(0, 1)))
    prev_states = prev_states.swapaxes(0, 1)  # [B,c,H,P,N]

    ch = jnp.repeat(cc.astype(f32), hg, axis=3)  # [B,c,Q,H,N]
    y_inter = jnp.einsum("bcqhn,bcqh,bchpn->bcqhp",
                         ch, jnp.exp(seg), prev_states)
    y = (y_intra + y_inter).reshape(bsz, s, h, p).astype(x.dtype)
    return y, final


def ssd_decode_step(state: jax.Array, x: jax.Array, dt: jax.Array,
                    a: jax.Array, b_in: jax.Array, c_in: jax.Array):
    """One-token SSD update.  state [B,H,P,N], x [B,H,P], dt [B,H],
    b_in/c_in [B,G,N].  Returns (y [B,H,P], new_state)."""
    f32 = jnp.float32
    h = x.shape[1]
    g = b_in.shape[1]
    hg = h // g
    bh = jnp.repeat(b_in, hg, axis=1).astype(f32)   # [B,H,N]
    ch = jnp.repeat(c_in, hg, axis=1).astype(f32)
    dtf = dt.astype(f32)
    da = jnp.exp(dtf * a)                            # [B,H]
    upd = (dtf[..., None] * x.astype(f32))[..., None] * bh[:, :, None, :]
    new_state = state * da[..., None, None] + upd    # [B,H,P,N]
    y = jnp.einsum("bhpn,bhn->bhp", new_state, ch).astype(x.dtype)
    return y, new_state


def mamba_block(cfg: ModelConfig, p: dict, x: jax.Array, *,
                use_pallas: bool = False, return_state: bool = False):
    """Full-sequence Mamba2 block.  x: [B,S,D] -> y [B,S,D].
    With ``return_state``: (y, (conv_tail [B,W-1,CC], ssm_state [B,H,P,N]))."""
    bsz, s, _ = x.shape
    di, h, n, g = cfg.d_inner, cfg.n_ssm_heads, cfg.ssm_state, cfg.ssm_groups
    hd = cfg.ssm_head_dim

    z = x @ p["wz"]
    z = constrain(z, ("act_batch", "act_seq", "act_inner"))
    xbc_pre = x @ p["wxbc"]
    xbc_pre = constrain(xbc_pre, ("act_batch", "act_seq", "act_inner"))
    dt = jax.nn.softplus((x @ p["wdt"]).astype(jnp.float32) + p["dt_bias"])

    xbc = jax.nn.silu(causal_conv(p["conv_w"], p["conv_b"], xbc_pre))
    xc = xbc[..., :di].reshape(bsz, s, h, hd)
    b_in = xbc[..., di:di + g * n].reshape(bsz, s, g, n)
    c_in = xbc[..., di + g * n:].reshape(bsz, s, g, n)
    a = -jnp.exp(p["a_log"])

    if use_pallas:
        from repro.kernels import ops as kops
        y, final = kops.ssd_scan(xc, dt, a, b_in, c_in, cfg.ssd_chunk)
    else:
        y, final = ssd_chunked(xc, dt, a, b_in, c_in, min(cfg.ssd_chunk, s))
    y = y + xc * p["d_skip"][None, None, :, None].astype(x.dtype)
    y = y.reshape(bsz, s, di) * jax.nn.silu(z)
    y = rmsnorm(p["norm"], y, cfg.norm_eps)
    y = constrain(y, ("act_batch", "act_seq", "act_inner"))
    out = y @ p["out"]
    if return_state:
        w = cfg.conv_width
        return out, (xbc_pre[:, s - (w - 1):, :], final)
    return out


def mamba_decode(cfg: ModelConfig, p: dict, x: jax.Array,
                 conv_state: jax.Array, ssm_state: jax.Array):
    """One-token Mamba2 step.  x: [B,1,D]; conv_state [B,W-1,CC];
    ssm_state [B,H,P,N].  Returns (y [B,1,D], conv_state, ssm_state)."""
    bsz = x.shape[0]
    di, h, n, g = cfg.d_inner, cfg.n_ssm_heads, cfg.ssm_state, cfg.ssm_groups
    hd = cfg.ssm_head_dim
    x1 = x[:, 0]

    z = x1 @ p["wz"]
    xbc = x1 @ p["wxbc"]
    dt = jax.nn.softplus((x1 @ p["wdt"]).astype(jnp.float32) + p["dt_bias"])

    window = jnp.concatenate([conv_state, xbc[:, None, :]], axis=1)  # [B,W,CC]
    conv = jnp.einsum("bwc,wc->bc", window, p["conv_w"]) + p["conv_b"]
    xbc = jax.nn.silu(conv)
    new_conv_state = window[:, 1:]

    xc = xbc[..., :di].reshape(bsz, h, hd)
    b_in = xbc[..., di:di + g * n].reshape(bsz, g, n)
    c_in = xbc[..., di + g * n:].reshape(bsz, g, n)
    a = -jnp.exp(p["a_log"])

    y, new_ssm = ssd_decode_step(ssm_state, xc, dt, a, b_in, c_in)
    y = y + xc * p["d_skip"][None, :, None].astype(x.dtype)
    y = y.reshape(bsz, di) * jax.nn.silu(z)
    y = rmsnorm(p["norm"], y, cfg.norm_eps)
    return (y @ p["out"])[:, None, :], new_conv_state, new_ssm


# ======================================================================
# Context-parallel SSD (sequence-sharded Mamba2 block)
#
# Under the train/prefill rule sets the residual stream is sequence-
# sharded over `model`.  Left to GSPMD, the inter-chunk state recurrence
# (a lax.scan whose xs are chunk-sharded) forces replication of the whole
# [B, n_chunks, H, P, N] state tensor — measured 640 MiB all-reduce +
# all-gather PER LAYER on mamba2-2.7b prefill_32k (the dominant roofline
# term, 25x over compute).  This shard_map implementation keeps everything
# sequence-local and exchanges only:
#   * a (W-1)-token halo for the causal conv   (collective-permute, ~KBs)
#   * one [tp, B, H, P, N] state summary       (all-gather, ~5 MB/shard)
#   * the replicated weights                   (the usual FSDP/TP gathers)
# The cross-shard prefix is exact: the SSD recurrence is linear in its
# initial state, so each shard runs zero-init locally and adds the decayed
# incoming prefix state afterwards.
# ======================================================================


def _cp_prefix(s_all: jax.Array, d_all: jax.Array, my_idx: jax.Array):
    """Incoming prefix state for this shard.
    s_all: [tp,B,H,P,N] zero-init final states; d_all: [tp,B,H] total decays.
    prefix_i = sum_{j<i} s_j * prod_{j<k<i} d_k  (linear-recurrence prefix)."""
    tp = s_all.shape[0]
    acc = jnp.zeros_like(s_all[0])
    incoming = []
    for j in range(tp):
        incoming.append(acc)
        acc = acc * d_all[j][..., None, None] + s_all[j]
    stacked = jnp.stack(incoming)              # [tp,B,H,P,N]
    return (jax.lax.dynamic_index_in_dim(stacked, my_idx, 0, keepdims=False),
            acc)


def _mamba_cp_body(cfg: ModelConfig, axis: str, tp: int, return_state: bool,
                   p: dict, x: jax.Array):
    """Per-shard body.  x: [b_loc, s_loc, D] (seq-sharded over `axis`)."""
    bsz, s_loc, _ = x.shape
    di, h, n, g = cfg.d_inner, cfg.n_ssm_heads, cfg.ssm_state, cfg.ssm_groups
    hd = cfg.ssm_head_dim
    w = cfg.conv_width
    idx = jax.lax.axis_index(axis)

    z = x @ p["wz"]
    xbc_pre = x @ p["wxbc"]
    dt = jax.nn.softplus((x @ p["wdt"]).astype(jnp.float32) + p["dt_bias"])

    # --- causal conv with left halo from the previous shard ---
    tail = xbc_pre[:, s_loc - (w - 1):, :]
    halo = jax.lax.ppermute(tail, axis,
                            [(i, i + 1) for i in range(tp - 1)])
    full = jnp.concatenate([halo, xbc_pre], axis=1)  # [b, s_loc+w-1, CC]
    conv = jnp.zeros_like(xbc_pre) + p["conv_b"]
    for k in range(w):
        conv = conv + full[:, k:k + s_loc, :] * p["conv_w"][k]
    xbc = jax.nn.silu(conv)

    xc = xbc[..., :di].reshape(bsz, s_loc, h, hd)
    b_in = xbc[..., di:di + g * n].reshape(bsz, s_loc, g, n)
    c_in = xbc[..., di + g * n:].reshape(bsz, s_loc, g, n)
    a = -jnp.exp(p["a_log"])

    # --- local zero-init SSD + cross-shard prefix correction ---
    y0, s_local = ssd_chunked(xc, dt, a, b_in, c_in,
                              min(cfg.ssd_chunk, s_loc))
    da = (dt * a)                                      # [b, s_loc, h]
    total_decay = jnp.exp(jnp.sum(da, axis=1))         # [b, h]
    s_all = jax.lax.all_gather(s_local, axis)          # [tp,b,h,p,n]
    d_all = jax.lax.all_gather(total_decay, axis)      # [tp,b,h]
    s_in, s_global = _cp_prefix(s_all, d_all, idx)

    decay_t = jnp.exp(jnp.cumsum(da, axis=1))          # [b, s_loc, h]
    hg = h // g
    c_h = jnp.repeat(c_in.astype(jnp.float32), hg, axis=2)  # [b,s,h,n]
    y_corr = jnp.einsum("bshn,bsh,bhpn->bshp", c_h, decay_t, s_in)
    y = y0 + y_corr.astype(y0.dtype)

    y = y + xc * p["d_skip"][None, None, :, None].astype(x.dtype)
    y = y.reshape(bsz, s_loc, di) * jax.nn.silu(z)
    y = rmsnorm(p["norm"], y, cfg.norm_eps)
    out = y @ p["out"]

    if not return_state:
        return out
    # global conv tail (last shard's) + exact global final state, both
    # computed replicated so the out_spec can declare them unsharded.
    tail_all = jax.lax.all_gather(full[:, -(w - 1):, :], axis)  # [tp,b,w-1,CC]
    conv_tail = tail_all[tp - 1]
    return out, (conv_tail, s_global)


def mamba_block_cp(cfg: ModelConfig, p: dict, x: jax.Array, *,
                   use_pallas: bool = False, return_state: bool = False):
    """Context-parallel Mamba2 block via shard_map (sequence sharded over
    the model axis).  Falls back to the GSPMD path when inapplicable."""
    from functools import partial

    from repro.parallel.ctx import _current

    ctx = _current()
    tp = ctx.axis_sizes.get("model", 1) if ctx else 1
    s = x.shape[1]
    applicable = (
        ctx is not None and tp > 1
        and ctx.rules.get("act_res") == "model"
        and s % tp == 0 and (s // tp) % min(cfg.ssd_chunk, s // tp) == 0)
    if not applicable:
        return mamba_block(cfg, p, x, use_pallas=use_pallas,
                           return_state=return_state)

    mesh = ctx.mesh
    x_spec = ctx.resolve(("act_batch", "act_res", None), x.shape)
    p_specs = jax.tree.map(lambda _: jax.sharding.PartitionSpec(), p)
    body = partial(_mamba_cp_body, cfg, "model", tp, return_state)
    if return_state:
        b_ax = x_spec[0]
        out_specs = (x_spec,
                     (jax.sharding.PartitionSpec(b_ax, None, None),
                      jax.sharding.PartitionSpec(b_ax, None, None, None)))
    else:
        out_specs = x_spec
    fn = jax.shard_map(body, mesh=mesh, in_specs=(p_specs, x_spec),
                       out_specs=out_specs, check_vma=False)
    return fn(p, x)
