"""GQA attention: train/prefill (chunked, kv-replicated) and decode
(kv-padded sharded cache) paths, plus cross-attention.

Sharding strategy (DESIGN.md §5): projections are TP-sharded on their
flattened head output dims (always divisible); per-head activation layouts
are reached by reshape so GSPMD propagates the tiling even when neither the
kv nor the group dim alone divides the model axis.  The q group dim is
zero-padded to ``g_pad`` (HeadGeom) so the flattened run layout divides tp;
decode caches zero-pad the kv dim itself to ``kv_pad`` so the cache shards.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import params as P
from repro.models.layers import (HeadGeom, head_geom, pad_group_dim,
                                 rmsnorm, rope, rs_project, sp_col_projects,
                                 sp_gather_seq)
from repro.parallel.ctx import _current, constrain

NEG_INF = -1e9


def tp_size() -> int:
    ctx = _current()
    if ctx is None:
        return 1
    return ctx.axis_sizes.get("model", 1)


def attn_specs(cfg: ModelConfig, layers: int | None, *, kv_d: int | None = None) -> dict:
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    h, kv = cfg.n_heads, cfg.n_kv_heads
    specs = {
        "wq": P.dense(d, h * hd, "embed", "heads_out", layers),
        "wk": P.dense(kv_d or d, kv * hd, "embed", "kv_out", layers),
        "wv": P.dense(kv_d or d, kv * hd, "embed", "kv_out", layers),
        "wo": P.dense(h * hd, d, "heads_out", "embed", layers),
    }
    if cfg.qk_norm:
        specs["q_scale"] = P.scale(hd, layers)
        specs["k_scale"] = P.scale(hd, layers)
    if cfg.attn_gate:
        specs["wg"] = P.dense(d, h * hd, "embed", "heads_out", layers)
    return specs


def _project_qkv(cfg: ModelConfig, p: dict, x: jax.Array, kv_x: jax.Array,
                 geom: HeadGeom, q_pos: jax.Array, k_pos: jax.Array | None,
                 self_attn: bool):
    """Returns q [B,Sq,h_run,hd] (flat padded-head layout — no 5-D grouped
    detour, which GSPMD cannot tile cleanly) and k,v [B,Sk,KV,hd].
    Self-attention fuses the SP gather with all three projections (one
    all-gather forward, one bf16 psum_scatter backward)."""
    hd, kv = geom.head_dim, geom.n_kv
    b, sq = x.shape[0], x.shape[1]
    sk = kv_x.shape[1]

    wq = pad_group_dim(p["wq"], geom, axis_is_out=True)
    if self_attn:
        q, k, v = sp_col_projects(x, (wq, p["wk"], p["wv"]),
                                  ("act_heads", None, None))
    else:
        (q,) = sp_col_projects(x, (wq,), ("act_heads",))
        k = kv_x @ p["wk"]
        v = kv_x @ p["wv"]
    q = constrain(q, ("act_batch", "act_seq", "act_heads"))
    q = q.reshape(b, sq, geom.h_run, hd)
    k = k.reshape(b, sk, kv, hd)
    v = v.reshape(b, sk, kv, hd)

    if cfg.qk_norm:
        q = rmsnorm(p["q_scale"], q, cfg.norm_eps)
        k = rmsnorm(p["k_scale"], k, cfg.norm_eps)

    if cfg.rope_theta > 0:
        q = rope(q, q_pos, cfg.rope_theta)
        if k_pos is not None:
            k = rope(k, k_pos, cfg.rope_theta)
    return q, k, v


def _attend(q: jax.Array, k: jax.Array, v: jax.Array, mask: jax.Array | None,
            hd: int) -> jax.Array:
    """q [B,Sq,H,hd], k/v [B,Sk,H,hd] (kv pre-repeated to the q-head count)
    -> out [B,Sq,H,hd].  Flat head layout: GSPMD shards the head dim 1-D,
    which avoids the mixed 5-D tilings that trigger involuntary
    rematerialization in the backward pass."""
    scores = jnp.einsum("bqhd,bshd->bhqs", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores * (hd ** -0.5)
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqs,bshd->bqhd", probs, v)


def full_attention(cfg: ModelConfig, p: dict, x: jax.Array, *,
                   kv_x: jax.Array | None = None, causal: bool = True,
                   pos0: int = 0, chunk: int = 1024,
                   return_kv: bool = False, gather_kv: bool = True,
                   use_pallas: bool = False):
    """Attention over full sequences (train / prefill / encoder / cross).

    kv replicated over tp (transient, small); q-chunked lax.scan keeps the
    fp32 score block [B, heads, chunk, Sk] bounded so the lowered program's
    peak memory stays within HBM even at 32k.
    """
    geom = head_geom(cfg, tp_size())
    hd = geom.head_dim
    b, sq, d = x.shape
    kv_src = x if kv_x is None else (
        sp_gather_seq(kv_x) if gather_kv else kv_x)
    sk = kv_src.shape[1]

    q_pos = pos0 + jnp.arange(sq)
    k_pos = (pos0 + jnp.arange(sk)) if kv_x is None else None
    q, k, v = _project_qkv(cfg, p, x, kv_src, geom, q_pos, k_pos,
                           self_attn=kv_x is None)

    # flat head layout: repeat kv to the (padded) q-head count and shard the
    # head dim.  The repeat is cheap (kv transient, sliced per shard by the
    # constraint) and buys clean 1-D head sharding through the whole block.
    k_r = jnp.repeat(k, geom.g_pad, axis=2)
    v_r = jnp.repeat(v, geom.g_pad, axis=2)
    q = constrain(q, ("act_batch", "act_seq", "act_heads", None))
    k_r = constrain(k_r, ("act_batch", "act_seq", "act_heads", None))
    v_r = constrain(v_r, ("act_batch", "act_seq", "act_heads", None))

    if (use_pallas and kv_x is None and pos0 == 0 and sq == sk
            and tp_size() == 1 and sq % min(128, sq) == 0):
        # Pallas flash path (TPU target; interpret on CPU).  Per-shard
        # only: under TP the jnp path lowers for GSPMD, the kernel runs
        # inside shard_map deployments.
        from repro.kernels import ops as kops
        bq = min(128, sq)
        qf = q.reshape(b, sq, geom.h_run, hd).swapaxes(1, 2) \
              .reshape(b * geom.h_run, sq, hd)
        kf = k_r.swapaxes(1, 2).reshape(b * geom.h_run, sk, hd)
        vf = v_r.swapaxes(1, 2).reshape(b * geom.h_run, sk, hd)
        out = kops.flash_attention(qf, kf, vf, causal=causal,
                                   block_q=bq, block_k=bq)
        out = out.reshape(b, geom.h_run, sq, hd).swapaxes(1, 2)
        out = out.reshape(b, sq, geom.h_run * hd)
        wo = pad_group_dim(p["wo"], geom, axis_is_out=False)
        y = rs_project(out, wo, "act_heads")
        if return_kv:
            return y, (k, v)
        return y

    k_posv = jnp.arange(sk)

    def block(q_blk: jax.Array, q_pos_blk: jax.Array) -> jax.Array:
        mask = None
        if causal:
            mask = (k_posv[None, :] <= q_pos_blk[:, None] - pos0)
            mask = mask[None, None, :, :]  # [1,1,Sq_blk,Sk]
        return _attend(q_blk, k_r, v_r, mask, hd)

    if sq > chunk and sq % chunk == 0:
        nq = sq // chunk
        q_chunks = jnp.moveaxis(q.reshape(b, nq, chunk, geom.h_run, hd), 1, 0)
        pos_chunks = q_pos.reshape(nq, chunk)
        # remat the chunk body: otherwise the scan stacks fp32 score/prob
        # blocks across chunks for backward — O(S²/chunk) bytes per layer.
        chunk_fn = jax.checkpoint(
            lambda qs, ps: block(qs, ps),
            policy=jax.checkpoint_policies.nothing_saveable)
        out = jax.lax.scan(
            lambda _, qs: (None, chunk_fn(qs[0], qs[1])), None,
            (q_chunks, pos_chunks)
        )[1]  # [nq, B, chunk, H_run, hd]
        out = jnp.moveaxis(out, 0, 1)
    else:
        out = block(q, q_pos)

    out = out.reshape(b, sq, geom.h_run * hd)
    out = constrain(out, ("act_batch", "act_seq", "act_heads"))
    wo = pad_group_dim(p["wo"], geom, axis_is_out=False)
    # SP exit: fused psum_scatter instead of GSPMD's all-reduce(+slice)
    y = rs_project(out, wo, "act_heads")
    if return_kv:
        return y, (k, v)
    return y


# ------------------------------------------------------------ decode path
#
# Cache layout is chosen by decode.cache_specs: when the kv-head count
# divides the model axis the cache shards over kv heads (zero collectives
# in the score path); otherwise the cache shards over SEQUENCE — no head
# padding at all, and the only cross-shard traffic is the softmax stats +
# the [B,H,hd]-sized partial-output reduction (tiny next to cache reads).


def decode_attention(cfg: ModelConfig, p: dict, x: jax.Array,
                     k_cache: jax.Array, v_cache: jax.Array,
                     pos: jax.Array, *, update_cache: bool = True,
                     k_pos_offset: int = 0):
    """Single-token decode: x [B,1,D]; caches [B,Smax,KV,hd]; pos [B].

    Returns (y [B,1,D], new_k_cache, new_v_cache).  With
    ``update_cache=False`` the caches are used read-only (cross-attention).
    """
    geom = head_geom(cfg, tp_size())
    hd, kv, g = geom.head_dim, geom.n_kv, geom.group
    b = x.shape[0]
    s_max = k_cache.shape[1]

    q = x @ p["wq"]
    q = constrain(q, ("act_batch", None, "act_heads"))
    q = q.reshape(b, 1, kv, g, hd)
    k_new = (x @ p["wk"]).reshape(b, 1, kv, hd)
    v_new = (x @ p["wv"]).reshape(b, 1, kv, hd)
    if cfg.qk_norm:
        q = rmsnorm(p["q_scale"], q, cfg.norm_eps)
        k_new = rmsnorm(p["k_scale"], k_new, cfg.norm_eps)
    if cfg.rope_theta > 0:
        posb = pos[:, None]  # [B,1]
        qf = q.reshape(b, 1, kv * g, hd)
        q = rope(qf, posb, cfg.rope_theta).reshape(b, 1, kv, g, hd)
        k_new = rope(k_new, posb, cfg.rope_theta)

    if update_cache:
        k_cache = k_cache.at[jnp.arange(b), pos].set(k_new[:, 0])
        v_cache = v_cache.at[jnp.arange(b), pos].set(v_new[:, 0])
        valid = jnp.arange(s_max)[None, :] <= pos[:, None]
    else:
        valid = jnp.arange(s_max)[None, :] >= k_pos_offset  # all-valid window

    q4 = q[:, 0]  # [B,KV,G,hd]
    scores = jnp.einsum("bkgh,bskh->bkgs", q4, k_cache,
                        preferred_element_type=jnp.float32) * (hd ** -0.5)
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    # fp32 softmax weights, one rounding after the PV product: the same
    # accumulation discipline as the Pallas paged kernel, so every decode
    # pathway (single-token, chunked, paged) rounds at the same points.
    # Their matmuls still accumulate in different orders, so served
    # logits agree to the bf16 band (core.verify.BF16_LOGITS_TOL), and a
    # greedy near-tie may part two pathways' streams
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskh->bkgh", probs,
                     v_cache.astype(jnp.float32)).astype(x.dtype)
    out = out.reshape(b, 1, kv * g * hd)
    out = constrain(out, ("act_batch", None, "act_heads"))
    y = out @ p["wo"]
    y = constrain(y, ("act_batch", None, None))
    return y, k_cache, v_cache


def chunk_decode_attention(cfg: ModelConfig, p: dict, x: jax.Array,
                           k_cache: jax.Array, v_cache: jax.Array,
                           pos: jax.Array, n_new: jax.Array):
    """Multi-token decode against the cache (chunked prefill / decode mix).

    x [B,C,D]; caches [B,Smax,KV,hd]; pos [B] is each lane's first write
    position; n_new [B] in [0, C] is how many of the lane's C tokens are
    real.  Rows beyond ``n_new`` are neither written to the cache nor
    attended by valid queries — their outputs are garbage the caller
    discards (the engine samples only from position ``n_new - 1``).

    Query i of a lane attends cache positions j <= pos + i, so a chunk is
    causally exact against both the pre-existing cache and itself.
    Returns (y [B,C,D], new_k_cache, new_v_cache).
    """
    geom = head_geom(cfg, tp_size())
    hd, kv, g = geom.head_dim, geom.n_kv, geom.group
    b, c, _ = x.shape
    s_max = k_cache.shape[1]

    q = (x @ p["wq"]).reshape(b, c, kv, g, hd)
    k_new = (x @ p["wk"]).reshape(b, c, kv, hd)
    v_new = (x @ p["wv"]).reshape(b, c, kv, hd)
    if cfg.qk_norm:
        q = rmsnorm(p["q_scale"], q, cfg.norm_eps)
        k_new = rmsnorm(p["k_scale"], k_new, cfg.norm_eps)
    idx = pos[:, None] + jnp.arange(c)[None, :]            # [B,C]
    if cfg.rope_theta > 0:
        qf = q.reshape(b, c, kv * g, hd)
        q = rope(qf, idx, cfg.rope_theta).reshape(b, c, kv, g, hd)
        k_new = rope(k_new, idx, cfg.rope_theta)

    # masked scatter: lanes write only their first n_new rows; out-of-range
    # indices (padding lanes, idle slots) drop instead of wrapping
    ok = jnp.arange(c)[None, :] < n_new[:, None]           # [B,C]
    safe = jnp.where(ok, idx, s_max)
    bi = jnp.broadcast_to(jnp.arange(b)[:, None], (b, c))
    k_cache = k_cache.at[bi, safe].set(k_new, mode="drop")
    v_cache = v_cache.at[bi, safe].set(v_new, mode="drop")

    scores = jnp.einsum("bckgh,bskh->bkgcs", q, k_cache,
                        preferred_element_type=jnp.float32) * (hd ** -0.5)
    valid = jnp.arange(s_max)[None, None, :] <= idx[:, :, None]  # [B,C,S]
    scores = jnp.where(valid[:, None, None, :, :], scores, NEG_INF)
    # fp32 weights, round once after PV — see decode_attention
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgcs,bskh->bckgh", probs,
                     v_cache.astype(jnp.float32)).astype(x.dtype)
    out = out.reshape(b, c, kv * g * hd)
    out = constrain(out, ("act_batch", None, "act_heads"))
    y = out @ p["wo"]
    y = constrain(y, ("act_batch", None, None))
    return y, k_cache, v_cache


def paged_chunk_decode_attention(cfg: ModelConfig, p: dict, x: jax.Array,
                                 k_pool: jax.Array, v_pool: jax.Array,
                                 layer: jax.Array, page_table: jax.Array,
                                 pos: jax.Array, n_new: jax.Array, *,
                                 attention: str,
                                 window: jax.Array | None = None,
                                 use_rope: jax.Array | None = None):
    """Chunked decode directly over the paged KV pool — no dense per-slot
    working cache, no gather.

    x [B,C,D]; k/v_pool [layers, num_blocks, KV, block_size, hd] (the
    whole shared device page pool, head-major — see
    ``kernels.paged_attention``) of which ``layer`` (traced int32) is
    written and attended: the pool is never sliced per layer, so the
    layer scan updates the carried pool in place; page_table [B, n_pages]
    int32 maps each lane's logical block index to its physical page;
    pos/n_new as in :func:`chunk_decode_attention`.

    Fresh K/V rows are scattered into the pool *through the page table*
    (each lane writes only its own private pages — shared, refcounted
    prefix pages are never a write target because writes start at
    ``pos >= matched_len`` and prefix matches are whole blocks), then
    attention reads every page by ``attention``: ``"pallas"`` (the
    kernel) or ``"reference"`` (the pure-JAX page-table reference), as
    chosen by ``kernels.ops.paged_attention_impl``.  The kernel runs per
    device; a tensor-parallel trace must ask for the reference.

    A configuration with a ``sliding_window`` passes this layer's
    ``window`` (traced int32; one at least the context is none) to the
    attention, and one with ``nope_global`` its ``use_rope`` (traced
    bool); with ``attn_gate`` the output is multiplied by
    ``sigmoid(x @ wg)`` before ``wo``.  Without them the step is what it
    was before they existed.

    Returns (y [B,C,D], new_k_pool, new_v_pool).
    """
    geom = head_geom(cfg, tp_size())
    hd, kv, g = geom.head_dim, geom.n_kv, geom.group
    b, c, _ = x.shape
    nb, bs = k_pool.shape[1], k_pool.shape[3]
    n_pages = page_table.shape[1]
    if attention == "pallas" and tp_size() > 1:
        raise ValueError("the Pallas paged kernel runs per device; under "
                         "tensor parallelism use attention='reference'")

    q = (x @ p["wq"]).reshape(b, c, kv, g, hd)
    k_new = (x @ p["wk"]).reshape(b, c, kv, hd)
    v_new = (x @ p["wv"]).reshape(b, c, kv, hd)
    if cfg.qk_norm:
        q = rmsnorm(p["q_scale"], q, cfg.norm_eps)
        k_new = rmsnorm(p["k_scale"], k_new, cfg.norm_eps)
    idx = pos[:, None] + jnp.arange(c)[None, :]            # [B,C]
    if cfg.rope_theta > 0:
        qf = q.reshape(b, c, kv * g, hd)
        q_r = rope(qf, idx, cfg.rope_theta).reshape(b, c, kv, g, hd)
        k_r = rope(k_new, idx, cfg.rope_theta)
        if cfg.nope_global:
            q_r = jnp.where(use_rope, q_r, q)
            k_r = jnp.where(use_rope, k_r, k_new)
        q, k_new = q_r, k_r

    # masked scatter through the page table: row idx lands in physical
    # page ``table[idx // bs]`` at offset ``idx % bs`` (all heads).  Lanes
    # write only their first n_new rows; anything out of range (idle
    # slots, padding rows, idx beyond the table) resolves to page ``nb``
    # and drops.
    ok = (jnp.arange(c)[None, :] < n_new[:, None]) & (idx < n_pages * bs)
    blk = jnp.clip(idx // bs, 0, n_pages - 1)
    page = jnp.take_along_axis(page_table, blk, axis=1)    # [B,C]
    page = jnp.where(ok, page, nb)
    off = idx % bs
    # one scatter element per (row, head): the update window is a single
    # hd-vector, minor in the pool's own layout, so XLA keeps the pool's
    # default layout (a [kv, hd] window made it re-lay the whole carried
    # pool out around the kernel call, twice per layer)
    heads = jnp.arange(kv)[None, None, :]
    page, off = page[:, :, None], off[:, :, None]
    if k_pool.shape[-1] > hd:
        # the pool's heads are wider (``kops.paged_pool_width``): the lanes
        # past hd are written zero
        lanes = ((0, 0),) * 3 + ((0, k_pool.shape[-1] - hd),)
        k_new, v_new = jnp.pad(k_new, lanes), jnp.pad(v_new, lanes)
    # named, so that the KV write is told apart from the kernel in a
    # profile (its operations carry ``kv_append`` in their op_name)
    with jax.named_scope("kv_append"):
        k_pool = k_pool.at[layer, page, heads, off].set(k_new, mode="drop")
        v_pool = v_pool.at[layer, page, heads, off].set(v_new, mode="drop")

    from repro.kernels import ops as kops
    out = kops.paged_attention(q, k_pool, v_pool, page_table, pos, n_new,
                               layer=layer, window=window, impl=attention)
    out = out.reshape(b, c, kv * g * hd)
    if cfg.attn_gate:
        out = (out * jax.nn.sigmoid((x @ p["wg"]).astype(jnp.float32))
               ).astype(out.dtype)
    out = constrain(out, ("act_batch", None, "act_heads"))
    y = out @ p["wo"]
    y = constrain(y, ("act_batch", None, None))
    return y, k_pool, v_pool
