"""Pallas TPU kernel: decode attention through the device page table.

The serving engine's KV lives in a shared page pool; each slot owns an
ordered list of pages (its page *table* row).  The previous pathway
gathered those pages into a dense per-slot working cache before every
attention call — exactly the contiguous-shaped host detour the audit
layer exists to flag.  This kernel consumes the paged layout directly:

  * grid ``(slots, kv_heads, pages)`` with the page dimension sequential,
    so the flash running max / denominator / accumulator live in VMEM
    scratch across a slot's pages;
  * the pool is head-major, ``[num_blocks, kv_heads, block_size, hd]``,
    so one grid step's K/V block ``(1, 1, block_size, hd)`` has the
    page's rows and the head dim as its last two (tiled) axes.  The
    token-major layout ``[num_blocks, block_size, kv_heads, hd]`` needs a
    block of 1 on the second-minor kv-head axis, which Mosaic refuses
    (a second-minor block dim must be a multiple of 8 or the whole axis);
    use a ``block_size`` that is a multiple of 8 (16 fills a bf16 tile);
  * the page table rides scalar prefetch
    (``pltpu.PrefetchScalarGridSpec``): the K/V block index maps read
    ``page_table[slot, page]`` to fetch the *physical* page, which is
    how refcount-shared prefix pages are attended by many slots with
    zero copies;
  * per-lane sequence state (``pos`` rows already written, ``n_new``
    fresh rows this call) is prefetched too: ragged last pages and the
    causal chunk mask (query ``i`` sees positions ``<= pos + i``) are
    masked inside the kernel, and pages past a lane's last valid row
    issue no MXU work at all (the same block-skipping economics as the
    causal flash kernel);
  * one kernel covers the whole chunked-serving step: ``C`` queries per
    lane, so prefill chunks (``n_new > 1``), plain decode ticks
    (``n_new == 1``) and idle lanes (``n_new == 0``, outputs discarded)
    share one fixed-shape program.

``paged_attention_ref`` is the pure-JAX oracle — the same math via a
dense gather *through the page table* — used by the parity tests and as
the paged step's attention off the TPU (``kernels.ops``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(layer_ref, pt_ref, pos_ref, nn_ref, q_ref, k_ref, v_ref,
                  o_ref,
                  m_ref, l_ref, acc_ref, *, scale: float, block_size: int,
                  chunk: int, group: int, n_pages: int):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[b]
    nn = nn_ref[b]
    # rows valid for this lane after its chunk is written: idle lanes
    # (nn == 0) still visit page 0 so the (discarded) output is finite
    total = pos + jnp.maximum(nn, 1)
    last = jnp.minimum((total - 1) // block_size, n_pages - 1)

    @pl.when(j <= last)
    def _compute():
        cg = chunk * group
        hd = q_ref.shape[-1]
        q = q_ref[0, :, 0].reshape(cg, hd).astype(jnp.float32)
        k = k_ref[0, 0, 0].astype(jnp.float32)      # [bs, hd]
        v = v_ref[0, 0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [cg, bs]
        # causal chunk mask on *physical* positions: query row i (rows
        # are [chunk, group] flattened) attends cache slots <= pos + i —
        # this both hides the ragged tail of the last page and keeps a
        # chunk causally exact against itself
        k_pos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (cg, block_size), 1)
        row = jax.lax.broadcasted_iota(
            jnp.int32, (cg, block_size), 0) // group
        s = jnp.where(k_pos <= pos + row, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = (acc_ref[...] * alpha[:, None]
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(j == last)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        out = acc_ref[...] / denom[:, None]
        o_ref[0, :, 0] = out.reshape(chunk, group,
                                     acc_ref.shape[-1]).astype(o_ref.dtype)


def paged_attention_pallas(q, k_pool, v_pool, page_table, pos, n_new, *,
                           layer=None, scale: float | None = None,
                           interpret: bool = False):
    """Decode/chunk attention over the paged KV pool.

    q          [B, C, KV, G, hd] — post-RoPE queries (C chunk positions)
    k/v_pool   [layers, num_blocks, KV, block_size, hd] — the shared page
               pool of every layer, already holding this call's fresh rows
               (writes go through the page table *before* attention,
               mirroring the dense path's update-then-attend order); a
               4-D ``[num_blocks, KV, block_size, hd]`` pool is one layer
    layer      int32 scalar (traced) — the layer to attend.  The kernel
               selects it in its K/V index maps, so the layer scan hands
               it the whole pool it carries: slicing one layer out first
               would make XLA materialize that slice for the custom call
               (the compile rehearsal measured a whole-pool temporary)
    page_table [B, n_pages] int32 — per-slot physical page indices; rows
               past a slot's allocation must hold a valid index (0) —
               they are masked, never out-of-bounds
    pos        [B] int32 — rows already in the cache per lane
    n_new      [B] int32 — fresh rows this call (0 = idle lane)

    Returns [B, C, KV, G, hd].  Rows ``>= n_new`` per lane are garbage
    the caller discards (same contract as ``chunk_decode_attention``).
    """
    if k_pool.ndim == 4:
        k_pool, v_pool, layer = k_pool[None], v_pool[None], 0
    b, c, kv, g, hd = q.shape
    _, nb, kv_p, bs, hd_p = k_pool.shape
    assert (kv_p, hd_p) == (kv, hd), (k_pool.shape, q.shape)
    assert v_pool.shape == k_pool.shape
    n_pages = page_table.shape[1]
    assert page_table.shape == (b, n_pages)
    scale = scale if scale is not None else hd ** -0.5
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, kv, n_pages),
        in_specs=[
            pl.BlockSpec((1, c, 1, g, hd),
                         lambda b, h, j, li, pt, pos, nn: (b, 0, h, 0, 0)),
            # the paged read: physical page via the prefetched table
            pl.BlockSpec((1, 1, 1, bs, hd),
                         lambda b, h, j, li, pt, pos, nn:
                         (li[0], pt[b, j], h, 0, 0)),
            pl.BlockSpec((1, 1, 1, bs, hd),
                         lambda b, h, j, li, pt, pos, nn:
                         (li[0], pt[b, j], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, c, 1, g, hd),
                               lambda b, h, j, li, pt, pos, nn:
                               (b, 0, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((c * g,), jnp.float32),      # running max
            pltpu.VMEM((c * g,), jnp.float32),      # running denominator
            pltpu.VMEM((c * g, hd), jnp.float32),   # output accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, block_size=bs,
                          chunk=c, group=g, n_pages=n_pages),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(layer, page_table, pos, n_new, q, k_pool, v_pool)


def paged_attention_ref(q, k_pool, v_pool, page_table, pos, n_new, *,
                        layer=None, scale: float | None = None):
    """Pure-JAX oracle: dense gather *through the page table* + masked
    softmax.  Bitwise-independent of the kernel (full softmax instead of
    the online accumulation) but mathematically identical on valid rows.
    Pools and ``layer`` as in :func:`paged_attention_pallas`."""
    if k_pool.ndim == 5:
        k_pool, v_pool = k_pool[layer], v_pool[layer]
    b, c, kv, g, hd = q.shape
    nb, _, bs, _ = k_pool.shape
    n_pages = page_table.shape[1]
    scale = scale if scale is not None else hd ** -0.5

    def rows(pool):   # [B, n_pages, KV, bs, hd] -> [B, n_pages*bs, KV, hd]
        return pool[page_table].swapaxes(2, 3).reshape(b, n_pages * bs, kv, hd)

    k, v = rows(k_pool), rows(v_pool)
    qf = q.astype(jnp.float32)
    s = jnp.einsum("bckgh,bskh->bkgcs", qf, k.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * scale
    idx = pos[:, None] + jnp.arange(c)[None, :]               # [B, C]
    valid = jnp.arange(n_pages * bs)[None, None, :] <= idx[:, :, None]
    s = jnp.where(valid[:, None, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgcs,bskh->bckgh", p, v.astype(jnp.float32))
    return out.astype(q.dtype)
