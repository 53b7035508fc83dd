"""Pallas TPU kernel: decode attention through the device page table.

The serving engine's KV lives in a shared page pool; each slot owns an
ordered list of pages (its page *table* row).  The previous pathway
gathered those pages into a dense per-slot working cache before every
attention call — exactly the contiguous-shaped host detour the audit
layer exists to flag.  This kernel consumes the paged layout directly:

  * grid ``(slots, kv_heads // heads_per_block, pages)`` with the page
    dimension sequential, so the flash running max / denominator /
    accumulator live in VMEM scratch across a slot's pages.  One grid
    step is one page of one lane for a block of kv heads — all of them
    unless the blocks would not fit the VMEM budget
    (:func:`heads_per_block`, from the shapes alone) — so the middle
    axis is 1 for every configuration served today and a step's fixed
    cost is paid once per page, not once per page and head;
  * the pool is head-major, ``[layers, num_blocks, kv_heads, block_size,
    hd]``, so one page of one layer for all its kv heads is one
    contiguous ``(1, 1, kv, block_size, hd)`` block whose last two
    (tiled) axes are the page's rows and the head dim; use a
    ``block_size`` that is a multiple of 8 (16 fills a bf16 tile);
  * queries and output are presented head-major with chunk and group
    merged, ``[B, kv, C·G, hd]`` (the wrapper transposes in XLA), so the
    tiled axes are ``(C·G, hd)``: ``(G, hd)`` tiles would pad every
    query tile 8-16× at G = 1;
  * the page table rides scalar prefetch
    (``pltpu.PrefetchScalarGridSpec``): the K/V block index maps read
    ``page_table[slot, page]`` to fetch the *physical* page, which is
    how refcount-shared prefix pages are attended by many slots with
    zero copies;
  * per-lane sequence state (``pos`` rows already written, ``n_new``
    fresh rows this call) is prefetched too: ragged last pages and the
    causal chunk mask (query ``i`` sees positions ``<= pos + i``) are
    masked inside the kernel.  Pages past a lane's last valid row
    (:func:`last_page`) do no MXU work, and the index maps clamp the
    walk to that page: every later step names the block already held,
    so the pipeline fetches nothing for it — the pages the engine
    reserves at admission for a request's answer stay unread until
    they are written;
  * a windowed layer (``window``, a fifth prefetched scalar) starts the
    walk at the window's first page (:func:`first_page`) as well: every
    earlier step names that page, so nothing before the window is
    fetched, and keys before a row's window are masked;
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

#: VMEM the kernel's blocks, scratch and score temporaries may take: the
#: kv heads of one grid step are cut to fit it (:func:`heads_per_block`),
#: with room left under the 16 MiB scoped VMEM of a v5e core.
VMEM_BUDGET = 8 * 2**20


def last_page(pos, n_new, block_size: int, n_pages: int):
    """A lane's last page holding a row it attends, after this call's
    ``n_new`` rows are written at ``pos``.  Idle lanes (``n_new == 0``)
    count one row, so they visit page 0 and their discarded output stays
    finite.  The kernel body and both K/V index maps
    (:func:`kv_page_index`) share it."""
    total = pos + jnp.maximum(n_new, 1)
    return jnp.minimum((total - 1) // block_size, n_pages - 1)


def kv_page_index(b, h, j, li, pt, pos, nn, *, block_size: int,
                  n_pages: int):
    """The K/V block index map: grid step ``(b, h, j)`` reads physical
    page ``pt[b, j]`` of layer ``li[0]``, for kv-head block ``h``.  Past
    the lane's last page the walk names that page again, so the pipeline
    starts no copy for the rest of the walk."""
    j = jnp.minimum(j, last_page(pos[b], nn[b], block_size, n_pages))
    return (li[0], pt[b, j], h, 0, 0)


def first_page(pos, window, block_size: int):
    """A lane's first page holding a row its window reaches: query row
    ``i`` sees keys ``pos + i - window < k <= pos + i``, so no row sees
    one before ``pos - window + 1``."""
    return jnp.maximum(pos - window + 1, 0) // block_size


def kv_page_index_windowed(b, h, j, li, win, pt, pos, nn, *,
                           block_size: int, n_pages: int):
    """:func:`kv_page_index` of a windowed layer (window ``win[0]``): the
    walk also starts at the window's first page, and every earlier grid
    step names that page, so no page before it is fetched."""
    j = jnp.clip(j, first_page(pos[b], win[0], block_size),
                 last_page(pos[b], nn[b], block_size, n_pages))
    return (li[0], pt[b, j], h, 0, 0)


def _tile(rows: int, cols: int, itemsize: int) -> int:
    """Bytes of a ``[rows, cols]`` VMEM array, padded to whole tiles."""
    sublanes = 8 * (4 // itemsize)
    return (-(-rows // sublanes) * sublanes * -(-cols // 128) * 128
            * itemsize)


def heads_per_block(kv: int, cg: int, bs: int, hd: int, q_bytes: int,
                    kv_bytes: int) -> int:
    """The largest divisor of ``kv`` whose heads' VMEM fits
    :data:`VMEM_BUDGET`: double-buffered query, output, K and V blocks,
    the f32 accumulator and running max / denominator, and the f32
    score-shaped temporaries of the body."""
    per_head = (4 * _tile(cg, hd, q_bytes) + 4 * _tile(bs, hd, kv_bytes)
                + _tile(cg, hd, 4) + 2 * _tile(cg, 1, 4)
                + 4 * _tile(cg, bs, 4))
    fits = [d for d in range(1, kv + 1)
            if kv % d == 0 and d * per_head <= VMEM_BUDGET]
    return max(fits, default=1)


def _paged_kernel(layer_ref, pt_ref, pos_ref, nn_ref, q_ref, k_ref, v_ref,
                  o_ref,
                  m_ref, l_ref, acc_ref, *, scale: float, block_size: int,
                  group: int, n_pages: int, win_ref=None):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[b]
    last = last_page(pos, nn_ref[b], block_size, n_pages)
    walked = j <= last
    if win_ref is not None:
        window = win_ref[0]
        walked = walked & (j >= first_page(pos, window, block_size))

    @pl.when(walked)
    def _compute():
        # every head of the block at once: batch dim 0 is the kv head
        q = q_ref[0]                                 # [kvb, cg, hd]
        k = k_ref[0, 0]                              # [kvb, bs, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale  # [kvb, cg, bs]
        # causal chunk mask on *physical* positions: query row i (rows
        # are [chunk, group] flattened) attends cache slots <= pos + i —
        # this both hides the ragged tail of the last page and keeps a
        # chunk causally exact against itself
        k_pos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 2)
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) // group
        seen = k_pos <= pos + row
        if win_ref is not None:
            seen = seen & (k_pos > pos + row - window)
        s = jnp.where(seen, s, NEG_INF)

        m_prev = m_ref[...]                          # [kvb, cg, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=2, keepdims=True)
        acc_ref[...] = (acc_ref[...] * alpha
                        + jax.lax.dot_general(
                            p, v, (((2,), (1,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(j == last)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def _windowed_kernel(layer_ref, win_ref, pt_ref, pos_ref, nn_ref, *refs,
                     **kw):
    """:func:`_paged_kernel` with the window's scalar prefetched second."""
    _paged_kernel(layer_ref, pt_ref, pos_ref, nn_ref, *refs, win_ref=win_ref,
                  **kw)


def paged_attention_pallas(q, k_pool, v_pool, page_table, pos, n_new, *,
                           layer=None, window=None,
                           scale: float | None = None,
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
               past a slot's allocation must hold a valid index (0);
               pages past a lane's last valid row are never read
    pos        [B] int32 — rows already in the cache per lane
    n_new      [B] int32 — fresh rows this call (0 = idle lane)
    window     None, or int32 scalar (traced): query row ``i`` of a lane
               sees keys ``pos + i - window < k <= pos + i``, and the walk
               starts at the window's first page (:func:`first_page`); a
               window at least the lane's context is none.  ``None``
               compiles the kernel without it

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
    cg = c * g
    kvb = heads_per_block(kv, cg, bs, hd, q.dtype.itemsize,
                          k_pool.dtype.itemsize)
    # [B, C, KV, G, hd] -> [B, KV, C*G, hd]: rows are (chunk, group)
    qh = q.transpose(0, 2, 1, 3, 4).reshape(b, kv, cg, hd)

    scalars = (layer, page_table, pos, n_new)
    kernel, kv_map = _paged_kernel, kv_page_index
    if window is not None:
        scalars = (layer, jnp.reshape(jnp.asarray(window, jnp.int32), (1,)),
                   page_table, pos, n_new)
        kernel, kv_map = _windowed_kernel, kv_page_index_windowed
    kv_page = functools.partial(kv_map, block_size=bs, n_pages=n_pages)

    def lane(b, h, j, *scalars):
        return (b, h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(b, kv // kvb, n_pages),
        in_specs=[
            pl.BlockSpec((1, kvb, cg, hd), lane),
            # the paged read: physical page via the prefetched table
            pl.BlockSpec((1, 1, kvb, bs, hd), kv_page),
            pl.BlockSpec((1, 1, kvb, bs, hd), kv_page),
        ],
        out_specs=pl.BlockSpec((1, kvb, cg, hd), lane),
        scratch_shapes=[
            pltpu.VMEM((kvb, cg, 1), jnp.float32),      # running max
            pltpu.VMEM((kvb, cg, 1), jnp.float32),      # running denominator
            pltpu.VMEM((kvb, cg, hd), jnp.float32),     # output accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(kernel, scale=scale, block_size=bs,
                          group=g, n_pages=n_pages),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qh.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="paged_attention",
    )(*scalars, qh, k_pool, v_pool)
    return out.reshape(b, kv, c, g, hd).transpose(0, 2, 1, 3, 4)


def paged_attention_ref(q, k_pool, v_pool, page_table, pos, n_new, *,
                        layer=None, window=None,
                        scale: float | None = None):
    """Pure-JAX oracle: dense gather *through the page table* + masked
    softmax.  Bitwise-independent of the kernel (full softmax instead of
    the online accumulation) but mathematically identical on valid rows.
    Pools, ``layer`` and ``window`` as in :func:`paged_attention_pallas`."""
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
    k_pos = jnp.arange(n_pages * bs)[None, None, :]
    valid = k_pos <= idx[:, :, None]
    if window is not None:
        valid = valid & (k_pos > idx[:, :, None] - window)
    s = jnp.where(valid[:, None, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgcs,bskh->bckgh", p, v.astype(jnp.float32))
    return out.astype(q.dtype)
