"""Pallas TPU kernel: decode attention through the device page table.

The serving engine's KV lives in a shared page pool; each slot owns an
ordered list of pages (its page *table* row).  The previous pathway
gathered those pages into a dense per-slot working cache before every
attention call — exactly the contiguous-shaped host detour the audit
layer exists to flag.  This kernel consumes the paged layout directly:

  * grid ``(slots, kv_heads // heads_per_block)``: one grid step is one
    lane for a block of kv heads — all of them unless the blocks would
    not fit the VMEM budget (:func:`heads_per_block`, from the shapes
    alone) — so the second axis is 1 for every configuration served
    today.  There is no page axis: the walk over a lane's pages is a
    loop in the body, so a step's fixed cost is paid once per lane;
  * the pool is head-major, ``[layers, num_blocks, kv_heads, block_size,
    hd_p]``, and stays in HBM (``pl.ANY``): the body copies one page of
    one layer for the step's kv heads, ``(kvb, block_size, hd_p)``, with
    a manual async copy.  Mosaic slices such a copy out of the pool only
    at whole tiles, so the pool's head width ``hd_p`` is ``hd`` rounded
    up to whole 128-lane tiles (:func:`pool_head_dim`; the chip pads
    every row to them anyway), and the queries are padded with zero
    lanes to meet it; use a ``block_size`` that is a multiple of 16 (a
    bf16 tile's rows);
  * the walk: a lane's pages from its first (:func:`first_page` in a
    windowed layer, else 0) to its last (:func:`last_page`), ``P`` pages
    to a compute block (:func:`pages_per_block`, from the shapes alone).
    Block ``i`` copies the logical pages :func:`block_pages` names, each
    through the prefetched page table, into one slot of a double buffer
    ``[2, kvb, P·block_size, hd_p]`` (one DMA semaphore a slot, for K and
    for V); block ``i + 1``'s copies start before block ``i`` is
    computed.  A last block that runs past the lane's last page copies
    no page after it — the pages the engine reserves at admission for a
    request's answer stay unread until they are written — and its slot
    keeps what it held there, masked; nothing before a window's first
    page is read.  Each grid step starts its own first block, so a lane
    boundary exposes one block's copy;
  * the grid runs in order on one core (``"arbitrary"``): the V buffer is
    zeroed once, in the first grid step, so the rows a last block leaves
    uncopied are never NaN;
  * queries and output are presented head-major with chunk and group
    merged, ``[B, kv, C·G, hd]`` (the wrapper transposes in XLA), so the
    tiled axes are ``(C·G, hd)``: ``(G, hd)`` tiles would pad every
    query tile 8-16× at G = 1; their blocks stay ``BlockSpec``s, so the
    pipeline overlaps them with the walk;
  * the page table rides scalar prefetch
    (``pltpu.PrefetchScalarGridSpec``), which is how refcount-shared
    prefix pages are attended by many slots with zero copies;
  * per-lane sequence state (``pos`` rows already written, ``n_new``
    fresh rows this call) is prefetched too: ragged last pages, the
    uncopied pages of a last block and the causal chunk mask (query
    ``i`` sees positions ``<= pos + i``) are masked inside the kernel;
  * a windowed layer (``window``, a fifth prefetched scalar) masks keys
    before a row's window as well;
  * one kernel covers the whole chunked-serving step: ``C`` queries per
    lane, so prefill chunks (``n_new > 1``), plain decode ticks
    (``n_new == 1``) and idle lanes (``n_new == 0``, outputs discarded,
    page 0 walked) share one fixed-shape program.

:func:`walk_blocks` counts the compute blocks of a lane's walk with the
kernel's own bounds; the engine's traced ``step`` event sums it
(``attn_blocks``, through ``kernels.ops``).

``paged_attention_ref`` is the pure-JAX oracle — the same math via a
dense gather *through the page table* — used by the parity tests and as
the paged step's attention off the TPU (``kernels.ops``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

#: VMEM the kernel's blocks, scratch and score temporaries may take: the
#: kv heads of one grid step (:func:`heads_per_block`) and the pages of
#: one compute block (:func:`pages_per_block`) are cut to fit it, with
#: room left under the 16 MiB scoped VMEM of a v5e core.
VMEM_BUDGET = 8 * 2**20

#: Most keys (pages × block_size) one compute block of the walk holds:
#: two full 128-lane tiles of scores a query row.  Not yet tuned on a
#: chip (PERF.md §7).
MAX_BLOCK_KEYS = 256

#: Lanes of a tile.  The pool holds each head at whole tiles
#: (:func:`pool_head_dim`): Mosaic slices a pool in HBM for a page copy
#: only where its last dim is a whole number of tiles.
LANES = 128


def pool_head_dim(hd: int) -> int:
    """The pool's head width for heads of ``hd``: rounded up to whole
    :data:`LANES`.  The chip lays each row out at whole tiles anyway, so
    this costs no device memory; the lanes past ``hd`` hold zeros."""
    return -(-hd // LANES) * LANES


def _xp(*xs):
    """jax.numpy for traced or device values, numpy for host ones: the
    engine's host counters share these helpers with the kernel body and
    must launch no device work."""
    return jnp if any(isinstance(x, jax.Array) for x in xs) else np


def last_page(pos, n_new, block_size: int, n_pages: int):
    """A lane's last page holding a row it attends, after this call's
    ``n_new`` rows are written at ``pos``.  Idle lanes (``n_new == 0``)
    count one row, so they visit page 0 and their discarded output stays
    finite."""
    xp = _xp(pos, n_new)
    total = pos + xp.maximum(n_new, 1)
    return xp.minimum((total - 1) // block_size, n_pages - 1)


def first_page(pos, window, block_size: int):
    """A lane's first page holding a row its window reaches: query row
    ``i`` sees keys ``pos + i - window < k <= pos + i``, so no row sees
    one before ``pos - window + 1``."""
    return _xp(pos, window).maximum(pos - window + 1, 0) // block_size


def _walk(pos, n_new, window, block_size: int, pages_per_block: int,
          n_pages: int):
    """A lane's walk: its first page, last page and compute blocks."""
    last = last_page(pos, n_new, block_size, n_pages)
    first = 0
    if window is not None:
        first = _xp(pos, window, last).minimum(
            first_page(pos, window, block_size), last)
    return first, last, (last - first) // pages_per_block + 1


def walk_blocks(pos, n_new, window, block_size: int, pages_per_block: int,
                n_pages: int):
    """Compute blocks the kernel walks for a lane at ``(pos, n_new)``:
    ``pages_per_block`` pages each, from the window's first page
    (``window`` None: page 0) to the lane's last.  The kernel's loop
    runs over exactly these."""
    return _walk(pos, n_new, window, block_size, pages_per_block,
                 n_pages)[2]


def block_pages(first, i, pages_per_block: int):
    """The logical pages of block ``i`` of a walk from ``first``, in
    order.  The kernel copies those up to the lane's last page and no
    later one, so a last block that runs past it copies fewer."""
    start = first + i * pages_per_block
    return [start + k for k in range(pages_per_block)]


def _tile(rows: int, cols: int, itemsize: int) -> int:
    """Bytes of a ``[rows, cols]`` VMEM array, padded to whole tiles."""
    sublanes = 8 * (4 // itemsize)
    return (-(-rows // sublanes) * sublanes * -(-cols // 128) * 128
            * itemsize)


def heads_per_block(kv: int, cg: int, bs: int, hd: int, q_bytes: int,
                    kv_bytes: int) -> int:
    """The largest divisor of ``kv`` whose heads' VMEM fits
    :data:`VMEM_BUDGET` at one page a block: double-buffered query,
    output, K and V blocks, the f32 accumulator and running max /
    denominator, and the f32 score-shaped temporaries of the body."""
    per_head = (4 * _tile(cg, hd, q_bytes) + 4 * _tile(bs, hd, kv_bytes)
                + _tile(cg, hd, 4) + 2 * _tile(cg, 1, 4)
                + 4 * _tile(cg, bs, 4))
    fits = [d for d in range(1, kv + 1)
            if kv % d == 0 and d * per_head <= VMEM_BUDGET]
    return max(fits, default=1)


def block_vmem(kvb: int, cg: int, bs: int, hd: int, q_bytes: int,
               kv_bytes: int, pages: int) -> int:
    """VMEM of one grid step at ``pages`` pages a compute block: for each
    of ``kvb`` heads the double-buffered query and output blocks, the
    f32 accumulator and running max / denominator, the double-buffered K
    and V blocks and two f32 ``[cg, pages·bs]`` score-shaped temporaries
    (scores and weights); and two more for the mask, which every head
    shares."""
    keys = pages * bs
    per_head = (4 * _tile(cg, hd, q_bytes) + _tile(cg, hd, 4)
                + 2 * _tile(cg, 1, 4) + 4 * _tile(keys, hd, kv_bytes)
                + 2 * _tile(cg, keys, 4))
    return kvb * per_head + 2 * _tile(cg, keys, 4)


def pages_per_block(kvb: int, cg: int, bs: int, hd: int, q_bytes: int,
                    kv_bytes: int, n_pages: int) -> int:
    """The most pages a compute block holds: up to :data:`MAX_BLOCK_KEYS`
    keys and the table's ``n_pages``, with :func:`block_vmem` within
    :data:`VMEM_BUDGET`.  Never below one."""
    cap = max(1, min(n_pages, MAX_BLOCK_KEYS // bs))
    return max((p for p in range(1, cap + 1)
                if block_vmem(kvb, cg, bs, hd, q_bytes, kv_bytes, p)
                <= VMEM_BUDGET), default=1)


def kernel_blocks(q_shape, pool_shape, q_bytes: int, kv_bytes: int,
                  n_pages: int) -> tuple[int, int]:
    """``(heads_per_block, pages_per_block)`` of the kernel for queries
    ``[B, C, KV, G, hd]`` over a pool ``[..., KV, block_size, hd_p]``."""
    _, c, kv, g, _ = q_shape
    bs, hd = pool_shape[-2:]
    kvb = heads_per_block(kv, c * g, bs, hd, q_bytes, kv_bytes)
    return kvb, pages_per_block(kvb, c * g, bs, hd, q_bytes, kv_bytes,
                                n_pages)


def _paged_kernel(layer_ref, pt_ref, pos_ref, nn_ref, q_ref, k_hbm, v_hbm,
                  o_ref, m_ref, l_ref, acc_ref, k_buf, v_buf, sems, *,
                  scale: float, block_size: int, group: int, n_pages: int,
                  pages: int, win_ref=None):
    b, h = pl.program_id(0), pl.program_id(1)
    kvb = k_buf.shape[1]
    bs = block_size
    window = None if win_ref is None else win_ref[0]
    pos = pos_ref[b]
    first, last, n_blocks = _walk(pos, nn_ref[b], window, bs, pages,
                                  n_pages)
    heads = pl.ds(h * kvb, kvb)

    def copies(i, slot, op):
        """Start (or wait for) the K and V copies of block ``i`` into
        ``slot``: one per page up to the lane's last."""
        for k, page in enumerate(block_pages(first, i, pages)):
            @pl.when(page <= last)
            def _page():
                phys = pt_ref[b, page]
                rows = pl.ds(k * bs, bs)
                for j, (src, buf) in enumerate(((k_hbm, k_buf),
                                                (v_hbm, v_buf))):
                    getattr(pltpu.make_async_copy(
                        src.at[layer_ref[0], phys, heads],
                        buf.at[slot, :, rows], sems.at[j, slot]), op)()

    @pl.when((b == 0) & (h == 0))
    def _zero_v():
        # the pages a last block leaves uncopied keep what the slot held:
        # weightless (masked) keys, whose V rows must never be NaN
        v_buf[...] = jnp.zeros_like(v_buf)

    copies(0, 0, "start")
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    q = q_ref[0]                                     # [kvb, cg, hd]

    def block(i, carry):
        slot = i % 2

        @pl.when(i + 1 < n_blocks)
        def _prefetch():
            copies(i + 1, 1 - slot, "start")

        copies(i, slot, "wait")
        # every head of the block at once: batch dim 0 is the kv head
        k = k_buf[slot]                              # [kvb, P*bs, hd]
        v = v_buf[slot].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale  # [kvb, cg, P*bs]
        # causal chunk mask on *physical* positions, one for every head:
        # query row i (rows are [chunk, group] flattened) attends cache
        # slots <= pos + i — this hides the ragged tail of the last page
        # and keeps a chunk causally exact against itself — and none past
        # the last page, which the discarded rows past n_new would reach
        # in a last block's uncopied pages
        k_pos = (first + i * pages) * bs + jax.lax.broadcasted_iota(
            jnp.int32, s.shape[1:], 1)
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape[1:], 0) // group
        seen = (k_pos <= pos + row) & (k_pos < (last + 1) * bs)
        if window is not None:
            seen = seen & (k_pos > pos + row - window)
        s = jnp.where(seen[None], s, NEG_INF)

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
        return carry

    jax.lax.fori_loop(0, n_blocks, block, 0)
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
    k/v_pool   [layers, num_blocks, KV, block_size, hd_p] — the shared
               page pool of every layer, already holding this call's fresh
               rows (writes go through the page table *before* attention,
               mirroring the dense path's update-then-attend order); a
               4-D ``[num_blocks, KV, block_size, hd_p]`` pool is one
               layer.  ``hd_p >= hd``: on the chip a whole number of
               tiles (:func:`pool_head_dim`), the lanes past ``hd`` zero
    layer      int32 scalar (traced) — the layer to attend.  The kernel
               selects it in its copies, so the layer scan hands it the
               whole pool it carries: slicing one layer out first would
               make XLA materialize that slice for the custom call (the
               compile rehearsal measured a whole-pool temporary)
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
    assert kv_p == kv and hd_p >= hd, (k_pool.shape, q.shape)
    assert v_pool.shape == k_pool.shape
    if hd_p % LANES and not interpret:
        raise ValueError(f"the pool's heads are {hd_p} wide: Mosaic copies "
                         f"its pages only at whole {LANES}-lane tiles "
                         f"(size it with pool_head_dim)")
    n_pages = page_table.shape[1]
    assert page_table.shape == (b, n_pages)
    scale = scale if scale is not None else hd ** -0.5
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    cg = c * g
    kvb, pages = kernel_blocks(q.shape, k_pool.shape, q.dtype.itemsize,
                               k_pool.dtype.itemsize, n_pages)
    # [B, C, KV, G, hd] -> [B, KV, C*G, hd_p]: rows are (chunk, group)
    qh = q.transpose(0, 2, 1, 3, 4).reshape(b, kv, cg, hd)
    if hd_p > hd:       # zero lanes meet the pool's lanes past hd
        qh = jnp.pad(qh, ((0, 0),) * 3 + ((0, hd_p - hd),))

    scalars = (layer, page_table, pos, n_new)
    kernel = _paged_kernel
    if window is not None:
        scalars = (layer, jnp.reshape(jnp.asarray(window, jnp.int32), (1,)),
                   page_table, pos, n_new)
        kernel = _windowed_kernel

    def lane(b, h, *scalars):
        return (b, h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(b, kv // kvb),
        in_specs=[
            pl.BlockSpec((1, kvb, cg, hd_p), lane),
            # the pools stay in HBM: the body copies their pages
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, kvb, cg, hd_p), lane),
        scratch_shapes=[
            pltpu.VMEM((kvb, cg, 1), jnp.float32),      # running max
            pltpu.VMEM((kvb, cg, 1), jnp.float32),      # running denominator
            pltpu.VMEM((kvb, cg, hd_p), jnp.float32),   # output accumulator
            pltpu.VMEM((2, kvb, pages * bs, hd_p), k_pool.dtype),  # K blocks
            pltpu.VMEM((2, kvb, pages * bs, hd_p), v_pool.dtype),  # V blocks
            pltpu.SemaphoreType.DMA((2, 2)),             # [K/V, slot]
        ],
    )
    out = pl.pallas_call(
        functools.partial(kernel, scale=scale, block_size=bs, group=g,
                          n_pages=n_pages, pages=pages),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qh.shape, q.dtype),
        # in order on one core: the first grid step zeroes the V buffer
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="paged_attention",
    )(*scalars, qh, k_pool, v_pool)
    return out[..., :hd].reshape(b, kv, c, g, hd).transpose(0, 2, 1, 3, 4)


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
        return pool[page_table][..., :hd].swapaxes(2, 3).reshape(
            b, n_pages * bs, kv, hd)

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
