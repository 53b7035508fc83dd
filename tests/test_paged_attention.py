"""Pallas paged-attention kernel vs the pure-JAX page-table reference.

The kernel-parity suite for the serving stack's paged decode pathway
(`kernels/paged_attention.py`): property-based parity in interpret mode
across head counts, page sizes, ragged last pages and GQA ratios, the
edge geometries (single-token sequence, exactly-full last page), the
no-aliasing guarantee for refcount-shared prefix pages, and the kernel
driven through the full `PagedServeEngine` against the gather fallback.

Everything runs the real kernel body — interpret mode off-accelerator
(forced by the session fixture in conftest), native Mosaic on TPU.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # invariants still run via the conftest property loop
    from conftest import given, settings, st

from repro.configs import ALL_ARCHS
from repro.kernels import paged_attention as pa
from repro.kernels.paged_attention import (MAX_BLOCK_KEYS, VMEM_BUDGET,
                                           block_pages, block_vmem,
                                           first_page, heads_per_block,
                                           last_page, paged_attention_pallas,
                                           paged_attention_ref,
                                           pages_per_block, pool_head_dim,
                                           walk_blocks)

pytestmark = pytest.mark.kernel

RNG = np.random.default_rng(1234)


def _case(b, c, kv, g, hd, bs, n_pages, num_blocks, pos, n_new, *,
          dtype=jnp.float32, seed=0):
    """Build one paged-attention problem: random pool, a random
    *permutation* page table (so physical order never coincides with
    logical order by accident), per-lane pos/n_new."""
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, c, kv, g, hd)), dtype)
    kp = jnp.asarray(rng.standard_normal((num_blocks, kv, bs, hd)), dtype)
    vp = jnp.asarray(rng.standard_normal((num_blocks, kv, bs, hd)), dtype)
    perm = rng.permutation(num_blocks)[:b * n_pages]
    pt = jnp.asarray(perm.reshape(b, n_pages).astype(np.int32))
    return (q, kp, vp, pt, jnp.asarray(pos, jnp.int32),
            jnp.asarray(n_new, jnp.int32))


def _assert_parity(args, *, rtol=2e-5, atol=2e-5):
    """Kernel (interpret) vs reference on every lane's valid rows
    (rows >= n_new are garbage both sides discard by contract)."""
    q, kp, vp, pt, pos, n_new = args
    out = paged_attention_pallas(q, kp, vp, pt, pos, n_new, interpret=True)
    ref = paged_attention_ref(q, kp, vp, pt, pos, n_new)
    for b in range(q.shape[0]):
        n = int(n_new[b])
        np.testing.assert_allclose(
            np.asarray(out, np.float32)[b, :n],
            np.asarray(ref, np.float32)[b, :n],
            rtol=rtol, atol=atol,
            err_msg=f"lane {b}: pos={int(pos[b])} n_new={n}")


# ----------------------------------------------------------- property sweep


@given(st.sampled_from([1, 2]),            # kv heads
       st.sampled_from([1, 2, 4]),         # GQA group (q heads per kv)
       st.sampled_from([4, 8, 16]),        # page size
       st.integers(1, 4),                  # chunk C
       st.integers(0, 10**9),              # case seed
       st.integers(0, 10**9))              # pos/n_new seed
@settings(max_examples=12, deadline=None)
def test_kernel_matches_gather_reference(kv, g, bs, c, seed, state_seed):
    """Parity across head counts, page sizes, GQA ratios, and random
    ragged per-lane (pos, n_new) states — including idle lanes."""
    b, hd, n_pages = 2, 32, 4
    rng = np.random.default_rng(state_seed)
    # lane state: pos + n_new must fit the table; n_new <= c; allow 0
    n_new = rng.integers(0, c + 1, size=b)
    pos = np.array([rng.integers(0, n_pages * bs - max(int(n), 1) + 1)
                    for n in n_new])
    args = _case(b, c, kv, g, hd, bs, n_pages, num_blocks=3 * n_pages,
                 pos=pos, n_new=n_new, seed=seed)
    _assert_parity(args)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_kernel_dtype_sweep(dtype, tol):
    args = _case(2, 4, 2, 2, 32, 8, 4, num_blocks=12,
                 pos=[13, 27], n_new=[4, 1], dtype=dtype, seed=7)
    _assert_parity(args, rtol=tol, atol=tol)


def _served_case(kv, g, hd, c, *, bs=16, n_pages=6, lanes=None,
                 window=None, layers=1, layer=0, pool_hd=None, seed=0):
    """Lanes as the engine leaves them, bf16 as the pool holds them.
    ``lanes`` lists each slot's ``(rows after this call, rows fed)``, or
    None for an idle slot (cleared table row, pos 0, n_new 0); the
    default is an idle slot, a lane whose last row sits mid-page and one
    whose last page is exactly full.  A running lane's row holds its
    written pages, then pages reserved at admission for its answer
    (never written) up to the table's end.  Every page its walk must
    not read holds NaN here: the reserved ones, those before a window's
    first page, and every page of the pool's other layers; physical page
    0 (an idle slot's) is finite.  ``pool_hd`` pads the pool's heads with
    zero lanes.  Returns the arguments, and the same with those pages
    finite for the reference: its softmax gives them weight 0, and 0 ×
    NaN would poison its valid rows."""
    rng = np.random.default_rng(seed)
    if lanes is None:
        lanes = [None, (2 * bs + 5, c), (3 * bs, c)]
    pool_hd = pool_hd or hd
    running = [ln for ln in lanes if ln is not None]
    nb = 1 + len(running) * n_pages
    shape = (layers, nb, kv, bs, pool_hd)
    kp = np.zeros(shape, np.float32)
    vp = np.zeros(shape, np.float32)
    kp[..., :hd] = rng.standard_normal(shape[:-1] + (hd,))
    vp[..., :hd] = rng.standard_normal(shape[:-1] + (hd,))
    ids = iter(rng.permutation(np.arange(1, nb)))
    pt = np.zeros((len(lanes), n_pages), np.int32)
    pos, n_new = np.zeros(len(lanes), np.int32), np.zeros(len(lanes),
                                                          np.int32)
    unread = [p for p in range(nb) if p]
    for slot, lane in enumerate(lanes):
        if lane is None:
            continue
        end, fed = lane
        pos[slot], n_new[slot] = end - fed, fed
        pt[slot] = [next(ids) for _ in range(n_pages)]
        first = int(first_page(end - fed, window, bs)) if window else 0
        last = int(last_page(end - fed, fed, bs, n_pages))
        for j in range(first, last + 1):
            unread.remove(pt[slot, j])
    kn, vn = kp.copy(), vp.copy()
    kn[:, unread] = vn[:, unread] = np.nan
    others = [li for li in range(layers) if li != layer]
    kn[others] = vn[others] = np.nan
    q = rng.standard_normal((len(lanes), c, kv, g, hd))
    bf = lambda a: jnp.asarray(a, jnp.bfloat16)
    state = (jnp.asarray(pt), jnp.asarray(pos), jnp.asarray(n_new))
    kw = dict(window=window, layer=layer)
    return ((bf(q), bf(kn), bf(vn)) + state, kw,
            (bf(q), bf(kp[layer]), bf(vp[layer])) + state)


def _assert_served_parity(served, kw, finite):
    """The kernel on the pool with NaN pages outside every walk: every
    output row finite, and each lane's valid rows as the reference gives
    them on the same layer with those pages finite."""
    out = np.asarray(paged_attention_pallas(*served, interpret=True, **kw),
                     np.float32)
    assert np.isfinite(out).all()
    ref = np.asarray(paged_attention_ref(*finite, window=kw["window"]),
                     np.float32)
    n_new = np.asarray(served[5])
    for b, n in enumerate(n_new.tolist()):
        np.testing.assert_allclose(out[b, :n], ref[b, :n], rtol=1e-2,
                                   atol=1e-2, err_msg=f"lane {b}")


_HEADS = [((4, 1), "mha-kv4"), ((8, 1), "mha-kv8"), ((2, 4), "gqa-kv2-g4"),
          ((4, 2), "gqa-kv4-g2")]
_SERVED = [pytest.param(kv, g, hd, c, 0, {}, id=f"{name}-{hd}-{cid}")
           for (kv, g), name in _HEADS for hd in (96, 128)
           for c, cid in ((1, "decode"), (32, "chunk32"))] + [
    # three blocks of two pages, the last one partial
    pytest.param(2, 4, 128, 8, 32, dict(n_pages=6, lanes=[(4 * 16 + 3, 8)]),
                 id="walk-of-several-blocks-ending-partial"),
    # the window's first page (4) falls inside a block of three counted
    # from page 0, and mid-page
    pytest.param(2, 4, 128, 8, 48, dict(
        n_pages=9, window=40, lanes=[(7 * 16 + 5, 8), (3 * 16 + 1, 1)]),
        id="window-first-page-mid-block"),
    # lanes of very different lengths, idle slots between them: each grid
    # step walks its own lane, whatever the last one left in the buffers
    pytest.param(4, 2, 96, 4, 32, dict(
        n_pages=12, pool_hd=128, lanes=[(1, 1), None, (11 * 16 + 9, 4),
                                        (2 * 16, 1), None, (6 * 16 + 2, 2)]),
        id="lanes-of-mixed-lengths-and-idle"),
    # 7 table pages in blocks of 3: the last block of a full-table walk
    # holds one page
    pytest.param(2, 2, 128, 4, 48, dict(n_pages=7, lanes=[(7 * 16, 4),
                                                          (5 * 16 + 1, 1)]),
                 id="table-not-a-multiple-of-the-block"),
    # a stacked pool read at layer 2 of 3, the others NaN
    pytest.param(2, 2, 128, 4, 32, dict(
        n_pages=6, layers=3, layer=2, window=24,
        lanes=[(5 * 16 + 4, 4), None, (2 * 16 + 3, 1)]),
        id="stacked-pool-layer-2"),
]


@pytest.mark.parametrize("kv,g,hd,c,max_keys,walk", _SERVED)
def test_kernel_matches_reference_on_served_lanes(kv, g, hd, c, max_keys,
                                                  walk, monkeypatch):
    """All kv heads of a lane in one grid step: valid rows match the
    reference, and no page outside a lane's walk — reserved past its
    last page, before its window, in another layer: all NaN here —
    reaches an output row.  ``max_keys`` cuts the compute block to that
    many keys (0: the kernel's own) so small tables walk several blocks
    (the walk's pages themselves are pinned by
    ``test_walk_is_clamped_to_each_lanes_last_page``)."""
    if max_keys:
        monkeypatch.setattr(pa, "MAX_BLOCK_KEYS", max_keys)
    _assert_served_parity(*_served_case(
        kv, g, hd, c, seed=kv * 1000 + g * 100 + hd + c + max_keys, **walk))


def test_kv_heads_split_across_grid_steps_when_they_do_not_fit(monkeypatch):
    """A budget that holds two of four kv heads splits the heads over the
    grid's middle axis; the result is the same."""
    monkeypatch.setattr(pa, "VMEM_BUDGET", 400 * 2**10)
    assert heads_per_block(4, 32, 16, 96, 2, 2) == 2
    _assert_served_parity(*_served_case(4, 1, 96, 32, seed=77))


def test_walk_is_clamped_to_each_lanes_last_page():
    """Block i of a lane's walk names pages ``first + i·P`` on, in order,
    and copies those up to the lane's last page: over the walk every page
    from the first (the window's, else 0) to the last is copied once and
    no other (rows past the table's end clip to its last page).
    ``walk_blocks`` is the count of blocks the kernel's loop visits,
    counted here one by one."""
    bs = 16
    lanes = [(0, 0), (20, 1), (100, 32), (127, 1), (300, 8)]
    for n_pages, pages, window in [(8, 1, None), (8, 3, None), (8, 8, None),
                                   (20, 3, 40), (20, 4, 64), (7, 3, None),
                                   (20, 16, 1000)]:
        for pos, nn in lanes:
            last = int(last_page(pos, nn, bs, n_pages))
            first = int(first_page(pos, window, bs)) if window else 0
            first = min(first, last)
            n_blocks = 0
            while first + n_blocks * pages <= last:     # the loop's visits
                n_blocks += 1
            case = (n_pages, pages, window, pos, nn)
            assert int(walk_blocks(pos, nn, window, bs, pages,
                                   n_pages)) == n_blocks, case
            copied = [[p for p in block_pages(first, i, pages) if p <= last]
                      for i in range(n_blocks)]
            for i, block in enumerate(copied):
                want = [first + i * pages + k for k in range(pages)
                        if first + i * pages + k <= last]
                assert block == want and block, case
            flat = [p for block in copied for p in block]
            assert flat == list(range(first, last + 1)), case
    assert last_page(jnp.array([0, 20, 100]), jnp.array([0, 1, 32]), bs,
                     8).tolist() == [0, 1, 7]


def test_every_config_folds_all_kv_heads_into_one_grid_step():
    """The serving geometry (chunk 32, 16-row pages, bf16) of every
    configuration fits all its kv heads in one block; a shape whose
    heads would not fit is cut to the largest divisor that does."""
    for cfg in ALL_ARCHS.values():
        if not cfg.n_kv_heads:
            continue
        kv, g = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
        assert heads_per_block(kv, 32 * g, 16, cfg.resolved_head_dim,
                               2, 2) == kv, cfg.name
    # 512 query rows of hd 256 take about 3.1 MB a head: two fit 8 MiB
    assert heads_per_block(48, 512, 16, 256, 2, 2) == 2
    assert heads_per_block(1, 4096, 16, 256, 4, 4) == 1   # never below one
    assert VMEM_BUDGET < 16 * 2**20


def test_every_config_walks_several_pages_a_block():
    """At chunk 32, 16-row pages and bf16, every configuration's kv-head
    block gets a compute block of at least two pages, within the VMEM
    budget and the key cap, with the pool's heads at whole tiles; one
    page more would pass one of them.  Where nothing fits, one page."""
    for cfg in ALL_ARCHS.values():
        if not cfg.n_kv_heads:
            continue
        kv, g = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
        hd = pool_head_dim(cfg.resolved_head_dim)
        assert hd % 128 == 0 and hd - cfg.resolved_head_dim < 128
        kvb = heads_per_block(kv, 32 * g, 16, hd, 2, 2)
        p = pages_per_block(kvb, 32 * g, 16, hd, 2, 2, 256)
        assert p >= 2, cfg.name
        assert block_vmem(kvb, 32 * g, 16, hd, 2, 2, p) <= VMEM_BUDGET
        assert (p + 1) * 16 > MAX_BLOCK_KEYS or block_vmem(
            kvb, 32 * g, 16, hd, 2, 2, p + 1) > VMEM_BUDGET, cfg.name
    assert pages_per_block(4, 256, 16, 128, 2, 2, 3) == 3   # the table's
    assert pages_per_block(1, 4096, 16, 256, 4, 4, 256) == 1


# ------------------------------------------------------------------- edges


def test_single_token_sequence():
    """pos=0, n_new=1: the kernel's smallest case — one valid row in one
    page, every other position masked."""
    args = _case(2, 4, 2, 2, 32, 8, 4, num_blocks=8,
                 pos=[0, 0], n_new=[1, 1], seed=3)
    _assert_parity(args)
    # and the output equals plain attention over that single position:
    # softmax over one element is 1, so out == v at the row the table maps
    q, kp, vp, pt, pos, n_new = args
    out = paged_attention_pallas(q, kp, vp, pt, pos, n_new, interpret=True)
    for b in range(2):
        want = np.asarray(vp)[int(pt[b, 0]), :, 0]       # [kv, hd]
        got = np.asarray(out)[b, 0]                      # [kv, g, hd]
        np.testing.assert_allclose(got, np.repeat(
            want[:, None], got.shape[1], axis=1), rtol=2e-5, atol=2e-5)


def test_exactly_full_last_page():
    """pos + n_new landing exactly on a page boundary must not read the
    following (unallocated / stale) page."""
    bs, n_pages = 8, 4
    for total_pages in (1, 2, 4):
        pos = total_pages * bs - 2
        args = _case(2, 2, 2, 2, 32, bs, n_pages, num_blocks=12,
                     pos=[pos, pos], n_new=[2, 2], seed=11 + total_pages)
        _assert_parity(args)


def test_ragged_last_page_lengths():
    """Every tail length of the last page, exercised one by one."""
    bs = 8
    for tail in range(1, bs + 1):
        pos = bs + tail - 1                  # last valid row index
        args = _case(2, 1, 2, 2, 32, bs, 4, num_blocks=12,
                     pos=[pos, pos], n_new=[1, 1], seed=100 + tail)
        _assert_parity(args)


def test_masked_rows_are_finite():
    """Idle lanes (n_new=0) and garbage chunk rows must come out finite —
    the engine discards them, but NaNs would poison donated buffers."""
    args = _case(2, 4, 2, 2, 32, 8, 4, num_blocks=8,
                 pos=[0, 5], n_new=[0, 2], seed=5)
    q, kp, vp, pt, pos, n_new = args
    out = paged_attention_pallas(q, kp, vp, pt, pos, n_new, interpret=True)
    assert np.isfinite(np.asarray(out, np.float32)).all()


# -------------------------------------------- shared prefix pages: no alias


def test_shared_prefix_pages_are_never_written():
    """Two slots whose page tables share refcounted prefix pages must not
    alias writes: the chunk scatter targets each lane's private pages
    only, and the shared page's bits stay identical."""
    from repro.configs import ALL_ARCHS, reduced
    from repro.models import build
    from repro.models.attention import paged_chunk_decode_attention

    cfg = reduced(ALL_ARCHS["deepseek-7b"])
    model = build(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    p = jax.tree.map(lambda a: a[0], params["layers"])["attn"]
    bs, c, nb = 8, 4, 6
    rng = np.random.default_rng(0)
    kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    kp = jnp.asarray(rng.standard_normal((nb, kv, bs, hd)), jnp.bfloat16)
    vp = jnp.asarray(rng.standard_normal((nb, kv, bs, hd)), jnp.bfloat16)
    # both lanes share physical page 0 as their logical block 0; private
    # continuation pages 1 and 2 respectively
    pt = jnp.asarray(np.array([[0, 1, 0], [0, 2, 0]], np.int32))
    x = jnp.asarray(rng.standard_normal((2, c, cfg.d_model)), jnp.bfloat16)
    pos = jnp.asarray([bs, bs], jnp.int32)     # writes start past page 0
    n_new = jnp.asarray([c, c], jnp.int32)

    before = {i: np.asarray(kp[i]).copy() for i in range(nb)}
    # a one-layer pool [1, nb, kv, bs, hd], layer 0
    _, kp2, vp2 = paged_chunk_decode_attention(cfg, p, x, kp[None], vp[None],
                                               0, pt, pos, n_new,
                                               attention="reference")
    after = np.asarray(kp2[0])
    # the shared page is bit-identical; each private page changed exactly
    # its first c rows (of every kv head: pages are [kv, bs, hd]);
    # everything else untouched
    assert (after[0] == before[0]).all(), "shared prefix page was written"
    for lane, page in ((0, 1), (1, 2)):
        for h in range(kv):
            assert not (after[page][h, :c] == before[page][h, :c]).all()
        assert (after[page][:, c:] == before[page][:, c:]).all()
    for untouched in (3, 4, 5):
        assert (after[untouched] == before[untouched]).all()


def test_two_slots_reading_shared_pages_agree_with_ref():
    """Shared pages attended by two lanes at once (the zero-copy prefix
    reuse case) — parity with the gather reference."""
    b, c, kv, g, hd, bs, n_pages = 2, 2, 2, 2, 32, 8, 4
    rng = np.random.default_rng(21)
    q = jnp.asarray(rng.standard_normal((b, c, kv, g, hd)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((10, kv, bs, hd)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((10, kv, bs, hd)), jnp.float32)
    # both lanes share pages 4 and 7 as blocks 0-1, then diverge
    pt = jnp.asarray(np.array([[4, 7, 1, 2], [4, 7, 5, 6]], np.int32))
    pos = jnp.asarray([2 * bs + 3, 3 * bs + 1], jnp.int32)
    n_new = jnp.asarray([2, 1], jnp.int32)
    _assert_parity((q, kp, vp, pt, pos, n_new))


# --------------------------------------------------- kernel through engine


@pytest.mark.slow
def test_kernel_through_engine_matches_gather_fallback():
    """Force the Pallas kernel (interpret mode) onto the live serving
    path and hold the full engine to the gather fallback's streams —
    the kernel analogue of the engine oracle, on a seeded trace."""
    from repro.configs import ALL_ARCHS, reduced
    from repro.kernels import ops
    from repro.models import build
    from repro.serve.engine import PagedServeEngine, Request, token_matrix

    cfg = reduced(ALL_ARCHS["deepseek-7b"])
    params = build(cfg).init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)
    shared = rng.integers(0, cfg.vocab_size, size=16).tolist()
    tails = [rng.integers(0, cfg.vocab_size, size=3 + i).tolist()
             for i in range(3)]

    def make():
        return [Request(rid=i, prompt=shared + tails[i], max_new=4)
                for i in range(3)]

    def run(force_kernel):
        # the engine reads the attention choice once, at construction
        prev = ops.FORCE_PAGED_KERNEL
        ops.FORCE_PAGED_KERNEL = force_kernel
        try:
            eng = PagedServeEngine(build(cfg), params, slots=2, max_len=48,
                                   block_size=8, chunk=4)
        finally:
            ops.FORCE_PAGED_KERNEL = prev
        assert eng.report()["attention"] == (
            "pallas" if force_kernel else "reference")
        mat = token_matrix(eng.run(make()), 3, 4)
        eng.alloc.check()
        assert eng.pstats.cached_tokens > 0     # prefix reuse really on
        return mat

    kernel_mat = run(True)
    gather_mat = token_matrix(
        PagedServeEngine(build(cfg), params, slots=2, max_len=48,
                         block_size=8, chunk=4,
                         kernel="gather").run(make()), 3, 4)
    assert (kernel_mat >= 0).all()
    assert (kernel_mat == gather_mat).all()
    # and the ref-dispatch default (CPU) agrees too
    ref_mat = run(False)
    assert (ref_mat == gather_mat).all()


def test_kernel_selects_the_layer_of_a_stacked_pool():
    """The serving step hands the kernel the whole ``[layers, ...]`` pool
    and a traced layer index: each layer attends its own pages."""
    b, c, kv, g, hd, bs, n_pages, nb = 2, 2, 2, 2, 32, 8, 3, 8
    layers = []
    for layer in range(3):
        args = _case(b, c, kv, g, hd, bs, n_pages, nb, pos=[5, 17],
                     n_new=[2, 1], seed=40 + layer)
        layers.append(args)
    q, _, _, pt, pos, n_new = layers[0]
    kp = jnp.stack([a[1] for a in layers])
    vp = jnp.stack([a[2] for a in layers])
    for layer in range(3):
        got = jax.jit(lambda li: paged_attention_pallas(
            q, kp, vp, pt, pos, n_new, layer=li, interpret=True))(layer)
        want = paged_attention_ref(q, layers[layer][1], layers[layer][2],
                                   pt, pos, n_new)
        for lane in range(b):
            n = int(n_new[lane])
            np.testing.assert_allclose(np.asarray(got)[lane, :n],
                                       np.asarray(want)[lane, :n],
                                       rtol=2e-5, atol=2e-5)
