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

from repro.kernels.paged_attention import (paged_attention_pallas,
                                           paged_attention_ref)

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
