"""KV memory tiering: host swap pool invariants, prefix-page spill and
page-in, swap-restore token exactness, the swap-vs-recompute cost model,
and randomized preempt/readmit/cancel/evict interleavings on both KV
pathways (satellite property suite)."""
import jax
import numpy as np
import pytest

from repro.configs import ALL_ARCHS, reduced
from repro.models import build
from repro.serve.api import SamplingParams
from repro.serve.engine import PagedServeEngine, Request
from repro.serve.paging import (BlockAllocator, BlockAllocatorError,
                                DevicePageView, HostSwapPool, PrefixCache,
                                chain_hashes)
from repro.serve.scheduler import PREEMPTED, RUNNING, SwapCostModel


@pytest.fixture(scope="module")
def served():
    cfg = reduced(ALL_ARCHS["deepseek-7b"])
    model = build(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return cfg, model, params


# ================================================== host swap pool units


def test_device_pages_reach_the_host_at_the_model_head_width():
    """A pool wider than the model's heads (pad lanes, as the kernel's
    page copies need) moves pages to and from the host tier at the
    model's ``head_dim``: the host holds no pad lanes, a written page
    reads back as it was, its pad lanes stay zero, and ``nbytes`` counts
    the heads alone."""
    view = DevicePageView(4, 4, 2, 1, 3, np.float32, slots=1, max_pages=2,
                          width=8)
    assert view.k.shape == (2, 4, 1, 4, 8)
    k_rows, v_rows = _rows(5)
    view.write_page(2, k_rows, v_rows)
    k_back, v_back = view.read_page(2)
    assert k_back.shape == k_rows.shape
    np.testing.assert_array_equal(k_back, k_rows)
    np.testing.assert_array_equal(v_back, v_rows)
    assert not np.asarray(view.k)[..., 3:].any()
    assert view.nbytes == 2 * 2 * 4 * 1 * 4 * 3 * 4




def _rows(fill, shape=(2, 1, 4, 3)):
    # one device page: (layers, kv, block_size, hd), head-major as the
    # paged pool lays it out
    return (np.full(shape, float(fill), np.float32),
            np.full(shape, -float(fill), np.float32))


def test_host_pool_roundtrip_refcounts_and_copy_semantics():
    pool = HostSwapPool(capacity=4, block_size=4)
    k, v = _rows(1)
    hid = pool.put(k, v)
    k[:] = 99.0                      # put copies: mutation must not leak in
    kk, vv = pool.get(hid)
    assert float(kk[0, 0, 0, 0]) == 1.0
    assert float(vv[0, 0, 0, 0]) == -1.0
    pool.incref(hid)
    pool.decref(hid)
    assert pool.in_use == 1 and pool.refcount(hid) == 1
    pool.decref(hid, swapped_in=True)
    assert pool.in_use == 0 and pool.refcount(hid) == 0
    assert pool.stats.swap_out_pages == 1
    assert pool.stats.swap_in_pages == 1
    assert pool.stats.dropped_pages == 0
    with pytest.raises(BlockAllocatorError):
        pool.get(hid)
    with pytest.raises(BlockAllocatorError):
        pool.decref(hid)
    with pytest.raises(BlockAllocatorError):
        pool.incref(hid)
    pool.check()


def test_host_pool_capacity_full_returns_none():
    pool = HostSwapPool(capacity=1, block_size=4)
    first = pool.put(*_rows(1))
    assert first is not None
    assert pool.put(*_rows(2)) is None       # graceful: caller recomputes
    pool.check()
    pool.decref(first)
    assert pool.put(*_rows(3)) is not None   # capacity freed by the drop
    assert pool.stats.dropped_pages == 1
    assert pool.stats.peak_in_use == 1
    pool.check()


def test_host_pool_ids_are_monotonic_never_reused():
    pool = HostSwapPool(capacity=2, block_size=4)
    a = pool.put(*_rows(1))
    pool.decref(a)
    b = pool.put(*_rows(2))
    assert b != a                # a stale id can never alias fresh storage
    pool.check()


# ================================================ prefix-cache spill units


def _spill_cache(num_blocks=4, block_size=2, capacity=8):
    """PrefixCache wired to fake spill hooks backed by a dict."""
    alloc = BlockAllocator(num_blocks, block_size)
    cache = PrefixCache(alloc)
    host: dict[int, int] = {}
    dropped: list[int] = []
    counter = iter(range(1000))

    def spill_out(bid):
        hid = next(counter)
        host[hid] = bid
        return hid

    def page_in(hid):
        if alloc.num_free == 0:
            return None
        assert hid in host
        return alloc.alloc()

    def drop(hid):
        dropped.append(hid)
        del host[hid]

    cache.attach_spill(spill_out=spill_out, page_in=page_in, drop=drop,
                       capacity=capacity)
    return alloc, cache, host, dropped


def test_prefix_spill_and_match_page_in_roundtrip():
    alloc, cache, host, dropped = _spill_cache()
    toks = [1, 2, 3, 4]
    h0, h1 = chain_hashes(toks, 2)
    b0, b1 = alloc.alloc(), alloc.alloc()
    cache.insert(h0, b0)
    cache.insert(h1, b1)
    alloc.decref(b0)
    alloc.decref(b1)                 # cache is now sole owner
    assert cache.evict(2) == 2
    assert cache.spilled == 2 and len(cache) == 0
    assert cache.stats.spills == 2 and len(host) == 2

    n, bids = cache.match(toks)      # pages both entries back in
    assert n == 4 and len(bids) == 2
    assert cache.stats.restores == 2
    assert cache.spilled == 0 and len(host) == 0 and len(dropped) == 2
    for bid in bids:
        alloc.decref(bid)
    alloc.check()


def test_prefix_spill_page_in_oom_stops_match_at_resident_prefix():
    alloc, cache, host, _ = _spill_cache(num_blocks=2)
    toks = [1, 2, 3, 4]
    h0, h1 = chain_hashes(toks, 2)
    b0, b1 = alloc.alloc(), alloc.alloc()
    cache.insert(h0, b0)
    cache.insert(h1, b1)
    alloc.decref(b0)
    alloc.decref(b1)
    assert cache.evict(2) == 2
    # burn every device page: page-in has nowhere to restore to
    pinned = [alloc.alloc() for _ in range(alloc.num_free)]
    n, bids = cache.match(toks)
    assert n == 0 and bids == []
    assert cache.spilled == 2 and len(host) == 2   # entries stay parked
    for bid in pinned:
        alloc.decref(bid)
    alloc.check()


def test_prefix_insert_drops_stale_spilled_duplicate():
    alloc, cache, host, dropped = _spill_cache()
    toks = [5, 6]
    (h,) = chain_hashes(toks, 2)
    b = alloc.alloc()
    cache.insert(h, b)
    alloc.decref(b)
    assert cache.evict(1) == 1 and cache.spilled == 1
    b2 = alloc.alloc()               # a slot re-registers the same chain
    cache.insert(h, b2)
    assert cache.spilled == 0 and len(dropped) == 1   # spill superseded
    alloc.decref(b2)
    alloc.check()


def test_prefix_spill_capacity_bound_drops_oldest():
    alloc, cache, host, dropped = _spill_cache(num_blocks=4, capacity=1)
    toks = [1, 2, 3, 4]
    h0, h1 = chain_hashes(toks, 2)
    b0, b1 = alloc.alloc(), alloc.alloc()
    cache.insert(h0, b0)
    cache.insert(h1, b1)
    alloc.decref(b0)
    alloc.decref(b1)
    assert cache.evict(2) == 2
    assert cache.spilled == 1        # capacity=1: oldest spill dropped
    assert len(dropped) == 1 and len(host) == 1
    alloc.check()


# ============================================== cost model + engine units


def test_swap_cost_model_prefers_recompute_for_tiny_victims():
    m = SwapCostModel()              # 2.0/page vs 1.0/token
    assert not m.prefer_swap(pages=1, tokens=1)    # restore 2.0 > redo 1.0
    assert m.prefer_swap(pages=1, tokens=2)        # tie goes to swap
    assert m.prefer_swap(pages=4, tokens=100)


def _preempt_once(model, params, kernel, *, sampling=None, **kw):
    """Tight single-slot engine: lo runs, hi preempts it, both finish.
    Returns (engine, lo_handle, hi_handle)."""
    rng = np.random.default_rng(11)
    lo_p = rng.integers(0, 50, 12).tolist()
    hi_p = rng.integers(50, 100, 8).tolist()
    eng = PagedServeEngine(model, params, slots=1, max_len=64, block_size=4,
                           num_blocks=10, chunk=4, kernel=kernel, **kw)
    lo = eng.submit(Request(rid=0, prompt=lo_p, max_new=16, priority=0,
                            sampling=sampling), arrival=0.0)
    for _ in range(4):
        eng.step()
    hi = eng.submit(Request(rid=1, prompt=hi_p, max_new=6, priority=5,
                            sampling=sampling))
    eng.drain()
    return eng, lo, hi


@pytest.mark.parametrize("kernel", ["paged", "gather"])
@pytest.mark.parametrize("sampled", [False, True])
def test_swap_restore_is_token_exact(served, kernel, sampled):
    _, model, params = served
    sp = (SamplingParams(temperature=0.7, top_k=16, top_p=0.95, seed=13)
          if sampled else None)
    # uninterrupted reference on an ample pool
    rng = np.random.default_rng(11)
    lo_p = rng.integers(0, 50, 12).tolist()
    hi_p = rng.integers(50, 100, 8).tolist()
    ref = PagedServeEngine(model, params, slots=2, max_len=64, block_size=4,
                           num_blocks=32, chunk=4, kernel=kernel)
    ref_out = {r.rid: list(r.out) for r in ref.run(
        [Request(rid=0, prompt=list(lo_p), max_new=16, sampling=sp),
         Request(rid=1, prompt=list(hi_p), max_new=6, sampling=sp)])}

    eng, lo, hi = _preempt_once(model, params, kernel, sampling=sp)
    rep = eng.report()
    assert rep["preemptions"] >= 1
    assert rep["swap_ins"] >= 1 and rep["restored_tokens"] > 0
    assert rep["recompute_tokens"] == 0
    assert rep["swap_restore_rate"] == 1.0
    assert lo.req.out == ref_out[0] and hi.req.out == ref_out[1]
    eng.alloc.check()
    eng.host.check()
    assert eng.host.in_use == eng.prefix.spilled   # no leaked swap records


@pytest.mark.parametrize("kernel", ["paged", "gather"])
def test_swap_disabled_recomputes_and_stays_exact(served, kernel):
    _, model, params = served
    ref_eng, ref_lo, ref_hi = _preempt_once(model, params, kernel)
    eng, lo, hi = _preempt_once(model, params, kernel, swap=False)
    rep = eng.report()
    assert rep["preemptions"] >= 1
    assert rep["swap_ins"] == 0 and rep["swap_outs"] == 0
    assert rep["restored_tokens"] == 0 and rep["recompute_tokens"] > 0
    assert rep["swap_restore_rate"] == 0.0
    assert eng.host.in_use == 0
    # recompute and restore produce the same streams
    assert lo.req.out == ref_lo.req.out and hi.req.out == ref_hi.req.out


def test_swap_cost_model_override_forces_recompute(served):
    _, model, params = served
    costly = SwapCostModel(swap_cost_per_page=1e9)
    eng, lo, hi = _preempt_once(model, params, "paged", swap_cost=costly)
    rep = eng.report()
    assert rep["preemptions"] >= 1
    assert rep["swap_outs"] >= 1     # pages were parked ...
    assert rep["swap_ins"] == 0      # ... but the model refused the restore
    assert rep["recompute_tokens"] > 0
    # the refused restore's host pages were dropped at readmission
    assert eng.host.in_use == eng.prefix.spilled
    eng.host.check()


def test_host_tier_full_falls_back_to_recompute(served):
    _, model, params = served
    eng, lo, hi = _preempt_once(model, params, "paged", host_blocks=0)
    rep = eng.report()
    assert rep["preemptions"] >= 1
    assert rep["swap_ins"] == 0 and rep["restored_tokens"] == 0
    assert rep["recompute_tokens"] > 0
    assert eng.host.in_use == 0
    eng.host.check()


def test_cancel_while_preempted_releases_host_pages(served):
    _, model, params = served
    rng = np.random.default_rng(11)
    lo_p = rng.integers(0, 50, 12).tolist()
    hi_p = rng.integers(50, 100, 8).tolist()
    eng = PagedServeEngine(model, params, slots=1, max_len=64, block_size=4,
                           num_blocks=10, chunk=4, kernel="paged")
    lo = eng.submit(Request(rid=0, prompt=lo_p, max_new=16, priority=0),
                    arrival=0.0)
    for _ in range(4):
        eng.step()
    hi = eng.submit(Request(rid=1, prompt=hi_p, max_new=6, priority=5))
    eng.step()
    assert lo.entry.state == PREEMPTED
    parked = eng.host.in_use - eng.prefix.spilled
    assert parked > 0                # the victim's pages sit in the tier
    assert lo.cancel()
    assert eng.host.in_use == eng.prefix.spilled   # released at cancel
    eng.drain()
    eng.alloc.check()
    eng.host.check()


# ====================================== satellite: generation-budget guard


@pytest.mark.parametrize("engine_cls", ["contiguous", "paged"])
def test_max_new_must_leave_room_for_the_prompt(served, engine_cls):
    from repro.serve.engine import ServeEngine
    _, model, params = served
    if engine_cls == "contiguous":
        eng = ServeEngine(model, params, slots=1, max_len=16)
    else:
        eng = PagedServeEngine(model, params, slots=1, max_len=16,
                               block_size=4, num_blocks=8, chunk=4)
    with pytest.raises(ValueError, match="max_new"):
        eng.submit(Request(rid=0, prompt=[1, 2, 3], max_new=16))
    with pytest.raises(ValueError, match="max_new"):
        eng.submit(Request(rid=1, prompt=[1, 2, 3], max_new=99))
    with pytest.raises(ValueError, match="max_new"):
        eng.submit(Request(rid=2, prompt=[1, 2, 3], max_new=0))
    # the boundary case max_new == max_len - 1 is legal
    h = eng.submit(Request(rid=3, prompt=[1, 2, 3], max_new=15))
    eng.drain()
    assert len(h.req.out) == 15


# ================================= property suite: random interleavings


@pytest.mark.parametrize("kernel", ["paged", "gather"])
@pytest.mark.parametrize("seed", [0, 1])
def test_random_interleavings_match_uninterrupted_run(served, kernel, seed):
    """Random priorities/arrivals/cancels on a tight pool: every request
    that survives must emit exactly the stream an unconstrained engine
    produced, and neither the device allocator nor the host tier may
    leak a page."""
    cfg, model, params = served
    rng = np.random.default_rng(seed)
    n_req = 5
    shared = rng.integers(0, cfg.vocab_size, 8).tolist()
    proto = []
    for rid in range(n_req):
        tail = rng.integers(0, cfg.vocab_size,
                            int(rng.integers(2, 9))).tolist()
        prompt = (shared + tail) if rng.integers(0, 2) else tail
        sp = (SamplingParams(temperature=0.8, top_k=20, seed=5)
              if rng.integers(0, 2) else None)
        proto.append(dict(prompt=prompt, max_new=int(rng.integers(3, 7)),
                          priority=int(rng.integers(0, 3)),
                          arrival=float(rng.integers(0, 6)), sampling=sp))

    def reqs():
        return [Request(rid=i, prompt=list(p["prompt"]),
                        max_new=p["max_new"], priority=p["priority"],
                        sampling=p["sampling"])
                for i, p in enumerate(proto)]

    ref = PagedServeEngine(model, params, slots=n_req, max_len=64,
                           block_size=4, num_blocks=64, chunk=4,
                           kernel=kernel)
    ref_out = {r.rid: list(r.out) for r in ref.run(reqs())}

    eng = PagedServeEngine(model, params, slots=2, max_len=64, block_size=4,
                           num_blocks=12, chunk=4, kernel=kernel)
    handles = [eng.submit(r, arrival=p["arrival"])
               for r, p in zip(reqs(), proto)]
    cancelled: set[int] = set()
    steps = 0
    while eng.has_work():
        eng.step()
        steps += 1
        if steps % 4 == 0 and rng.integers(0, 2):
            victim = int(rng.integers(0, n_req))
            if handles[victim].cancel():
                cancelled.add(victim)
        eng.alloc.check()
        eng.host.check()
        assert steps < 2000, "interleaved run failed to converge"

    for rid in range(n_req):
        if rid in cancelled:
            continue
        assert handles[rid].req.out == ref_out[rid], (
            f"seed={seed} kernel={kernel} rid={rid} diverged")
    assert eng.host.in_use == eng.prefix.spilled   # swap records all drained
    eng.alloc.check()
    eng.host.check()
