"""Unified request-lifecycle serving API: the Engine protocol over both
engines, streaming handles, cancellation releasing pages and prefix-cache
references, and counter-based sampled decoding that is deterministic and
replayable across engines, runs, and preemption."""
import jax
import numpy as np
import pytest

from repro.configs import ALL_ARCHS, reduced
from repro.models import build
from repro.serve import (Engine, PagedServeEngine, Request, RequestHandle,
                         SamplingParams, ServeEngine, compare_engines,
                         run_requests, token_matrix)

SAMPLED = SamplingParams(temperature=0.9, top_k=24, top_p=0.95, seed=17)


@pytest.fixture(scope="module")
def served():
    cfg = reduced(ALL_ARCHS["deepseek-7b"])
    model = build(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return cfg, model, params


def _prompts(cfg, n=4, shared=18, seed=7):
    rng = np.random.default_rng(seed)
    pre = rng.integers(0, cfg.vocab_size, size=shared).tolist()
    return [pre + rng.integers(0, cfg.vocab_size, size=4 + i).tolist()
            for i in range(n)]


def _paged(model, params, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("block_size", 8)
    kw.setdefault("chunk", 4)
    return PagedServeEngine(model, params, **kw)


# ------------------------------------------------------------- protocol


def test_both_engines_satisfy_the_protocol(served):
    cfg, model, params = served
    paged = _paged(model, params)
    contig = ServeEngine(model, params, slots=2, max_len=64)
    assert isinstance(paged, Engine) and isinstance(contig, Engine)
    for eng in (paged, contig):
        for method in ("submit", "step", "drain", "cancel", "has_work",
                       "report"):
            assert callable(getattr(eng, method))


def test_run_shim_has_one_shape_on_both_engines(served):
    """The retired run(list) call shape survives as one uniform shim:
    requests + optional arrivals, identical on both engines."""
    cfg, model, params = served
    prompts = _prompts(cfg, n=3)

    def reqs():
        return [Request(rid=i, prompt=list(p), max_new=4)
                for i, p in enumerate(prompts)]

    a = ServeEngine(model, params, slots=2, max_len=64).run(
        reqs(), arrivals=[0.0, 0.0, 1.0])
    b = _paged(model, params).run(reqs(), arrivals=[0.0, 0.0, 1.0])
    assert (token_matrix(a, 3, 4) == token_matrix(b, 3, 4)).all()
    # and the function-shaped shim drives any Engine
    c = run_requests(_paged(model, params), reqs())
    assert len(c) == 3


def test_out_of_order_arrivals_agree_across_engines(served):
    """A future-dated request submitted first must not block a ready one
    behind it — on either engine (one protocol, one arrival semantics)."""
    cfg, model, params = served
    prompts = _prompts(cfg, n=2)

    def reqs():
        return [Request(rid=0, prompt=list(prompts[0]), max_new=3),
                Request(rid=1, prompt=list(prompts[1]), max_new=3)]

    for eng in (ServeEngine(model, params, slots=1, max_len=64),
                _paged(model, params, slots=1)):
        done = eng.run(reqs(), arrivals=[50.0, 0.0])
        assert [r.rid for r in done] == [1, 0]   # ready rid 1 served first


def test_handle_streams_tokens_incrementally(served):
    cfg, model, params = served
    eng = _paged(model, params)
    h = eng.submit(Request(rid=0, prompt=_prompts(cfg)[0], max_new=6))
    assert isinstance(h, RequestHandle) and not h.done
    seen = []
    for tok in h:
        seen.append(tok)
        # tokens arrive no later than the engine produces them
        assert len(seen) <= len(h.req.out)
    assert h.finished and seen == h.req.out and len(seen) == 6


def test_result_drains_to_completion_and_matches_run(served):
    cfg, model, params = served
    prompts = _prompts(cfg, n=2)
    eng = _paged(model, params)
    h0 = eng.submit(Request(rid=0, prompt=list(prompts[0]), max_new=5))
    h1 = eng.submit(Request(rid=1, prompt=list(prompts[1]), max_new=5))
    out0 = list(h0.result().out)
    assert h0.finished and len(out0) == 5
    ref = _paged(model, params).run(
        [Request(rid=0, prompt=list(prompts[0]), max_new=5),
         Request(rid=1, prompt=list(prompts[1]), max_new=5)])
    assert out0 == token_matrix(ref, 2, 5)[0].tolist()
    h1.result()
    assert h1.finished


# ---------------------------------------------------------- cancellation


def _assert_pages_clean(eng):
    """All non-free pages must be exactly the prefix cache's registered
    blocks (readers all released); the allocator invariants must hold.
    Kernel mode additionally requires every inactive slot's device
    page-table row to be cleared — a stale row would let the next
    occupant attend a freed (possibly reallocated) page."""
    eng.alloc.check()
    assert eng.alloc.in_use == len(eng.prefix)
    for bid in eng.prefix._map.values():
        assert eng.alloc.refcount(bid) == 1     # cache's own ref only
    if eng.view is not None:
        for slot in range(eng.slots):
            if slot not in eng.active:
                assert (eng.view.page_table[slot] == 0).all()


def test_cancel_mid_prefill_releases_pages(served):
    cfg, model, params = served
    rng = np.random.default_rng(0)
    long_prompt = rng.integers(0, cfg.vocab_size, size=40).tolist()
    eng = _paged(model, params, max_len=64, chunk=4)
    h = eng.submit(Request(rid=0, prompt=long_prompt, max_new=8))
    eng.step()
    eng.step()
    st = eng.active[h.entry.slot]
    assert st.pending, "request must still be mid-prefill for this test"
    held = len(st.shared) + len(st.private)
    assert held > 0
    assert h.cancel()
    assert h.cancelled and not h.finished and h.done
    assert h.entry.state == "cancelled"
    _assert_pages_clean(eng)
    assert not eng.has_work()
    # cancel is idempotent
    assert not h.cancel()


def test_cancel_mid_decode_releases_pages_and_stops_stream(served):
    cfg, model, params = served
    prompts = _prompts(cfg, n=2)
    eng = _paged(model, params)
    h0 = eng.submit(Request(rid=0, prompt=list(prompts[0]), max_new=16))
    h1 = eng.submit(Request(rid=1, prompt=list(prompts[1]), max_new=4))
    while not h0.req.out:
        eng.step()
    n_at_cancel = len(h0.req.out)
    assert h0.cancel()
    done = eng.drain()
    assert [r.rid for r in done] == [1]          # cancelled never completes
    assert len(h0.req.out) == n_at_cancel        # stream stopped immediately
    assert h1.finished and len(h1.req.out) == 4
    _assert_pages_clean(eng)


def test_cancel_waiting_request_never_occupies_a_slot(served):
    cfg, model, params = served
    prompts = _prompts(cfg, n=3)
    eng = _paged(model, params, slots=1)
    h0 = eng.submit(Request(rid=0, prompt=list(prompts[0]), max_new=4))
    h_wait = eng.submit(Request(rid=1, prompt=list(prompts[1]), max_new=4))
    eng.step()
    assert h_wait.entry.state == "waiting"
    assert h_wait.cancel()
    eng.drain()
    assert h0.finished and not h_wait.req.out
    assert eng.stats.cancelled == 1
    assert eng.sched.stats.cancellations == 1
    _assert_pages_clean(eng)


def test_cancelled_page_release_unblocks_waiting_work(served):
    """Cancellation must actually return capacity: a waiting request that
    only fits once the cancelled one's pages free must then be admitted."""
    cfg, model, params = served
    rng = np.random.default_rng(1)
    big = rng.integers(0, cfg.vocab_size, size=24).tolist()
    eng = _paged(model, params, slots=2, max_len=48, block_size=4,
                 num_blocks=10, chunk=4)
    h_big = eng.submit(Request(rid=0, prompt=big, max_new=8))
    h_blocked = eng.submit(Request(rid=1, prompt=list(big), max_new=8))
    eng.step()
    assert h_blocked.entry.state == "waiting"    # pool too small for both
    h_big.cancel()
    done = eng.drain()
    assert [r.rid for r in done] == [1]
    _assert_pages_clean(eng)


def test_contiguous_engine_cancels_waiting_and_active(served):
    cfg, model, params = served
    prompts = _prompts(cfg, n=3)
    eng = ServeEngine(model, params, slots=1, max_len=64)
    h0 = eng.submit(Request(rid=0, prompt=list(prompts[0]), max_new=12))
    h1 = eng.submit(Request(rid=1, prompt=list(prompts[1]), max_new=4))
    eng.step()                                   # h0 active, h1 waiting
    assert h0.cancel() and h1.cancel()
    assert not eng.has_work()
    assert eng.stats.cancelled == 2
    h2 = eng.submit(Request(rid=2, prompt=list(prompts[2]), max_new=4))
    assert len(h2.result().out) == 4             # engine still serves


# ------------------------------------------------- kernel-pinned oracles


def test_engine_kwargs_pins_kernel_on_and_off(served):
    """The oracle must be holdable over an explicitly chosen KV pathway:
    kernel-on (attend through the device page table) and kernel-off
    (dense working-cache gather) both reproduce the contiguous streams,
    greedy and sampled — no reliance on engine defaults or globals."""
    cfg, model, params = served
    prompts = _prompts(cfg)

    def make():
        return [Request(rid=i, prompt=list(p), max_new=8)
                for i, p in enumerate(prompts)]

    for kernel in ("paged", "gather"):
        for sampling in (None, SAMPLED):
            report = compare_engines(
                model, params, make, slots=2, max_len=64, block_size=8,
                chunk=4, sampling=sampling,
                engine_kwargs={"paged": {"kernel": kernel}})
            assert report.ok, (kernel, sampling, report.summary())


def test_kernel_mode_is_the_default_and_reported(served):
    cfg, model, params = served
    eng = _paged(model, params)
    assert eng.kernel == "paged" and eng.pool is None
    assert eng.view is not None
    assert eng.report()["kernel"] == "paged"
    gather = _paged(model, params, kernel="gather")
    assert gather.report()["kernel"] == "gather" and gather.view is None
    with pytest.raises(ValueError, match="kernel"):
        _paged(model, params, kernel="dense")


def test_cancel_mid_decode_under_device_page_view(served):
    """Cancel mid-decode on the kernel path: every page reference is
    released, the slot's device page-table row is cleared, and the freed
    pages are immediately reusable by a waiting request whose stream
    stays correct (end-to-end vs the contiguous oracle)."""
    cfg, model, params = served
    prompts = _prompts(cfg, n=3)
    eng = _paged(model, params)
    h0 = eng.submit(Request(rid=0, prompt=list(prompts[0]), max_new=16))
    h1 = eng.submit(Request(rid=1, prompt=list(prompts[1]), max_new=4))
    while not h0.req.out:
        eng.step()
    slot0 = h0.entry.slot
    assert (eng.view.page_table[slot0] != 0).any()
    assert h0.cancel()
    assert (eng.view.page_table[slot0] == 0).all()
    done = eng.drain()
    assert [r.rid for r in done] == [1]
    _assert_pages_clean(eng)
    # freed capacity is genuinely reusable: a fresh request decodes the
    # same stream the contiguous oracle produces for its prompt
    h2 = eng.submit(Request(rid=2, prompt=list(prompts[2]), max_new=4))
    out = list(h2.result().out)
    ref = ServeEngine(model, params, slots=2, max_len=64).run(
        [Request(rid=0, prompt=list(prompts[2]), max_new=4)])[0].out
    assert out == ref
    _assert_pages_clean(eng)


# -------------------------------------------------------------- sampling


def test_sampled_streams_identical_across_engines(served):
    """Same SamplingParams + seed => token-identical sampled streams on
    the contiguous oracle and the paged engine (the dual-environment
    verdict, extended to sampled mode)."""
    cfg, model, params = served
    prompts = _prompts(cfg)

    def make():
        return [Request(rid=i, prompt=list(p), max_new=8)
                for i, p in enumerate(prompts)]

    report = compare_engines(model, params, make, slots=2, max_len=64,
                             block_size=8, chunk=4, sampling=SAMPLED)
    assert report.ok, report.summary()
    [verdict] = report.verdicts
    assert verdict.kind == "numeric" and verdict.measured == 0.0


def test_sampled_streams_identical_across_runs(served):
    cfg, model, params = served
    prompts = _prompts(cfg)

    def one():
        eng = _paged(model, params)
        done = eng.run([Request(rid=i, prompt=list(p), max_new=8,
                                sampling=SAMPLED)
                        for i, p in enumerate(prompts)])
        return token_matrix(done, len(prompts), 8)

    a, b = one(), one()
    assert (a == b).all()
    assert (a >= 0).all()


def test_different_request_ids_decorrelate_streams(served):
    """Identical prompt + identical SamplingParams but different rids must
    draw different streams (keys fold in the request id)."""
    cfg, model, params = served
    prompt = _prompts(cfg, n=1)[0]
    eng = _paged(model, params, slots=2)
    done = eng.run([Request(rid=i, prompt=list(prompt), max_new=12,
                            sampling=SAMPLED) for i in range(2)])
    mat = token_matrix(done, 2, 12)
    assert not (mat[0] == mat[1]).all()


def test_greedy_param_is_exact_argmax(served):
    """temperature=0 through the sampling path must equal the legacy
    greedy stream (the oracle-gated behaviour is the default)."""
    cfg, model, params = served
    prompts = _prompts(cfg, n=2)

    def run_with(sampling):
        eng = _paged(model, params)
        done = eng.run([Request(rid=i, prompt=list(p), max_new=6,
                                sampling=sampling)
                        for i, p in enumerate(prompts)])
        return token_matrix(done, 2, 6)

    assert (run_with(None) == run_with(SamplingParams())).all()


def test_sampled_stream_survives_preemption(served):
    """Counter-based keys make sampled decoding replayable through
    preempt + recompute-on-readmit: the preempted request's stream equals
    an uninterrupted run's, exactly as the greedy contract."""
    cfg, model, params = served
    rng = np.random.default_rng(3)
    p_lo = rng.integers(0, cfg.vocab_size, size=12).tolist()
    p_hi = rng.integers(0, cfg.vocab_size, size=12).tolist()
    sp = SamplingParams(temperature=1.1, top_k=0, top_p=0.9, seed=5)

    base = PagedServeEngine(model, params, slots=1, max_len=48,
                            block_size=4, chunk=4)
    [alone] = base.run([Request(rid=0, prompt=list(p_lo), max_new=10,
                                sampling=sp)])

    eng = PagedServeEngine(model, params, slots=1, max_len=48,
                           block_size=4, num_blocks=8, chunk=4)
    done = eng.run(
        [Request(rid=0, prompt=list(p_lo), max_new=10, priority=0,
                 sampling=sp),
         Request(rid=1, prompt=list(p_hi), max_new=6, priority=5,
                 sampling=sp)],
        arrivals=[0.0, 5.0])
    out = {r.rid: r.out for r in done}
    assert eng.sched.stats.preemptions >= 1
    assert out[0] == alone.out


def test_sampling_params_validation():
    with pytest.raises(ValueError, match="temperature"):
        SamplingParams(temperature=-0.1)
    with pytest.raises(ValueError, match="top_p"):
        SamplingParams(top_p=0.0)
    with pytest.raises(ValueError, match="top_k"):
        SamplingParams(top_k=-1)
    with pytest.raises(ValueError, match="seed"):
        SamplingParams(seed=2**31)          # must fit the int32 lane array
    assert SamplingParams().greedy
    assert SamplingParams().describe() == "greedy"
    assert "seed=3" in SamplingParams(temperature=0.5, seed=3).describe()


def test_oversized_request_id_rejected_at_submit(served):
    cfg, model, params = served
    prompt = _prompts(cfg, n=1)[0]
    for eng in (_paged(model, params),
                ServeEngine(model, params, slots=2, max_len=64)):
        with pytest.raises(ValueError, match="int32"):
            eng.submit(Request(rid=2**31, prompt=list(prompt), max_new=4))


def test_max_new_one_finishes_at_admission_on_both_engines(served):
    """The admission-produced first token can already satisfy max_new:
    both engines must stop at exactly one token and agree."""
    cfg, model, params = served
    prompts = _prompts(cfg, n=3)

    def make():
        return [Request(rid=i, prompt=list(p), max_new=1)
                for i, p in enumerate(prompts)]

    report = compare_engines(model, params, make, slots=2, max_len=64,
                             block_size=8, chunk=4)
    assert report.ok, report.summary()
    done = ServeEngine(model, params, slots=2, max_len=64).run(make())
    assert all(len(r.out) == 1 and r.finished for r in done)


def test_all_greedy_batches_never_compile_the_sampling_program(served):
    """Greedy serving (the default) must dispatch the argmax-only fused
    program; the sampling variant compiles only once a sampled request
    actually enters a batch."""
    cfg, model, params = served
    prompts = _prompts(cfg, n=2)
    eng = _paged(model, params)
    eng.run([Request(rid=i, prompt=list(p), max_new=4)
             for i, p in enumerate(prompts)])
    assert eng._chunk_sample_fn.calls == 0
    assert eng._chunk_fn.calls > 0
    eng.run([Request(rid=9, prompt=list(prompts[0]), max_new=4,
                     sampling=SAMPLED)])
    assert eng._chunk_sample_fn.calls > 0
    assert eng.report()["compiles"] <= 1        # worst per-program count


def test_top_k_one_is_argmax_of_the_filtered_set(served):
    """top_k=1 collapses sampling to argmax regardless of temperature —
    a direct check that the rank-based filter works."""
    cfg, model, params = served
    prompts = _prompts(cfg, n=2)

    def run_with(sampling):
        eng = _paged(model, params)
        done = eng.run([Request(rid=i, prompt=list(p), max_new=5,
                                sampling=sampling)
                        for i, p in enumerate(prompts)])
        return token_matrix(done, 2, 5)

    greedy = run_with(None)
    k1 = run_with(SamplingParams(temperature=0.7, top_k=1, seed=9))
    assert (greedy == k1).all()


# ----------------------------------------------------- lifecycle tracing


def test_lifecycle_events_feed_ttft_expectations(served):
    """submit / first-token / finish / cancel events must reconstruct
    per-request latencies (the audit's TTFT evidence)."""
    from repro.audit import Evidence, Tracer

    cfg, model, params = served
    prompts = _prompts(cfg, n=3)
    tr = Tracer()
    eng = _paged(model, params, tracer=tr)
    hs = [eng.submit(Request(rid=i, prompt=list(p), max_new=4,
                             sampling=SAMPLED if i == 1 else None))
          for i, p in enumerate(prompts)]
    eng.step()
    hs[2].cancel()
    eng.drain()

    lat = Evidence(tracer=tr).request_latencies()
    assert set(lat) == {0, 1}                  # cancelled rid 2 excluded
    for rec in lat.values():
        assert rec["ttft_ticks"] > 0
        assert rec["decode_gap_ticks"] >= 1.0  # >= one tick per token
        assert rec["tokens"] == 4
    subs = {e.data["rid"]: e.data for e in tr.events("submit")}
    assert subs[1]["sampling"] != "greedy" and subs[0]["sampling"] == "greedy"
    [cancel] = tr.events("cancel")
    assert cancel.data["rid"] == 2


# ------------------------------------------------------ served logits


def test_keep_logits_records_the_row_each_token_came_from(served):
    """Both engines append one float32 row per output token to requests
    that ask (greedy: the token is that row's argmax), and nothing to
    requests that do not."""
    cfg, model, params = served
    prompts = _prompts(cfg, n=3)
    for eng in (_paged(model, params),
                ServeEngine(model, params, slots=2, max_len=64)):
        reqs = [Request(rid=i, prompt=list(p), max_new=5, keep_logits=i < 2)
                for i, p in enumerate(prompts)]
        done = {r.rid: r for r in eng.run(reqs)}
        for rid in (0, 1):
            r = done[rid]
            assert len(r.logits) == len(r.out) == 5
            assert all(row.shape == (cfg.vocab_size,)
                       and row.dtype == np.float32 for row in r.logits)
            assert [int(row.argmax()) for row in r.logits] == r.out
        assert done[2].logits == []


def test_contiguous_and_paged_logits_agree(served):
    """The logits form of the dual-environment oracle, greedy and
    sampled: rows agree within the bf16 pathway band."""
    from repro.core.verify import BF16_LOGITS_TOL

    cfg, model, params = served
    prompts = _prompts(cfg)

    def make():
        return [Request(rid=i, prompt=list(p), max_new=6)
                for i, p in enumerate(prompts)]

    for sp in (None, SAMPLED):
        rep = compare_engines(model, params, make, slots=2, max_len=64,
                              block_size=8, chunk=4, sampling=sp,
                              logits_tol=BF16_LOGITS_TOL)
        (v,) = rep.verdicts
        assert rep.ok and v.kind == "logits", v.detail


def test_engine_reports_the_attention_its_step_lowered(served):
    from repro.kernels import ops

    cfg, model, params = served
    assert ops.paged_attention_impl() == "reference"   # CPU, unforced
    assert _paged(model, params).report()["attention"] == "reference"
    assert _paged(model, params, kernel="gather").report()[
        "attention"] == "dense"
    prev = ops.FORCE_PAGED_KERNEL
    ops.FORCE_PAGED_KERNEL = True
    try:
        assert _paged(model, params).report()["attention"] == "pallas"
    finally:
        ops.FORCE_PAGED_KERNEL = prev
    with pytest.raises(ValueError, match="impl"):
        ops.paged_attention(None, None, None, None, None, None,
                            impl="interpret")
