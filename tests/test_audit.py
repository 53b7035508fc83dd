"""Runtime audit pipeline: tracer invariants, expectation-registry
checks over fabricated evidence, perf-ledger baseline/compare semantics,
and the end-to-end proof that seeded pathway misconfigurations are
caught while the dual-environment oracle stays green."""
import jax
import numpy as np
import pytest

from repro.audit import (DEFAULT_REGISTRY, AuditContext, Evidence,
                         ExpectationRegistry, ExpectedSignature, Ledger,
                         MetricSpec, RunAudit, Rule, Tracer)
from repro.audit.trace import NULL_TRACER
from repro.core.inspector import CollectiveOp, TransportReport


# ---------------------------------------------------------------- tracer


def test_tracer_ring_overflow_keeps_exact_counts():
    tr = Tracer(capacity=8, clock=lambda: 0.0)
    for i in range(30):
        tr.emit("tick", i=i)
    tr.emit("other")
    assert len(tr.events()) == 8               # ring bounded
    assert tr.count("tick") == 30              # counts exact
    assert tr.count("other") == 1
    assert tr.dropped == 23
    assert tr.events()[-1].kind == "other"
    assert tr.last("tick").data["i"] == 29
    s = tr.summary()
    assert s["emitted"] == 31 and s["retained"] == 8
    assert s["counts"] == {"tick": 30, "other": 1}


def test_tracer_span_measures_and_attaches_results():
    tr = Tracer()
    with tr.span("work", step=3) as ev:
        ev["loss"] = 1.5
    [e] = tr.events("work")
    assert e.data["step"] == 3 and e.data["loss"] == 1.5
    assert e.data["dt_s"] >= 0


def test_tracer_payload_may_shadow_reserved_names():
    """Event payloads can carry their own ``kind`` (emit's first arg is
    positional-only) and span bodies can attach keys colliding with span
    kwargs — the body wins, ``dt_s`` always wins."""
    tr = Tracer()
    tr.emit("step", kind="chunk")
    assert tr.events("step")[0].data["kind"] == "chunk"
    with tr.span("work", loss=0.0, dt_s="shadowed") as ev:
        ev["loss"] = 2.5
    [e] = tr.events("work")
    assert e.data["loss"] == 2.5
    assert isinstance(e.data["dt_s"], float)


def test_tracer_injected_clock_is_deterministic():
    t = {"now": 0.0}
    tr = Tracer(clock=lambda: t["now"])
    tr.emit("a")
    t["now"] = 5.0
    tr.emit("b")
    assert [e.t for e in tr.events()] == [0.0, 5.0]


def test_null_tracer_records_nothing():
    NULL_TRACER.emit("x", a=1)
    with NULL_TRACER.span("y"):
        pass
    assert NULL_TRACER.events() == []
    assert NULL_TRACER.count("x") == 0
    assert not NULL_TRACER.enabled


def test_spans_and_phases_land_on_the_profiler_clock(tmp_path):
    """``span`` and ``phase`` annotate the profiler's host plane, on a
    real tracer and on ``NULL_TRACER`` alike; only ``span`` records."""
    import glob

    from jax.profiler import ProfileData

    tr = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.phase("outer", step_num=7):
            with tr.span("work"):
                pass
            with tr.phase("inner"):
                pass
        with NULL_TRACER.span("null-span"), NULL_TRACER.phase("null-phase"):
            pass
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")
    host = {e.name: dict(e.stats)
            for p in ProfileData.from_file(path).planes
            if p.name.startswith("/host:") for line in p.lines
            for e in line.events}
    assert {"outer", "work", "inner", "null-span", "null-phase"} <= set(host)
    assert host["outer"]["step_num"] == 7
    assert [e.kind for e in tr.events()] == ["work"]
    assert tr.summary()["counts"] == {"work": 1}
    assert NULL_TRACER.events() == [] and NULL_TRACER.emitted == 0


def test_tracer_subscriber_sees_every_event_past_overflow():
    """The live-metrics feed contract: a subscriber observes the complete
    stream even after the bounded ring has evicted the early events."""
    tr = Tracer(capacity=4, clock=lambda: 0.0)
    seen = []
    fn = tr.subscribe(seen.append)
    for i in range(25):
        tr.emit("tick", i=i)
    assert len(tr.events()) == 4 and tr.dropped == 21  # ring wrapped...
    assert len(seen) == 25                             # ...subscriber exact
    assert [e.data["i"] for e in seen] == list(range(25))
    assert [e.seq for e in seen] == list(range(25))
    tr.unsubscribe(fn)
    tr.emit("tick", i=99)
    assert len(seen) == 25                 # unsubscribed: no more delivery
    assert tr.count("tick") == 26          # counts still exact


def test_tracer_spans_nest_across_overflow():
    """Span closing events land in order (inner first) with exact counts
    even when the events emitted inside the spans wrap the ring."""
    tr = Tracer(capacity=3)
    seen = []
    tr.subscribe(seen.append)
    with tr.span("outer"):
        with tr.span("inner"):
            for i in range(10):
                tr.emit("tick", i=i)
    assert tr.count("tick") == 10
    assert tr.count("inner") == 1 and tr.count("outer") == 1
    assert tr.dropped == 12 - 3
    # the ring retains only the tail, but the subscriber saw everything
    assert [e.kind for e in seen[-2:]] == ["inner", "outer"]
    assert sum(e.kind == "tick" for e in seen) == 10
    # the retained tail ends with the two span closings
    assert [e.kind for e in tr.events()[-2:]] == ["inner", "outer"]


def test_emitted_kinds_are_declared_in_known_kinds():
    """Emit-kind lint: every ``tracer.emit("...")`` / ``tracer.span("...")``
    / ``tracer.phase("...")`` string literal in ``src/``, ``benchmarks/``,
    and ``scripts/`` must appear in ``KNOWN_KINDS`` — a typo'd kind cannot
    silently create an event stream nothing subscribes to, no matter which
    layer emits it."""
    import ast
    from pathlib import Path

    from repro.audit.trace import KNOWN_KINDS

    repo = Path(__file__).resolve().parent.parent
    roots = [repo / "src", repo / "benchmarks", repo / "scripts"]

    def literal_kinds(node):
        """String constants reachable as the call's kind argument
        (plain literals and both arms of conditional expressions)."""
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            return [node.value]
        if isinstance(node, ast.IfExp):
            return literal_kinds(node.body) + literal_kinds(node.orelse)
        return []

    found = {}
    for root in roots:
        for path in sorted(root.rglob("*.py")):
            tree = ast.parse(path.read_text(), filename=str(path))
            for node in ast.walk(tree):
                if (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr in ("emit", "span", "phase")
                        and node.args):
                    for kind in literal_kinds(node.args[0]):
                        found.setdefault(kind, []).append(
                            f"{path.relative_to(repo)}:{node.lineno}")
    undeclared = {k: v for k, v in found.items() if k not in KNOWN_KINDS}
    assert not undeclared, (
        f"emit/span kinds missing from KNOWN_KINDS: {undeclared}")
    # the lint must not be vacuous: the instrumented layers are present,
    # including the cluster router's route events
    assert len(found) >= 15, sorted(found)
    assert "route" in found
    # ... and the engine step's profiler phases
    assert {"serve.step", "serve.wait"} <= set(found)


# ---------------------------------------------------------- expectations


def _serve_ctx(**kw):
    defaults = dict(workload="serve", family="dense", arch="t",
                    shared_prefix=True)
    defaults.update(kw)
    return AuditContext(**defaults)


def _paged_report(**over):
    rep = {"engine": "paged", "block_size": 8, "prefix_cache": True,
           "prefix_hit_rate": 0.5}
    rep.update(over)
    return rep


def _kinds(findings):
    return {f["kind"] for f in findings}


def test_registry_matching_on_family_workload_mesh():
    reg = ExpectationRegistry([
        Rule("a", ExpectedSignature(), families=("dense",),
             workloads=("serve",)),
        Rule("b", ExpectedSignature(), families=("ssm",)),
        Rule("c", ExpectedSignature(), min_devices=8),
    ])
    assert [r.name for r in reg.match(_serve_ctx())] == ["a"]
    assert [r.name for r in reg.match(_serve_ctx(family="ssm"))] == ["b"]
    big = _serve_ctx(mesh=(2, 2, 2))
    assert "c" in [r.name for r in reg.match(big)]
    # "bench:<name>" workloads match rules declared for "bench"
    reg2 = ExpectationRegistry(
        [Rule("d", ExpectedSignature(), workloads=("bench",))])
    assert reg2.match(_serve_ctx(workload="bench:audit_pathways"))


def test_clean_paged_evidence_yields_no_findings():
    ev = Evidence(engine_report=_paged_report())
    assert DEFAULT_REGISTRY.evaluate(_serve_ctx(), ev) == []


def test_engine_selection_mismatch_is_error():
    ev = Evidence(engine_report={"engine": "contiguous"})
    fs = DEFAULT_REGISTRY.evaluate(_serve_ctx(), ev)
    assert _kinds(fs) == {"pathway-engine-selection"}
    assert all(f["severity"] == "error" for f in fs)
    # ...and the inverse: paged where contiguous is the correct pathway
    fs = DEFAULT_REGISTRY.evaluate(
        _serve_ctx(family="ssm"), Evidence(engine_report=_paged_report()))
    assert "pathway-engine-selection" in _kinds(fs)


def test_shrunk_page_size_is_flagged():
    ev = Evidence(engine_report=_paged_report(block_size=2))
    fs = DEFAULT_REGISTRY.evaluate(_serve_ctx(), ev)
    assert "pathway-page-geometry" in _kinds(fs)


def test_prefix_cache_disabled_or_ineffective_is_flagged():
    fs = DEFAULT_REGISTRY.evaluate(
        _serve_ctx(), Evidence(engine_report=_paged_report(
            prefix_cache=False)))
    assert "pathway-prefix-cache" in _kinds(fs)
    fs = DEFAULT_REGISTRY.evaluate(
        _serve_ctx(), Evidence(engine_report=_paged_report(
            prefix_hit_rate=0.0)))
    assert "pathway-prefix-cache" in _kinds(fs)
    # not an expectation without prompt sharing
    fs = DEFAULT_REGISTRY.evaluate(
        _serve_ctx(shared_prefix=False),
        Evidence(engine_report=_paged_report(prefix_hit_rate=0.0)))
    assert "pathway-prefix-cache" not in _kinds(fs)


def test_recompilation_in_hot_loop_is_flagged():
    tr = Tracer()
    tr.emit("engine-init", engine="paged", block_size=8, prefix_cache=True)
    for shape in ((2, 4), (2, 5), (2, 6)):
        tr.emit("compile", fn="decode_chunk", reason="new-shapes",
                signature=shape)
    fs = DEFAULT_REGISTRY.evaluate(
        _serve_ctx(shared_prefix=False), Evidence(tracer=tr))
    assert "pathway-recompilation" in _kinds(fs)


def test_p99_slo_rule_fires_on_tail_and_abstains_within_bound():
    """``pathway-slo``: the quantile expectations judge the population
    tail from the lifecycle trace — one pathological straggler out of
    many breaches a p99 bound the per-request max rule would also catch,
    but a *fleet-wide* bound set above the healthy p99 stays silent."""
    from repro.audit.expectations import nearest_rank

    def traced_run(ttfts):
        tr = Tracer(clock=lambda: 0.0)
        for rid, ttft in enumerate(ttfts):
            tr.emit("submit", rid=rid, arrival=0.0)
            tr.emit("first-token", rid=rid, tick=float(ttft),
                    ttft_ticks=float(ttft))
            # 5 tokens over 8 ticks after the first: gap 2.0 each
            tr.emit("finish", rid=rid, tick=float(ttft) + 8.0, tokens_out=5)
        return Evidence(tracer=tr)

    ttfts = [2.0] * 19 + [40.0]            # p99 == the straggler
    assert nearest_rank(ttfts, 0.99) == 40.0

    def reg(**sig):
        return ExpectationRegistry([Rule(
            "slo", ExpectedSignature(**sig), workloads=("serve",))])

    fs = reg(p99_ttft_ticks=10.0).evaluate(_serve_ctx(), traced_run(ttfts))
    assert _kinds(fs) == {"pathway-slo"}
    assert all(f["severity"] == "error" for f in fs)
    # bound above the tail: clean
    assert reg(p99_ttft_ticks=50.0).evaluate(
        _serve_ctx(), traced_run(ttfts)) == []
    # decode-gap SLO over the same evidence (every gap is 2.0 ticks)
    assert reg(p99_decode_gap_ticks=1.5).evaluate(
        _serve_ctx(), traced_run(ttfts)) != []
    assert reg(p99_decode_gap_ticks=2.0).evaluate(
        _serve_ctx(), traced_run(ttfts)) == []
    # no lifecycle evidence -> the check is skipped, not failed
    assert reg(p99_ttft_ticks=1.0).evaluate(
        _serve_ctx(), Evidence(tracer=Tracer())) == []


def test_nearest_rank_is_the_ceil_rank_order_statistic():
    from repro.audit.expectations import nearest_rank

    assert nearest_rank([3.0, 1.0, 2.0], 0.5) == 2.0
    assert nearest_rank([3.0, 1.0, 2.0], 1.0) == 3.0
    assert nearest_rank([7.0], 0.99) == 7.0
    assert nearest_rank(list(range(100)), 0.99) == 98  # ceil(99) = 99th
    with pytest.raises(ValueError, match="empty"):
        nearest_rank([], 0.5)
    with pytest.raises(ValueError, match="quantile"):
        nearest_rank([1.0], 0.0)


def test_non_moe_train_must_not_emit_expert_dispatch():
    report = TransportReport(ops=[CollectiveOp(
        name="a2a", kind="all-to-all", payload_bytes=64, group_size=2,
        computation="main")])
    ctx = AuditContext(workload="train", family="dense", mesh=(2,))
    fs = DEFAULT_REGISTRY.evaluate(ctx, Evidence(transport=report))
    assert "pathway-collective-kind" in _kinds(fs)
    # the same op is the expected pathway for expert (moe) dispatch
    moe_ctx = AuditContext(workload="train", family="moe", mesh=(2,))
    fs = DEFAULT_REGISTRY.evaluate(moe_ctx, Evidence(transport=report))
    assert "pathway-collective-kind" not in _kinds(fs)


def test_transport_expectations_group_and_host_transfer():
    report = TransportReport(
        ops=[CollectiveOp(name="ar", kind="all-reduce", payload_bytes=1024,
                          group_size=16, computation="main")],
        findings=[{"severity": "warn", "kind": "host-transfer",
                   "detail": "outfeed in module"}])
    ctx = AuditContext(workload="train", family="dense", mesh=(2, 2, 2))
    fs = DEFAULT_REGISTRY.evaluate(ctx, Evidence(transport=report))
    kinds = _kinds(fs)
    assert "pathway-collective-group" in kinds     # 16 > 8 devices
    assert "pathway-host-transfer" in kinds
    assert all(f["severity"] == "error" for f in fs)


# ---------------------------------------------------------------- ledger


SPECS = [MetricSpec("tokens_per_s", higher_is_better=True, rel_tol=0.2),
         MetricSpec("ttft_s", higher_is_better=False, rel_tol=0.2),
         MetricSpec("wall_s", gate=False)]


def test_ledger_roundtrip_baseline_then_pass_then_regression(tmp_path):
    led = Ledger(tmp_path)
    m = {"tokens_per_s": 100.0, "ttft_s": 0.5, "wall_s": 2.0}

    first = led.compare("serve", m, SPECS)
    assert first.baseline_written and first.ok
    assert led.path("serve").exists()
    assert led.baseline("serve") == m

    again = led.compare("serve", dict(m), SPECS)   # unchanged re-run passes
    assert not again.baseline_written and again.ok
    assert all(d["status"] == "ok" for d in again.deltas.values())

    # ≥20% synthetic throughput regression fails the gate
    worse = led.compare("serve", {**m, "tokens_per_s": 79.0}, SPECS)
    assert not worse.ok
    [f] = [f for f in worse.findings if f["severity"] == "error"]
    assert f["kind"] == "perf-regression"
    assert worse.deltas["tokens_per_s"]["status"] == "regression"


def test_ledger_direction_and_ungated_metrics(tmp_path):
    led = Ledger(tmp_path)
    m = {"tokens_per_s": 100.0, "ttft_s": 0.5, "wall_s": 2.0}
    led.compare("b", m, SPECS)
    # latency rising 50% is a regression; wall_s is tracked but never gates
    res = led.compare("b", {**m, "ttft_s": 0.75, "wall_s": 99.0}, SPECS)
    assert not res.ok
    assert res.deltas["ttft_s"]["status"] == "regression"
    assert res.deltas["wall_s"]["status"] == "ok"
    # improvements are info findings, not errors
    res = led.compare("b", {**m, "tokens_per_s": 150.0}, SPECS)
    assert res.ok
    assert any(f["kind"] == "perf-improvement" for f in res.findings)


def test_ledger_update_baseline_and_new_metrics(tmp_path):
    led = Ledger(tmp_path)
    led.compare("b", {"x": 10.0}, [MetricSpec("x")])
    # a metric the baseline has never seen is adopted, not judged
    res = led.compare("b", {"x": 10.0, "y": 1.0}, [MetricSpec("x")])
    assert res.ok and led.baseline("b")["y"] == 1.0
    res = led.compare("b", {"x": 5.0}, [MetricSpec("x")],
                      update_baseline=True)
    assert res.baseline_written and led.baseline("b")["x"] == 5.0
    res = led.compare("b", {"x": 5.0}, [MetricSpec("x")])
    assert res.ok


def test_ledger_corrupt_file_rewrites_baseline(tmp_path):
    led = Ledger(tmp_path)
    led.compare("b", {"x": 1.0}, [MetricSpec("x")])
    led.path("b").write_text("{not json")
    res = led.compare("b", {"x": 99.0}, [MetricSpec("x")])
    assert res.baseline_written and res.ok


def test_ledger_history_is_bounded(tmp_path):
    from repro.audit.ledger import HISTORY_KEEP
    led = Ledger(tmp_path)
    for i in range(HISTORY_KEEP + 9):
        led.compare("b", {"x": 1.0}, [MetricSpec("x")])
    rec = led.load("b")
    assert len(rec["history"]) == HISTORY_KEEP


def test_ledger_orphan_audit_flags_unowned_bench_files(tmp_path):
    """``audit_owned``: a BENCH file whose benchmark is not registered is
    an error — a baseline nobody maintains silently attests metrics
    nothing measures."""
    led = Ledger(tmp_path)
    led.compare("serve_throughput_smoke", {"x": 1.0}, [MetricSpec("x")])
    assert led.audit_owned(["serve_throughput_smoke"]) == []

    # a stray ledger from a deleted benchmark
    led.compare("serve_retired_smoke", {"y": 2.0}, [MetricSpec("y")])
    [f] = led.audit_owned(["serve_throughput_smoke"])
    assert f["kind"] == "ledger-orphan" and f["severity"] == "error"
    assert "serve_retired_smoke" in f["detail"]

    # unparseable files are judged by filename, not skipped
    (tmp_path / "BENCH_mystery.json").write_text("{not json")
    kinds = [f["kind"] for f in led.audit_owned(["serve_throughput_smoke",
                                                 "serve_retired_smoke"])]
    assert kinds == ["ledger-orphan"]


def test_smoke_all_gate_fails_on_orphan_ledger(tmp_path):
    """The harness-level proof: ``scripts/smoke_all.py``'s owned-key set
    plus ``Diagnostics.gate()`` turns an unowned BENCH file into a
    failing gate."""
    import importlib.util
    import os

    from repro.core.diagnostics import Diagnostics

    spec = importlib.util.spec_from_file_location(
        "smoke_all", os.path.join(os.path.dirname(__file__), os.pardir,
                                  "scripts", "smoke_all.py"))
    smoke_all = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke_all)

    owned = smoke_all.owned_ledger_keys()
    assert {"serve_throughput_smoke", "audit_pathways_full",
            "serve_workloads_smoke"} <= set(owned)

    led = Ledger(tmp_path)
    for key in owned:                       # everything owned: gate passes
        led.compare(key, {"x": 1.0}, [MetricSpec("x")])
    diag = Diagnostics()
    diag.extend(led.audit_owned(owned), source="ledger-integrity")
    assert diag.gate()

    led.compare("serve_retired_smoke", {"y": 1.0}, [MetricSpec("y")])
    diag = Diagnostics()
    diag.extend(led.audit_owned(owned), source="ledger-integrity")
    assert not diag.gate()


def test_ledger_rolling_median_over_history(tmp_path):
    led = Ledger(tmp_path)
    assert led.rolling_median("b", "wall_s") is None    # no ledger at all
    for v in [10.0, 30.0, 20.0]:
        led.compare("b", {"x": 1.0, "wall_s": v},
                    [MetricSpec("x"), MetricSpec("wall_s", gate=False)])
    trend = led.rolling_median("b", "wall_s")
    assert trend == {"median": 20.0, "n": 3, "latest": 20.0}
    # even-length window averages the middle pair
    led.compare("b", {"x": 1.0, "wall_s": 100.0},
                [MetricSpec("x"), MetricSpec("wall_s", gate=False)])
    assert led.rolling_median("b", "wall_s")["median"] == 25.0
    # the window slides: only the most recent entries count
    assert led.rolling_median("b", "wall_s", window=2) == {
        "median": 60.0, "n": 2, "latest": 100.0}
    # a metric history never carried -> None, not a crash
    assert led.rolling_median("b", "nope") is None


# ------------------------------------------------------- compile watcher


def test_compile_watcher_counts_shape_cache_misses():
    from repro.models.decode import CompileWatcher

    fired = []
    fn = jax.jit(lambda x: x + 1)
    w = CompileWatcher(fn, "step",
                       on_compile=lambda *a: fired.append(a))
    import jax.numpy as jnp
    w(jnp.zeros((2, 4)))
    w(jnp.zeros((2, 4)))           # same shapes: no new compile
    assert w.compiles == 1 and w.calls == 2
    w(jnp.zeros((2, 8)))           # new shapes: a miss
    assert w.compiles == 2
    assert fired[0][0] == "step" and fired[0][1] == "new-shapes"


# ------------------------------------------- end-to-end seeded misconfigs


@pytest.mark.slow
def test_seeded_misconfigurations_detected_while_oracle_green():
    """The acceptance proof: each seeded misconfiguration (contiguous
    fallback on a dense arch, shrunk page size, disabled prefix cache)
    leaves the greedy token streams identical to the healthy run —
    ``compare_engines`` stays green — yet the audit flags each as an
    error-severity pathway finding."""
    from repro.configs import ALL_ARCHS, reduced
    from repro.models import build
    from repro.serve.engine import (PagedServeEngine, Request, ServeEngine,
                                    compare_engines, token_matrix)

    cfg = reduced(ALL_ARCHS["deepseek-7b"])
    model = build(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(11)
    prefix = rng.integers(0, cfg.vocab_size, size=16).tolist()
    tails = [rng.integers(0, cfg.vocab_size, size=3 + i).tolist()
             for i in range(4)]

    def make():
        return [Request(rid=i, prompt=prefix + tails[i], max_new=4)
                for i in range(4)]

    ctx = AuditContext(workload="serve", family=cfg.family, arch=cfg.name,
                       shared_prefix=True)

    # oracle: paged == contiguous on this trace
    assert compare_engines(model, params, make, slots=2, max_len=48,
                           block_size=8, chunk=4).ok

    # healthy run: audit is clean and page reuse actually happened
    audit = RunAudit(ctx)
    eng = PagedServeEngine(model, params, slots=2, max_len=48, block_size=8,
                           chunk=4, tracer=audit.tracer)
    healthy = token_matrix(eng.run(make()), 4, 4)
    assert eng.pstats.cached_tokens > 0
    assert audit.evaluate(engine_report=eng.report()) == []

    def contiguous(tr):
        return ServeEngine(model, params, slots=2, max_len=48, tracer=tr)

    def shrunk(tr):
        return PagedServeEngine(model, params, slots=2, max_len=48,
                                block_size=2, chunk=4, tracer=tr)

    def no_cache(tr):
        return PagedServeEngine(model, params, slots=2, max_len=48,
                                block_size=8, chunk=4,
                                use_prefix_cache=False, tracer=tr)

    seeds = {"pathway-engine-selection": contiguous,
             "pathway-page-geometry": shrunk,
             "pathway-prefix-cache": no_cache}
    for expected_kind, builder in seeds.items():
        s_audit = RunAudit(ctx)
        s_eng = builder(s_audit.tracer)
        tokens = token_matrix(s_eng.run(make()), 4, 4)
        assert (tokens == healthy).all(), expected_kind  # answer unchanged
        findings = s_audit.evaluate(engine_report=s_eng.report())
        hits = [f for f in findings if f["kind"] == expected_kind]
        assert hits and all(f["severity"] == "error" for f in hits), (
            expected_kind, findings)

    # degraded pathway is visible in the evidence, not just the verdict:
    # the cache-disabled run recomputed every prompt token
    assert s_eng.pstats.cached_tokens == 0
    assert s_eng.pstats.prefill_tokens > eng.pstats.prefill_tokens


@pytest.mark.slow
def test_sub_block_shared_prefix_does_not_false_positive():
    """A shared prefix shorter than one page cannot hit the cache (only
    full blocks register), so the serve launcher must not declare the
    workload shared-prefix — a healthy run stays gate-clean."""
    from repro.launch.serve import serve

    res = serve("deepseek-7b", reduced=True, n_requests=3, slots=2, max_len=48,
                max_new=4, shared_prefix=4, block_size=8)
    assert res["audit"]["gate_ok"], res["audit"]["findings"]


def test_empty_prompt_rejected_cleanly():
    from repro.configs import ALL_ARCHS, reduced
    from repro.models import build
    from repro.serve.engine import PagedServeEngine, Request, ServeEngine

    cfg = reduced(ALL_ARCHS["deepseek-7b"])
    model = build(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    paged = PagedServeEngine(model, params, slots=1, max_len=32,
                             block_size=4, chunk=4)
    with pytest.raises(ValueError, match="empty prompt"):
        paged.submit(Request(rid=0, prompt=[], max_new=4))
    contig = ServeEngine(model, params, slots=1, max_len=32)
    with pytest.raises(ValueError, match="empty prompt"):
        contig.run([Request(rid=0, prompt=[], max_new=4)])


@pytest.mark.slow
def test_paged_engine_trace_replays_deterministically():
    """Same trace (prompts, arrivals, priorities) → identical
    (kind, data) event stream, ``tick`` payloads included: the audit's
    replay-debugging contract.  (Wall-clock ``t`` stamps are excluded —
    the engine does not rebind a shared tracer's clock.)"""
    from repro.configs import ALL_ARCHS, reduced
    from repro.models import build
    from repro.serve.engine import PagedServeEngine, Request

    cfg = reduced(ALL_ARCHS["deepseek-7b"])
    model = build(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, size=8 + i).tolist()
               for i in range(3)]

    def run_traced():
        tr = Tracer()
        eng = PagedServeEngine(model, params, slots=2, max_len=48,
                               block_size=4, chunk=4, tracer=tr)
        eng.run([Request(rid=i, prompt=list(p), max_new=4)
                 for i, p in enumerate(prompts)],
                arrivals=[0.0, 0.0, 2.0])
        return [(e.kind, tuple(sorted(e.data.items())))
                for e in tr.events() if e.kind != "compile"]

    assert run_traced() == run_traced()
