"""Dev harness: tiny forward/train/prefill/decode for every family on CPU,
plus the serving-throughput, audit-pathway, workload-SLO,
cluster-scaling, and KV-tiering smokes gated on their diagnostics
findings, a timeline
determinism check (same seed + trace must render a byte-identical
``/timeline`` Chrome-trace body, mirroring the ``/metrics``
byte-identity gate), a ledger integrity audit (orphan ``BENCH_*.json``
files are errors), and the rolling-median throughput trend over ledger
history (a collapse beyond ``TREND_FACTOR`` is a warn-level finding).

    PYTHONPATH=src python scripts/smoke_all.py [archs...] [--json]
        [--ledger-dir DIR] [--update-baseline] [--artifacts-dir DIR]

``--json`` prints one machine-readable report (per-arch results, all
findings, ledger deltas) on stdout's last line; the exit code is driven
by ``Diagnostics.gate()`` either way — the paper's performance-verified
bar, where an error finding fails the harness.

``--artifacts-dir DIR`` publishes the run's evidence for CI archiving:
the ``BENCH_*.json`` perf-ledger files (baselines + bounded history) and
the machine-readable report, so a perf regression can be bisected across
PRs from build artifacts alone (ROADMAP PR 2 follow-up).
"""
import argparse
import json
import os
import shutil
import sys
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import ALL_ARCHS, ShapeConfig, reduced
from repro.configs.base import RunConfig, TrainConfig
from repro.core.diagnostics import Diagnostics
from repro.models import build
from repro.train.step import init_train_state, make_train_step

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: Benchmarks this harness runs, in order.  Their ``<name>_{smoke,full}``
#: keys are the only ledger files allowed to exist in the ledger dir —
#: ``Ledger.audit_owned`` flags anything else as an orphan (a baseline
#: nobody maintains silently attests metrics nothing measures).
BENCHES = ["serve_throughput", "audit_pathways", "serve_workloads",
           "serve_cluster", "serve_tiering"]

#: In-process checks that also own ledger keys (no benchmarks/ script):
#: the timeline determinism gate below ledgers its deterministic
#: counters under ``serve_timeline_smoke``.
EXTRA_LEDGER_BENCHES = ["serve_timeline"]

#: Throughput-trend regression factor: the latest ungated wall-clock
#: throughput sample dropping below median/TREND_FACTOR over the ledger
#: history window is a warn-level ``perf-trend`` finding — wall time on
#: shared CI is too noisy to gate run-to-run, but a sustained halving
#: against the rolling median is a real trajectory signal, not noise.
TREND_FACTOR = 1.5


def owned_ledger_keys(benches=None) -> list[str]:
    return [f"{b}_{mode}"
            for b in (benches or BENCHES + EXTRA_LEDGER_BENCHES)
            for mode in ("smoke", "full")]


def timeline_smoke(ledger_dir: str, update_baseline: bool) -> dict:
    """Timeline determinism gate: run the same seeded bursty trace twice
    through a fresh paged engine + tracer + log and require the
    ``/timeline`` endpoint to render byte-identical, Perfetto-loadable
    Chrome-trace JSON, with every closed request's phase shares summing
    to exactly 1.  Ledgers the deterministic counts under
    ``serve_timeline_smoke``; returns the report record (``findings``
    inside, same contract as the benchmark scripts)."""
    from repro.audit import (EventLog, Ledger, MetricSpec, MetricsServer,
                             ServeMetrics, Tracer, build_timelines)
    from repro.serve import PagedServeEngine, WorkloadSpec, generate

    cfg = reduced(ALL_ARCHS["deepseek-7b"])
    model = build(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    trace = generate(WorkloadSpec(
        name="timeline-smoke", family="chat", arrival="bursty",
        n_requests=6, vocab_size=cfg.vocab_size, seed=13, max_new=4,
        prefix_len=8, n_streams=2, suffix_lo=2, suffix_hi=4,
        burst_size=3, burst_gap=8.0, priorities=(0, 1)))

    def run_once():
        tracer = Tracer()
        log = EventLog()
        tracer.subscribe(log.append)
        metrics = ServeMetrics()
        metrics.attach(tracer)
        eng = PagedServeEngine(model, params, slots=2, max_len=48,
                               block_size=8, chunk=4, tracer=tracer)
        eng.run(trace.requests(), arrivals=list(trace.arrivals))
        status, _, body = MetricsServer(
            metrics.registry, log).handle("/timeline")
        return status, body, log

    status, body1, log = run_once()
    _, body2, _ = run_once()
    findings: list[dict] = []
    if body1 != body2:
        findings.append({
            "severity": "error", "kind": "timeline-nondeterminism",
            "detail": "two same-seed runs rendered different /timeline "
                      "bodies: wall-clock state leaked into the "
                      "Chrome-trace export"})
    doc = json.loads(body1)
    valid = (status == 200 and isinstance(doc.get("traceEvents"), list)
             and bool(doc["traceEvents"])
             and all("ph" in e and "pid" in e for e in doc["traceEvents"]))
    if not valid:
        findings.append({
            "severity": "error", "kind": "timeline-invalid",
            "detail": "/timeline body is not valid Chrome trace-event "
                      "JSON (traceEvents list with ph/pid per event)"})
    timelines = build_timelines(log)
    closed = [tl for tl in timelines.values() if tl.end is not None]
    exact = bool(closed) and all(sum(tl.shares().values()) == 1
                                 for tl in closed)
    if not exact:
        findings.append({
            "severity": "error", "kind": "timeline-inexact",
            "detail": "per-request phase shares do not sum to exactly 1 "
                      "on the smoke trace"})

    ledger = Ledger(ledger_dir)
    metrics_l = {
        "timeline_requests": float(len(timelines)),
        "timeline_events": float(len(doc["traceEvents"])),
        "timeline_bytes": float(len(body1)),
        "share_sum_exact": 1.0 if exact else 0.0,
    }
    specs = [MetricSpec(n, higher_is_better=True, rel_tol=0.0)
             for n in metrics_l]
    res = ledger.compare("serve_timeline_smoke", metrics_l, specs,
                         update_baseline=update_baseline)
    findings.extend(res.findings)
    return {
        "deterministic": body1 == body2,
        "valid_chrome_trace": valid,
        "share_sum_exact": exact,
        "requests": len(timelines),
        "events": len(doc["traceEvents"]),
        "bytes": len(body1),
        "ledger": {"baseline_written": res.baseline_written,
                   "deltas": res.deltas,
                   "path": str(ledger.path("serve_timeline_smoke"))},
        "findings": findings,
    }


def smoke_arch(name: str) -> dict:
    cfg = reduced(ALL_ARCHS[name])
    shape = ShapeConfig("smoke", "train", 32, 2)
    model = build(cfg)
    key = jax.random.PRNGKey(0)
    params = model.init_params(key)
    n = sum(x.size for x in jax.tree.leaves(params))
    batch = model.sample_batch(shape, key)
    loss, metrics = jax.jit(lambda p, b: model.loss(p, b))(params, batch)
    assert jnp.isfinite(loss), (name, loss)

    # one train step
    run = RunConfig(model=cfg, shape=shape, train=TrainConfig(remat="full"))
    state = init_train_state(model, key)
    step = jax.jit(make_train_step(model, run))
    state2, m = step(state, batch)
    assert jnp.isfinite(m["loss"]), name

    # prefill + decode
    pb = model.sample_batch(ShapeConfig("smoke", "prefill", 32, 2), key)
    logits, cache = jax.jit(
        lambda p, b: model.prefill(p, b, cache_len=32))(params, pb)
    assert logits.shape == (2, cfg.padded_vocab), (name, logits.shape)
    cache2 = model.zero_cache(2, 32)
    # sizes line up?
    jax.tree.map(lambda a, b: None if a.shape == b.shape else
                 (_ for _ in ()).throw(AssertionError((name, a.shape, b.shape))),
                 cache, cache2)
    tok = jnp.zeros((2, 1), jnp.int32)
    pos = jnp.full((2,), 31, jnp.int32)
    dl, cache3 = jax.jit(model.decode_step)(params, cache, tok, pos)
    assert dl.shape == (2, cfg.padded_vocab)
    assert jnp.all(jnp.isfinite(dl)), name
    return {"arch": name, "params": int(n), "loss": float(loss),
            "step_loss": float(m["loss"])}


def run_bench(name: str, ledger_dir: str, update_baseline: bool) -> dict:
    """One ``benchmarks/<name>.py --smoke`` run, in this process: the
    harness has already touched JAX, and a child that needed the same
    accelerator would fail or hang on it."""
    import importlib

    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    mod = importlib.import_module(f"benchmarks.{name}")
    return mod.bench(smoke=True, ledger_dir=ledger_dir,
                     update_baseline=update_baseline)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("archs", nargs="*", default=None)
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="machine-readable report on the last stdout line")
    ap.add_argument("--ledger-dir", default=REPO,
                    help="BENCH_*.json directory for the perf ledger")
    ap.add_argument("--update-baseline", action="store_true")
    ap.add_argument("--artifacts-dir", default=None,
                    help="copy the BENCH_*.json ledgers and the json "
                         "report here (CI build artifacts)")
    args = ap.parse_args()
    names = args.archs or list(ALL_ARCHS)
    quiet = args.as_json

    archs = []
    for name in names:
        rec = smoke_arch(name)
        archs.append(rec)
        if not quiet:
            print(f"OK {name:24s} params={rec['params']:>10,} "
                  f"loss={rec['loss']:.3f} step_loss={rec['step_loss']:.3f}")

    # serving + audit smokes: findings fold into the one diagnostics gate
    diag = Diagnostics()
    ledger_flags = (args.ledger_dir, args.update_baseline)

    serve_rec = run_bench("serve_throughput", *ledger_flags)
    diag.extend(serve_rec["findings"], source="serve_throughput")

    audit_rec = run_bench("audit_pathways", *ledger_flags)
    diag.extend(audit_rec["findings"], source="audit_pathways")

    workloads_rec = run_bench("serve_workloads", *ledger_flags)
    diag.extend(workloads_rec["findings"], source="serve_workloads")

    cluster_rec = run_bench("serve_cluster", *ledger_flags)
    diag.extend(cluster_rec["findings"], source="serve_cluster")

    tiering_rec = run_bench("serve_tiering", *ledger_flags)
    diag.extend(tiering_rec["findings"], source="serve_tiering")

    timeline_rec = timeline_smoke(args.ledger_dir, args.update_baseline)
    diag.extend(timeline_rec["findings"], source="serve_timeline")

    ledger_deltas = {
        "serve_throughput": serve_rec.get("ledger"),
        "audit_pathways": audit_rec.get("ledger"),
        "serve_workloads": workloads_rec.get("ledger"),
        "serve_cluster": cluster_rec.get("ledger"),
        "serve_tiering": tiering_rec.get("ledger"),
        "serve_timeline": timeline_rec.get("ledger"),
    }

    # ledger integrity + trend: orphan BENCH files are errors; the
    # rolling median of the ungated wall-clock throughput is the
    # trajectory signal the per-run numbers are too noisy to carry —
    # and a latest sample collapsing below median/TREND_FACTOR is a
    # warn-level finding, not just a printout
    from repro.audit import Ledger

    ledger = Ledger(args.ledger_dir)
    diag.extend(ledger.audit_owned(owned_ledger_keys()),
                source="ledger-integrity")
    throughput_trend = ledger.rolling_median(
        "serve_throughput_smoke", "paged_tokens_per_s")
    if throughput_trend and throughput_trend["n"] >= 3:
        median, latest = throughput_trend["median"], throughput_trend["latest"]
        if median > 0 and latest < median / TREND_FACTOR:
            diag.extend([{
                "severity": "warn", "kind": "perf-trend",
                "detail": f"paged_tokens_per_s latest {latest} fell below "
                          f"median {median} / {TREND_FACTOR} over the last "
                          f"{throughput_trend['n']} ledger entries: "
                          f"sustained throughput regression"}],
                source="ledger-trend")
    ok = diag.gate()

    report = {
        "ok": ok,
        "worst": diag.worst,
        "archs": archs,
        "serve_throughput": {
            k: serve_rec[k] for k in
            ("speedup", "oracle_ok", "contiguous_tokens_per_s",
             "paged_tokens_per_s", "kernel_parity_ok",
             "kernel_vs_gather_speedup")},
        "audit_pathways": {
            "oracle_ok": audit_rec["oracle_ok"],
            "detected_all": audit_rec["detected_all"],
            "lifecycle": audit_rec.get("lifecycle"),
            "metrics": audit_rec["metrics"]},
        "serve_workloads": {
            "oracle_ok": workloads_rec["oracle_ok"],
            "slo_ok": workloads_rec["slo_ok"],
            "families": [{
                "workload": f["workload"]["workload"],
                "p99_ttft_ticks": f["p99_ttft_ticks"],
                "p99_decode_gap_ticks": f["p99_decode_gap_ticks"],
                "prefix_hit_rate": f["report"]["prefix_hit_rate"],
            } for f in workloads_rec["families"]]},
        "serve_cluster": {
            "oracle_ok": cluster_rec["oracle_ok"],
            "scaling_rmax": cluster_rec["scaling_rmax"],
            "routed_affinity": cluster_rec["routed_affinity"],
            "shared_hit_rate": cluster_rec["shared_hit_rate"],
            "replica_sweep": cluster_rec["replica_sweep"]},
        "serve_tiering": {
            "oracle_ok": tiering_rec["oracle_ok"],
            "exact_vs_reference": tiering_rec["exact_vs_reference"],
            "swap_restore_rate": tiering_rec["swap"]["swap_restore_rate"],
            "recompute_tokens_saved": tiering_rec["recompute_tokens_saved"],
            "preemptions": tiering_rec["swap"]["preemptions"]},
        "serve_timeline": {
            k: timeline_rec[k] for k in
            ("deterministic", "valid_chrome_trace", "share_sum_exact",
             "requests", "events", "bytes")},
        "paged_tokens_per_s_trend": throughput_trend,
        "findings": diag.findings,
        "ledger": ledger_deltas,
    }

    if args.artifacts_dir:
        adir = Path(args.artifacts_dir)
        adir.mkdir(parents=True, exist_ok=True)
        copied = []
        for f in sorted(Path(args.ledger_dir).glob("BENCH_*.json")):
            shutil.copy2(f, adir / f.name)
            copied.append(f.name)
        # metadata goes in before writing, so the archived report itself
        # names the ledgers that accompany it
        report["artifacts"] = {"dir": str(adir),
                               "ledgers": copied,
                               "report": "smoke_report.json"}
        (adir / "smoke_report.json").write_text(json.dumps(report, indent=1))

    if quiet:
        print(json.dumps(report))
    else:
        print(diag.render())
        print(f"OK serve_throughput        speedup={serve_rec['speedup']}x "
              f"oracle_ok={serve_rec['oracle_ok']} "
              f"hit_rate={serve_rec['paged']['prefix_hit_rate']} "
              f"kernel_parity={serve_rec['kernel_parity_ok']} "
              f"kernel_vs_gather={serve_rec['kernel_vs_gather_speedup']}x")
        print(f"OK audit_pathways          "
              f"detected_all={audit_rec['detected_all']} "
              f"oracle_ok={audit_rec['oracle_ok']}")
        print(f"OK serve_workloads         "
              f"slo_ok={workloads_rec['slo_ok']} "
              f"oracle_ok={workloads_rec['oracle_ok']}")
        print(f"OK serve_cluster           "
              f"rmax={cluster_rec['scaling_rmax']} "
              f"affinity={cluster_rec['routed_affinity']} "
              f"shared_hit={cluster_rec['shared_hit_rate']} "
              f"oracle_ok={cluster_rec['oracle_ok']}")
        print(f"OK serve_tiering           "
              f"restore_rate={tiering_rec['swap']['swap_restore_rate']} "
              f"saved={tiering_rec['recompute_tokens_saved']} "
              f"exact={tiering_rec['exact_vs_reference']} "
              f"oracle_ok={tiering_rec['oracle_ok']}")
        print(f"OK serve_timeline          "
              f"deterministic={timeline_rec['deterministic']} "
              f"valid={timeline_rec['valid_chrome_trace']} "
              f"share_sum_exact={timeline_rec['share_sum_exact']} "
              f"requests={timeline_rec['requests']}")
        if throughput_trend:
            print(f"   paged_tokens_per_s     "
                  f"median={throughput_trend['median']} "
                  f"over n={throughput_trend['n']} "
                  f"latest={throughput_trend['latest']}")
        print("ALL OK" if ok else "GATE FAILED")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
