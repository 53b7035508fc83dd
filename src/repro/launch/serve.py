"""Serving driver: the paged-KV engine over one architecture.

    PYTHONPATH=src python -m repro.launch.serve --arch phi3-mini-3.8b \
        --requests 8 --slots 4 [--temperature 0.8 --top-k 20 --top-p 0.95]

The model is built at its published widths (``resolve_arch``).
``--reduced`` (``serve(..., reduced=True)``) swaps in the tiny same-family
``configs.base.reduced`` config instead: the CPU callers' choice (tests,
``scripts/smoke_all.py``, quick runs without an accelerator).

The paged path (prefix cache + chunked prefill + scheduler) is the
default for attention-cache families; ``--engine contiguous`` selects the
seed slot engine, which is also the automatic fallback for families the
chunked decode does not cover (ssm/hybrid/vlm/encdec) and the
dual-environment oracle for ``repro.serve.compare_engines``.

Both engines are driven through the unified request-lifecycle API
(``serve.api``): requests are submitted with per-request
``SamplingParams`` (greedy by default; counter-based PRNG keys make
sampled streams deterministic and engine-independent) and drained, and
per-request TTFT comes from the audit tracer's lifecycle events.

``--replicas N`` (N > 1) serves through ``repro.serve.cluster``: N paged
replicas behind prefix-affinity routing (``--routing`` selects the
policy; ``random`` deliberately misroutes so operators can watch the
``pathway-routing`` detector fire without changing a single token).

``--metrics-port`` starts the live observability endpoint
(``audit.metrics.MetricsServer``): a ``ServeMetrics`` registry and an
``EventLog`` subscribe to the audit tracer, so ``/metrics`` (Prometheus
text), ``/metrics.json`` (snapshot with deterministic quantiles),
``/events`` (filtered JSONL), ``/timeline`` (Chrome-trace JSON of the
reconstructed per-request phase timelines), ``/requests/<rid>`` (one
request's history + phase decomposition), and ``/healthz`` reflect the
run as it happens.  Port 0 picks an ephemeral port (reported in the
output); ``--metrics-linger`` keeps the endpoint up after the drain so
an operator can scrape the finished run.

``--trace-out FILE`` writes the same Chrome-trace-event JSON to disk
after the drain — load it in Perfetto (https://ui.perfetto.dev) or
``chrome://tracing`` to see one track per replica/slot plus a queue
track (see ``docs/observability.md``).
"""
from __future__ import annotations

import argparse
import json
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Any

import jax
import numpy as np

from repro.audit import (AuditContext, Evidence, EventLog, MetricsServer,
                         RunAudit, ServeMetrics, Tracer, attribution,
                         build_timelines, chrome_trace_bytes)
from repro.configs.base import reduced as reduce_config
from repro.core.bootstrap import setup_compile_cache
from repro.core.registry import resolve_arch
from repro.models import build
from repro.serve import (ClusterEngine, PagedServeEngine, Request,
                         SamplingParams, ServeEngine)


@dataclass
class ServeRun:
    """One ``run_serve`` call: the report ``serve`` returns, plus what a
    caller that checks the run needs — the model and weights it served,
    the engine after the drain, and the finished requests."""
    out: dict
    model: Any
    params: Any
    engine: Any
    done: list


def serve(arch: str, **kwargs) -> dict:
    """Serve one seeded workload and return the JSON-able report (see
    :func:`run_serve` for the arguments)."""
    return run_serve(arch, **kwargs).out


def run_serve(arch: str, *, reduced: bool = False, n_requests: int = 8,
              slots: int = 4, max_len: int = 96, max_new: int = 16,
              seed: int = 0, engine: str = "paged", block_size: int = 16,
              chunk: int = 4, num_blocks: int | None = None,
              shared_prefix: int = 0, keep_logits: bool = False,
              use_prefix_cache: bool = True, kernel: str = "paged",
              swap: bool = True, replicas: int = 1,
              routing: str = "affinity", audit: bool = True,
              metrics_port: int | None = None, metrics_linger: float = 0.0,
              trace_out: str | None = None, temperature: float = 0.0,
              top_k: int = 0, top_p: float = 1.0,
              sampling_seed: int = 0) -> ServeRun:
    """Build ``arch`` (published widths, or ``reduced``) with weights from
    ``seed``, serve ``n_requests`` seeded prompts (a ``shared_prefix``
    common to all, then 4-16 own tokens) and drain.  ``keep_logits``
    records each output token's logits row on the requests."""
    if trace_out is not None and not audit:
        raise ValueError("--trace-out reconstructs timelines from the "
                         "audit tracer; drop --no-audit")
    cfg = resolve_arch(arch)
    if reduced:
        cfg = reduce_config(cfg)
    model = build(cfg)
    params = model.init_params(jax.random.PRNGKey(seed))
    sampling = SamplingParams(temperature=temperature, top_k=top_k,
                              top_p=top_p, seed=sampling_seed)

    if engine == "paged" and cfg.family not in ("dense", "moe"):
        engine = "contiguous"   # no chunked path for stateful caches yet
    # cluster replicas are paged engines; anything that forces the
    # contiguous path also collapses the cluster to a single engine
    if engine != "paged":
        replicas = 1
    is_cluster = replicas > 1
    # a shared prefix shorter than one page cannot produce cache hits
    # (only full blocks register), so only declare the workload
    # shared-prefix when a hit is actually possible
    run_audit = RunAudit(AuditContext(
        workload="serve", family=cfg.family, arch=cfg.name,
        shared_prefix=shared_prefix >= block_size)) if audit else None
    tracer = run_audit.tracer if run_audit else None
    replica_tracers = [Tracer() for _ in range(replicas)] if is_cluster else []

    # live observability: metrics + event log fed from the tracer's
    # subscription hook, exposed over HTTP while the engine runs.  A
    # cluster attaches one replica-labelled ServeMetrics per replica
    # tracer to the SAME registry, so the single endpoint serves every
    # replica's series side by side.
    metrics = server = None
    if metrics_port is not None:
        if tracer is None:
            raise ValueError("--metrics-port needs the audit tracer; "
                             "drop --no-audit")
        metrics = ServeMetrics()
        metrics.attach(tracer)
        for i, rt in enumerate(replica_tracers):
            ServeMetrics(metrics.registry,
                         labels={"replica": str(i)}).attach(rt)
        log = EventLog()
        tracer.subscribe(log.append)
        # replica tracers carry the admit/prefill-done/finish lifecycle
        # a cluster's front tracer never sees — /timeline and
        # /requests/<rid> need the merged stream
        for rt in replica_tracers:
            rt.subscribe(log.append)
        server = MetricsServer(metrics.registry, log)
        bound_port = server.serve(port=metrics_port)
    if is_cluster:
        eng = ClusterEngine(model, params, replicas=replicas, slots=slots,
                            max_len=max_len, block_size=block_size,
                            chunk=chunk, routing=routing,
                            num_blocks=num_blocks,
                            use_prefix_cache=use_prefix_cache,
                            kernel=kernel, tracer=tracer,
                            replica_tracers=replica_tracers)
    elif engine == "paged":
        eng = PagedServeEngine(model, params, slots=slots, max_len=max_len,
                               block_size=block_size, chunk=chunk,
                               num_blocks=num_blocks,
                               use_prefix_cache=use_prefix_cache,
                               kernel=kernel, swap=swap, tracer=tracer)
    else:
        eng = ServeEngine(model, params, slots=slots, max_len=max_len,
                          tracer=tracer)

    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, cfg.vocab_size, size=shared_prefix).tolist()
    reqs = [
        Request(rid=i,
                prompt=prefix + rng.integers(
                    0, cfg.vocab_size, size=rng.integers(4, 17)).tolist(),
                max_new=max_new, sampling=sampling, keep_logits=keep_logits)
        for i in range(n_requests)
    ]
    t0 = time.time()
    for req in reqs:
        eng.submit(req)
    done = eng.drain()
    wall = time.time() - t0

    ttfts = [r.t_first - r.t_submit for r in done if r.t_first]
    rep = eng.report()
    dev = jax.devices()[0]
    out = {
        "arch": cfg.name,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "engine": rep["engine"],
        "sampling": sampling.describe(),
        "served": rep["served"],
        "decode_steps": rep["decode_steps"],
        "tokens_out": rep["tokens_out"],
        "mean_batch_occupancy": rep["mean_batch_occupancy"],
        "mean_ttft_s": round(float(np.mean(ttfts)), 4) if ttfts else None,
        "tokens_per_s": round(rep["tokens_out"] / max(wall, 1e-9), 1),
        "wall_s": round(wall, 2),
    }
    if is_cluster:
        out.update({k: rep[k] for k in
                    ("replicas", "routing", "routed", "routed_affinity",
                     "routed_spills", "shared_hit_rate", "prefix_hit_rate",
                     "preemptions", "kernel", "attention",
                     "summary_rebuilds")})
    elif engine == "paged":
        out.update({k: rep[k] for k in
                    ("prefill_tokens", "cached_tokens", "prefix_hit_rate",
                     "pages", "page_peak_utilization", "preemptions",
                     "kernel", "attention", "swap", "swap_restore_rate",
                     "restored_tokens", "recompute_tokens")})
    if run_audit is not None:
        lat = Evidence(tracer=run_audit.tracer).request_latencies()
        if lat:
            ttft_ticks = [l["ttft_ticks"] for l in lat.values()]
            out["mean_ttft_ticks"] = round(float(np.mean(ttft_ticks)), 2)
            out["max_ttft_ticks"] = round(float(np.max(ttft_ticks)), 2)
        timelines = build_timelines(tracer, *replica_tracers)
        att = attribution(timelines)
        if att:
            out["attribution"] = {
                "p99_ttft_ticks": att["p99_ttft_ticks"],
                "dominant_phase": att["dominant_phase"],
                "p99_shares": {k: round(v, 3)
                               for k, v in att["p99_shares"].items()},
                "preempted_share": round(att["preempted_share"], 3),
            }
        if trace_out is not None:
            data = chrome_trace_bytes(timelines)
            Path(trace_out).write_bytes(data)
            out["trace_out"] = {"path": trace_out,
                                "requests": len(timelines),
                                "bytes": len(data)}
        diag = run_audit.finish(engine_report=eng.report(), source="serve")
        out["audit"] = {
            "findings": diag.findings,
            "worst": diag.worst,
            "gate_ok": diag.gate(),
            "trace": run_audit.tracer.summary()["counts"],
        }
    if server is not None:
        metrics.observe_report(eng.report())
        out["metrics"] = {
            "port": bound_port,
            "endpoints": ["/metrics", "/metrics.json", "/events",
                          "/timeline", "/requests/<rid>", "/healthz"],
            "finished": metrics.finished.value,
            "p99_ttft_bucket": metrics.ttft.quantile(0.99),
        }
        if metrics_linger > 0:
            time.sleep(metrics_linger)
        server.close()
    return ServeRun(out=out, model=model, params=params, engine=eng,
                    done=done)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="serve the tiny same-family config (configs.base."
                         "reduced) instead of the published widths")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=96)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--engine", choices=["paged", "contiguous"],
                    default="paged")
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--chunk", type=int, default=4)
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="length of a prompt prefix shared by all requests")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy; > 0 samples with counter-based "
                         "per-request PRNG (deterministic, replayable)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="keep only the k most likely tokens (0 = no limit)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus bound in (0, 1]")
    ap.add_argument("--sampling-seed", type=int, default=0)
    ap.add_argument("--kernel", choices=["paged", "gather"], default="paged",
                    help="paged-engine KV pathway: attend through the "
                         "device page table (default) or fall back to the "
                         "dense working-cache gather — the latter exists "
                         "so operators can watch the pathway-kernel "
                         "detector fire")
    ap.add_argument("--swap", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="host KV swap tier for preempted requests "
                         "(--no-swap recomputes on readmission instead — "
                         "token streams do not change; the pathway-tiering "
                         "detector exists to catch exactly that)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="> 1 serves through a ClusterEngine: N paged "
                         "replicas behind prefix-affinity routing, one "
                         "metrics endpoint with replica-labelled series")
    ap.add_argument("--routing",
                    choices=["affinity", "round_robin", "random"],
                    default="affinity",
                    help="cluster routing policy (random exists so "
                         "operators can watch the pathway-routing "
                         "detector fire; token streams do not change)")
    ap.add_argument("--no-prefix-cache", dest="use_prefix_cache",
                    action="store_false",
                    help="disable prefix-KV reuse (the audit flags this "
                         "on shared-prefix workloads)")
    ap.add_argument("--no-audit", dest="audit", action="store_false",
                    help="skip runtime pathway auditing")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve /metrics, /metrics.json, /events and "
                         "/healthz on this port while the run is live "
                         "(0 = ephemeral; reported in the output)")
    ap.add_argument("--metrics-linger", type=float, default=0.0,
                    help="seconds to keep the metrics endpoint up after "
                         "the drain completes")
    ap.add_argument("--trace-out", default=None, metavar="FILE",
                    help="write the per-request phase timelines as "
                         "Chrome-trace-event JSON (open in Perfetto or "
                         "chrome://tracing); needs the audit tracer")
    args = ap.parse_args()
    setup_compile_cache()
    res = serve(args.arch, reduced=args.reduced, n_requests=args.requests,
                slots=args.slots, max_len=args.max_len,
                max_new=args.max_new, engine=args.engine,
                block_size=args.block_size, chunk=args.chunk,
                shared_prefix=args.shared_prefix,
                use_prefix_cache=args.use_prefix_cache, kernel=args.kernel,
                swap=args.swap,
                replicas=args.replicas, routing=args.routing,
                audit=args.audit, metrics_port=args.metrics_port,
                metrics_linger=args.metrics_linger,
                trace_out=args.trace_out,
                temperature=args.temperature, top_k=args.top_k,
                top_p=args.top_p, sampling_seed=args.sampling_seed)
    print(json.dumps(res, indent=1))
    if res.get("audit") and not res["audit"]["gate_ok"]:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
