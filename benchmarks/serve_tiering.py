"""KV memory tiering: swap-restore vs recompute under preemption pressure.

The host swap tier's value proposition, measured: a preemption-heavy
priority trace runs on a tight single-digit-page pool three ways —

  * **reference**: ample slots, no interference (the uninterrupted
    streams every constrained run must reproduce);
  * **swap on**: preempted requests park their written pages in the host
    tier and readmission swaps them back (no re-prefill);
  * **swap off**: every readmission re-prefills prompt + generated
    tokens from scratch (the PR-4 recompute pathway, now the costed
    fallback).

Correctness first: ``compare_engines`` (greedy AND sampled) must stay
green with the tier on, and both constrained runs must emit exactly the
reference streams — swap restore is bit-exact (the restored rows ARE the
rows an uninterrupted run wrote), recompute is the established
equivalence.  Then the contrast: the swap run's ``restored_tokens``
(= ``recompute_tokens_saved``) and ``swap_restore_rate`` go into the
persisted ledger with tight bands, the re-prefill chunk steps the
no-swap run wastes are reported, and wall-clock throughput is tracked
ungated.

    PYTHONPATH=src python benchmarks/serve_tiering.py [--smoke]
        [--ledger-dir DIR] [--update-baseline]

Prints one JSON object on the last line.  ``findings`` carries the
machine-checkable diagnostics records scripts/smoke_all.py folds into
the CI gate.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

try:  # run as a module (benchmarks.run) or as a script
    from benchmarks.serve_throughput import (PAGED_COUNTER_SPECS,
                                             paged_counter_metrics)
except ImportError:  # pragma: no cover - script path
    from serve_throughput import PAGED_COUNTER_SPECS, paged_counter_metrics


def _tier_trace(vocab: int, *, n_low: int, n_high: int, low_max_new: int,
                high_max_new: int, seed: int):
    """Preemption bait: long low-priority requests saturate the slots,
    staggered pairs of short high-priority requests arrive later and
    evict them — twice, so readmitted lows are preempted *again* with
    more written pages parked each time."""
    from repro.serve.engine import Request

    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, vocab, size=12).tolist()
    tails = [rng.integers(0, vocab, size=int(rng.integers(3, 7))).tolist()
             for _ in range(n_low + n_high)]

    def make() -> list:
        reqs = [Request(rid=i, prompt=prefix + tails[i],
                        max_new=low_max_new, priority=0)
                for i in range(n_low)]
        reqs += [Request(rid=n_low + j, prompt=prefix + tails[n_low + j],
                         max_new=high_max_new, priority=5)
                 for j in range(n_high)]
        return reqs

    # lows at t=0; highs in two waves so the lows resume in between
    arrivals = [0.0] * n_low
    wave_gap = 6.0 + 3.0 * low_max_new / 4
    for j in range(n_high):
        arrivals.append(8.0 + 2.0 * (j % (n_high // 2))
                        + wave_gap * (j // (n_high // 2)))
    return make, arrivals


def _timed_run(eng, reqs, arrivals):
    t0 = time.perf_counter()
    for req, arr in zip(reqs, arrivals):
        eng.submit(req, arrival=arr)
    done = eng.drain()
    return time.perf_counter() - t0, done


def bench(arch: str = "deepseek-7b", *, smoke: bool = False, seed: int = 0,
          ledger_dir: str | None = None,
          update_baseline: bool = False) -> dict:
    from repro.audit import AuditContext, Ledger, MetricSpec, RunAudit
    from repro.configs import ALL_ARCHS, reduced
    from repro.models import build
    from repro.serve import SamplingParams
    from repro.core.verify import BF16_LOGITS_TOL
    from repro.serve.engine import (PagedServeEngine, compare_engines,
                                    token_matrix)

    if smoke:
        n_low, n_high, low_max_new, high_max_new = 2, 4, 20, 4
        slots, max_len, block, chunk, blocks = 2, 64, 4, 4, 24
    else:
        n_low, n_high, low_max_new, high_max_new = 3, 6, 28, 6
        slots, max_len, block, chunk, blocks = 3, 96, 4, 4, 48

    cfg = reduced(ALL_ARCHS[arch])
    model = build(cfg)
    params = model.init_params(jax.random.PRNGKey(seed))
    make, arrivals = _tier_trace(cfg.vocab_size, n_low=n_low, n_high=n_high,
                                 low_max_new=low_max_new,
                                 high_max_new=high_max_new, seed=seed)
    n_req = n_low + n_high
    findings: list[dict] = []

    # ------- correctness: the dual-environment verdict with the tier on
    sampled = SamplingParams(temperature=0.8, top_k=20, top_p=0.95,
                             seed=seed + 1)
    oracle_ok: dict[str, bool] = {}
    for mode, sp in (("greedy", None), ("sampled", sampled)):
        # judged on logits: this trace parts the contiguous and paged
        # greedy streams at a near-tie (two logits 0.017 apart that the
        # two pathways' bf16 rounding orders differently)
        verify = compare_engines(model, params, make, slots=slots,
                                 max_len=max_len, block_size=block,
                                 chunk=chunk, sampling=sp,
                                 logits_tol=BF16_LOGITS_TOL)
        oracle_ok[mode] = verify.ok
        for v in verify.verdicts:
            if not v.ok:
                findings.append({"severity": "error",
                                 "kind": f"serve-oracle-{mode}-{v.kind}",
                                 "detail": v.detail})

    # ------- reference: enough slots for everyone, nothing preempted
    ref = PagedServeEngine(model, params, slots=n_req, max_len=max_len,
                           block_size=block, chunk=chunk)
    _, ref_done = _timed_run(ref, make(), arrivals)
    ref_tokens = token_matrix(ref_done, n_req, low_max_new)
    if ref.report()["preemptions"] != 0:  # the contrast needs a clean ref
        findings.append({
            "severity": "error", "kind": "tiering-reference-preempted",
            "detail": "ample reference engine preempted: trace geometry "
                      "no longer isolates the swap pathway"})

    # ------- the contrast: same tight engine, tier on vs off
    from repro.serve.engine import Request

    def tight_run(swap: bool):
        audit = RunAudit(AuditContext(workload="bench:serve_tiering",
                                      family=cfg.family, arch=cfg.name,
                                      shared_prefix=True))
        eng = PagedServeEngine(model, params, slots=slots, max_len=max_len,
                               block_size=block, chunk=chunk,
                               num_blocks=blocks, swap=swap,
                               tracer=audit.tracer)
        # compile warm-up on disjoint prompts, then rewind the tick clock
        # so the measured arrivals mean what they say
        warm_rng = np.random.default_rng(seed + 99)
        eng.run([Request(rid=10_000 + i,
                         prompt=warm_rng.integers(
                             0, cfg.vocab_size, 6).tolist(), max_new=2)
                 for i in range(slots)])
        eng.now = 0.0
        wall, done = _timed_run(eng, make(), arrivals)
        return audit, eng, wall, token_matrix(done, n_req, low_max_new)

    sw_audit, sw_eng, sw_wall, sw_tokens = tight_run(swap=True)
    sw_rep = sw_eng.report()
    findings.extend(sw_audit.evaluate(engine_report=sw_rep))

    ns_audit, ns_eng, ns_wall, ns_tokens = tight_run(swap=False)
    ns_rep = ns_eng.report()

    for name, toks in (("swap", sw_tokens), ("no-swap", ns_tokens)):
        if not bool((toks == ref_tokens).all()):
            findings.append({
                "severity": "error", "kind": "tiering-exactness",
                "detail": f"{name} constrained run diverged from the "
                          f"uninterrupted reference streams — preemption "
                          f"must never change the answer"})

    # the trace must actually exercise the tier, or the bands attest air
    if sw_rep["preemptions"] == 0 or sw_rep["swap_ins"] == 0 \
            or sw_rep["restored_tokens"] == 0:
        findings.append({
            "severity": "error", "kind": "tiering-no-swap-activity",
            "detail": f"swap run shows no tier activity (preemptions="
                      f"{sw_rep['preemptions']} swap_ins="
                      f"{sw_rep['swap_ins']} restored_tokens="
                      f"{sw_rep['restored_tokens']}): the trace no longer "
                      f"triggers preemption"})

    sw_tokens_out = sum((r >= 0).sum() for r in sw_tokens)
    sw_tps = float(sw_tokens_out) / max(sw_wall, 1e-9)
    ns_tps = float(sw_tokens_out) / max(ns_wall, 1e-9)

    # ---- persisted perf ledger: deterministic tiering counters carry
    # tight bands (they only move when the pathway itself changes);
    # wall-clock throughput is recorded ungated
    ledger_out = None
    if ledger_dir is not None:
        bench_key = f"serve_tiering_{'smoke' if smoke else 'full'}"
        res = Ledger(ledger_dir).compare(
            bench_key,
            {**paged_counter_metrics(sw_rep),
             "swap_restore_rate": float(sw_rep["swap_restore_rate"]),
             "recompute_tokens_saved":
                 float(sw_rep["recompute_tokens_saved"]),
             "preemptions": float(sw_rep["preemptions"]),
             "noswap_extra_decode_steps":
                 float(ns_rep["decode_steps"] - sw_rep["decode_steps"]),
             "swap_tokens_per_s": round(sw_tps, 1),
             "noswap_tokens_per_s": round(ns_tps, 1)},
            PAGED_COUNTER_SPECS
            + [MetricSpec("swap_restore_rate", higher_is_better=True,
                          rel_tol=0.0),
               MetricSpec("recompute_tokens_saved", higher_is_better=True,
                          rel_tol=0.0),
               MetricSpec("preemptions", higher_is_better=False,
                          rel_tol=0.0),
               MetricSpec("noswap_extra_decode_steps",
                          higher_is_better=True, rel_tol=0.0),
               MetricSpec("swap_tokens_per_s", gate=False),
               MetricSpec("noswap_tokens_per_s", gate=False)],
            update_baseline=update_baseline)
        findings.extend(res.findings)
        ledger_out = {"baseline_written": res.baseline_written,
                      "deltas": res.deltas}

    return {
        "bench": "serve_tiering",
        "arch": cfg.name,
        "mode": "smoke" if smoke else "full",
        "oracle_ok": all(oracle_ok.values()),
        "oracle_modes": oracle_ok,
        "trace": {"requests": n_req, "low_max_new": low_max_new,
                  "slots": slots, "num_blocks": blocks,
                  "block_size": block, "chunk": chunk},
        "exact_vs_reference": bool((sw_tokens == ref_tokens).all()
                                   and (ns_tokens == ref_tokens).all()),
        "swap": {
            "preemptions": sw_rep["preemptions"],
            "swap_outs": sw_rep["swap_outs"],
            "swap_ins": sw_rep["swap_ins"],
            "swap_restore_rate": sw_rep["swap_restore_rate"],
            "restored_tokens": sw_rep["restored_tokens"],
            "recompute_tokens": sw_rep["recompute_tokens"],
            "decode_steps": sw_rep["decode_steps"],
            "host_page_peak": sw_rep["host_page_peak"],
            "tokens_per_s": round(sw_tps, 1),
        },
        "no_swap": {
            "preemptions": ns_rep["preemptions"],
            "recompute_tokens": ns_rep["recompute_tokens"],
            "decode_steps": ns_rep["decode_steps"],
            "tokens_per_s": round(ns_tps, 1),
        },
        "recompute_tokens_saved": sw_rep["recompute_tokens_saved"],
        "ledger": ledger_out,
        "findings": findings,
    }


def run():
    """benchmarks.run CSV protocol."""
    res = bench(smoke=True)
    yield {"name": "serve_tiering.swap_vs_recompute",
           "us_per_call": 1e6 / max(res["swap"]["tokens_per_s"], 1e-9),
           "derived": (f"restore_rate={res['swap']['swap_restore_rate']} "
                       f"saved={res['recompute_tokens_saved']} "
                       f"exact={res['exact_vs_reference']} "
                       f"oracle_ok={res['oracle_ok']}")}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-7b")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny trace sized for a ~2s measured run")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ledger-dir", default=None,
                    help="BENCH_*.json directory; omit to skip the ledger")
    ap.add_argument("--update-baseline", action="store_true")
    args = ap.parse_args()
    # one JSON object on the last line (the repo's benchmark convention)
    print(json.dumps(bench(args.arch, smoke=args.smoke, seed=args.seed,
                           ledger_dir=args.ledger_dir,
                           update_baseline=args.update_baseline)))


if __name__ == "__main__":
    main()
