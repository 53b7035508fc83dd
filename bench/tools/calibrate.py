"""Readings that set a routed cell's limits: for each seed, one run of the
cell, then on the same served requests the reference's logits and
routing margins, and (with ``<control>`` 1) the fp8 control's tokens.
Not part of the benchmark's runs.

    python3 bench/tools/calibrate.py <cell> <seconds> <traced> <control> <seed> ...

With ``<traced>`` 1 every run is traced: its per-layer metrics are read,
the operations under the program's ``moe_`` scopes are written out, and
the engine gets the program's own tracer, whose ``step`` events give
``expert_rows`` beside the fed rows times the held experts' expected
share, and :func:`expert_load_max_share` (the tracer's counters cost the
host a little each step; untraced runs are as the benchmark's).  Per
seed, ``bench_out/calibrate_<cell>_<seed>.npz`` holds, at every served
position, the program's gap (``bench.check.gaps``; no position is left
out here), the control's gap, and the position's smallest routing margin
over the layers (``reference.forward``), so that a tie margin and the
limits can be chosen afterwards; one JSON line per seed is printed with
the run's result and, for a few tie margins, the share of positions left
undecided and the checks the program and the control would read.
"""
import time

T_PROCESS = time.perf_counter()

import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

TIES = (0.0, 0.0005, 0.001, 0.002, 0.005, 0.01)


def expert_load_max_share(events: list[dict]) -> float | None:
    """The most rows one held expert took in a step, as a share of the
    rows routed to the held experts, over the ``step`` events that carry
    them (%): 100 / held where the load is even."""
    rows = sum(e["expert_rows"] for e in events if "expert_rows" in e)
    if not rows:
        return None
    return 100.0 * sum(e["expert_rows_max"] for e in events
                       if "expert_rows" in e) / rows


def checks_at(gap, margin, tie, limits) -> dict:
    """The checks at tie margin ``tie``, over the positions it keeps."""
    keep = margin >= tie
    g = gap[keep]
    share = limits.get("share_over_gap", {"gap": 0.1})
    return {"undecided": float(1 - keep.mean()) * 100,
            "widest": float(g.max()) if g.size else None,
            "over": float((g > share["gap"]).mean() * 100) if g.size else None,
            "compared": int(g.size)}


def main() -> None:
    from bench import check, harness, program, spec
    from bench import trace as tracing
    from repro.audit.trace import Tracer
    from repro.core.bootstrap import setup_compile_cache

    setup_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    out_dir = ROOT / "bench_out"
    out_dir.mkdir(exist_ok=True)
    events: list[dict] = []
    build = program.build_engine

    def build_traced(model, params, serving):
        tracer = Tracer(capacity=16)
        tracer.subscribe(lambda ev: ev.kind == "step"
                         and events.append(ev.data))
        eng = build(model, params, serving)
        eng.trace = tracer
        return eng

    name, seconds = sys.argv[1], float(sys.argv[2])
    traced, control = sys.argv[3] == "1", sys.argv[4] == "1"
    if traced:
        program.build_engine = build_traced
    read = tracing.read

    def read_and_dump(d):
        device, host, labels = read(d)
        ops = {}
        for evs in device.values():
            for e in evs:
                lab = labels.get(e.name, "")
                if "moe_" in e.name or "moe_" in lab:
                    ops.setdefault(tracing.short(e.name),
                                   [0.0, 0, lab[:400]])
                    ops[tracing.short(e.name)][0] += e.end - e.start
                    ops[tracing.short(e.name)][1] += 1
        (out_dir / f"calibrate_ops_{name}.json").write_text(
            json.dumps(sorted(ops.items(), key=lambda kv: -kv[1][0]),
                       indent=1))
        return device, host, labels

    tracing.read = read_and_dump
    for i, seed in enumerate(map(int, sys.argv[5:])):
        cell = spec.load(ROOT, name, traced)
        served: list = []
        # the first run's set-up counts from process start, as run.py's
        t = T_PROCESS if i == 0 else time.perf_counter()

        def log(msg):
            print(msg, file=sys.stderr, flush=True)
            if msg.startswith("bench: set-up"):
                events.clear()       # the window opens: its steps follow

        out = harness.run(cell, seed, seconds, traced, t, log=log,
                          keep=served)
        t_run = time.perf_counter() - t
        window = [e for e in events if "expert_rows" in e]
        fed = sum(n for e in window for _, n in e["work"])
        cfg = cell.config
        share = (cfg["num_experts_per_tok"] * cfg["num_experts"]
                 / cfg["expert_parallel"]["experts_routed"])
        layers = cfg["num_hidden_layers"] - cfg["num_dense_layers"]
        tokens, positions = check.reference_inputs(served)
        t = time.perf_counter()
        exact, margins = cell.reference.forward(cfg, seed, tokens, positions)
        t_ref = time.perf_counter() - t
        gap = check.gaps(exact, [s.out for s in served])
        ctl = np.full_like(gap, np.nan)
        if control:
            quant, _ = cell.reference.forward(cfg, seed, tokens, positions,
                                              quantize=check.fp8_weights)
            ctl = check.gaps(exact, [q.argmax(axis=1) for q in quant])
        margin = np.concatenate(margins)
        np.savez(out_dir / f"calibrate_{name}_{seed}.npz", gap=gap, ctl=ctl,
                 margin=margin)
        limits = cfg["limits"]
        print(json.dumps({
            "seed": seed, "traced": traced, "run_s": t_run, "ref_s": t_ref,
            "result": out,
            "expert_rows": sum(e["expert_rows"] for e in window),
            "fed_rows_x_share_x_layers": fed * share * layers,
            "expert_load_max_share": expert_load_max_share(window),
            "window_pages": sum(e.get("window_pages", 0) for e in window),
            "steps": len(window),
            "ties": {str(tie): {"program": checks_at(gap, margin, tie,
                                                      limits),
                                "control": checks_at(ctl, margin, tie,
                                                     limits)
                                if control else None}
                     for tie in TIES}}), flush=True)


if __name__ == "__main__":
    main()
