"""Runs a cell with the program's own spans and counters read beside the
benchmark's metrics.  Not part of the benchmark's runs.

    python3 bench/tools/phases.py <cell> <seconds> <trace> <tracer> <seed> ...

Each seed is one ``harness.run`` in this process.  ``<tracer>`` is
``real`` (the engine gets a ``repro.audit.trace.Tracer``, as
``run_serve`` gives it, and each ``step`` event is kept with the
harness's step that emitted it) or ``null`` (the engine as the benchmark
builds it).  Every run adds to its result, under ``phases``:

- ``compiles_in_window``: compiles of the engine's step programs during
  the window (``engine.report()["compiles"]`` before its first and after
  its last step);
- with ``<trace>`` 1: the metrics ``step_host_ms``, ``useful_row_share``
  and ``kv_pages_used_share`` (``bench/phases.py``; absent where the
  program has no such span or counter), ``idle_phases``, the mean
  length of each program phase per step and the mean device idle inside
  it, the profile of every window step that took 2% longer than the
  window's median step (each phase's length, the device idle inside it
  and its longest device gap, with the operations on either side), and
  for the heaviest device operations the marks their labels hold
  (``kv_append`` and those by which ``paged_attn_roofline`` finds the
  kernel), and every operation that ``paged_attn_roofline`` sums as the
  kernel, with its time and the marks it matched.

Result lines go to ``bench_out/phases_<cell>.jsonl``; a short line per
run is printed.
"""
import functools
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

SLOW = 1.02          # a step this much over the window's median is profiled
METRICS = (("step_host_ms", "ms"), ("useful_row_share", "%"),
           ("kv_pages_used_share", "%"))


class Capture:
    """What one run leaves beside the harness's own record."""

    def __init__(self):
        self.events: list[dict] = []       # step events, in order
        self.device: dict = {}
        self.host: list = []               # bench.* and serve.* spans
        self.labels: dict = {}

    def on_event(self, ev) -> None:
        if ev.kind == "step":
            self.events.append(ev.data)


def install(cap: list, tracer_kind: str) -> None:
    """Wrap the harness's engine build, step and trace read so that the
    current ``Capture`` (``cap[0]``) receives the program's events and
    spans."""
    import repro.serve as serve
    from bench import harness
    from bench import phases as ph
    from bench import trace as tracing
    from repro.audit.trace import Tracer

    engine_cls = serve.PagedServeEngine

    def build(*args, **kw):
        if tracer_kind == "real":
            tr = Tracer()
            tr.subscribe(cap[0].on_event)
            kw["tracer"] = tr
        return engine_cls(*args, **kw)

    # bench.program builds the engine from repro.serve at call time
    serve.PagedServeEngine = build

    step = harness.Driver.step

    @functools.wraps(step)
    def traced_step(self):
        mark = len(cap[0].events)
        before = self.engine.report()["compiles"]
        st = step(self)
        st.compiles = (before, self.engine.report()["compiles"])
        st.events = cap[0].events[mark:]
        return st

    harness.Driver.step = traced_step
    read = tracing.read

    def read_all(trace_dir):
        device, host, labels = read(trace_dir)
        cap[0].device, cap[0].labels = device, labels
        cap[0].host = host + ph.read(trace_dir)
        return device, host, labels

    tracing.read = read_all


def readers(cap: list) -> dict:
    """The tool's metric readers, each ``read(window)``."""
    from types import SimpleNamespace

    from bench import phases as ph

    def spans():
        window = ph.window_of(cap[0].host)
        return (cap[0].host, *window) if window else None

    def events(w):
        return [e for s in w.window_steps() for e in getattr(s, "events", ())]

    def host_ms(w):
        got = spans()
        return ph.step_host_ms(*got) if got else None

    return {"step_host_ms": SimpleNamespace(read=host_ms),
            "useful_row_share": SimpleNamespace(
                read=lambda w: ph.useful_row_share(events(w))),
            "kv_pages_used_share": SimpleNamespace(
                read=lambda w: ph.kv_pages_used_share(events(w)))}


def extras(cap, window, traced: bool) -> dict:
    from bench import phases as ph
    from bench import spec
    from bench import trace as tracing

    roofline = spec.load_module(ROOT / "bench" / "metrics"
                                / "paged_attn_roofline.py")
    marks = ("kv_append",) + roofline.KERNEL

    steps = window.window_steps()
    out = {"compiles_in_window": (steps[-1].compiles[1]
                                  - steps[0].compiles[0]) if steps else None}
    if not traced:
        return out
    out["idle_phases"] = ph.idle_phases(cap.device, cap.host)

    def marked(name, of):
        return [m for m in of if m in name or m in cap.labels.get(name, "")]

    heavy = sorted(window.trace.ops.items(), key=lambda kv: -kv[1])[:10]
    out["op_marks"] = [[tracing.short(name), marked(name, marks)]
                       for name, _ in heavy]
    # every operation paged_attn_roofline sums as the kernel, and why
    out["kernel_ops"] = [[tracing.short(name), t, marked(name, roofline.KERNEL)]
                         for name, t in window.trace.ops.items()
                         if marked(name, roofline.KERNEL)]
    profile = ph.step_profile(cap.device, cap.host)
    out["steps"] = len(profile)
    if profile:
        times = ph.phase_times(cap.host, *ph.window_of(cap.host))
        out["phase_ms"] = {name: 1e3 * times.get(name, 0.0) / len(profile)
                           for name in ph.PHASES}
        out["phase_idle_ms"] = {name: 1e3 * statistics.mean(
            p["idle"].get(name, 0.0) for p in profile)
            for name in ph.PHASES}
        median = statistics.median(p["length"] for p in profile)
        out["median_step_ms"] = 1e3 * median
        out["slow_steps"] = [
            {"start_s": p["start"], "length_ms": 1e3 * p["length"],
             "phase_ms": {k: 1e3 * v for k, v in p["phases"].items()},
             "device_idle_ms": {k: 1e3 * v for k, v in p["idle"].items()},
             "longest_gap": p["gap"]}
            for p in profile if p["length"] > SLOW * median]
        typical = min(profile, key=lambda p: abs(p["length"] - median))
        out["typical_longest_gap"] = typical["gap"]
    return out


def main() -> None:
    cell_name, seconds, trace, tracer_kind = sys.argv[1:5]
    seeds = [int(s) for s in sys.argv[5:]]
    traced = trace == "1"
    from bench import harness, spec
    from repro.core.bootstrap import setup_compile_cache

    setup_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cap = [Capture()]
    install(cap, tracer_kind)
    windows = []
    window_cls = harness.Window

    def keep_window(*args, **kw):
        windows.append(window_cls(*args, **kw))
        return windows[-1]

    harness.Window = keep_window
    out_path = ROOT / "bench_out" / f"phases_{cell_name}.jsonl"
    out_path.parent.mkdir(exist_ok=True)
    for seed in seeds:
        cap[0] = Capture()
        cell = spec.load(ROOT, cell_name, traced)
        if traced:
            cell.readers.update(readers(cap))
            cell.metrics += [{"name": n, "unit": u} for n, u in METRICS]
        t = time.perf_counter()
        try:
            res = harness.run(cell, seed, float(seconds), traced, t)
            res["phases"] = extras(cap[0], windows[-1], traced)
        except Exception as e:  # noqa: BLE001 - a tool reports and goes on
            import traceback
            traceback.print_exc()
            res = {"error": repr(e)}
        res.update(seed=seed, tracer=tracer_kind, checkout=str(ROOT))
        with out_path.open("a") as f:
            f.write(json.dumps(res) + "\n")
        ph_res = res.get("phases", {})
        print(json.dumps(
            {"seed": seed, "correct": res.get("correct"),
             "compiles_in_window": ph_res.get("compiles_in_window"),
             "slow_steps": len(ph_res.get("slow_steps", ()))}
            | {m: v["value"] for m, v in res.get("metrics", {}).items()}),
            flush=True)


if __name__ == "__main__":
    main()
