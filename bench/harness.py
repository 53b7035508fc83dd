"""One run of one cell: set-up, the measured window, the comparison with
the reference, and the result line.

Set-up (``setup_s``, from process start to the window's opening): the
weights are drawn from the seed on the device, the engine is built from
the configuration's serving geometry, one throwaway request is served
from admission to finish and one of its pages is evicted to the swap
tier (every host and device path of a request runs once), and the
backlog fills every lane: the mix's in-flight requests first, each
prefilled with the part of its answer it stands for.  The window opens
when every lane is decoding.

The window drives ``PagedServeEngine.submit`` / ``.step`` from this one
thread, for ``seconds`` on the host clock: it closes at the return of
the first step that ends past that time, so every step it holds is
whole.  The backlog keeps ``backlog_per_slot`` x slots requests waiting,
so a lane that finishes is refilled at the next step.

Host spans (``bench.window``, ``bench.submit``, ``bench.step``,
``bench.collect``) go into the profiler's trace when the run is traced;
every metric is computed by its reader under ``bench/metrics`` from the
:class:`Window` record.
"""
from __future__ import annotations

import contextlib
import gc
import shutil
import sys
import time
from dataclasses import dataclass, field
from types import ModuleType

import jax
import numpy as np
from jax.experimental.compilation_cache.compilation_cache import reset_cache

from bench import check, peaks, program, spec, traffic
from bench import trace as tracing

TRACE_DIR = ".bench_trace"     # under the checkout; emptied every traced run


@dataclass
class Rec:
    """What the harness saw of one request."""
    item: traffic.Item
    req: object                   # the program's Request
    admitted: float | None = None     # start of the step it first held a lane
    tokens: list[float] = field(default_factory=list)  # each token's step return
    failed: bool = False


@dataclass
class Step:
    start: float
    end: float
    lanes: int
    work: list[tuple[int, int]] | None = None     # (pos, n_new) per lane


@dataclass
class Window:
    """The record every metric reader reads."""
    config: dict
    family: ModuleType            # the configuration's work counts
    setup_s: float
    t0: float
    t1: float
    slots: int
    recs: dict[int, Rec]
    steps: list[Step]
    peak: object                  # peaks.ChipPeaks of the chip
    trace: tracing.Reduced | None = None

    @property
    def length(self) -> float:
        return self.t1 - self.t0

    def window_steps(self) -> list[Step]:
        return [s for s in self.steps if self.t0 <= s.start and s.end <= self.t1]


class Driver:
    """The engine, the requests it was given and the stamps it left."""

    def __init__(self, engine, items: list[traffic.Item], mix: dict,
                 slots: int, chunk: int):
        self.engine = engine
        self.items = items
        self.mix = mix
        self.slots = slots
        self.chunk = chunk
        self.recs: dict[int, Rec] = {}
        self.steps: list[Step] = []
        self.next = 0
        self.traced = False

    # ------------------------------------------------------------ intake
    def submit(self, item: traffic.Item) -> None:
        req = program.request(item.rid, item.prompt, item.max_new)
        rec = Rec(item=item, req=req)
        self.recs[item.rid] = rec
        try:
            self.engine.submit(req)
        except ValueError as e:
            rec.failed = True
            print(f"bench: request {item.rid} refused: {e}", file=sys.stderr)

    def waiting(self) -> int:
        return sum(1 for r in self.recs.values()
                   if r.admitted is None and not r.failed)

    def top_up(self) -> None:
        want = self.mix["backlog_per_slot"] * self.slots
        while self.waiting() < want and self.next < len(self.items):
            self.submit(self.items[self.next])
            self.next += 1

    # -------------------------------------------------------------- step
    def step(self) -> Step:
        eng = self.engine
        before = ({slot: (st, st.consumed, len(st.pending))
                   for slot, st in eng.active.items()} if self.traced else None)
        t_start = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.step"):
            done = eng.step()
        t_end = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.collect"):
            running = {id(st.req): st for st in eng.active.values()}
            step = Step(t_start, t_end, len(running) + len(done))
            for req in [st.req for st in running.values()] + done:
                rec = self.recs.get(req.rid)
                if rec is None:
                    continue
                if rec.admitted is None:
                    rec.admitted = t_start
                while len(rec.tokens) < len(req.out):
                    rec.tokens.append(t_end)
            if before is not None:
                step.work = self._work(before, running, done)
        self.steps.append(step)
        return step

    def _work(self, before, running, done) -> list[tuple[int, int]]:
        """(pos, n_new) of each lane that ran: lanes held before the step
        fed one token or their next prefill chunk from where they stood;
        lanes admitted in it started after their matched prefix."""
        finished = {id(r) for r in done}
        work, old = [], set()
        for st, consumed, pending in before.values():
            if id(st.req) in running or id(st.req) in finished:
                old.add(id(st))
                work.append((consumed, min(self.chunk, pending) if pending
                             else 1))
        for st in running.values():
            if id(st) not in old:
                pos = len(st.shared) * self.engine.alloc.block_size
                work.append((pos, st.consumed - pos))
        return work

    def live_pages(self) -> int:
        """Pages that hold rows written by the lanes now running."""
        bs = self.engine.alloc.block_size
        return sum(-(-st.consumed // bs) for st in self.engine.active.values())


@contextlib.contextmanager
def uncached():
    """Compile without JAX's persistent cache.  The engine pins its page
    pool to a row-major layout (``serve.paging.pool_format``); a program
    with that layout, read back from the persistent cache on a TPU,
    returns the pool in XLA's default layout, and the next step refuses
    it.  So the programs that touch the pool compile in every run's
    set-up, and every other program is read from the cache."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        reset_cache()


def _peak_bytes(dev) -> int:
    return int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))


def device_info(chips: int) -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips,
            "memory_peak_bytes": max(_peak_bytes(d) for d in devs[:chips])}


def run(cell: spec.Cell, seed: int, seconds: float, traced: bool,
        t_process: float, log=None, keep: list | None = None) -> dict:
    """One run; returns the result object (the last stdout line).
    ``keep`` receives the requests that were compared (the control tool
    reads them)."""
    log = log or (lambda m: print(m, file=sys.stderr, flush=True))
    cfg, mix, serving = cell.config, cell.mix, cell.config["serving"]
    limit = cell.family.longest_context(cfg, serving)
    if traffic.longest_context(mix) > limit:
        raise ValueError(f"the mix can fill {traffic.longest_context(mix)} "
                         f"positions; the cell holds {limit}")
    dev = jax.devices()[0]
    peak = peaks.chip_peaks(dev.device_kind) if dev.platform == "tpu" else None

    marks = [("start", time.perf_counter())]
    model = program.build_model(cell.family.model_config(cfg))
    params = jax.block_until_ready(
        program.make_params(model, cell.family, cell.reference, cfg, seed))
    marks.append(("weights drawn", time.perf_counter()))
    slots = serving["slots"]
    items = traffic.generate(mix, seed, cfg["vocab_size"], slots)
    # a prompt of its own, so that it leaves nothing a request of the mix
    # could match in the prefix cache
    own = np.random.default_rng([seed, 1]).integers(
        0, cfg["vocab_size"], 2 * serving["chunk"]).tolist()
    with uncached():
        engine = program.build_engine(model, params, serving)
        engine.submit(program.request(-1, own, 2))
        engine.drain()
        # its pages leave through the swap tier, as the window's
        # evictions will once finished requests fill the pool: that path
        # compiles here
        engine.prefix.evict(1)
    marks.append(("engine built, step compiled, one request served",
                  time.perf_counter()))
    drv = Driver(engine, items, mix, slots, serving["chunk"])
    # fill every lane and let each reach decoding: the window opens on a
    # full batch
    drv.top_up()
    while not (len(engine.active) == slots
               and all(st.req.out for st in engine.active.values())):
        drv.step()
        drv.top_up()
    drv.steps.clear()
    jax.block_until_ready(engine.cache)
    pages_open = drv.live_pages()
    marks.append(("in-flight lanes prefilled", time.perf_counter()))
    log("bench: set-up " + ", ".join(
        f"{name} {t - t_prev:.3f} s" for (_, t_prev), (name, t)
        in zip(marks, marks[1:])) + f" (after {marks[0][1] - t_process:.3f} "
        f"s of process start)")

    trace_dir = cell.root / TRACE_DIR / cell.name
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        # device operations and the benchmark's own spans; tracing every
        # Python call would slow the host loop the window measures
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
        drv.traced = True
    window_span = jax.profiler.TraceAnnotation("bench.window")
    window_span.__enter__()
    t0 = time.perf_counter()
    setup_s = t0 - t_process
    t_end = t0 + seconds
    while True:
        with jax.profiler.TraceAnnotation("bench.submit"):
            drv.top_up()
        if not engine.has_work():
            raise RuntimeError(f"the backlog of {len(items)} requests ran "
                               f"dry inside the window")
        st = drv.step()
        if st.end >= t_end:
            t1 = st.end
            break
    window_span.__exit__(None, None, None)
    if traced:
        jax.profiler.stop_trace()
    pages_close = drv.live_pages()
    slowest = sorted(drv.steps, key=lambda s: s.end - s.start)[-3:]
    log("bench: slowest window steps (start after the opening, length): "
        + ", ".join(f"{s.start - t0:.3f} s {s.end - s.start:.4f} s"
                    for s in reversed(slowest)))
    report = engine.report()
    log(f"bench: pages written by the running lanes: {pages_open} at the "
        f"opening, {pages_close} at the close, of {report['pages']}; the "
        f"pool's peak in use {report['page_peak_utilization']}, "
        f"{report['preemptions']} preemptions")

    window = Window(config=cfg, family=cell.family, setup_s=setup_s, t0=t0,
                    t1=t1, slots=slots, recs=drv.recs, steps=drv.steps,
                    peak=peak)
    device = device_info(cell.chips)
    if traced:
        window.trace = tracing.reduce(*tracing.read(trace_dir))
        device["busy_s"] = window.trace.busy_s
        device["window_s"] = window.trace.window_s
        shutil.rmtree(trace_dir, ignore_errors=True)

    metrics = {}
    for entry in cell.metrics:
        value = cell.readers[entry["name"]].read(window)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}

    served = [check.Served(r.item.rid, r.item.prompt, list(r.req.out))
              for r in drv.recs.values() if not r.failed and r.req.out]
    if keep is not None:
        keep.extend(served)
    # free the program's state before the reference runs
    del engine, drv, params, model, window_span
    gc.collect()
    t_ref = time.perf_counter()
    checks = check.judge(cell.reference, cfg, seed, served)
    log(f"bench: reference over {len(served)} requests took "
        f"{time.perf_counter() - t_ref:.1f} s")

    # the requests that held a lane by the close, and those the engine
    # refused; one still queued has simply not been served yet
    attempted = [r for r in window.recs.values()
                 if r.failed or (r.admitted is not None and r.admitted <= t1)]
    out = {
        "correct": check.passed(checks),
        "attempted": len(attempted),
        "failed": sum(1 for r in attempted if r.failed),
        "metrics": metrics,
        "device": device,
    }
    if traced:
        out["breakdown"] = {"device_ops": window.trace.top_ops(),
                            "idle_gaps": window.trace.top_idle()}
    out["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                     for k, c in checks.items()}
    for k, c in checks.items():
        log(f"check {k}: {c['value']} "
            f"{'<=' if c['at_most'] else '>='} {c['limit']}")
    return out
