"""The program's own spans and counters in a traced run.

``PagedServeEngine.step`` annotates each tick on the profiler's host
plane: the step ``serve.step`` and its five contiguous phases
``serve.schedule``, ``serve.inputs``, ``serve.dispatch``, ``serve.wait``
and ``serve.commit`` (``repro.audit.trace.Tracer.phase``).  With a real
tracer, each tick's ``step`` event carries what the step program was
given: ``work`` (``(pos, n_new)`` per running lane), ``rows`` (slots x
chunk), ``pages_bound`` and ``pages_written``.

Everything here reads those and nothing of the engine's internals:

- ``read``: the ``serve.*`` host events of the newest trace;
- ``phase_times``: each span name's time inside the traced window;
- ``step_host_ms``: the mean host time of a window step outside
  ``serve.wait``;
- ``idle_phases``: the window's device idle, each gap put down to the
  innermost program span it overlaps most, else to the benchmark span it
  overlaps most, else to ``other``;
- ``step_profile``: per window step, each phase's length, the device
  idle inside it and the step's longest device gap (where a slow step
  lost its time);
- ``useful_row_share`` and ``kv_pages_used_share``: the ``step`` events'
  counters.

A program without the spans or the counters reads ``None`` (or an empty
breakdown) here, never an error.  ``bench/tools/phases.py`` runs a cell
with these readers beside its own.
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from pathlib import Path

from bench import trace as tracing

PREFIX = "serve."
STEP = "serve.step"
WAIT = "serve.wait"
PHASES = ("serve.schedule", "serve.inputs", "serve.dispatch", "serve.wait",
          "serve.commit")

Event = tracing.Event


def read(trace_dir: Path) -> list[Event]:
    """The program's ``serve.*`` spans on the host planes of the newest
    trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(str(files[-1]))
    return [Event(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(PREFIX)]


def window_of(host: list[Event]) -> tuple[float, float] | None:
    """The traced window (the benchmark's ``bench.window`` span)."""
    for e in host:
        if e.name == tracing.WINDOW:
            return e.start, e.end
    return None


def inside(spans: list[Event], lo: float, hi: float, name: str
           ) -> list[Event]:
    return sorted((e for e in spans if e.name == name and lo <= e.start
                   and e.end <= hi), key=lambda e: e.start)


def phase_times(spans: list[Event], lo: float, hi: float) -> dict[str, float]:
    """Seconds of each program span name inside the window."""
    out: dict[str, float] = defaultdict(float)
    for e in spans:
        t = tracing.overlap((e.start, e.end), (lo, hi))
        if t > 0:
            out[e.name] += t
    return dict(out)


def step_host_ms(spans: list[Event], lo: float, hi: float) -> float | None:
    """Mean over the window's ``serve.step`` spans of their time outside
    ``serve.wait``: scheduling, inputs, dispatch and commit."""
    steps = inside(spans, lo, hi, STEP)
    waits = inside(spans, lo, hi, WAIT)
    if not steps or not waits:
        return None
    starts = [w.start for w in waits]
    host = 0.0
    for s in steps:
        i = bisect.bisect_left(starts, s.start)
        wait = 0.0
        while i < len(waits) and waits[i].start < s.end:
            wait += tracing.overlap((waits[i].start, waits[i].end),
                                    (s.start, s.end))
            i += 1
        host += (s.end - s.start) - wait
    return 1e3 * host / len(steps)


def _levels(spans: list[Event]) -> list[list[Event]]:
    """Spans grouped by nesting depth, outermost first; spans of one
    level do not overlap (a step's phases are contiguous, steps follow
    one another), each level sorted by start."""
    levels: list[list[Event]] = []
    open_: list[Event] = []
    for e in sorted(spans, key=lambda e: (e.start, -e.end)):
        while open_ and open_[-1].end <= e.start:
            open_.pop()
        depth = len(open_)
        if depth == len(levels):
            levels.append([])
        levels[depth].append(e)
        open_.append(e)
    return levels


def _most(level: list[Event], starts: list[float], gap) -> tuple[str, float]:
    """The span of one level that overlaps ``gap`` most."""
    best, most = None, 0.0
    i = max(bisect.bisect_right(starts, gap[0]) - 1, 0)
    while i < len(level) and level[i].start < gap[1]:
        t = tracing.overlap(gap, (level[i].start, level[i].end))
        if t > most:
            best, most = level[i].name, t
        i += 1
    return best, most


def idle_phases(device: dict[str, list[Event]], host: list[Event]
                ) -> dict[str, float]:
    """The traced window's device idle by the span the host was in: each
    gap goes to the innermost program span (``serve.*``) that overlaps
    it, the one that overlaps it most where several of that depth do;
    with none, to the benchmark span (``bench.*``) that overlaps it most;
    else to ``other``.  Averaged over chips, so the values sum to the
    window's length less its busy time.  Empty without a window or a
    device plane."""
    window = window_of(host)
    if window is None or not device:
        return {}
    lo, hi = window
    program = [e for e in host if e.name.startswith(PREFIX)]
    levels = [(lv, [e.start for e in lv]) for lv in _levels(program)]
    bench = sorted((e for e in host if e.name.startswith(tracing.SPAN_PREFIX)
                    and e.name != tracing.WINDOW), key=lambda e: e.start)
    bench_starts = [e.start for e in bench]
    out: dict[str, float] = defaultdict(float)
    for events in device.values():
        busy = tracing.union(tracing.clip(
            [(e.start, e.end) for e in events], lo, hi))
        for gap in tracing.gaps(busy, lo, hi):
            name = None
            for level, starts in reversed(levels):
                name, _ = _most(level, starts, gap)
                if name is not None:
                    break
            if name is None:
                name, _ = _most(bench, bench_starts, gap)
            out[name or "other"] += (gap[1] - gap[0]) / len(device)
    return dict(out)


def _idle_within(busy: list[tuple[float, float]], lo: float, hi: float
                 ) -> float:
    ends = [e for _, e in busy]
    i = bisect.bisect_right(ends, lo)
    covered = 0.0
    while i < len(busy) and busy[i][0] < hi:
        covered += tracing.overlap(busy[i], (lo, hi))
        i += 1
    return (hi - lo) - covered


def _longest_gap(events: list[Event], busy: list[tuple[float, float]],
                 lo: float, hi: float) -> dict | None:
    """The longest stretch of ``[lo, hi]`` with no operation running:
    where it starts (seconds after ``lo``), its length, and the
    operations that ended before it and began after it."""
    idle = tracing.gaps(tracing.clip(busy, lo, hi), lo, hi)
    if not idle:
        return None
    a, b = max(idle, key=lambda g: g[1] - g[0])
    before = max((e for e in events if e.end <= a), key=lambda e: e.end,
                 default=None)
    after = min((e for e in events if e.start >= b), key=lambda e: e.start,
                default=None)
    return {"at": a - lo, "length": b - a,
            "before": tracing.short(before.name) if before else None,
            "after": tracing.short(after.name) if after else None}


def step_profile(device: dict[str, list[Event]], host: list[Event]
                 ) -> list[dict]:
    """Per ``serve.step`` inside the window, in order: its length, each
    phase's length and the device idle inside each phase (seconds, the
    first chip's device), and its longest device gap."""
    window = window_of(host)
    if window is None or not device:
        return []
    lo, hi = window
    events = next(iter(device.values()))
    busy = tracing.union(tracing.clip([(e.start, e.end) for e in events],
                                      lo, hi))
    phases = sorted((e for e in host if e.name in PHASES),
                    key=lambda e: e.start)
    starts = [e.start for e in phases]
    out = []
    for s in inside(host, lo, hi, STEP):
        rec = {"start": s.start - lo, "length": s.end - s.start,
               "phases": {}, "idle": {}}
        i = bisect.bisect_left(starts, s.start)
        while i < len(phases) and phases[i].start < s.end:
            p = phases[i]
            rec["phases"][p.name] = rec["phases"].get(p.name, 0.0) + (
                p.end - p.start)
            rec["idle"][p.name] = rec["idle"].get(p.name, 0.0) + (
                _idle_within(busy, p.start, p.end))
            i += 1
        rec["gap"] = _longest_gap(events, busy, s.start, s.end)
        out.append(rec)
    return out


def _counters(events: list[dict], *keys: str) -> list[dict] | None:
    if not events or any(k not in e for e in events for k in keys):
        return None
    return events


def useful_row_share(events: list[dict]) -> float | None:
    """Rows the lanes needed (``n_new``) as a share of the rows the step
    program computed, over the window's ``step`` events (%)."""
    events = _counters(events, "work", "rows")
    if events is None:
        return None
    rows = sum(e["rows"] for e in events)
    return 100.0 * sum(n for e in events for _, n in e["work"]) / rows


def kv_pages_used_share(events: list[dict]) -> float | None:
    """Pages filled by written rows as a share of the pages the running
    lanes hold, over the window's ``step`` events (%)."""
    events = _counters(events, "pages_written", "pages_bound")
    if events is None:
        return None
    bound = sum(e["pages_bound"] for e in events)
    return (100.0 * sum(e["pages_written"] for e in events) / bound
            if bound else None)
