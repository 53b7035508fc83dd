"""Reduction of a JAX profiler trace to what the metrics read.

The profiler writes an ``.xplane.pb`` under ``<dir>/plugins/profile/``.
Device planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line holds
one event per operation that ran on the chip.  The host plane holds the
benchmark's own spans (``jax.profiler.TraceAnnotation``, names starting
``bench.``), on the same clock.  The traced window is the span
``bench.window``.

- busy: the union of the operations' intervals inside the window, per
  chip, averaged over the chips used;
- ops: each operation name's summed device time inside the window
  (averaged over chips), for the kernel metrics and the breakdown;
- idle gaps: the stretches inside the window where no operation ran,
  each put down to the benchmark span the host spent most of it in;
- labels: per operation name, the text of its first event's statistics
  (its HLO and the op_name of the JAX call that made it).  XLA may name
  an operation after the call around it (the program's paged kernel is
  an HLO ``closed_call.<n>``), so a reader finds a kernel by its label.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
WINDOW = "bench.window"
SPAN_PREFIX = "bench."


@dataclass
class Event:
    name: str
    start: float          # seconds
    end: float


@dataclass
class Reduced:
    window_s: float
    busy_s: float
    chips: int
    ops: dict[str, float] = field(default_factory=dict)
    idle_by_span: dict[str, float] = field(default_factory=dict)
    events: int = 0
    labels: dict[str, str] = field(default_factory=dict)

    def time_of(self, marks: tuple[str, ...]) -> float:
        """Summed device time of the operations whose name or label
        holds any of ``marks``."""
        return sum(t for name, t in self.ops.items()
                   if any(m in name or m in self.labels.get(name, "")
                          for m in marks))

    def top_ops(self, n: int = 10) -> list[list]:
        return [[short(k), v] for k, v in sorted(self.ops.items(),
                                                 key=lambda kv: -kv[1])[:n]]

    def top_idle(self, n: int = 10) -> list[list]:
        return [[k, v] for k, v in sorted(self.idle_by_span.items(),
                                          key=lambda kv: -kv[1])[:n]]


def short(name: str) -> str:
    """An operation's name for the breakdown.  On a TPU the name is the
    whole HLO instruction (``%fusion.161 = bf16[...] fusion(...), ...``),
    which runs to thousands of characters for a loop; keep its handle,
    its opcode and a custom call's target."""
    head, eq, rest = name.partition(" = ")
    if not eq:
        return name
    op = re.search(r"\s([a-z][\w.-]*)\(", rest)
    target = re.search(r'custom_call_target="([^"]+)"', rest)
    return " ".join([head] + ([op.group(1)] if op else [])
                    + ([target.group(1)] if target else []))


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: list[tuple[float, float]], lo: float, hi: float
         ) -> list[tuple[float, float]]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def overlap(a: tuple[float, float], b: tuple[float, float]) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def reduce(device: dict[str, list[Event]], host: list[Event],
           labels: dict[str, str] | None = None) -> Reduced:
    """``device``: plane name -> its operation events; ``host``: the
    benchmark's spans; ``labels``: operation name -> label."""
    windows = [e for e in host if e.name == WINDOW]
    if not windows or not device:
        raise ValueError(f"trace holds {len(windows)} {WINDOW!r} spans and "
                         f"{len(device)} device planes with operations")
    lo, hi = windows[0].start, windows[0].end
    spans = sorted((e for e in host if e.name != WINDOW),
                   key=lambda e: e.start)
    starts = [e.start for e in spans]
    chips = len(device)
    busy_total, ops, idle = 0.0, defaultdict(float), defaultdict(float)
    n_events = 0
    for events in device.values():
        n_events += len(events)
        for e in events:
            t = overlap((e.start, e.end), (lo, hi))
            if t > 0:
                ops[e.name] += t / chips
        busy = union(clip([(e.start, e.end) for e in events], lo, hi))
        busy_total += sum(e - s for s, e in busy)
        for gap in gaps(busy, lo, hi):
            # the spans do not nest: start from the last that began
            # before the gap
            best, most = "other", 0.0
            i = max(bisect.bisect_right(starts, gap[0]) - 1, 0)
            while i < len(spans) and spans[i].start < gap[1]:
                t = overlap(gap, (spans[i].start, spans[i].end))
                if t > most:
                    best, most = spans[i].name, t
                i += 1
            idle[best] += (gap[1] - gap[0]) / chips
    return Reduced(window_s=hi - lo, busy_s=busy_total / chips, chips=chips,
                   ops=dict(ops), idle_by_span=dict(idle), events=n_events,
                   labels=labels or {})


def read(trace_dir: Path) -> tuple[dict[str, list[Event]], list[Event],
                                   dict[str, str]]:
    """Device operation events, host spans and operation labels of the
    newest trace."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(str(files[-1]))
    device: dict[str, list[Event]] = {}
    host: list[Event] = []
    labels: dict[str, str] = {}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    events = device[plane.name] = []
                    for e in line.events:
                        events.append(Event(e.name, e.start_ns * 1e-9,
                                            e.end_ns * 1e-9))
                        if e.name not in labels:
                            labels[e.name] = " ".join(
                                str(v) for _, v in e.stats)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Event(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                            for e in line.events
                            if e.name.startswith(SPAN_PREFIX))
    return {k: v for k, v in device.items() if v}, host, labels
