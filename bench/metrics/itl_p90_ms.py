"""90th percentile of the gap between consecutive output tokens, on the
host clock (each token stamped at the return of the step that delivered
it).  A host timing is good to about half a millisecond, so each sample
spans at least ``SPAN_S``: a request's tokens that came back inside the
window are cut into runs of consecutive tokens, each closed as soon as
it spans ``SPAN_S``, a short remainder joining the run before it, and a
run gives its mean gap.  Every in-window gap of every request is in
exactly one sample, except those of a request whose in-window tokens
span less than ``SPAN_S`` in all."""
import numpy as np

SPAN_S = 0.25


def samples(stamps: list[float]) -> list[float]:
    runs, start, n = [], 0, len(stamps)
    for i in range(1, n):
        if stamps[i] - stamps[start] >= SPAN_S:
            runs.append((start, i))
            start = i
    if runs and start < n - 1:
        runs[-1] = (runs[-1][0], n - 1)
    return [(stamps[b] - stamps[a]) / (b - a) for a, b in runs]


def read(w):
    gaps = []
    for r in w.recs.values():
        inside = [t for t in r.tokens if w.t0 <= t <= w.t1]
        gaps += samples(inside)
    return float(np.percentile(gaps, 90)) * 1e3 if gaps else None
