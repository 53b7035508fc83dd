"""Model step: the operations the served tokens need (``bench.work.
step_flops`` of every step in the traced window: fed rows through the
layers' matrix products, attention over each lane's real context, the
head at each lane's last row; padded rows and idle lanes do not count)
over the traced window's length times the chip's bf16 peak."""
from bench import work


def read(w):
    steps = [s for s in w.window_steps() if s.work is not None]
    if w.trace is None or not steps:
        return None
    flops = sum(work.step_flops(w.config, s.work) for s in steps)
    return 100.0 * flops / (w.trace.window_s * w.peak.bf16_flops)
