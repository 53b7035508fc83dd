"""Model step: the operations the served tokens need over the traced
window's length times the chip's bf16 peak.  A step's operations are
:func:`step_flops` of its lanes, from the configuration family's counts;
padded rows and idle lanes do not count."""


def step_flops(family, cfg: dict, lanes) -> int:
    """One step's useful operations: every fed row through the layers'
    matrix products (the family's ``matmul_params``), attention over its
    real context (``paged_attn_flops``), and the head at each active
    lane's last row."""
    lanes = [(p, n) for p, n in lanes if n > 0]
    rows = sum(n for _, n in lanes)
    return (2 * rows * family.matmul_params(cfg)
            + family.paged_attn_flops(cfg, lanes)
            + 2 * len(lanes) * cfg["hidden_size"] * cfg["vocab_size"])


def read(w):
    steps = [s for s in w.window_steps() if s.work is not None]
    if w.trace is None or not steps:
        return None
    flops = sum(step_flops(w.family, w.config, s.work) for s in steps)
    return 100.0 * flops / (w.trace.window_s * w.peak.bf16_flops)
