"""Device: the share of the traced window in which no operation ran on
the chip (one minus the union of the operations' intervals)."""


def read(w):
    if w.trace is None:
        return None
    return 100.0 * (1.0 - w.trace.busy_s / w.trace.window_s)
