"""Scheduler: the mean, over the window's steps, of the lanes that ran
in a step as a share of the engine's slots."""


def read(w):
    steps = w.window_steps()
    if not steps:
        return None
    return 100.0 * sum(s.lanes for s in steps) / (len(steps) * w.slots)
