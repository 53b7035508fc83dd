"""Model step: the mean host time of ``engine.step()`` over the window's
steps, each ending in the host's read of the sampled tokens."""


def read(w):
    steps = w.window_steps()
    if not steps:
        return None
    return 1e3 * sum(s.end - s.start for s in steps) / len(steps)
