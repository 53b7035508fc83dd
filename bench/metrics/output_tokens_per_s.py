"""Output tokens that came back inside the window, over the window's
length (host clock; every step the window holds is whole)."""


def read(w):
    n = sum(1 for r in w.recs.values() for t in r.tokens if w.t0 < t <= w.t1)
    return n / w.length
