"""Output tokens emitted inside the window, over the window's length."""


def read(run):
    w0, w1 = run.window
    n = sum(1 for r in run.recs.values() for t in r.tokens if w0 <= t < w1)
    return n / (w1 - w0)
