"""Output tokens that reached clients in the window, over the window's
seconds."""


def read(run):
    t0, t1 = run.window
    n = sum(1 for r in run.records.values() for t in r.token_s if t0 < t <= t1)
    return n / (t1 - t0)
