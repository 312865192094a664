"""Median, over every request due in the window, of the time from when it
was due to when its first token reached the client (ms)."""


def read(run):
    return run.percentile(run.ttfts_s(), 50, scale=1e3)
