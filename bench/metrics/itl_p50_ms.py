"""Median of every gap between consecutive output tokens of a request,
pooled over all requests, for each gap that ends in the window (ms)."""


def read(run):
    return run.percentile(run.token_gaps_s(), 50, scale=1e3)
