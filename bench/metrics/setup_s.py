"""Process start to window open (s): start-up, weights, plan warm-up,
compiles or compile-cache reads, and the lane fill."""


def read(run):
    return run.setup_s
