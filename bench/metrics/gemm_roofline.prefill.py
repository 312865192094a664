"""Kernels (kernels/matmul.py): the least time of the prefill step's weight
GEMMs, at the chunk's padded rows, over the device time of the kernels
that did them, in the traced window (%)."""


def read(run):
    return run.gemm_roofline("prefill")
