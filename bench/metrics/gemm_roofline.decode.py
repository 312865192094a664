"""Kernels (kernels/decode_matvec.py, kernels/matmul.py): the least time of
the decode step's weight GEMMs, at all of its lanes, over the device time
of the kernels that did them, in the traced window (%)."""


def read(run):
    return run.gemm_roofline("decode")
