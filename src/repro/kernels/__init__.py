"""Pallas TPU kernels for the perf-critical GEMM paths.

matmul.py        — output-stationary tiled GEMM (the paper's core design)
decode_matvec.py — decode-time skinny GEMM/GEMV (paper §5.3.4 future work)
paged_attention.py — decode attention over the live blocks of a KV pool
ops.py           — jit'd public wrappers (padding, plan selection, fallback)
ref.py           — pure-jnp oracles
"""
from repro.kernels.ops import GemmPlan, balanced_matmul, decode_matvec

__all__ = ["GemmPlan", "balanced_matmul", "decode_matvec"]
