"""Decode-time GEMV/skinny-GEMM Pallas kernel.

The paper defers GEMV (the decode-step special case of GEMM) to future work
(§5.3.4); we implement it as a beyond-paper extension. Decode matmuls are
x[B,K] @ W[K,N] with tiny B (1–128 tokens): utterly memory-bound on W, so the
design inverts the training kernel's priorities:

* The full (padded) B rows of x are kept resident in VMEM — x is the
  *stationary* operand; W streams through once (no reuse exists to exploit).
* Grid ``(N/bn, K/bk)`` with K innermost: the (B, bn) accumulator is the
  output-stationary buffer, as in the main kernel.
* bk is chosen large so W reads are long contiguous HBM runs — the k_mt idea
  applied to the weight stream.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref as _ref
from repro.kernels.matmul import _acc_dtype


def _gemv_kernel(x_ref, w_ref, o_ref, acc_ref, *, k_steps, out_dtype, w_layout):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if w_layout == "col":
        dim_nums = (((1,), (1,)), ((), ()))
    else:
        dim_nums = (((1,), (0,)), ((), ()))
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], dim_nums, preferred_element_type=acc_ref.dtype
    )

    @pl.when(k == k_steps - 1)
    def _emit():
        o_ref[...] = _ref.saturating_cast(acc_ref[...], out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bk", "bn", "out_dtype", "w_layout", "interpret",
                     "vmem_limit_bytes"),
)
def decode_matvec(
    x: jax.Array,
    w: jax.Array,
    *,
    bk: int = 1024,
    bn: int = 256,
    out_dtype=None,
    w_layout: str = "row",
    interpret: bool = False,
    vmem_limit_bytes: int | None = None,
) -> jax.Array:
    """out[B,N] = x[B,K] @ W, W (K,N) row- or (N,K) col-major; B small.

    ``vmem_limit_bytes`` as in :func:`repro.kernels.matmul.matmul`."""
    if out_dtype is None:
        out_dtype = x.dtype
    B, K = x.shape
    if w_layout == "col":
        N, Kw = w.shape
    else:
        Kw, N = w.shape
    if Kw != K:
        raise ValueError(f"contraction mismatch: x has K={K}, W has K={Kw}")
    if K % bk or N % bn:
        raise ValueError("K, N must be multiples of bk, bn (ops.py pads)")

    k_steps = K // bk
    acc = _acc_dtype(x.dtype)
    w_spec = (
        pl.BlockSpec((bn, bk), lambda j, k: (j, k))
        if w_layout == "col"
        else pl.BlockSpec((bk, bn), lambda j, k: (k, j))
    )
    return pl.pallas_call(
        functools.partial(
            _gemv_kernel, k_steps=k_steps, out_dtype=out_dtype, w_layout=w_layout
        ),
        grid=(N // bn, k_steps),
        in_specs=[
            # x is stationary: same (whole) block at every grid step.
            pl.BlockSpec((B, bk), lambda j, k: (0, k)),
            w_spec,
        ],
        out_specs=pl.BlockSpec((B, bn), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((B, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((B, bn), acc)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes,
        ),
        interpret=interpret,
    )(x, w)
