"""Output-stationary tiled GEMM as a Pallas TPU kernel.

This is the TPU rendition of the paper's core/array GEMM design (§4.1–§4.3):

* Grid ``(M/bm, N/bn, K/bk)`` with K as the innermost *arbitrary* (sequential)
  dimension — K is reduced **in time** while M and N are parallel **in
  space**, exactly the paper's output-stationary mapping (§4.2.1).
* The output block lives in a VMEM accumulator scratch for the whole
  K-reduction and is written to HBM **once**, at ``k == K/bk - 1`` — the
  paper's single-output-buffer design (§5.3.2). Pallas's software pipeline
  double-buffers the A/B input blocks (the L1 double-buffering of §4.2.1).
* ``BlockSpec.index_map`` gathers tiles directly out of row-/column-major HBM
  arrays — the on-the-fly re-tiling of §4.3; matrices are never pre-tiled.
* ``b_layout='col'`` consumes B stored as (N, K): the index map walks the
  transposed array and the MXU contracts over b's last axis in-register (the
  AIE shuffle-transpose analog, §4.3).
* int8 inputs accumulate in i32 and support fused saturating "precision
  reduction" to int8/int16/int32 outputs (§5.1); floats accumulate in f32.

Block sizes (bm, bk, bn) are the paper's (m_ct, k_ct, n_ct); the balanced-point
solver in ``repro.core.balance`` chooses them. bk additionally plays the role
of the paper's contiguity parameter k_mt: it sets the contiguous HBM run
length of each A-row read (bk * itemsize bytes).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref as _ref

# Sublane alignment per dtype (second-to-last dim); lane dim is always 128.
SUBLANE = {4: 8, 2: 16, 1: 32}
LANE = 128


def _acc_dtype(dtype) -> Any:
    return jnp.int32 if jnp.issubdtype(dtype, jnp.integer) else jnp.float32


def _mm_kernel(
    *refs,
    k_steps: int,
    out_dtype,
    b_layout: str,
    activation: str | None,
    has_bias: bool,
    has_scale: bool,
):
    """One (i, j, k) grid step: acc += A[i,k] @ B[k,j]; emit at last k.

    The emit phase is the paper's fused epilogue (§5.1): bias add (in the
    accumulator domain), optional per-output-channel requantization scale,
    activation, and the saturating precision-reduction cast — all before the
    single HBM write of the output block (§5.3.2).
    """
    it = iter(refs)
    a_ref, b_ref = next(it), next(it)
    bias_ref = next(it) if has_bias else None
    scale_ref = next(it) if has_scale else None
    o_ref, acc_ref = next(it), next(it)

    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]
    b = b_ref[...]
    if b_layout == "col":
        # b block is (bn, bk): contract over both operands' last axis. The MXU
        # consumes the transposed operand without any HBM-side transpose.
        dim_nums = (((1,), (1,)), ((), ()))
    else:
        dim_nums = (((1,), (0,)), ((), ()))
    acc_ref[...] += jax.lax.dot_general(
        a, b, dim_nums, preferred_element_type=acc_ref.dtype
    )

    @pl.when(k == k_steps - 1)
    def _emit():
        out = acc_ref[...]
        if scale_ref is not None:
            # requantize first, THEN add the (real-units, f32) bias: adding
            # in the i32 accumulator domain would need bias/scale, which
            # overflows i32 for small scales (tiny activations x weights)
            out = out.astype(jnp.float32) * scale_ref[...]
        if bias_ref is not None:
            out = out + bias_ref[...].astype(out.dtype)
        if activation is not None and activation != "none":
            out = _ref.apply_activation(out, activation)
        if scale_ref is not None and jnp.issubdtype(out_dtype, jnp.integer):
            out = jnp.round(out)
        o_ref[...] = _ref.saturating_cast(out, out_dtype)


def _check_divisible(name: str, dim: int, block: int) -> None:
    if dim % block != 0:
        raise ValueError(
            f"{name}={dim} not divisible by block {block}; "
            "use repro.kernels.ops which zero-pads to the native GEMM size"
        )


@functools.partial(
    jax.jit,
    static_argnames=(
        "bm", "bk", "bn", "out_dtype", "b_layout", "activation", "interpret",
        "vmem_limit_bytes",
    ),
)
def matmul(
    a: jax.Array,
    b: jax.Array,
    bias: jax.Array | None = None,
    out_scale: jax.Array | None = None,
    *,
    bm: int = 128,
    bk: int = 512,
    bn: int = 128,
    out_dtype=None,
    b_layout: str = "row",
    activation: str | None = None,
    interpret: bool = False,
    vmem_limit_bytes: int | None = None,
) -> jax.Array:
    """C[M,N] = act(A[M,K] @ B * out_scale + bias), B (K,N) row or (N,K) col.

    Dimensions must already be multiples of the block sizes — callers go
    through ``repro.kernels.ops`` which applies the paper's zero-padding to
    the native GEMM size (§5.3.1).

    ``out_scale`` is the (N,)-shaped f32 per-output-channel requantization
    multiplier applied to the accumulator inside the epilogue (the in-kernel
    generalization of §5.1 precision reduction); ``bias`` is added *after*
    it, in real f32 units — never pre-scale a bias into the i32 domain.
    Without ``out_scale``, bias is added to the raw accumulator as before.
    Semantics match :func:`repro.kernels.ref.matmul_ref`.

    ``vmem_limit_bytes`` is the scoped VMEM Mosaic may use
    (``HardwareSpec.vmem_limit_bytes``); None keeps the compiler's default.
    """
    if out_dtype is None:
        out_dtype = a.dtype
    M, K = a.shape
    if b_layout == "col":
        N, Kb = b.shape
    else:
        Kb, N = b.shape
    if Kb != K:
        raise ValueError(f"contraction mismatch: A has K={K}, B has K={Kb}")
    _check_divisible("M", M, bm)
    _check_divisible("K", K, bk)
    _check_divisible("N", N, bn)

    k_steps = K // bk
    acc = _acc_dtype(a.dtype)

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
        (
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k))
            if b_layout == "col"
            else pl.BlockSpec((bk, bn), lambda i, j, k: (k, j))
        ),
    ]
    args = [a, b]
    if bias is not None:
        if bias.shape != (N,):
            raise ValueError(f"bias must be (N,)=({N},), got {bias.shape}")
        # Keep the bias 2D for TPU layout friendliness; broadcast over bm.
        args.append(bias.reshape(1, N))
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, k: (0, j)))
    if out_scale is not None:
        if out_scale.shape != (N,):
            raise ValueError(
                f"out_scale must be (N,)=({N},), got {out_scale.shape}")
        args.append(out_scale.astype(jnp.float32).reshape(1, N))
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, k: (0, j)))

    kernel = functools.partial(
        _mm_kernel,
        k_steps=k_steps,
        out_dtype=out_dtype,
        b_layout=b_layout,
        activation=activation,
        has_bias=bias is not None,
        has_scale=out_scale is not None,
    )

    return pl.pallas_call(
        kernel,
        grid=(M // bm, N // bn, k_steps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes,
        ),
        interpret=interpret,
    )(*args)


def vmem_bytes(
    bm: int, bk: int, bn: int, ty_in: int, ty_out: int, acc_bytes: int = 4
) -> int:
    """VMEM working set of one grid step — the TPU Eq. 5 (§4.5.1), counted
    as Mosaic allocates it.

    The Pallas pipeline double-buffers every blocked operand: the A and B
    input blocks and the output block. The accumulator scratch is single
    (output-stationary), and ``acc_ref[...] += dot(...)`` materializes the
    bm x bn dot result in the accumulator dtype before the add.
    """
    return (
        2 * bm * bk * ty_in
        + 2 * bk * bn * ty_in
        + 2 * bm * bn * ty_out
        + 2 * bm * bn * acc_bytes
    )
