"""Public jit'd wrappers around the Pallas kernels.

Responsibilities (the paper's system-level glue, §5.3.1):
* zero-pad arbitrary (M, K, N) up to the *native GEMM size* — the block-size
  multiples the kernel requires — and slice the result back;
* pick block sizes from an explicit plan or from the balanced-point defaults;
* resolve the backend: ``xla`` is plain ``dot_general``, ``pallas`` the
  Mosaic kernel (TPU only — elsewhere it raises rather than lower to
  something else), ``interpret`` the kernel body run by the Pallas
  interpreter (CPU tests), ``auto`` pallas on a TPU and xla elsewhere.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.kernels import matmul as _mm
from repro.kernels import decode_matvec as _mv
from repro.kernels import ref as _ref


@dataclasses.dataclass(frozen=True)
class GemmPlan:
    """A solved tiling plan: the paper's (m_ct, k_ct, n_ct) for one GEMM."""

    bm: int = 128
    bk: int = 512
    bn: int = 128

    def native_size(self, M: int, K: int, N: int) -> tuple[int, int, int]:
        """Smallest (M', K', N') multiples of the blocks covering (M, K, N)."""
        r = lambda x, b: -(-x // b) * b
        return r(M, self.bm), r(K, self.bk), r(N, self.bn)


def resolve_backend(backend: str) -> str:
    """The concrete backend for ``backend``; 'pallas' off a TPU raises."""
    platform = jax.default_backend()
    if backend == "auto":
        return "pallas" if platform == "tpu" else "xla"
    if backend == "pallas" and platform != "tpu":
        raise ValueError(
            f"matmul backend 'pallas' compiles Mosaic kernels for a TPU, but "
            f"JAX's backend is {platform!r}: use 'interpret' to run the "
            f"kernels here, or 'xla'")
    return backend


def _pad2(x: jax.Array, rows: int, cols: int) -> jax.Array:
    pr, pc = rows - x.shape[0], cols - x.shape[1]
    if pr == 0 and pc == 0:
        return x
    return jnp.pad(x, ((0, pr), (0, pc)))


def _clamp_plan(plan: GemmPlan, M: int, K: int, N: int, dtype) -> GemmPlan:
    """Shrink blocks for problems smaller than one block, keeping TPU
    alignment (sublane multiple on second-to-last dim, 128 on lane dim)."""
    sub = _mm.SUBLANE[jnp.dtype(dtype).itemsize]
    al = lambda x, a: max(a, -(-min(x, a * (-(-x // a))) // a) * a)
    bm = min(plan.bm, al(M, sub))
    bk = min(plan.bk, al(K, _mm.LANE))
    bn = min(plan.bn, al(N, _mm.LANE))
    return GemmPlan(bm=bm, bk=bk, bn=bn)


def balanced_matmul(
    a: jax.Array,
    b: jax.Array,
    bias: jax.Array | None = None,
    *,
    plan: GemmPlan | None = None,
    out_dtype=None,
    b_layout: str = "row",
    activation: str | None = None,
    out_scale: jax.Array | None = None,
    backend: str = "auto",
    vmem_limit_bytes: int | None = None,
) -> jax.Array:
    """General GEMM through the balanced Pallas kernel with zero-padding.

    backend: 'pallas' | 'interpret' | 'xla' | 'auto' (:func:`resolve_backend`).
    ``out_scale``: (N,) per-output-channel requantization multiplier, fused
    into the kernel epilogue (see kernels/matmul.py). ``vmem_limit_bytes``:
    the kernel's scoped VMEM (``HardwareSpec.vmem_limit_bytes``).
    """
    if out_dtype is None:
        out_dtype = a.dtype
    backend = resolve_backend(backend)
    M, K = a.shape
    N = b.shape[0] if b_layout == "col" else b.shape[1]
    if out_scale is not None:
        # normalize per-tensor (scalar) scales to (N,) and surface shape
        # errors against the *unpadded* N, before zero-padding obscures it
        if out_scale.ndim not in (0, 1) or (
                out_scale.ndim == 1 and out_scale.shape != (N,)):
            raise ValueError(
                f"out_scale must be scalar or (N,)=({N},), "
                f"got {out_scale.shape}")
        out_scale = jnp.broadcast_to(out_scale.astype(jnp.float32), (N,))
    if backend == "xla":
        return _ref.matmul_ref(
            a, b, out_dtype=out_dtype, b_layout=b_layout, bias=bias,
            activation=activation, out_scale=out_scale,
        )

    plan = _clamp_plan(plan or GemmPlan(), M, K, N, a.dtype)
    Mp, Kp, Np = plan.native_size(M, K, N)
    ap = _pad2(a, Mp, Kp)
    bp = _pad2(b, Np, Kp) if b_layout == "col" else _pad2(b, Kp, Np)
    biasp = None
    if bias is not None:
        biasp = jnp.pad(bias, (0, Np - N)) if Np != N else bias
    scalep = None
    if out_scale is not None:
        # pad with ones: padded channels are sliced off below, but a zero
        # scale would turn 0 * inf-ish garbage into NaN under activations
        scalep = (jnp.pad(out_scale, (0, Np - N), constant_values=1.0)
                  if Np != N else out_scale)
    out = _mm.matmul(
        ap,
        bp,
        biasp,
        scalep,
        bm=plan.bm,
        bk=plan.bk,
        bn=plan.bn,
        out_dtype=out_dtype,
        b_layout=b_layout,
        activation=activation,
        interpret=(backend == "interpret"),
        vmem_limit_bytes=vmem_limit_bytes,
    )
    if (Mp, Np) != (M, N):
        out = out[:M, :N]
    return out


def decode_matvec(
    x: jax.Array,
    w: jax.Array,
    *,
    bk: int = 1024,
    bn: int = 256,
    out_dtype=None,
    w_layout: str = "row",
    backend: str = "auto",
    vmem_limit_bytes: int | None = None,
) -> jax.Array:
    """Decode-step skinny GEMM with padding; see decode_matvec.py."""
    if out_dtype is None:
        out_dtype = x.dtype
    backend = resolve_backend(backend)
    if backend == "xla":
        return _ref.gemv_ref(x, w, out_dtype=out_dtype, w_layout=w_layout)

    B, K = x.shape
    N = w.shape[0] if w_layout == "col" else w.shape[1]
    sub = _mm.SUBLANE[jnp.dtype(x.dtype).itemsize]
    Bp = -(-B // sub) * sub
    bk = min(bk, -(-K // _mm.LANE) * _mm.LANE)
    bn = min(bn, -(-N // _mm.LANE) * _mm.LANE)
    Kp, Np = -(-K // bk) * bk, -(-N // bn) * bn
    xp = _pad2(x, Bp, Kp)
    wp = _pad2(w, Np, Kp) if w_layout == "col" else _pad2(w, Kp, Np)
    out = _mv.decode_matvec(
        xp, wp, bk=bk, bn=bn, out_dtype=out_dtype, w_layout=w_layout,
        interpret=(backend == "interpret"), vmem_limit_bytes=vmem_limit_bytes,
    )
    if (Bp, Np) != (B, N):
        out = out[:B, :N]
    return out
