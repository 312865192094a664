"""Analytical performance model — the paper's Eqs. 1–10, TPU-adapted.

The paper models GEMM time as two competing terms:

* compute time  T_comp = 2·M·K·N / (eff · peak)                    (Eq. 9)
* memory time   T_mem  = (A_mem + B_mem + C_mem) / DRAM_BW         (Eq. 10)

with the *inverse relationship*: larger output tiles (bm, bn) cut DRAM
traffic (Eqs. 6–7 put them in the denominator) but shrink bk under the
capacity constraint (Eq. 5) and so reduce kernel efficiency. The optimum is
the balanced point T_comp ≈ T_mem.

TPU adaptations (DESIGN.md §2):
* L1 (64 KB) → VMEM (default 16 MiB budget);
* the k_mt contiguity parameter → block-K: the effective-HBM-bandwidth curve
  ``effective_bw`` models long-contiguous-read saturation (paper Fig. 6);
* MXU alignment derate replaces the AIE intrinsic-mode efficiency table;
* accumulator load/store traffic models the paper's bank-conflict rationale
  for the second objective (minimize m_ct·n_ct, §4.5.1).
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from repro.kernels.matmul import LANE, SUBLANE, vmem_bytes

# Scoped VMEM a kernel asks for beyond its Eq. 5 working set: Mosaic's own
# internal scratch, the (8, bn) padding of bias/scale rows and layout
# padding. For v5e the unembed plan (1024, 1280, 512) bf16->f32, 15.5 MiB
# by Eq. 5, overflows the compiler's 16 MiB default and compiles at 24.
MOSAIC_VMEM_HEADROOM = 8 * 2**20


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    """Per-chip hardware constants (defaults: TPU v5e)."""

    name: str
    peak_flops_bf16: float  # FLOP/s (MAC = 2 FLOPs)
    peak_flops_int8: float  # OP/s
    hbm_bw: float           # B/s
    ici_bw: float           # B/s per link
    vmem_bytes: int         # per-core VMEM budget for the GEMM working set
    vmem_bw: float          # B/s VMEM <-> VREG (for accumulator traffic)
    hbm_latency_bytes: float  # contiguity knee of effective_bw (paper Fig. 6)
    mxu: int = 128          # native MXU tile edge
    peak_flops_f32: float = 0.0  # FLOP/s for f32 passes (0 -> bf16/2)

    @property
    def vmem_limit_bytes(self) -> int:
        """Scoped VMEM the kernels request: the solver's budget plus
        headroom, so every plan the solver admits compiles."""
        return self.vmem_bytes + MOSAIC_VMEM_HEADROOM

    def peak_flops(self, dtype) -> float:
        """Per-dtype peak table — the Table 2 vs Table 3 analog: int8 runs
        at 2x the bf16 MAC rate, f32 at half (two bf16 passes)."""
        dt = jnp.dtype(dtype)
        if jnp.issubdtype(dt, jnp.integer):
            return self.peak_flops_int8
        if dt == jnp.dtype(jnp.float32):
            return self.peak_flops_f32 or self.peak_flops_bf16 / 2
        return self.peak_flops_bf16


TPU_V5E = HardwareSpec(
    name="tpu_v5e",
    peak_flops_bf16=197e12,
    peak_flops_int8=394e12,
    hbm_bw=819e9,
    ici_bw=50e9,
    vmem_bytes=16 * 2**20,
    vmem_bw=11e12,
    hbm_latency_bytes=512.0,
    peak_flops_f32=98.5e12,
)


def effective_bw(hw: HardwareSpec, run_bytes: float) -> float:
    """Effective HBM bandwidth for reads of ``run_bytes``-long contiguous runs.

    Saturating latency/granularity model with a sharp knee at a few times
    ``hbm_latency_bytes``. Reproduces the paper's Fig. 6 shape — steep
    growth, then a knee past which larger k_mt buys <1 % (their criterion
    for picking the smallest saturating value).
    """
    import math

    return hw.hbm_bw * (1.0 - math.exp(-run_bytes / hw.hbm_latency_bytes))


def mxu_efficiency(hw: HardwareSpec, bm: int, bk: int, bn: int, itemsize: int) -> float:
    """Fraction of MXU peak attainable for one (bm, bk, bn) block.

    Dim-alignment derate: a dimension that is not a multiple of the native
    tile wastes the remainder rows/columns of the systolic pass. This is the
    TPU analog of the AIE-API intrinsic-mode table (paper Table 1's r×s×t).
    """
    def util(d: int, native: int) -> float:
        full = -(-d // native) * native
        return d / full

    sub = SUBLANE[itemsize]
    return util(bm, max(sub, hw.mxu)) * util(bk, hw.mxu) * util(bn, hw.mxu)


@dataclasses.dataclass(frozen=True)
class BlockTimes:
    """Per-grid-step times (seconds) — the Eq. 1–3 analog."""

    t_comp: float   # Eq. 1: MXU time for the bm×bk×bn block
    t_a: float      # Eq. 2: HBM read of the A block
    t_b: float      # Eq. 3: HBM read of the B block
    t_acc: float    # accumulator VMEM read+write traffic (min m·n rationale)

    @property
    def compute_bound(self) -> bool:  # Eq. 4
        return self.t_comp >= max(self.t_a, self.t_b)


def block_times(
    hw: HardwareSpec,
    bm: int,
    bk: int,
    bn: int,
    *,
    in_dtype=jnp.bfloat16,
    b_layout: str = "row",
) -> BlockTimes:
    ty = jnp.dtype(in_dtype).itemsize
    eff = mxu_efficiency(hw, bm, bk, bn, ty)
    t_comp = 2.0 * bm * bk * bn / (eff * hw.peak_flops(in_dtype))
    # A is row-major: a (bm, bk) window reads bm runs of bk·ty bytes.
    t_a = bm * bk * ty / effective_bw(hw, bk * ty)
    # B col-major reads bn runs of bk·ty; row-major reads bk runs of bn·ty.
    b_run = (bk if b_layout == "col" else bn) * ty
    t_b = bk * bn * ty / effective_bw(hw, b_run)
    # Output-stationary accumulate: read+write the f32 accumulator per step.
    t_acc = 2.0 * bm * bn * 4 / hw.vmem_bw
    return BlockTimes(t_comp=t_comp, t_a=t_a, t_b=t_b, t_acc=t_acc)


def kernel_efficiency(
    hw: HardwareSpec, bm: int, bk: int, bn: int, *, in_dtype=jnp.bfloat16,
    b_layout: str = "row",
) -> float:
    """Modeled single-kernel efficiency `eff` (§4.5.1): attained / peak.

    The pipelined step time is max(compute, input DMA) plus the accumulator
    traffic that cannot hide behind the MXU.
    """
    bt = block_times(hw, bm, bk, bn, in_dtype=in_dtype, b_layout=b_layout)
    step = max(bt.t_comp, bt.t_a, bt.t_b) + bt.t_acc
    return bt.t_comp * mxu_efficiency(
        hw, bm, bk, bn, jnp.dtype(in_dtype).itemsize
    ) / step


# --------------------------------------------------------------- system level
def dram_traffic(
    M: int, K: int, N: int, bm: int, bn: int, *,
    ty_in: int, ty_out: int, m_rows: int = 1, n_cols: int = 1,
) -> tuple[float, float, float]:
    """Eqs. 6–8: total HBM traffic (bytes) for A reads, B reads, C writes.

    (m_rows, n_cols) generalize to the spatial array/mesh level exactly as in
    the paper; at single-chip kernel level they are 1.
    """
    a_mem = M * K * N * ty_in / (bn * n_cols)
    b_mem = M * K * N * ty_in / (bm * m_rows)
    c_mem = M * N * ty_out
    return a_mem, b_mem, c_mem


@dataclasses.dataclass(frozen=True)
class GemmEstimate:
    t_comp: float
    t_mem: float
    eff: float
    a_mem: float
    b_mem: float
    c_mem: float

    @property
    def t_total(self) -> float:
        # Double-buffered pipeline: compute and memory overlap; the slower
        # stream dominates (the balanced point is t_comp == t_mem).
        return max(self.t_comp, self.t_mem)

    @property
    def tops(self) -> float:
        return 0.0 if self.t_total == 0 else float("nan")


def estimate_gemm(
    hw: HardwareSpec,
    M: int, K: int, N: int,
    bm: int, bk: int, bn: int,
    *,
    in_dtype=jnp.bfloat16,
    out_dtype=None,
    b_layout: str = "row",
    m_rows: int = 1,
    n_cols: int = 1,
) -> GemmEstimate:
    """End-to-end modeled GEMM time — Eqs. 9–10 with the measured-BW analog.

    ``m_rows``/``n_cols`` extend the model to the mesh level (paper §4.2):
    the A tile is broadcast across ``m_rows`` and B across ``n_cols``, so
    per-"array" traffic divides exactly as Eqs. 6–7 prescribe.
    """
    if out_dtype is None:
        out_dtype = in_dtype
    ty_in = jnp.dtype(in_dtype).itemsize
    ty_out = jnp.dtype(out_dtype).itemsize
    # zero-padding to the native GEMM size (§5.3.1): the hardware runs the
    # padded problem — tile underfill is how skinny GEMMs lose throughput
    r = lambda x, b: -(-x // b) * b
    M, K, N = r(M, bm * m_rows), r(K, bk), r(N, bn * n_cols)
    eff = kernel_efficiency(hw, bm, bk, bn, in_dtype=in_dtype, b_layout=b_layout)
    chips = m_rows * n_cols
    t_comp = 2.0 * M * K * N / (eff * hw.peak_flops(in_dtype) * chips)  # Eq. 9
    a_mem, b_mem, c_mem = dram_traffic(
        M, K, N, bm, bn, ty_in=ty_in, ty_out=ty_out,
        m_rows=m_rows, n_cols=n_cols,
    )
    # Effective DRAM BW: A's contiguity is bk·ty (k_mt role); B's depends on
    # layout; take the traffic-weighted harmonic combination.
    bw_a = effective_bw(hw, bk * ty_in)
    bw_b = effective_bw(hw, (bk if b_layout == "col" else bn) * ty_in)
    bw_c = effective_bw(hw, bn * ty_out)
    t_mem = (a_mem / bw_a + b_mem / bw_b + c_mem / bw_c) / chips  # Eq. 10
    return GemmEstimate(
        t_comp=t_comp, t_mem=t_mem, eff=eff,
        a_mem=a_mem, b_mem=b_mem, c_mem=c_mem,
    )


def gemm_tops(hw, M, K, N, bm, bk, bn, **kw) -> float:
    """Modeled achieved TOP/s for the full GEMM (paper's headline metric)."""
    est = estimate_gemm(hw, M, K, N, bm, bk, bn, **kw)
    return 2.0 * M * K * N / est.t_total / 1e12


# ----------------------------------------------------------------- roofline
@dataclasses.dataclass(frozen=True)
class RooflineTerms:
    """The three dry-run roofline terms (seconds) for one compiled step."""

    compute: float
    memory: float
    collective: float

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute,
            "memory": self.memory,
            "collective": self.collective,
        }
        return max(terms, key=terms.get)

    @property
    def bound(self) -> float:
        """Step-time lower bound if all three streams fully overlap."""
        return max(self.compute, self.memory, self.collective)


def roofline_terms(
    hw: HardwareSpec,
    *,
    hlo_flops: float,
    hlo_bytes: float,
    collective_bytes: float,
    chips: int,
    dtype=jnp.bfloat16,
) -> RooflineTerms:
    """Terms per the assignment: FLOPs/(chips·peak), bytes/(chips·HBM BW),
    collective bytes/(chips·ICI BW). ``hlo_flops``/``hlo_bytes`` may be
    either per-device (XLA CPU reports per-device) or global — callers pass
    chips=1 for per-device numbers."""
    return RooflineTerms(
        compute=hlo_flops / (chips * hw.peak_flops(dtype)),
        memory=hlo_bytes / (chips * hw.hbm_bw),
        collective=collective_bytes / (chips * hw.ici_bw),
    )
