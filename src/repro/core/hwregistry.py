"""Named hardware generations — the paper's XDNA→XDNA2 axis, TPU-adapted.

The paper's core claim is that one optimization *methodology* spans NPU
generations whose constants differ (peak rate, DRAM bandwidth, local-memory
size, intrinsic tile). This registry makes the generation a first-class,
swappable input: every solver/perfmodel/benchmark entry point resolves its
``HardwareSpec`` through here (via the active :mod:`repro.core.context`)
instead of baking one chip in, so Table-2-vs-Table-3 style cross-generation
sweeps are a loop over ``list_hw()``.

Selection precedence: explicit argument > active context > ``REPRO_HW`` env
var > the attached TPU's ``device_kind`` > ``tpu_v5e``. The last is only a
modelling default for hosts without a TPU; on a TPU whose kind the registry
does not know, the default raises instead of describing another chip.
"""
from __future__ import annotations

import os

import jax

from repro.core.perfmodel import TPU_V5E, HardwareSpec

DEFAULT_HW_ENV = "REPRO_HW"

# Modeled generations. v4 (the "previous gen"): higher absolute peak than
# v5e but no int8 rate doubling and a lower compute:bandwidth ratio; v6e
# (Trillium, the "next gen"): ~4.7x bf16 peak, 2x HBM BW, and a 256-wide
# MXU whose alignment derate pushes the solver to coarser tiles — each
# generation lands on a *different* balanced point (the paper's Table 2 vs
# Table 3 contrast).
TPU_V4 = HardwareSpec(
    name="tpu_v4",
    peak_flops_bf16=275e12,
    peak_flops_int8=275e12,   # v4 MXU: int8 runs at the bf16 MAC rate
    hbm_bw=1228e9,
    ici_bw=50e9,
    vmem_bytes=16 * 2**20,
    vmem_bw=9e12,
    hbm_latency_bytes=512.0,
    peak_flops_f32=137.5e12,
)

TPU_V6E = HardwareSpec(
    name="tpu_v6e",
    peak_flops_bf16=918e12,
    peak_flops_int8=1836e12,
    hbm_bw=1640e9,
    ici_bw=100e9,
    vmem_bytes=32 * 2**20,
    vmem_bw=22e12,
    hbm_latency_bytes=512.0,
    mxu=256,
    peak_flops_f32=459e12,
)

_REGISTRY: dict[str, HardwareSpec] = {}


def register_hw(spec: HardwareSpec) -> HardwareSpec:
    """Register (or replace) a named generation; returns the spec."""
    _REGISTRY[spec.name.lower()] = spec
    return spec


for _spec in (TPU_V4, TPU_V5E, TPU_V6E):
    register_hw(_spec)


def get_hw(name: str | HardwareSpec) -> HardwareSpec:
    """Resolve a generation by name (a HardwareSpec passes through)."""
    if isinstance(name, HardwareSpec):
        return name
    try:
        return _REGISTRY[name.lower()]
    except KeyError:
        raise KeyError(
            f"unknown hardware generation {name!r}; "
            f"registered: {sorted(_REGISTRY)}"
        ) from None


def list_hw() -> list[str]:
    return sorted(_REGISTRY)


# ``jax.Device.device_kind`` of each modelled chip.
DEVICE_KINDS: dict[str, str] = {
    "TPU v4": TPU_V4.name,
    "TPU v5 lite": TPU_V5E.name,
    "TPU v6 lite": TPU_V6E.name,
}


def hw_for_device_kind(kind: str) -> HardwareSpec:
    """The spec of a chip by its ``device_kind``; an unmodelled kind raises."""
    try:
        return get_hw(DEVICE_KINDS[kind])
    except KeyError:
        raise KeyError(
            f"no hardware spec for device_kind {kind!r} (known: "
            f"{sorted(DEVICE_KINDS)}); pass --hw to model it as one of "
            f"{list_hw()}") from None


def default_hw() -> HardwareSpec:
    """Process default: ``REPRO_HW``, else the attached TPU's spec, else
    tpu_v5e (modelling default off the TPU)."""
    env = os.environ.get(DEFAULT_HW_ENV)
    if env:
        return get_hw(env)
    if jax.default_backend() == "tpu":
        return hw_for_device_kind(jax.devices()[0].device_kind)
    return TPU_V5E
