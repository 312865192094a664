"""Production mesh construction.

Single pod: (16, 16) = 256 chips, axes ("data", "model").
Multi-pod:  (2, 16, 16) = 512 chips, axes ("pod", "data", "model") — the
"pod" axis is pure DP whose gradient all-reduce crosses the inter-pod DCI.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — the dry-run must set XLA_FLAGS before any
device initialization.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(data: int = 1, model: int = 1) -> Mesh:
    """A ``data`` x ``model`` mesh over the first ``data * model`` devices.

    The device count is always explicit: a one-chip server stays on one chip
    however many the host shows.
    """
    devices = jax.devices()
    if data * model > len(devices):
        raise ValueError(
            f"a {data}x{model} mesh needs {data * model} devices; "
            f"{len(devices)} visible")
    devs = np.array(devices[: data * model]).reshape(data, model)
    return Mesh(devs, ("data", "model"))
