"""Shared layer utilities: parameter init, the dense() GEMM wrapper, norms.

Every matmul in every architecture routes through :func:`dense`, which calls
``repro.core.balanced_gemm`` — the paper's technique as the framework-wide
GEMM substrate. ``backend='xla'`` (default off-TPU) lowers to a plain
``dot_general`` so dry-runs and CPU training use XLA; on TPU the balanced
Pallas kernel is selected per-shape by the plan cache.

Execution state (kernel backend, quantization mode, activation mesh) lives
in the active :class:`repro.core.context.GemmContext`; the ``set_*``/
``get_*`` functions here are thin shims over it, kept for the established
call sites — their effect is scoped by any enclosing ``use_context`` block.
"""
from __future__ import annotations

import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp

from repro.core.context import current_context
from repro.core.gemm import balanced_gemm
from repro.quant.int8 import QuantizedLinear


def set_matmul_backend(backend: str) -> None:
    """'auto' | 'xla' | 'pallas' | 'interpret' for every dense() call."""
    from repro.core.context import BACKENDS

    if backend not in BACKENDS:
        raise ValueError(f"matmul backend must be one of {BACKENDS}, "
                         f"got {backend!r}")
    current_context().matmul_backend = backend


def get_matmul_backend() -> str:
    return current_context().matmul_backend


def set_quant_mode(mode: str | None) -> None:
    """None (full precision) or 'int8': every dense() routes through the
    W8A8 balanced-GEMM path with the fused requantize epilogue."""
    if mode not in (None, "none", "int8"):
        raise ValueError(f"quant mode must be None|'none'|'int8', got {mode!r}")
    current_context().quant_mode = None if mode == "none" else mode


def get_quant_mode() -> str | None:
    return current_context().quant_mode


def dense(
    x: jax.Array,
    w: jax.Array,
    bias: jax.Array | None = None,
    *,
    activation: str | None = None,
    out_dtype=None,
) -> jax.Array:
    """x @ w (+bias, +activation) through the balanced-GEMM substrate.

    ``w`` may be a float (K, N) weight or a pre-quantized
    :class:`QuantizedLinear` (int8 (N, K) + per-channel scales), in which
    case only int8 weights stream from HBM and the dequantize rides the
    kernel epilogue. Float weights under ``quant_mode='int8'`` take the
    dynamic W8A8 path (numerics demo: weights re-quantized in-graph).
    """
    ctx = current_context()
    out_dtype = out_dtype or x.dtype
    if isinstance(w, QuantizedLinear):
        from repro.layers import quantized as qz

        ql = w
        if bias is not None:
            ql = ql._replace(bias=bias.astype(jnp.float32))
        return qz.qdense(
            x, ql, activation=activation, out_dtype=out_dtype,
            backend=ctx.matmul_backend,
        )
    if ctx.quant_mode == "int8" and not jnp.issubdtype(x.dtype, jnp.integer):
        from repro.layers import quantized as qz

        return qz.dynamic_qdense(
            x, w, bias, activation=activation, out_dtype=out_dtype,
            backend=ctx.matmul_backend,
        )
    return balanced_gemm(
        x, w, bias, out_dtype=out_dtype, activation=activation,
        backend=ctx.matmul_backend,
    )


def embed_lookup(table: jax.Array, ids: jax.Array, mesh=None) -> jax.Array:
    """Vocab-parallel embedding lookup (Megatron-style).

    With the table sharded vocab-over-'model', a naive gather would make
    GSPMD all-gather the whole table (GBs for 256k vocabs). Instead each
    model-rank gathers its local rows (out-of-range ids masked to zero) and
    the shards psum — traffic is (B, S, d) activations, not the table.
    """
    from jax.sharding import PartitionSpec as P

    if mesh is None or "model" not in getattr(mesh, "axis_names", ()):
        return jnp.take(table, ids, axis=0)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    tp = sizes.get("model", 1)
    V = table.shape[0]
    if tp == 1 or V % tp != 0:
        return jnp.take(table, ids, axis=0)
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp_total = 1
    for a in dp:
        dp_total *= sizes[a]
    if ids.shape[0] % max(dp_total, 1) != 0:
        dp = ()  # tiny batches (long_500k: B=1) replicate over DP
    dp_spec = (dp if len(dp) > 1 else dp[0]) if dp else None

    def local(tbl, ids_l):
        shard = jax.lax.axis_index("model")
        local_v = tbl.shape[0]
        local_ids = ids_l - shard * local_v
        ok = (local_ids >= 0) & (local_ids < local_v)
        rows = jnp.take(tbl, jnp.clip(local_ids, 0, local_v - 1), axis=0)
        rows = jnp.where(ok[..., None], rows, 0)
        return jax.lax.psum(rows, "model")

    ids_spec = P(dp_spec, *([None] * (ids.ndim - 1)))
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P("model", None), ids_spec),
        out_specs=P(dp_spec, *([None] * ids.ndim)),
        check_vma=False,
    )(table, ids)


# --------------------------------------------------- activation sharding
# The mesh is recorded at trace time by the model entry points (into the
# active GemmContext) so layers can place with_sharding_constraint hints
# without threading it through every signature. Hints are advisory: a dim
# that does not divide its mesh axis degrades to None.
def set_activation_mesh(mesh) -> None:
    current_context().mesh = mesh


def get_activation_mesh():
    return current_context().mesh


def axis_size(name: str) -> int:
    mesh = current_context().mesh
    if mesh is None or name not in getattr(mesh, "axis_names", ()):
        return 1
    return dict(zip(mesh.axis_names, mesh.devices.shape))[name]


def dp_axes_present() -> tuple[str, ...]:
    mesh = current_context().mesh
    if mesh is None:
        return ()
    return tuple(a for a in ("pod", "data")
                 if a in getattr(mesh, "axis_names", ()))


def hint(x: jax.Array, *entries) -> jax.Array:
    """with_sharding_constraint by logical entries: 'dp' | mesh axis | None.

    Invalid entries (missing axis, non-dividing dim, axis already used)
    silently degrade to None — the hint never breaks a small mesh.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = current_context().mesh
    if mesh is None:
        return x
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    used: set[str] = set()
    spec = []
    for dim, e in zip(x.shape, entries):
        if e == "dp":
            dpax = [a for a in ("pod", "data") if a in sizes and a not in used]
            tot = 1
            for a in dpax:
                tot *= sizes[a]
            if dpax and dim % tot == 0:
                spec.append(tuple(dpax) if len(dpax) > 1 else dpax[0])
                used.update(dpax)
                continue
        elif e in sizes and e not in used and dim % sizes[e] == 0:
            spec.append(e)
            used.add(e)
            continue
        spec.append(None)
    while spec and spec[-1] is None:
        spec.pop()
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*spec)))


# ------------------------------------------------------------------ init
def normal_init(key, shape, dtype, scale: float | None = None):
    fan_in = shape[0] if len(shape) >= 2 else 1
    if scale is None:
        scale = 1.0 / math.sqrt(max(1, fan_in))
    return (scale * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def split_keys(key, n: int):
    return list(jax.random.split(key, n))


# ------------------------------------------------------------------ norms
def rms_norm(x: jax.Array, gamma: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * gamma.astype(jnp.float32)).astype(dt)


def layer_norm(
    x: jax.Array, gamma: jax.Array, beta: jax.Array, eps: float = 1e-5
) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * gamma.astype(jnp.float32) + beta.astype(jnp.float32)).astype(dt)


# ------------------------------------------------------------------ rotary
def rotary_embedding(
    positions: jax.Array, head_dim: int, theta: float = 10000.0
) -> tuple[jax.Array, jax.Array]:
    """Returns (sin, cos) of shape (..., head_dim/2)."""
    freqs = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    angles = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.sin(angles), jnp.cos(angles)


def apply_rotary(x: jax.Array, sin: jax.Array, cos: jax.Array) -> jax.Array:
    """x: (..., seq, heads, head_dim); sin/cos: (..., seq, head_dim/2)."""
    dt = x.dtype
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    s = sin[..., None, :]  # broadcast over heads
    c = cos[..., None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1).astype(dt)
