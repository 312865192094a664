"""Distributed GEMM — the paper's array mapping (§4.2) at mesh scale.

``output_stationary_gemm``
    The paper's mapping verbatim, one mesh axis per array dimension:
    A is sharded M-over-``data`` (each "row" of the device array holds one
    M-slice, replicated over ``model`` — the broadcast of A tiles across a
    row of cores); B is sharded N-over-``model`` (the column broadcast); K is
    kept whole on every device and reduced locally *in time*. The result C is
    sharded over both axes and **no collective is issued inside the GEMM** —
    the mesh rendition of "all cores compute independently" that the paper
    credits for beating the Versal K-partitioned designs.

``k_sharded_gemm``
    The foil: K partitioned over ``model`` (the Versal adder-tree/cascade
    analog) with a ``psum`` to combine partials. Exists so benchmarks and the
    roofline table can quantify the collective cost the paper's mapping
    avoids.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.gemm import balanced_gemm
from repro.kernels.ops import GemmPlan


def output_stationary_gemm(
    a: jax.Array,
    b: jax.Array,
    mesh: Mesh,
    *,
    m_axis: str = "data",
    n_axis: str = "model",
    out_dtype=None,
    backend: str = "auto",
    plan: GemmPlan | None = None,
) -> jax.Array:
    """C[M,N] = A[M,K] @ B[K,N], A sharded on M, B on N, K local (in time)."""

    def local(a_blk, b_blk):
        # Each device runs the *same independent kernel* on its (M/m, K) x
        # (K, N/n) slice — zero collectives, exactly §4.2.1.
        return balanced_gemm(
            a_blk, b_blk, out_dtype=out_dtype, backend=backend, plan=plan
        )

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(m_axis, None), P(None, n_axis)),
        out_specs=P(m_axis, n_axis),
        check_vma=False,
    )(a, b)


def k_sharded_gemm(
    a: jax.Array,
    b: jax.Array,
    mesh: Mesh,
    *,
    k_axis: str = "model",
    out_dtype=None,
    backend: str = "auto",
    plan: GemmPlan | None = None,
) -> jax.Array:
    """The Versal-style foil: K partitioned in space, psum to reduce."""

    def local(a_blk, b_blk):
        part = balanced_gemm(
            a_blk, b_blk, out_dtype=jnp.float32, backend=backend, plan=plan
        )
        part = jax.lax.psum(part, k_axis)
        return part.astype(out_dtype or a.dtype)

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(None, k_axis), P(k_axis, None)),
        out_specs=P(None, None),
        check_vma=False,
    )(a, b)
