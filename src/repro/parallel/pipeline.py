"""GPipe-style pipeline parallelism via shard_map + collective_permute.

For depth-dominated models a ``stage`` mesh axis splits the layer stack into
S contiguous stages; microbatches stream through with the classic GPipe
schedule (S - 1 + M ticks). Steady-state utilization is M / (M + S - 1) —
the launcher picks M >= 4·S.

The assigned production meshes name no ``stage`` axis (DP x TP covers the
assigned archs), so PP is off by default in dry-runs; it exists as the
composable building block for deeper-than-memory models and is covered by
tests/test_pipeline.py on a local mesh.

Implementation notes: each device holds its stage's layer slice
(L/S layers). At every tick a device runs its stage on its current
microbatch and passes the activation to the next stage with
``ppermute``; microbatch i enters at tick i. Outputs collect on the last
stage, which re-distributes with a final permute chain.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(
    fn: Callable,           # (stage_params, x, stage_index) -> y
    stage_params,           # leaves with leading dim = n_stages
    x: jax.Array,           # (M, B, ...) microbatched input
    mesh: Mesh,
    *,
    axis: str = "stage",
) -> jax.Array:
    """Run ``fn`` as a GPipe pipeline over mesh axis ``axis``.

    stage_params leaves are sharded on dim 0 over ``axis``; x is replicated
    (every stage sees the full microbatch stream but only contributes its
    stage's compute).
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    S = sizes[axis]
    M = x.shape[0]
    if M < S:
        raise ValueError(f"need at least {S} microbatches, got {M}")
    perm_fwd = [(i, (i + 1) % S) for i in range(S)]

    def local(params_l, x_l):
        stage = jax.lax.axis_index(axis)
        params_l = jax.tree.map(lambda p: p[0], params_l)  # (1, ...) -> (...)
        n_ticks = M + S - 1

        def tick(carry, t):
            buf, outs = carry
            # which microbatch this stage works on at tick t
            mb_idx = t - stage
            active = (mb_idx >= 0) & (mb_idx < M)
            # stage 0 pulls a fresh microbatch; others use the handed-off buf
            fresh = jax.lax.dynamic_index_in_dim(
                x_l, jnp.clip(mb_idx, 0, M - 1), axis=0, keepdims=False)
            inp = jnp.where(stage == 0, fresh, buf)
            out = fn(params_l, inp, stage)
            out = jnp.where(active, out, buf)
            # last stage records its finished microbatch
            done_idx = t - (S - 1)
            outs = jax.lax.cond(
                (stage == S - 1) & (done_idx >= 0) & (done_idx < M),
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, out, jnp.clip(done_idx, 0, M - 1), axis=0),
                lambda o: o,
                outs,
            )
            # hand activations to the next stage
            buf_next = jax.lax.ppermute(out, axis, perm_fwd)
            return (buf_next, outs), None

        buf0 = jnp.zeros_like(x_l[0])
        outs0 = jnp.zeros_like(x_l)
        (buf, outs), _ = jax.lax.scan(
            tick, (buf0, outs0), jnp.arange(n_ticks))
        # broadcast results from the last stage to all stages (masked psum)
        outs = jax.lax.psum(
            jnp.where(stage == S - 1, outs, jnp.zeros_like(outs)), axis)
        return outs

    def leaf_spec(p):
        return P(axis, *([None] * (p.ndim - 1)))

    pspec = jax.tree.map(leaf_spec, stage_params)
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(pspec, P(*([None] * x.ndim))),
        out_specs=P(*([None] * x.ndim)),
        check_vma=False,
    )(stage_params, x)
