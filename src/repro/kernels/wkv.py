"""Pallas TPU kernel for the RWKV-6 WKV recurrence (chunk-parallel form).

The §Perf cell-1 analysis showed the token recurrence is the worst
memory-bound computation in the framework: the (N,N) state crosses the HBM
boundary every token. The chunk-parallel formulation (see
``repro.layers.rwkv.wkv_chunk_parallel``) fixes the *graph-level* traffic;
this kernel is the TPU-native version: one grid cell owns one (batch, head)
pair, keeps the state in a VMEM scratch across the whole sequence, and
walks T in C-sized blocks with the factored intra-chunk matmuls on the MXU.

HBM traffic per (b, h): read r/k/v/wlog once, write y once, state io once —
the roofline floor for this op.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 32


def _wkv_kernel(r_ref, k_ref, v_ref, wl_ref, u_ref, s0_ref,
                y_ref, sout_ref, state, *, T: int, N: int):
    """One (b, h): refs are (T, N) except u (1, N) and states (N, N)."""
    state[...] = s0_ref[...].astype(jnp.float32)
    nc = T // CHUNK
    causal = jnp.tril(jnp.ones((CHUNK, CHUNK), jnp.float32), -1)
    u = u_ref[0, :]

    def chunk_body(c, _):
        sl = pl.ds(c * CHUNK, CHUNK)
        rc = r_ref[sl, :].astype(jnp.float32)
        kc = k_ref[sl, :].astype(jnp.float32)
        vc = v_ref[sl, :].astype(jnp.float32)
        wl = wl_ref[sl, :].astype(jnp.float32)
        cl = jnp.cumsum(wl, axis=0) - wl
        ce = cl[-1, :] + wl[-1, :]
        S = state[...]
        y1 = jnp.dot(rc * jnp.exp(cl), S,
                     preferred_element_type=jnp.float32)
        mid = cl[CHUNK // 2, :][None, :]
        rDm = rc * jnp.exp(cl - mid)
        kinv = kc * jnp.exp(jnp.clip(mid - (cl + wl), max=60.0))
        A = jax.lax.dot_general(
            rDm, kinv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * causal
        diag = jnp.sum(rc * u[None, :] * kc, axis=-1, keepdims=True)
        y2 = jnp.dot(A, vc, preferred_element_type=jnp.float32) + diag * vc
        y_ref[sl, :] = (y1 + y2).astype(y_ref.dtype)
        kdec = kc * jnp.exp(jnp.clip(ce[None, :] - (cl + wl), max=0.0))
        state[...] = jnp.exp(ce)[:, None] * S + jax.lax.dot_general(
            kdec, vc, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return _

    jax.lax.fori_loop(0, nc, chunk_body, 0)
    sout_ref[...] = state[...].astype(sout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def wkv(r, k, v, wlog, u, state, *, interpret: bool = False):
    """r/k/v/wlog: (BH, T, N); u: (BH, N) broadcast rows; state (BH, N, N).

    Returns (y (BH, T, N), new_state). T must be a multiple of CHUNK.
    """
    BH, T, N = r.shape
    if T % CHUNK:
        raise ValueError(f"T={T} must be a multiple of {CHUNK}")
    spec_tn = pl.BlockSpec((1, T, N), lambda i: (i, 0, 0))
    spec_n = pl.BlockSpec((1, 1, N), lambda i: (i, 0, 0))
    spec_nn = pl.BlockSpec((1, N, N), lambda i: (i, 0, 0))

    def kernel(r_ref, k_ref, v_ref, wl_ref, u_ref, s0_ref, y_ref, sout_ref,
               scratch):
        _wkv_kernel(
            r_ref.at[0], k_ref.at[0], v_ref.at[0], wl_ref.at[0],
            u_ref.at[0], s0_ref.at[0], y_ref.at[0], sout_ref.at[0],
            scratch, T=T, N=N)

    y, s_out = pl.pallas_call(
        kernel,
        grid=(BH,),
        in_specs=[spec_tn, spec_tn, spec_tn, spec_tn, spec_n, spec_nn],
        out_specs=[spec_tn, spec_nn],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, N), r.dtype),
            jax.ShapeDtypeStruct((BH, N, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(r, k, v, wlog, u.reshape(BH, 1, N), state)
    return y, s_out
