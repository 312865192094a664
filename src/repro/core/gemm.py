"""Public balanced-GEMM API — the paper's technique as a first-class feature.

``balanced_gemm(a, b)`` is the drop-in matmul the rest of the framework (all
model layers) routes through. Plans are solved once per
(hw, M, K, N, dtypes, layout) signature via the §4.5 machinery and served
from the active context's :class:`repro.core.plancache.PlanCache` — the
paper's §5.3.1 observation that re-using solved parameters across GEMM sizes
is free (only the grid counts change) is what makes the cache sound, and the
cache's JSON backend extends the reuse across *process lifetimes*.

Unified dispatch: every call resolves a plan through ``plan_for``; skinny-M
calls (decode-shaped, M ≤ ``SKINNY_M``) route to the ``decode_matvec``
kernel with the planner's (bk, bn) instead of that kernel's historical
hard-coded blocks, so one planned entry point covers prefill, training and
decode GEMMs alike.

``plan_model(cfg)`` pre-solves every GEMM signature a model configuration
will issue (prefill + decode, all projections) by abstractly tracing the
model under the active context — server start-up warms the cache once
instead of paying a solver call on every first-seen shape mid-traffic.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.core import balance, perfmodel as pm
from repro.core.context import current_context, resolve_hw
from repro.core.plancache import BalanceSnapshot, PlanCache, plan_key
from repro.kernels import ops
from repro.kernels.ops import GemmPlan

# Decode-shaped threshold: at or below this many rows the output tile cannot
# amortize weight streaming and the x-stationary GEMV kernel wins (§5.3.4
# extension). 128 covers the paper's decode batches (1–128 tokens).
SKINNY_M = 128

# Observers of plan *consultation* — distinct from the plan cache's solver
# listeners (miss/warm_solve/lazy_solve): these fire on every ``plan_for``
# resolution, hit or miss, so an attribution ledger can count how many times
# each GEMM signature is dispatched per phase. fn(key, plan) with plan
# possibly None (cache-only consult that missed).
_dispatch_listeners: list = []


def add_dispatch_listener(fn) -> None:
    """Register ``fn(key, plan)`` called on every plan_for consultation."""
    _dispatch_listeners.append(fn)


def remove_dispatch_listener(fn) -> None:
    try:
        _dispatch_listeners.remove(fn)
    except ValueError:
        pass


def plan_for(
    M: int, K: int, N: int,
    *,
    in_dtype,
    out_dtype=None,
    b_layout: str = "row",
    hw: pm.HardwareSpec | str | None = None,
    cache: PlanCache | None = None,
    solve: bool = True,
) -> GemmPlan | None:
    """Fetch (or solve) the balanced plan for one GEMM signature.

    With ``solve=False`` this is a pure cache consultation: it returns the
    cached plan or None without invoking the solver — the mode the XLA
    fallback backend uses (XLA ignores tile plans, but the lookup keeps the
    cache's hit/miss telemetry complete). During a cache warm-up phase
    (:meth:`PlanCache.warmup`) misses always solve, regardless of ``solve``.
    """
    hw = resolve_hw(hw)
    if cache is None:
        cache = current_context().plan_cache
    key = plan_key(
        hw.name, M, K, N, jnp.dtype(in_dtype).name,
        jnp.dtype(out_dtype or in_dtype).name, b_layout,
    )
    plan = cache.get(key)
    if plan is None and (solve or cache.warming):
        # exhaustive model sweep (beyond-paper; free without per-probe
        # hardware compiles) — the paper's walk is kept for benchmarks
        res = balance.solve_exhaustive(
            M, K, N, hw=hw, in_dtype=in_dtype, out_dtype=out_dtype,
            b_layout=b_layout,
        )
        plan = res.plan
        step = res.chosen_step
        cache.put(key, plan,
                  balance=None if step is None else BalanceSnapshot(
                      t_comp=step.t_comp, t_mem=step.t_mem))
    if _dispatch_listeners:
        for fn in _dispatch_listeners:
            fn(key, plan)
    return plan


def clear_plan_cache() -> None:
    """Clear the active context's plan cache (entries and counters)."""
    current_context().plan_cache.clear()


def _is_skinny(M: int, K: int, N: int) -> bool:
    """Decode-shaped: few rows, and (K, N) large enough for the GEMV
    kernel's weight-streaming design to make sense (tiny operands
    degenerate to a single block either way)."""
    return M <= SKINNY_M and K >= 256 and N >= 128


def balanced_gemm(
    a: jax.Array,
    b: jax.Array,
    bias: jax.Array | None = None,
    *,
    out_dtype=None,
    b_layout: str = "row",
    activation: str | None = None,
    out_scale: jax.Array | None = None,
    backend: str | None = None,
    plan: GemmPlan | None = None,
    hw: pm.HardwareSpec | str | None = None,
) -> jax.Array:
    """Balanced tiled GEMM. Leading dims of ``a`` are flattened (batch).

    ``out_scale`` (N,) fuses per-output-channel requantization into the
    kernel epilogue — the quantized-inference path (docs/quantization.md).
    ``backend=None`` resolves to the active context's backend; 'auto' picks
    pallas on TPU, xla elsewhere, and 'pallas' off a TPU raises
    (:func:`repro.kernels.ops.resolve_backend`). The kernels get the scoped
    VMEM of ``hw`` — the solver's Eq. 5 budget plus Mosaic's headroom.
    """
    ctx = current_context()
    backend = ops.resolve_backend(
        ctx.matmul_backend if backend is None else backend)
    hw = resolve_hw(hw)
    *lead, K = a.shape
    M = 1
    for d in lead:
        M *= d
    N = b.shape[0] if b_layout == "col" else b.shape[1]
    a2 = a.reshape(M, K)
    if plan is None:
        # XLA lowers to dot_general and never consumes the tiles, so the
        # lookup is cache-only there; kernel backends solve on miss.
        plan = plan_for(
            M, K, N, in_dtype=a.dtype, out_dtype=out_dtype,
            b_layout=b_layout, hw=hw, solve=(backend != "xla"),
        )
    if (
        backend != "xla"
        and plan is not None
        and bias is None
        and activation in (None, "none")
        and out_scale is None
        and _is_skinny(M, K, N)
    ):
        # Unified dispatch: decode-shaped GEMMs go to the x-stationary GEMV
        # kernel, with the planner's blocks replacing its old hard-coded
        # (bk=1024, bn=256).
        out = ops.decode_matvec(
            a2, b, bk=plan.bk, bn=plan.bn, out_dtype=out_dtype,
            w_layout=b_layout, backend=backend,
            vmem_limit_bytes=hw.vmem_limit_bytes,
        )
    else:
        out = ops.balanced_matmul(
            a2, b, bias, plan=plan, out_dtype=out_dtype, b_layout=b_layout,
            activation=activation, out_scale=out_scale, backend=backend,
            vmem_limit_bytes=hw.vmem_limit_bytes,
        )
    return out.reshape(*lead, N)


# ------------------------------------------------------------ model warm-up
def plan_model(
    cfg,
    *,
    batch: int,
    prompt_len: int,
    max_len: int,
    params: Any = None,
    extras: dict[str, Any] | None = None,
) -> dict[str, int]:
    """Pre-solve every GEMM plan a model config will issue when serving.

    Abstractly traces prefill (full ``prompt_len`` sequence) and decode (one
    token) under the active context — every ``dense``/``balanced_gemm`` a
    layer issues calls ``plan_for`` at trace time, so the trace itself
    enumerates the exact signature set (all projections, both phases, the
    active quantization mode) with no hand-maintained shape list to drift.
    Runs under ``jax.eval_shape``: no FLOPs, no device buffers.

    ``params`` may be the real (possibly pre-quantized) parameter tree or
    None to derive abstract float params from the config. Returns warm-up
    statistics: 'signatures' (distinct GEMM signatures the model issues),
    'solved' (solver invocations this warm-up) and 'from_cache'
    (signatures already present — e.g. loaded from disk).
    """
    from repro import models

    cache = current_context().plan_cache
    before = cache.stats.snapshot()
    if params is None:
        params = jax.eval_shape(
            lambda: models.init(jax.random.PRNGKey(0), cfg))
    state = jax.eval_shape(
        lambda: models.init_decode_state(cfg, batch, max_len))
    batch_in = {
        "tokens": jax.ShapeDtypeStruct((batch, prompt_len), jnp.int32),
        **(extras or {}),
    }
    tok = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
    with cache.warmup():
        jax.eval_shape(
            lambda p, bi, s: models.prefill(p, bi, cfg, s),
            params, batch_in, state)
        jax.eval_shape(
            lambda p, t, s: models.decode_step(p, t, cfg, s),
            params, tok, state)
    solved = cache.stats.warm_solves - before.warm_solves
    signatures = len(cache.warm_keys)
    return {
        "signatures": signatures,
        "solved": solved,
        "from_cache": signatures - solved,
    }
