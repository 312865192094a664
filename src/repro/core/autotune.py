"""Measured-feedback tile autotuner.

Wraps the §4.5.2 iterative procedure with a measurement callback and adds a
generic neighbor-hillclimb refinement (the beyond-paper part): after the
paper's bk-descent converges, probe the ±1-step neighborhood of the balanced
plan. On hardware ``measure_fn`` is wall clock of the Pallas kernel; off the
chip the default is the analytical model, and tests time the kernel in
interpret mode.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Iterable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import balance, perfmodel as pm
from repro.core.context import current_context, resolve_hw
from repro.core.plancache import BalanceSnapshot
from repro.kernels.matmul import LANE, SUBLANE, vmem_bytes
from repro.kernels.ops import GemmPlan, balanced_matmul, resolve_backend


@dataclasses.dataclass
class TuneRecord:
    plan: GemmPlan
    seconds: float
    source: str  # 'paper-iteration' | 'hillclimb'


@dataclasses.dataclass
class TuneResult:
    plan: GemmPlan
    seconds: float
    history: list[TuneRecord]


def model_measure_fn(
    M: int, K: int, N: int, *, hw=None, in_dtype=jnp.bfloat16,
    out_dtype=None, b_layout="row", m_rows=1, n_cols=1,
) -> Callable[[GemmPlan], float]:
    """Analytical-model 'measurement' (the CPU-container default)."""
    hw = resolve_hw(hw)

    def fn(plan: GemmPlan) -> float:
        return pm.estimate_gemm(
            hw, M, K, N, plan.bm, plan.bk, plan.bn, in_dtype=in_dtype,
            out_dtype=out_dtype, b_layout=b_layout, m_rows=m_rows,
            n_cols=n_cols,
        ).t_total

    return fn


def wallclock_measure_fn(
    M: int, K: int, N: int, *, in_dtype=jnp.bfloat16, out_dtype=None,
    b_layout="row", backend: str | None = None, repeats=3,
) -> Callable[[GemmPlan], float]:
    """Wall-clock time of the tiled kernel under one plan.

    ``backend=None`` takes the active context's backend — 'pallas' (the
    Mosaic kernel) on a TPU; 'interpret' only when a caller asks for it.
    'xla' is refused: it ignores tile plans, so its times cannot rank them.
    """
    ctx = current_context()
    backend = resolve_backend(backend or ctx.matmul_backend)
    if backend == "xla":
        raise ValueError(
            "plan refinement times the tiled kernel; the xla backend ignores "
            "tile plans — use --matmul-backend pallas on a TPU")
    vmem_limit = ctx.hw.vmem_limit_bytes
    rng = np.random.default_rng(0)

    def _mk(shape):
        if jnp.issubdtype(jnp.dtype(in_dtype), jnp.integer):
            return jnp.asarray(rng.integers(-100, 100, size=shape), in_dtype)
        return jnp.asarray(rng.normal(size=shape), in_dtype)

    a = _mk((M, K))
    b = _mk((N, K) if b_layout == "col" else (K, N))

    def fn(plan: GemmPlan) -> float:
        out = balanced_matmul(
            a, b, plan=plan, out_dtype=out_dtype, b_layout=b_layout,
            backend=backend, vmem_limit_bytes=vmem_limit,
        )
        jax.block_until_ready(out)
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(
                balanced_matmul(
                    a, b, plan=plan, out_dtype=out_dtype, b_layout=b_layout,
                    backend=backend, vmem_limit_bytes=vmem_limit,
                )
            )
            best = min(best, time.perf_counter() - t0)
        return best

    return fn


def refine_cached_plans(
    cache,
    keys: Iterable[tuple] | None = None,
    *,
    measure_factory: Callable[..., Callable[[GemmPlan], float]] | None = None,
    backend: str | None = None,
    repeats: int = 2,
    rounds: int = 1,
    resolve: bool = False,
) -> dict[str, int]:
    """Refine cached plans in place with measured feedback (ROADMAP item).

    For each plan-cache key (default: the signatures the most recent warm-up
    consulted, ``cache.warm_keys``), measure the cached model-solved plan
    against its ±1-step tile neighborhood and keep the measured-best —
    on-hardware starts thereby turn the analytical plans into wall-clock
    plans without changing the cache schema (a plan is a plan; only its
    provenance improves). The caller persists via ``cache.save()``.

    ``measure_factory(M, K, N, in_dtype=…, out_dtype=…, b_layout=…)`` builds
    the per-signature measurement; the default is
    :func:`wallclock_measure_fn` on ``backend``, which defaults to the
    active context's backend: the real Pallas kernel on a TPU. Interpret
    mode runs only when a caller passes ``backend='interpret'`` (the CPU
    tests do). Entries whose key is missing from the cache are skipped —
    refinement never *adds* signatures.

    ``resolve=True`` is the balance auditor's re-solve path: each key is
    first re-solved from the analytic model (``solve_exhaustive``, direct —
    no cache counters touched) and the fresh plan competes with the cached
    one as the hillclimb start. Either way the entry's
    :class:`~repro.core.plancache.BalanceSnapshot` is refreshed to the
    winning plan's current model evaluation, so a refined signature stops
    reading as drifted.
    """
    if measure_factory is None:
        def measure_factory(M, K, N, **kw):
            return wallclock_measure_fn(
                M, K, N, backend=backend, repeats=repeats, **kw)
    keys = list(cache.warm_keys if keys is None else keys)
    stats = {"measured": 0, "refined": 0, "kept": 0, "skipped": 0}
    for key in keys:
        plan = cache.entries.get(key)
        if plan is None:
            stats["skipped"] += 1
            continue
        _hw, M, K, N, in_dtype, out_dtype, b_layout = key
        fn = measure_factory(
            M, K, N, in_dtype=jnp.dtype(in_dtype),
            out_dtype=jnp.dtype(out_dtype), b_layout=b_layout)
        ty = jnp.dtype(in_dtype).itemsize
        ty_out = jnp.dtype(out_dtype).itemsize
        hw = resolve_hw(_hw)
        best_plan, best_t = plan, fn(plan)
        stats["measured"] += 1
        if resolve:
            fresh = balance.solve_exhaustive(
                M, K, N, hw=hw, in_dtype=jnp.dtype(in_dtype),
                out_dtype=jnp.dtype(out_dtype), b_layout=b_layout).plan
            if fresh != plan:
                t = fn(fresh)
                stats["measured"] += 1
                if t < best_t:
                    best_plan, best_t = fresh, t
        for _ in range(max(1, rounds)):
            improved = False
            for cand in _neighbors(best_plan, ty):
                if vmem_bytes(cand.bm, cand.bk, cand.bn, ty, ty_out) \
                        > hw.vmem_bytes:
                    continue
                t = fn(cand)
                stats["measured"] += 1
                if t < best_t:
                    best_plan, best_t, improved = cand, t, True
            if not improved:
                break
        est = pm.estimate_gemm(
            hw, M, K, N, best_plan.bm, best_plan.bk, best_plan.bn,
            in_dtype=jnp.dtype(in_dtype), out_dtype=jnp.dtype(out_dtype),
            b_layout=b_layout)
        cache.update(key, best_plan, balance=BalanceSnapshot(
            t_comp=est.t_comp, t_mem=est.t_mem))
        if best_plan is not plan:
            stats["refined"] += 1
        else:
            stats["kept"] += 1
    return stats


def _neighbors(plan: GemmPlan, itemsize: int) -> list[GemmPlan]:
    sub = SUBLANE[itemsize]
    out = []
    for dm in (-128, -sub, 0, sub, 128):
        for dk in (-LANE, 0, LANE):
            for dn in (-LANE, 0, LANE):
                bm, bk, bn = plan.bm + dm, plan.bk + dk, plan.bn + dn
                if bm >= sub and bk >= LANE and bn >= LANE:
                    if (bm, bk, bn) != (plan.bm, plan.bk, plan.bn):
                        out.append(GemmPlan(bm=bm, bk=bk, bn=bn))
    return out


def autotune(
    M: int, K: int, N: int,
    *,
    hw: pm.HardwareSpec | str | None = None,
    in_dtype=jnp.bfloat16,
    out_dtype=None,
    b_layout: str = "row",
    m_rows: int = 1,
    n_cols: int = 1,
    measure_fn: Callable[[GemmPlan], float] | None = None,
    hillclimb_rounds: int = 3,
    min_gain: float = 0.05,
) -> TuneResult:
    """Paper iteration (§4.5.2) + neighbor hillclimb refinement.

    Stops the refinement after ``hillclimb_rounds`` consecutive rounds with
    < ``min_gain`` relative improvement (the assignment's stopping rule).
    """
    hw = resolve_hw(hw)
    if measure_fn is None:
        measure_fn = model_measure_fn(
            M, K, N, hw=hw, in_dtype=in_dtype, out_dtype=out_dtype,
            b_layout=b_layout, m_rows=m_rows, n_cols=n_cols,
        )
    ty = jnp.dtype(in_dtype).itemsize
    budget = hw.vmem_bytes

    res = balance.solve_balanced(
        M, K, N, hw=hw, in_dtype=in_dtype, out_dtype=out_dtype,
        b_layout=b_layout, m_rows=m_rows, n_cols=n_cols,
        measure_fn=measure_fn,
    )
    history = [
        TuneRecord(plan=s.plan, seconds=s.t_total, source="paper-iteration")
        for s in res.steps
    ]
    best_plan = res.plan
    best_t = min(s.t_total for s in res.steps)

    stale = 0
    while stale < hillclimb_rounds:
        round_best_plan, round_best_t = None, best_t
        for cand in _neighbors(best_plan, ty):
            ty_out = jnp.dtype(out_dtype or in_dtype).itemsize
            if vmem_bytes(cand.bm, cand.bk, cand.bn, ty, ty_out) > budget:
                continue
            t = measure_fn(cand)
            history.append(TuneRecord(plan=cand, seconds=t, source="hillclimb"))
            if t < round_best_t:
                round_best_plan, round_best_t = cand, t
        if round_best_plan is None or (best_t - round_best_t) / best_t < min_gain:
            stale += 1
            if round_best_plan is not None and round_best_t < best_t:
                best_plan, best_t = round_best_plan, round_best_t
        else:
            stale = 0
            best_plan, best_t = round_best_plan, round_best_t
    return TuneResult(plan=best_plan, seconds=best_t, history=history)
