"""Straggler monitor, autotuner, optimizer math, pipeline scheduling."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.core import autotune, balance
from repro.ft.straggler import StragglerMonitor, StragglerConfig
from repro.train import optimizer as opt_lib


# ------------------------------------------------------------- straggler
def test_straggler_steady_state_ok():
    m = StragglerMonitor()
    for s in range(50):
        assert m.record(s, 0.1 + 0.001 * (s % 3)) in ("ok", "warn")


def test_straggler_detects_persistent_slowdown():
    m = StragglerMonitor(StragglerConfig(patience=3))
    verdicts = [m.record(s, 0.1) for s in range(20)]
    # a persistently slow tail (chip degradation) must escalate
    verdicts += [m.record(20 + i, 1.5) for i in range(6)]
    assert "checkpoint_and_rebalance" in verdicts


def test_straggler_one_spike_no_action():
    m = StragglerMonitor()
    for s in range(20):
        m.record(s, 0.1)
    assert m.record(20, 2.0) == "warn"   # single spike: warn only
    assert m.record(21, 0.1) == "ok"


# ------------------------------------------------------------- autotuner
def test_autotune_improves_or_matches_model_seed():
    calls = []

    def measure(plan):
        # synthetic landscape with a known optimum at (256, 1024, 512)
        calls.append(plan)
        return (abs(plan.bm - 256) + abs(plan.bk - 1024)
                + abs(plan.bn - 512)) * 1e-6 + 1e-3

    res = autotune.autotune(
        1024, 1024, 1024, measure_fn=measure, hillclimb_rounds=2)
    assert res.seconds <= measure(res.plan) + 1e-12
    assert len(res.history) == len(calls) - 1  # final call re-measured above


def test_autotune_respects_vmem():
    from repro.kernels.matmul import vmem_bytes
    from repro.core.perfmodel import TPU_V5E

    res = autotune.autotune(2048, 2048, 2048, hillclimb_rounds=1)
    assert vmem_bytes(res.plan.bm, res.plan.bk, res.plan.bn, 2, 2) \
        <= TPU_V5E.vmem_bytes


def test_exhaustive_at_least_as_good_as_walk():
    for M, K, N in [(4096, 4096, 4096), (512, 2048, 512), (64, 8192, 1024)]:
        walk = balance.solve_balanced(M, K, N, in_dtype=jnp.bfloat16)
        ex = balance.solve_exhaustive(M, K, N, in_dtype=jnp.bfloat16)
        assert ex.tops >= walk.tops * (1 - 1e-9)


# ------------------------------------------------------------- optimizers
def _quadratic_losses(opt_cfg, steps=60):
    opt = opt_lib.make_optimizer(opt_cfg)
    target = jnp.asarray(np.random.default_rng(0).normal(size=(8, 8)),
                         jnp.float32)
    params = {"w": jnp.zeros((8, 8), jnp.float32)}
    state = opt.init(params)
    losses = []
    for t in range(steps):
        g = {"w": 2 * (params["w"] - target)}
        losses.append(float(jnp.sum((params["w"] - target) ** 2)))
        params, state, _ = opt.update(
            params, g, state, jnp.asarray(t, jnp.int32))
    return losses


@pytest.mark.parametrize("name,b1", [("adamw", 0.9), ("adafactor", 0.0),
                                     ("adafactor", 0.9)])
def test_optimizers_descend(name, b1):
    cfg = opt_lib.OptConfig(name=name, b1=b1, lr=0.05, warmup_steps=5,
                            weight_decay=0.0)
    losses = _quadratic_losses(cfg)
    assert losses[-1] < 0.25 * losses[1]


def test_adafactor_stacked_leaf_matches_unstacked():
    """The lax.map sliced update must equal updating slices independently."""
    cfg = opt_lib.OptConfig(name="adafactor", b1=0.0, lr=0.01,
                            warmup_steps=1, weight_decay=0.0)
    opt = opt_lib.make_optimizer(cfg)
    rng = np.random.default_rng(1)
    p3 = jnp.asarray(rng.normal(size=(3, 8, 16)), jnp.float32)
    g3 = jnp.asarray(rng.normal(size=(3, 8, 16)), jnp.float32)
    st3 = opt.init({"w": p3})
    new3, _, _ = opt.update({"w": p3}, {"w": g3}, st3,
                            jnp.asarray(0, jnp.int32))
    for i in range(3):
        sti = opt.init({"w": p3[i]})
        newi, _, _ = opt.update({"w": p3[i]}, {"w": g3[i]}, sti,
                                jnp.asarray(0, jnp.int32))
        np.testing.assert_allclose(np.asarray(new3["w"][i]),
                                   np.asarray(newi["w"]), rtol=2e-5,
                                   atol=2e-6)


@settings(max_examples=20, deadline=None)
@given(scale=st.floats(0.1, 100.0))
def test_property_grad_clip(scale):
    tree = {"a": jnp.full((4, 4), scale), "b": jnp.full((3,), -scale)}
    clipped, norm = opt_lib.clip_by_global_norm(tree, 1.0)
    new_norm = float(opt_lib.global_norm(clipped))
    assert new_norm <= 1.0 + 1e-3
    assert float(norm) == pytest.approx(
        float(np.sqrt(16 * scale**2 + 3 * scale**2)), rel=1e-3)


# ------------------------------------------- measured plan refinement
def test_refine_cached_plans_keeps_measured_best():
    """ROADMAP satellite: the warm-up's model-solved plans refine in place
    under a measurement callback; a measure that prefers a neighbor moves
    the cache entry there, and refinement never adds signatures."""
    from repro.core.gemm import plan_for
    from repro.core.plancache import PlanCache
    from repro.core.context import use_context
    from repro.kernels.ops import GemmPlan

    cache = PlanCache()
    with use_context(plan_cache=cache):
        with cache.warmup():
            plan_for(256, 512, 512, in_dtype=jnp.bfloat16)
            plan_for(64, 512, 1024, in_dtype=jnp.bfloat16)
        assert len(cache.warm_keys) == 2
        seed_plans = dict(cache.entries)

        target = GemmPlan(bm=8, bk=128, bn=128)

        def factory(M, K, N, **kw):
            # prefer plans closest to `target` — deterministic, instant
            def fn(plan):
                return abs(plan.bm - target.bm) + abs(plan.bk - target.bk) \
                    + abs(plan.bn - target.bn)
            return fn

        stats = autotune.refine_cached_plans(
            cache, measure_factory=factory, rounds=8)
        assert stats["measured"] > 2 and stats["skipped"] == 0
        assert stats["refined"] + stats["kept"] == 2
        assert len(cache.entries) == len(seed_plans)  # no new signatures
        for key, seed in seed_plans.items():
            new = cache.entries[key]

            def d(p):
                return (abs(p.bm - target.bm) + abs(p.bk - target.bk)
                        + abs(p.bn - target.bn))
            assert d(new) <= d(seed)  # measured-best never regresses


def test_refine_cached_plans_wallclock_smoke():
    """The wall-clock measure path runs end-to-end on a tiny signature
    (interpret-mode kernel timing, asked for explicitly off the chip)."""
    from repro.core.gemm import plan_for
    from repro.core.plancache import PlanCache
    from repro.core.context import use_context

    cache = PlanCache()
    with use_context(plan_cache=cache):
        with cache.warmup():
            plan_for(32, 256, 128, in_dtype=jnp.float32)
        stats = autotune.refine_cached_plans(
            cache, repeats=1, backend="interpret")
    assert stats["measured"] >= 1
    assert stats["refined"] + stats["kept"] == 1
