"""GemmContext subsystem: registry, context isolation, plan cache, dispatch."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import balance, hwregistry
from repro.core import gemm as gemm_lib
from repro.core.context import GemmContext, current_context, use_context
from repro.core.gemm import balanced_gemm, plan_for, plan_model
from repro.core.plancache import PLAN_CACHE_VERSION, PlanCache
from repro.kernels import ops, ref
from repro.layers import common as cm


# ------------------------------------------------------------- hw registry
def test_registry_has_three_generations():
    names = hwregistry.list_hw()
    for gen in ("tpu_v4", "tpu_v5e", "tpu_v6e"):
        assert gen in names
        assert hwregistry.get_hw(gen).name == gen
    with pytest.raises(KeyError):
        hwregistry.get_hw("xdna3")


def test_get_hw_passes_spec_through():
    spec = hwregistry.get_hw("tpu_v6e")
    assert hwregistry.get_hw(spec) is spec


def test_env_driven_default(monkeypatch):
    monkeypatch.setenv(hwregistry.DEFAULT_HW_ENV, "tpu_v6e")
    assert hwregistry.default_hw().name == "tpu_v6e"
    monkeypatch.delenv(hwregistry.DEFAULT_HW_ENV)
    assert hwregistry.default_hw().name == "tpu_v5e"


def test_device_kind_resolves_spec_and_unknown_tpu_raises(monkeypatch):
    assert hwregistry.hw_for_device_kind("TPU v5 lite").name == "tpu_v5e"
    assert hwregistry.hw_for_device_kind("TPU v4").name == "tpu_v4"
    assert hwregistry.hw_for_device_kind("TPU v6 lite").name == "tpu_v6e"

    class _Chip:
        device_kind = "TPU v7 unknown"

    # on a TPU the default follows the attached chip, never tpu_v5e
    monkeypatch.delenv(hwregistry.DEFAULT_HW_ENV, raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda *a: [_Chip()])
    with pytest.raises(KeyError, match="TPU v7 unknown"):
        hwregistry.default_hw()
    _Chip.device_kind = "TPU v5 lite"
    assert hwregistry.default_hw().name == "tpu_v5e"


def test_kernel_vmem_limit_covers_solver_budget():
    for name in hwregistry.list_hw():
        hw = hwregistry.get_hw(name)
        assert hw.vmem_limit_bytes > hw.vmem_bytes


# ------------------------------------------------------ context isolation
def test_use_context_nested_isolation():
    base_hw = current_context().hw.name
    base_backend = cm.get_matmul_backend()
    with use_context(hw="tpu_v6e", matmul_backend="interpret"):
        assert current_context().hw.name == "tpu_v6e"
        assert cm.get_matmul_backend() == "interpret"
        cm.set_matmul_backend("pallas")  # mutation scoped to this context
        with use_context(hw="tpu_v4"):
            assert current_context().hw.name == "tpu_v4"
            # non-overridden fields inherit from the enclosing context
            assert cm.get_matmul_backend() == "pallas"
        assert current_context().hw.name == "tpu_v6e"
        assert cm.get_matmul_backend() == "pallas"
    assert current_context().hw.name == base_hw
    assert cm.get_matmul_backend() == base_backend


def test_context_scopes_quant_mode_and_mesh():
    base_quant = cm.get_quant_mode()
    base_mesh = cm.get_activation_mesh()
    with use_context(quant_mode="int8", mesh="not-a-real-mesh"):
        assert cm.get_quant_mode() == "int8"
        assert cm.get_activation_mesh() == "not-a-real-mesh"
        cm.set_quant_mode("none")
        assert cm.get_quant_mode() is None
    assert cm.get_quant_mode() == base_quant
    assert cm.get_activation_mesh() == base_mesh


def test_context_validates_inputs():
    with pytest.raises(ValueError):
        GemmContext(hw="tpu_v5e", matmul_backend="cuda")
    with pytest.raises(ValueError):
        GemmContext(hw="tpu_v5e", quant_mode="int4")
    with pytest.raises(KeyError):
        GemmContext(hw="no-such-chip")


def test_solver_defaults_follow_context_hw():
    with use_context(hw="tpu_v6e"):
        r6 = balance.solve_single_core()
    with use_context(hw="tpu_v5e"):
        r5 = balance.solve_single_core()
    assert r6.vmem <= hwregistry.get_hw("tpu_v6e").vmem_bytes
    assert r6.plan != r5.plan  # 256-wide MXU + 32 MiB budget move the IP


# ------------------------------------------------------- multi-generation
def test_newer_generation_models_faster():
    """v6e must model >= v5e end-to-end TOPS, per precision."""
    for din, dout in [(jnp.bfloat16, jnp.bfloat16), (jnp.int8, jnp.int8)]:
        tops = {
            gen: balance.solve_exhaustive(
                4096, 4096, 4096, hw=gen, in_dtype=din, out_dtype=dout).tops
            for gen in ("tpu_v5e", "tpu_v6e")
        }
        assert tops["tpu_v6e"] >= tops["tpu_v5e"], (din, tops)


def test_generations_pick_distinct_balanced_points():
    plans = {
        gen: balance.solve_exhaustive(
            4096, 4096, 4096, hw=gen, in_dtype=jnp.bfloat16).plan
        for gen in ("tpu_v4", "tpu_v5e", "tpu_v6e")
    }
    assert len(set(plans.values())) >= 2, plans


# ----------------------------------------------------------- plan cache
def test_plan_cache_disk_round_trip(tmp_path):
    path = str(tmp_path / "plans.json")
    cache = PlanCache(path=path)
    with use_context(hw="tpu_v5e", plan_cache=cache):
        p = plan_for(256, 1024, 512, in_dtype=jnp.bfloat16)
        p8 = plan_for(64, 1024, 512, in_dtype=jnp.int8, b_layout="col")
    assert cache.save() == path

    cache2 = PlanCache(path=path)
    assert cache2.load() == 2
    with use_context(hw="tpu_v5e", plan_cache=cache2):
        # solve=False: a pure cache consultation must find both plans
        assert plan_for(256, 1024, 512, in_dtype=jnp.bfloat16,
                        solve=False) == p
        assert plan_for(64, 1024, 512, in_dtype=jnp.int8, b_layout="col",
                        solve=False) == p8
    assert cache2.stats.lazy_solves == 0 and cache2.stats.warm_solves == 0


def test_plan_cache_version_invalidation(tmp_path):
    path = str(tmp_path / "plans.json")
    cache = PlanCache(path=path)
    with use_context(plan_cache=cache):
        plan_for(256, 1024, 512, in_dtype=jnp.bfloat16)
    cache.save()

    payload = json.load(open(path))
    payload["version"] = PLAN_CACHE_VERSION + 1
    json.dump(payload, open(path, "w"))
    assert PlanCache(path=path).load() == 0  # stale version: start fresh

    with open(path, "w") as f:
        f.write("{not json")
    assert PlanCache(path=path).load() == 0  # corrupt file: start fresh


def test_plan_cache_keys_on_generation():
    cache = PlanCache()
    with use_context(plan_cache=cache):
        p5 = plan_for(4096, 4096, 4096, in_dtype=jnp.bfloat16, hw="tpu_v5e")
        p6 = plan_for(4096, 4096, 4096, in_dtype=jnp.bfloat16, hw="tpu_v6e")
    assert p5 != p6
    assert len(cache) == 2


def test_clear_plan_cache_clears_active_context():
    cache = PlanCache()
    with use_context(plan_cache=cache):
        plan_for(256, 1024, 512, in_dtype=jnp.bfloat16)
        assert len(cache) == 1
        gemm_lib.clear_plan_cache()
        assert len(cache) == 0


# ------------------------------------------------------- model warm-up
def test_plan_model_warmup_leaves_no_lazy_solves():
    from repro import configs as C

    cfg = C.smoke(C.get_config("qwen1.5-4b"))
    cache = PlanCache()
    with use_context(plan_cache=cache, hw="tpu_v5e"):
        warm = plan_model(cfg, batch=2, prompt_len=8, max_len=12)
        assert warm["signatures"] > 0
        assert warm["solved"] == warm["signatures"]
        before = cache.stats.snapshot()

        # re-trace the exact serving computations: every plan must hit
        from repro import models
        params = jax.eval_shape(
            lambda: models.init(jax.random.PRNGKey(0), cfg))
        state = jax.eval_shape(
            lambda: models.init_decode_state(cfg, 2, 12))
        jax.eval_shape(
            lambda p, b, s: models.prefill(p, b, cfg, s), params,
            {"tokens": jax.ShapeDtypeStruct((2, 8), jnp.int32)}, state)
        jax.eval_shape(
            lambda p, t, s: models.decode_step(p, t, cfg, s), params,
            jax.ShapeDtypeStruct((2, 1), jnp.int32), state)

        st = cache.stats
        assert st.misses == before.misses, "serving trace missed the cache"
        assert st.lazy_solves == 0
        assert st.hits > before.hits


# ------------------------------------------------------ unified dispatch
def _skinny_cases():
    return [(1, 512, 256), (8, 512, 384), (33, 1024, 256), (128, 512, 128)]


def test_skinny_m_routes_to_decode_matvec(monkeypatch):
    calls = []
    real = ops.decode_matvec

    def spy(*a, **kw):
        calls.append(kw.get("bk"))
        return real(*a, **kw)

    monkeypatch.setattr(ops, "decode_matvec", spy)
    rng = np.random.default_rng(7)
    with use_context(plan_cache=PlanCache()):
        a = jnp.asarray(rng.normal(size=(8, 512)), jnp.float32)
        b = jnp.asarray(rng.normal(size=(512, 256)), jnp.float32)
        out = balanced_gemm(a, b, backend="interpret")
        assert calls, "skinny GEMM did not route to the GEMV kernel"
        assert calls[0] is not None  # planner-provided bk, not the default
        # fat GEMM stays on the tiled kernel
        calls.clear()
        af = jnp.asarray(rng.normal(size=(256, 512)), jnp.float32)
        balanced_gemm(af, b, backend="interpret")
        assert not calls
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.matmul_ref(a, b)), rtol=1e-4,
        atol=1e-4)


@pytest.mark.parametrize("M,K,N", _skinny_cases())
@pytest.mark.parametrize("in_dtype", [jnp.float32, jnp.int8])
def test_skinny_dispatch_matches_reference(M, K, N, in_dtype):
    rng = np.random.default_rng(M * 7 + N)
    if jnp.issubdtype(in_dtype, jnp.integer):
        a = jnp.asarray(rng.integers(-100, 100, size=(M, K)), in_dtype)
        b = jnp.asarray(rng.integers(-100, 100, size=(K, N)), in_dtype)
        out_dtype = jnp.int32
        tol = dict(rtol=0, atol=0)
    else:
        a = jnp.asarray(rng.normal(size=(M, K)), in_dtype)
        b = jnp.asarray(rng.normal(size=(K, N)), in_dtype)
        out_dtype = in_dtype
        tol = dict(rtol=1e-4, atol=1e-4)
    with use_context(plan_cache=PlanCache()):
        got = balanced_gemm(a, b, out_dtype=out_dtype, backend="interpret")
    want = ref.matmul_ref(a, b, out_dtype=out_dtype)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float64), np.asarray(want, np.float64), **tol)


def test_skinny_dispatch_col_major():
    rng = np.random.default_rng(3)
    a = jnp.asarray(rng.normal(size=(4, 512)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(384, 512)), jnp.float32)  # (N, K)
    with use_context(plan_cache=PlanCache()):
        got = balanced_gemm(a, b, b_layout="col", backend="interpret")
    want = ref.matmul_ref(a, b, b_layout="col")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_fused_epilogue_stays_on_tiled_kernel(monkeypatch):
    """bias/activation/out_scale are epilogue features of the tiled kernel;
    skinny calls carrying them must not be routed to the GEMV kernel."""
    called = []
    monkeypatch.setattr(
        ops, "decode_matvec",
        lambda *a, **kw: called.append(1) or (_ for _ in ()).throw(
            AssertionError("routed to gemv")))
    rng = np.random.default_rng(5)
    a = jnp.asarray(rng.normal(size=(8, 512)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(512, 256)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(256,)), jnp.float32)
    with use_context(plan_cache=PlanCache()):
        got = balanced_gemm(a, b, bias, activation="relu",
                            backend="interpret")
    want = ref.matmul_ref(a, b, bias=bias, activation="relu")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    assert not called
