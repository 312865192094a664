"""Balance auditor: ledger mechanics, snapshot persistence, engine
attribution (reconciliation + drift detection + re-solve restoration),
and the SLO burn-rate monitor."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as C
from repro import models
from repro.core import gemm
from repro.core.autotune import model_measure_fn, refine_cached_plans
from repro.core.context import use_context
from repro.core.plancache import BalanceSnapshot, PlanCache, _key_str
from repro.kernels.ops import GemmPlan
from repro.launch.mesh import make_local_mesh
from repro.obs import AttributionLedger, GEMM_PHASES, Tracer
from repro.serve import Request, ServeEngine, SimClock


# ---------------------------------------------------------------- snapshot
def test_balance_snapshot_roundtrips_through_cache_json(tmp_path):
    path = tmp_path / "plans.json"
    cache = PlanCache(path=str(path))
    key = ("tpu_v6e", 8, 64, 128, "float32", "float32", "row")
    cache.put(key, GemmPlan(bm=8, bk=128, bn=128),
              balance=BalanceSnapshot(t_comp=1e-7, t_mem=2e-7))
    bare = ("tpu_v6e", 4, 64, 64, "float32", "float32", "row")
    cache.put(bare, GemmPlan(bm=8, bk=128, bn=128))   # snapshot-less
    cache.save()
    fresh = PlanCache(path=str(path))
    fresh.load()
    snap = fresh.balance[key]
    assert snap.t_comp == 1e-7 and snap.t_mem == 2e-7
    assert snap.t_total == 2e-7 and snap.ratio == pytest.approx(0.5)
    assert bare in fresh.entries and bare not in fresh.balance
    # pre-v2 records (no t_comp/t_mem) still load, just without snapshots
    obj = json.loads(path.read_text())
    for rec in obj["plans"].values():
        rec.pop("t_comp", None)
        rec.pop("t_mem", None)
    path.write_text(json.dumps(obj))
    legacy = PlanCache(path=str(path))
    legacy.load()
    assert key in legacy.entries and legacy.balance == {}


def test_cache_update_replaces_plan_without_touching_counters():
    cache = PlanCache()
    key = ("tpu_v6e", 8, 64, 128, "float32", "float32", "row")
    cache.put(key, GemmPlan(bm=8, bk=128, bn=128),
              balance=BalanceSnapshot(t_comp=1.0, t_mem=1.0))
    before = cache.stats.snapshot()
    cache.update(key, GemmPlan(bm=16, bk=128, bn=128),
                 balance=BalanceSnapshot(t_comp=2.0, t_mem=1.0))
    assert cache.entries[key].bm == 16
    assert cache.balance[key].t_comp == 2.0
    cache.update(key, GemmPlan(bm=8, bk=128, bn=128), balance=None)
    assert key not in cache.balance
    after = cache.stats.snapshot()
    assert (after.hits, after.misses, after.lazy_solves) == (
        before.hits, before.misses, before.lazy_solves)


# ------------------------------------------------------------------ ledger
def test_ledger_capture_records_plan_for_consultations():
    led = AttributionLedger()
    with use_context(plan_cache=PlanCache(), hw="tpu_v6e"):
        with led.capture("decode"):
            gemm.plan_for(8, 64, 128, in_dtype=jnp.float32)
            gemm.plan_for(8, 64, 128, in_dtype=jnp.float32)
            gemm.plan_for(4, 64, 64, in_dtype=jnp.float32)
    assert gemm._dispatch_listeners == []       # capture detaches cleanly
    prof = led.profiles["decode"]
    assert sum(prof.values()) == 3 and len(prof) == 2
    led.dispatch("decode")
    led.dispatch("decode", 4)
    assert led.dispatches["decode"] == 5
    led.reset_run()
    assert led.dispatches == {} and led.profiles  # profiles survive resets


def test_ledger_attribution_reconciles_and_classifies():
    """Synthetic join: two phases, two signatures — attributed seconds must
    sum exactly to the traced phase totals and split by modeled weight."""
    cache = PlanCache()
    k1 = ("tpu_v6e", 8, 64, 128, "float32", "float32", "row")
    k2 = ("tpu_v6e", 8, 64, 512, "float32", "float32", "row")
    with use_context(plan_cache=cache, hw="tpu_v6e"):
        for (_, m, k, n, *_r) in (k1, k2):
            gemm.plan_for(m, k, n, in_dtype=jnp.float32)
    assert set(cache.entries) == {k1, k2}
    assert set(cache.balance) == {k1, k2}       # solves store snapshots
    led = AttributionLedger(tol=0.25)
    led.profiles = {"decode": {k1: 2, k2: 1}, "prefill-chunk@8": {k2: 3}}
    led.dispatches = {"decode": 10, "prefill-chunk@8": 4}
    # tracer phases are bare names; the @8 capture tag folds under
    # "prefill-chunk". Host phases (sample) are never a basis.
    durs = {"decode": [0.25, 0.75], "prefill-chunk": [2.0],
            "sample": [9.0]}
    s = led.summarize(durs, cache=cache)
    assert s["traced_device_s"] == pytest.approx(3.0)
    assert s["attributed_device_s"] == pytest.approx(3.0)
    assert s["reconciliation_error"] == pytest.approx(0.0)
    assert s["signatures"] == 2 and s["drifted_count"] == 0
    rows = {r["key"]: r for r in s["by_device_s"]}
    assert set(rows) == {_key_str(k1), _key_str(k2)}
    # calls = dispatches x per-execution profile count
    assert rows[_key_str(k1)]["calls"] == 20
    assert rows[_key_str(k2)]["calls"] == 10 + 12
    assert sum(r["device_s"] for r in rows.values()) == pytest.approx(3.0)
    assert sum(r["share"] for r in rows.values()) == pytest.approx(1.0)
    for r in rows.values():
        assert r["bound"] in ("compute", "memory") and not r["drifted"]
        assert r["suggested_bm"] is None        # no drift, no solver work
    assert sum(s["bound_s"].values()) == pytest.approx(3.0)
    # an unattributable phase (no profile) surfaces as reconciliation error
    durs["spec-draft"] = [1.0]
    s2 = led.summarize(durs, cache=cache)
    assert s2["unattributed_device_s"] == pytest.approx(1.0)
    assert s2["reconciliation_error"] == pytest.approx(0.25)
    cs = led.class_seconds(durs, cache=cache)
    assert set(cs) == {"compute", "memory", "drifted"}
    assert sum(cs.values()) == pytest.approx(3.0)


def test_ledger_flags_perturbed_plan_as_drifted():
    cache = PlanCache()
    key = ("tpu_v6e", 8, 64, 512, "float32", "float32", "row")
    with use_context(plan_cache=cache, hw="tpu_v6e"):
        plan = gemm.plan_for(8, 64, 512, in_dtype=jnp.float32)
    led = AttributionLedger(tol=0.25)
    led.profiles = {"decode": {key: 1}}
    led.dispatches = {"decode": 1}
    durs = {"decode": [1.0]}
    assert led.summarize(durs, cache=cache)["drifted_count"] == 0
    # double bk behind the auditor's back; the snapshot stays stale
    cache.entries[key] = GemmPlan(bm=plan.bm, bk=plan.bk * 2, bn=plan.bn)
    s = led.summarize(durs, cache=cache)
    assert s["drifted"] == [_key_str(key)]
    assert led.drifted_keys() == [key]
    row = s["by_device_s"][0]
    assert row["drifted"] and row["time_deviation"] > 0.25
    # the suggestion is the solver's (original) plan, with modeled gain
    assert (row["suggested_bm"], row["suggested_bk"], row["suggested_bn"]) \
        == (plan.bm, plan.bk, plan.bn)
    assert row["suggested_gain"] > 1.0
    assert led.class_seconds(durs, cache=cache)["drifted"] == \
        pytest.approx(1.0)


def test_gemm_phase_set_matches_tracer_device_phases():
    # every phase the auditor apportions is a span the tracer names
    from repro.obs import PHASES
    for p in GEMM_PHASES:
        assert p in PHASES


# ------------------------------------------------------ engine integration
@pytest.fixture(scope="module")
def dense_setup():
    cfg = C.smoke(C.get_config("qwen1.5-4b"))
    mesh = make_local_mesh()
    params = models.init(jax.random.PRNGKey(3), cfg)
    return cfg, mesh, params


def _reqs(spec, seed=7, **kw):
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(0, 503, size=p, dtype=np.int32),
                    max_new_tokens=g, **kw)
            for p, g in spec]


def _make_engine(cfg, mesh, params, tracer):
    return ServeEngine(cfg, mesh, params, num_slots=2, max_len=24,
                       prompt_pad=8, kv_block_size=4, num_kv_blocks=17,
                       prefill_chunk=4, clock=SimClock(1e-3), tracer=tracer,
                       metrics_interval_ticks=4)


def test_traced_engine_attribution_reconciles(dense_setup):
    cfg, mesh, params = dense_setup
    cache = PlanCache()
    with use_context(plan_cache=cache):
        tr = Tracer()
        engine = _make_engine(cfg, mesh, params, tr)
        engine.plan_warmup()
        m = engine.run(_reqs([(8, 4), (4, 6), (6, 2), (5, 5)]))
        assert m.plan_cache["steady_state"] is True     # zero lazy solves
        a = m.to_dict()["attribution"]
        assert a["signatures"] > 0 and a["drifted_count"] == 0
        # the join apportions *all* traced GEMM-phase device seconds
        assert a["reconciliation_error"] <= 0.05
        traced = sum(sum(d) for p, d in tr.phase_durations().items()
                     if p in GEMM_PHASES)
        assert a["traced_device_s"] == pytest.approx(traced)
        assert sum(a["bound_s"].values()) == \
            pytest.approx(a["attributed_device_s"])
        shares = [v for v in a["bound_share"].values() if v is not None]
        assert sum(shares) == pytest.approx(1.0)
        rows = a["by_device_s"]
        assert rows == sorted(rows, key=lambda r: (-r["device_s"], r["key"]))
        assert all(r["calls"] > 0 for r in rows)
        # registry gauges + ratio histogram published alongside
        flat = engine.registry.collect()
        assert flat["repro_attrib_signatures"] == a["signatures"]
        assert flat["repro_attrib_drifted"] == 0.0
        assert flat["repro_attrib_measured_vs_modeled"]["count"] > 0
        # counter tracks sampled at the metrics interval
        cs = [e for e in tr.to_chrome()["traceEvents"] if e["ph"] == "C"]
        assert {e["name"] for e in cs} >= {"engine_progress",
                                           "attrib_device_s", "block_pool"}


def test_untraced_engine_exports_no_attribution(dense_setup):
    cfg, mesh, params = dense_setup
    with use_context(plan_cache=PlanCache()):
        engine = _make_engine(cfg, mesh, params, None)
        engine.plan_warmup()
        d = engine.run(_reqs([(8, 4), (4, 2)])).to_dict()
        assert "attribution" not in d
        assert "slo_burn" in d          # the burn monitor is always on


def test_perturbed_plan_is_flagged_and_rebalance_restores_it(dense_setup):
    """The acceptance loop: perturb one cached plan after warm-up, run →
    the auditor flags exactly that signature; refine_cached_plans with
    resolve=True restores the balanced plan + snapshot; a rerun is clean.
    All under SimClock with zero lazy solves."""
    cfg, mesh, params = dense_setup
    cache = PlanCache()
    with use_context(plan_cache=cache):
        tr = Tracer()
        engine = _make_engine(cfg, mesh, params, tr)
        engine.plan_warmup()
        key = max(cache.entries, key=lambda k: (k[1], k[3]))  # biggest M,N
        original = cache.entries[key]
        # doubled bk pads K up in the model: clearly off-balance vs the
        # stored snapshot, and strictly slower than the solver's choice
        cache.entries[key] = GemmPlan(
            bm=original.bm, bk=original.bk * 2, bn=original.bn)
        m = engine.run(_reqs([(8, 4), (4, 6), (6, 2), (5, 5)]))
        assert m.plan_cache["steady_state"] is True
        a = m.attribution
        assert a["drifted"] == [_key_str(key)]
        assert engine.attrib.drifted_keys() == [key]
        assert engine.registry.collect()["repro_attrib_drifted"] == 1.0
        assert a["bound_s"]["drifted"] > 0

        stats = refine_cached_plans(
            cache, keys=engine.attrib.drifted_keys(), resolve=True,
            measure_factory=lambda M, K, N, **kw: model_measure_fn(
                M, K, N, hw=key[0], **kw))
        assert stats["refined"] == 1
        assert cache.entries[key] == original   # balanced plan restored
        snap = cache.balance[key]
        assert snap.t_total > 0                 # snapshot refreshed too

        tr2 = Tracer()
        engine2 = _make_engine(cfg, mesh, params, tr2)
        engine2.plan_warmup()
        warm = cache.stats.snapshot()
        m2 = engine2.run(_reqs([(8, 4), (4, 6), (6, 2), (5, 5)]))
        assert m2.plan_cache["steady_state"] is True
        assert m2.attribution["drifted_count"] == 0
        assert cache.stats.lazy_solves == warm.lazy_solves


# ---------------------------------------------------------------- slo burn
def test_slo_burn_summary_windows_and_alerts():
    from repro.serve.metrics import EngineMetrics
    m = EngineMetrics()
    # 6 fast then 4 slow requests in one class, plus a clean class
    for i in range(6):
        m.requests.append({"priority": 2, "queue_s": 0.0, "ttft_s": 0.01,
                           "finish_reason": "stop", "preemptions": 0})
    for i in range(4):
        m.requests.append({"priority": 2, "queue_s": 0.1, "ttft_s": 0.2,
                           "finish_reason": "stop", "preemptions": 0})
    m.requests.append({"priority": 0, "queue_s": None, "ttft_s": None,
                       "finish_reason": "deadline_missed", "preemptions": 0})
    s = m.slo_burn_summary(0.05, window=8, budget_miss_rate=0.1)
    hi = s["classes"]["2"]
    # window of 8 = last 2 fast + 4 slow -> 4/8 misses, burn 5x
    assert (hi["n"], hi["window_n"], hi["misses_in_window"]) == (10, 8, 4)
    assert hi["rolling_miss_rate"] == pytest.approx(0.5)
    assert hi["burn_rate"] == pytest.approx(5.0) and hi["alert"]
    lo = s["classes"]["0"]
    assert lo["misses_in_window"] == 1 and lo["alert"]  # hard miss counts
    # no target: only deadline_missed requests burn budget
    s2 = m.slo_burn_summary(None, window=8)
    assert s2["classes"]["2"]["misses_in_window"] == 0
    assert not s2["classes"]["2"]["alert"]
    assert s2["classes"]["0"]["misses_in_window"] == 1
