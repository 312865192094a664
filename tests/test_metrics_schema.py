"""Golden schema for ``EngineMetrics.to_dict()`` (docs/observability.md).

One recursive walker replaces the per-section key-enumeration spot
checks: every leaf path in the exported JSON must match a pattern below
with the right type, and every pattern must be exercised by at least one
of the four representative runs (contiguous, paged+prefix, speculative,
traced SLO).  Adding/removing/retyping a metrics key fails here first —
schema drift is a reviewed change, not an accident.
"""
import json

import numpy as np
import pytest
import jax

from repro import configs as C
from repro import models
from repro.launch.mesh import make_local_mesh
from repro.obs import Tracer
from repro.serve import Request, ServeEngine, SimClock, bursty_trace

NONE = type(None)
INT = (int,)
NUM = (int, float)
OPT_INT = (int, NONE)
OPT_NUM = (int, float, NONE)
BOOL = (bool,)
STR = (str,)
OPT_STR = (str, NONE)
LIST = (list,)

# path pattern -> allowed leaf types.  "*" matches exactly one segment
# (a dynamic key: request index, priority class, eviction reason, phase
# name).  Lists of scalars are leaves of type list; lists of dicts
# recurse with "*" for the index.
GOLDEN = {
    # ---------------------------------------------------------- engine
    "engine.arch": STR,
    "engine.num_slots": INT,
    "engine.max_len": INT,
    "engine.prompt_pad": INT,
    "engine.hw": STR,
    "engine.backend": STR,
    "engine.quant": OPT_STR,
    "engine.paged": BOOL,
    "engine.temperature": NUM,
    "engine.top_p": NUM,
    "engine.sched_policy": STR,
    "engine.ttft_target_ms": OPT_NUM,
    "engine.spec": BOOL,
    # paged engines only
    "engine.kv_block_size": INT,
    "engine.num_kv_blocks": INT,
    "engine.kv_dtype": STR,
    "engine.prefill_chunk": OPT_INT,
    "engine.chunk_buckets": LIST,
    "engine.prefix_cache": BOOL,
    "engine.prefix_cache_blocks": OPT_INT,
    # speculative engines only
    "engine.spec_k": INT,
    "engine.spec_draft_arch": STR,
    "engine.spec_draft_quant": OPT_STR,
    # ------------------------------------------------------- aggregate
    "aggregate.wall_s": NUM,
    "aggregate.ticks": INT,
    "aggregate.generated_tokens": INT,
    "aggregate.tokens_per_sec": OPT_NUM,
    "aggregate.tokens_per_tick": OPT_NUM,
    "aggregate.mean_occupancy": OPT_NUM,
    "aggregate.admissions": INT,
    "aggregate.deferred_admissions": INT,
    "aggregate.evictions.finished.*": INT,
    "aggregate.evictions.preempted": INT,
    "aggregate.evictions.deadline_missed": INT,
    "aggregate.preemptions": INT,
    "aggregate.resumes": INT,
    "aggregate.deadline_missed": INT,
    "aggregate.policy": STR,
    "aggregate.queue_peak": INT,
    "aggregate.decode_attn_kernel_steps": INT,
    "aggregate.decode_attn_fallback_steps": INT,
    "aggregate.decode_attn_blocks_read": INT,
    "aggregate.decode_attn_blocks_table": INT,
    # -------------------------------------------------------- requests
    "requests.*.request_id": INT,
    "requests.*.priority": INT,
    "requests.*.deadline_s": OPT_NUM,
    "requests.*.prompt_len": INT,
    "requests.*.cached_tokens": INT,
    "requests.*.tokens": INT,
    "requests.*.queue_s": OPT_NUM,
    "requests.*.ttft_s": OPT_NUM,
    "requests.*.ttft_ticks": OPT_INT,
    "requests.*.total_s": OPT_NUM,
    "requests.*.per_token_s": OPT_NUM,
    "requests.*.preemptions": INT,
    "requests.*.finish_reason": STR,
    "requests.*.arrival_tick": INT,
    "requests.*.admitted_tick": INT,
    "requests.*.finished_tick": OPT_INT,
    # -------------------------------------------------------- slo burn
    "slo_burn.target_ttft_s": OPT_NUM,
    "slo_burn.window": INT,
    "slo_burn.budget_miss_rate": NUM,
    "slo_burn.classes.*.n": INT,
    "slo_burn.classes.*.window_n": INT,
    "slo_burn.classes.*.misses_in_window": INT,
    "slo_burn.classes.*.rolling_miss_rate": OPT_NUM,
    "slo_burn.classes.*.burn_rate": OPT_NUM,
    "slo_burn.classes.*.alert": BOOL,
    # ------------------------------------------------------------- slo
    "slo.*.n": INT,
    "slo.*.finished": INT,
    "slo.*.deadline_missed": INT,
    "slo.*.miss_rate": NUM,
    "slo.*.preemptions": INT,
    "slo.*.p50_ttft_s": OPT_NUM,
    "slo.*.p99_ttft_s": OPT_NUM,
    "slo.*.p50_ttft_ticks": OPT_NUM,
    "slo.*.p99_ttft_ticks": OPT_NUM,
    # ---------------------------------------------------------- budget
    "budget.target_ttft_s": OPT_NUM,
    "budget.ema_ttft_s": OPT_NUM,
    "budget.observations": INT,
    "budget.raises": INT,
    "budget.drops": INT,
    "budget.min_chunks": INT,
    "budget.max_chunks": INT,
    "budget.final_chunks": INT,
    # ------------------------------------------------------ block pool
    "block_pool.num_blocks": INT,
    "block_pool.block_size": INT,
    "block_pool.blocks_in_use": INT,
    "block_pool.free_blocks": INT,
    "block_pool.cached_idle_blocks": INT,
    "block_pool.peak_in_use": INT,
    "block_pool.peak_utilization": NUM,
    "block_pool.allocs": INT,
    "block_pool.frees": INT,
    "block_pool.failed_allocs": INT,
    "block_pool.increfs": INT,
    "block_pool.reclaimed_blocks": INT,
    "block_pool.peak_fragmentation_tokens": INT,
    "block_pool.pool_tokens": INT,
    "block_pool.contiguous_tokens": INT,
    "block_pool.memory_ratio": NUM,
    # byte accounting (pools constructed with bytes_per_block — all
    # engine-owned pools; bare unit-test pools omit these keys)
    "block_pool.bytes_per_block": INT,
    "block_pool.pool_bytes": INT,
    "block_pool.bytes_in_use": INT,
    "block_pool.peak_bytes_in_use": INT,
    # -------------------------------------------------------- kv cache
    "kv_cache.kv_dtype": STR,
    "kv_cache.quantized": BOOL,
    "kv_cache.bytes_per_block": INT,
    "kv_cache.pool_bytes": INT,
    "kv_cache.bf16_pool_bytes": INT,
    "kv_cache.bytes_ratio": NUM,
    # dequant-error gauges (quantized pools only): worst-case block
    # quantization error is scale/2
    "kv_cache.scale_k_mean": NUM,
    "kv_cache.scale_k_max": NUM,
    "kv_cache.scale_v_mean": NUM,
    "kv_cache.scale_v_max": NUM,
    # ---------------------------------------------------- prefix cache
    "prefix_cache.lookups": INT,
    "prefix_cache.lookup_tokens": INT,
    "prefix_cache.hits": INT,
    "prefix_cache.hit_tokens": INT,
    "prefix_cache.hit_rate": NUM,
    "prefix_cache.inserted_blocks": INT,
    "prefix_cache.duplicate_blocks": INT,
    "prefix_cache.cached_blocks": INT,
    "prefix_cache.cached_idle_blocks": INT,
    "prefix_cache.reclaimed_blocks": INT,
    "prefix_cache.trimmed_blocks": INT,
    "prefix_cache.max_cached_blocks": OPT_INT,
    # ----------------------------------------------------- speculation
    "speculation.enabled": BOOL,
    "speculation.spec_k": INT,
    "speculation.rounds": INT,
    "speculation.proposed_tokens": INT,
    "speculation.accepted_tokens": INT,
    "speculation.bonus_tokens": INT,
    "speculation.committed_tokens": INT,
    "speculation.acceptance_rate": NUM,
    "speculation.mean_accepted_len": NUM,
    "speculation.mean_committed_per_round": NUM,
    "speculation.draft_s": NUM,
    "speculation.verify_s": NUM,
    "speculation.draft_arch": OPT_STR,
    "speculation.draft_quant": OPT_STR,
    # ------------------------------------------------------ plan cache
    "plan_cache.hits": INT,
    "plan_cache.misses": INT,
    "plan_cache.lazy_solves": INT,
    "plan_cache.warm_solves": INT,
    "plan_cache.steady_state": BOOL,
    # ------------------------------------------- timing (traced runs)
    "timing.phases.*.count": INT,
    "timing.phases.*.total_s": NUM,
    "timing.phases.*.mean_s": NUM,
    "timing.phases.*.p50_s": NUM,
    "timing.phases.*.p99_s": NUM,
    "timing.events_recorded": INT,
    "timing.events_dropped": INT,
    # -------------------------------------- attribution (traced runs)
    "attribution.tol": NUM,
    "attribution.top_k": INT,
    "attribution.signatures": INT,
    "attribution.attributed_device_s": NUM,
    "attribution.traced_device_s": NUM,
    "attribution.unattributed_device_s": NUM,
    "attribution.reconciliation_error": OPT_NUM,
    "attribution.bound_s.*": NUM,
    "attribution.bound_share.*": OPT_NUM,
    "attribution.drifted_count": INT,
    "attribution.drifted": LIST,
    "attribution.by_device_s.*.key": STR,
    "attribution.by_device_s.*.hw": STR,
    "attribution.by_device_s.*.m": INT,
    "attribution.by_device_s.*.k": INT,
    "attribution.by_device_s.*.n": INT,
    "attribution.by_device_s.*.in_dtype": STR,
    "attribution.by_device_s.*.out_dtype": STR,
    "attribution.by_device_s.*.layout": STR,
    "attribution.by_device_s.*.bm": INT,
    "attribution.by_device_s.*.bk": INT,
    "attribution.by_device_s.*.bn": INT,
    "attribution.by_device_s.*.calls": INT,
    "attribution.by_device_s.*.device_s": NUM,
    "attribution.by_device_s.*.share": OPT_NUM,
    "attribution.by_device_s.*.t_comp_s": NUM,
    "attribution.by_device_s.*.t_mem_s": NUM,
    "attribution.by_device_s.*.t_total_s": NUM,
    "attribution.by_device_s.*.balance_ratio": OPT_NUM,
    "attribution.by_device_s.*.snapshot_ratio": OPT_NUM,
    "attribution.by_device_s.*.snapshot_t_total_s": OPT_NUM,
    "attribution.by_device_s.*.ratio_deviation": OPT_NUM,
    "attribution.by_device_s.*.time_deviation": OPT_NUM,
    "attribution.by_device_s.*.bound": STR,
    "attribution.by_device_s.*.drifted": BOOL,
    "attribution.by_device_s.*.measured_per_call_s": OPT_NUM,
    "attribution.by_device_s.*.measured_vs_modeled": OPT_NUM,
    "attribution.by_device_s.*.suggested_bm": OPT_INT,
    "attribution.by_device_s.*.suggested_bk": OPT_INT,
    "attribution.by_device_s.*.suggested_bn": OPT_INT,
    "attribution.by_device_s.*.suggested_gain": OPT_NUM,
}

TOP_LEVEL = {"engine", "aggregate", "requests", "slo", "slo_burn",
             "budget", "block_pool", "kv_cache", "prefix_cache",
             "speculation", "plan_cache"}


def walk(node, prefix=""):
    """Yield (path, leaf) pairs; list-of-dict indices become '*'."""
    if isinstance(node, dict):
        for k, v in node.items():
            yield from walk(v, f"{prefix}.{k}" if prefix else str(k))
    elif isinstance(node, list) and node and isinstance(node[0], dict):
        for item in node:
            yield from walk(item, f"{prefix}.*")
    else:
        yield prefix, node


def match(path):
    """The golden pattern for ``path``, or None."""
    segs = path.split(".")
    for pattern in GOLDEN:
        ps = pattern.split(".")
        if len(ps) == len(segs) and all(
                p == "*" or p == s for p, s in zip(ps, segs)):
            return pattern
    return None


def check(d):
    """Assert every leaf matches the golden schema; return patterns hit."""
    seen = set()
    for path, value in walk(d):
        pattern = match(path)
        assert pattern is not None, f"unknown metrics key: {path}"
        allowed = GOLDEN[pattern]
        assert type(value) in allowed, (
            f"{path}: {type(value).__name__} not in "
            f"{[t.__name__ for t in allowed]} (value {value!r})")
        seen.add(pattern)
    return seen


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


def _export(engine, reqs):
    engine.plan_warmup()
    m = engine.run(reqs)
    d = json.loads(m.to_json())   # through JSON: pure python leaf types
    assert set(d) - {"timing", "attribution"} == TOP_LEVEL
    return d


def test_metrics_schema_golden(dense_setup):
    cfg, mesh, params = dense_setup
    seen = set()

    # 1. contiguous FIFO — the baseline sections, empty paged dicts
    d = _export(
        ServeEngine(cfg, mesh, params, num_slots=2, max_len=16,
                    prompt_pad=8),
        _reqs([(8, 4), (4, 2), (6, 3)]))
    assert d["block_pool"] == {} and d["prefix_cache"] == {}
    assert d["kv_cache"] == {}
    assert d["speculation"] == {"enabled": False}
    assert "timing" not in d
    seen |= check(d)

    # 2. paged + prefix cache + budget target + quantized KV pool
    d = _export(
        ServeEngine(cfg, mesh, params, num_slots=2, max_len=16,
                    prompt_pad=8, kv_block_size=4, num_kv_blocks=33,
                    prefix_cache=True, prefix_cache_blocks=8,
                    prefill_chunk=4, ttft_target_ms=50.0,
                    kv_quantize="int8"),
        _reqs([(8, 4), (4, 2), (6, 3)]))
    assert d["engine"]["prefix_cache"] is True
    assert d["engine"]["kv_dtype"] == "int8"
    assert d["kv_cache"]["quantized"] is True
    assert d["kv_cache"]["bytes_ratio"] < 1.0
    seen |= check(d)

    # 3. speculative decoding
    d = _export(
        ServeEngine(cfg, mesh, params, num_slots=2, max_len=24,
                    prompt_pad=8, kv_block_size=8, spec_draft_cfg=cfg,
                    spec_draft_params=params, spec_k=2,
                    spec_draft_quant=None),
        _reqs([(8, 4), (4, 6), (6, 3)]))
    assert d["speculation"]["enabled"] is True
    seen |= check(d)

    # 4. traced SLO run: bursty EDF under SimClock, deadline + timing
    d = _export(
        ServeEngine(cfg, mesh, params, num_slots=2, max_len=24,
                    prompt_pad=8, kv_block_size=4, num_kv_blocks=17,
                    prefill_chunk=4, sched_policy="edf",
                    clock=SimClock(1e-3), tracer=Tracer()),
        bursty_trace(8, vocab_size=503, burst_size=4, burst_gap_s=0.02,
                     classes=[
                         dict(priority=2, prompt_lens=(6,),
                              max_new_tokens=(4,), deadline_slack_s=30.0,
                              weight=1.0),
                         dict(priority=0, prompt_lens=(8,),
                              max_new_tokens=(8,), deadline_slack_s=None,
                              weight=1.0)],
                     seed=0))
    assert "timing" in d and d["timing"]["phases"]
    assert "attribution" in d and d["attribution"]["by_device_s"]
    assert d["attribution"]["drifted"] == []   # clean cache, no drift
    seen |= check(d)

    unexercised = set(GOLDEN) - seen
    assert not unexercised, (
        f"golden schema entries never produced by any run: "
        f"{sorted(unexercised)}")
