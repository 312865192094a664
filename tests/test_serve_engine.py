"""Continuous-batching engine: scheduler policy, engine/static parity,
plan-cache steady state, EOS handling, and metrics export."""
import json

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro import configs as C
from repro import models
from repro.core.context import current_context, use_context
from repro.core.plancache import PlanCache, PlanCacheColdError
from repro.launch.mesh import make_local_mesh
from repro.launch.serve import serve_batch
from repro.serve import Request, ServeEngine, SlotScheduler, synthetic_trace

EOS = 17


def _requests(spec, vocab=503, stop=(EOS,), seed=7):
    rng = np.random.default_rng(seed)
    return [
        Request(prompt=rng.integers(0, vocab, size=p, dtype=np.int32),
                max_new_tokens=g, stop_ids=stop)
        for p, g in spec
    ]


# ------------------------------------------------------------- scheduler
def test_scheduler_admission_is_fifo():
    s = SlotScheduler(2, max_len=32)
    reqs = _requests([(4, 4), (4, 4), (4, 4)])
    for r in reqs:
        s.submit(r)
    a, b = s.admit_next(), s.admit_next()
    assert (a.request.request_id, b.request.request_id) == (
        reqs[0].request_id, reqs[1].request_id)
    assert s.admit_next() is None            # both lanes occupied
    assert [a.slot, b.slot] == [0, 1]
    s.evict(0, "length")
    c = s.admit_next()
    assert c.request.request_id == reqs[2].request_id


def test_scheduler_reuses_evicted_slots():
    s = SlotScheduler(2, max_len=32)
    for r in _requests([(4, 4)] * 5):
        s.submit(r)
    first = s.admit_next()
    s.admit_next()
    s.evict(first.slot, "stop")
    again = s.admit_next()
    assert again.slot == first.slot          # lowest freed lane is reused
    assert s.occupancy() == 2 and s.pending == 2
    assert s.counters()["evictions"] == {
        "finished": {"stop": 1}, "preempted": 0, "deadline_missed": 0}


def test_scheduler_rejects_oversized_prompt():
    s = SlotScheduler(1, max_len=8)
    with pytest.raises(ValueError):
        s.submit(_requests([(8, 1)])[0])     # no decode headroom


# ------------------------------------------------------- engine vs static
@pytest.fixture(scope="module")
def dense_setup():
    cfg = C.smoke(C.get_config("qwen1.5-4b"))
    mesh = make_local_mesh()
    params = models.init(jax.random.PRNGKey(3), cfg)
    return cfg, mesh, params


def test_engine_matches_isolated_static_decode(dense_setup):
    """Greedy determinism: a mixed-length trace through the slot engine
    produces exactly the tokens each request gets when served alone through
    static serve_batch (padded prefill + per-slot decode are bit-exact)."""
    cfg, mesh, params = dense_setup
    spec = [(12, 8), (5, 8), (9, 3), (12, 6), (3, 8), (7, 8), (6, 1)]
    engine = ServeEngine(cfg, mesh, params, num_slots=3, max_len=21,
                         prompt_pad=12)
    engine.plan_warmup()
    engine.run(_requests(spec))
    assert len(engine.finished) == len(spec)
    by_prompt = {st.request.prompt.tobytes(): st.tokens
                 for st in engine.finished}

    for r in _requests(spec):
        alone = np.asarray(serve_batch(
            cfg, mesh, params, jnp.asarray(r.prompt[None]),
            gen_len=r.max_new_tokens,
            max_len=r.prompt_len + r.max_new_tokens + 1,
            eos_id=EOS)[0])
        want = alone.tolist()
        if EOS in want:
            want = want[: want.index(EOS) + 1]
        assert by_prompt[r.prompt.tobytes()] == want


def test_engine_steady_state_zero_lazy_solves(dense_setup):
    """After plan_warmup the serving loop must not touch the solver: zero
    lazy solves and zero misses, tracked per-run in the metrics export."""
    cfg, mesh, params = dense_setup
    with use_context(plan_cache=PlanCache()):
        cache = current_context().plan_cache
        engine = ServeEngine(cfg, mesh, params, num_slots=2, max_len=16,
                             prompt_pad=8)
        warm = engine.plan_warmup()
        assert warm["signatures"] > 0 and warm["solved"] > 0
        before = cache.stats.snapshot()
        m = engine.run(_requests([(8, 4), (4, 6), (6, 2), (5, 5)]))
        assert cache.stats.lazy_solves == before.lazy_solves
        assert cache.stats.misses == before.misses
        assert m.plan_cache["lazy_solves"] == 0
        assert m.plan_cache["misses"] == 0
        assert m.plan_cache["steady_state"] is True


def test_expect_steady_state_raises_when_cold():
    cache = PlanCache()
    from repro.core.gemm import plan_for
    with use_context(plan_cache=cache):
        with pytest.raises(PlanCacheColdError):
            with cache.expect_steady_state("cold test"):
                plan_for(256, 512, 512, in_dtype=jnp.bfloat16)


def test_engine_metrics_export(dense_setup, tmp_path):
    cfg, mesh, params = dense_setup
    engine = ServeEngine(cfg, mesh, params, num_slots=2, max_len=16,
                         prompt_pad=8)
    engine.plan_warmup()
    m = engine.run(_requests([(8, 4), (4, 2), (6, 3)]))
    path = tmp_path / "metrics.json"
    m.to_json(str(path))
    d = json.loads(path.read_text())
    assert d["engine"]["num_slots"] == 2
    agg = d["aggregate"]
    assert agg["generated_tokens"] == sum(len(s.tokens)
                                          for s in engine.finished)
    assert agg["admissions"] == 3
    assert sum(agg["evictions"]["finished"].values()) == 3
    assert agg["evictions"]["preempted"] == 0
    assert agg["evictions"]["deadline_missed"] == 0
    assert agg["preemptions"] == 0 and agg["resumes"] == 0
    assert agg["policy"] == "fifo"
    assert 0 < agg["mean_occupancy"] <= 2
    assert agg["tokens_per_sec"] > 0
    for r in d["requests"]:
        assert r["ttft_s"] is not None and r["ttft_s"] >= 0
        assert r["queue_s"] is not None and r["queue_s"] >= 0
        assert r["ttft_ticks"] is not None and r["ttft_ticks"] >= 0
        assert r["per_token_s"] > 0
        assert r["preemptions"] == 0
        assert r["finish_reason"] in ("stop", "length")
        assert r["cached_tokens"] == 0       # no prefix cache on this engine
    assert set(d["slo"]) == {"0"}            # one priority class (default)
    assert d["slo"]["0"]["n"] == 3 and d["slo"]["0"]["miss_rate"] == 0.0
    assert d["budget"]["target_ttft_s"] is None
    assert d["budget"]["final_chunks"] == 1  # no target: pinned at min
    # section presence/shape is pinned by tests/test_metrics_schema.py
    assert d["plan_cache"]["steady_state"] is True


def test_engine_metrics_speculation_consistency(dense_setup, tmp_path):
    """Semantic checks for the speculation counters (key/type coverage
    lives in tests/test_metrics_schema.py's golden walker)."""
    cfg, mesh, params = dense_setup
    engine = ServeEngine(cfg, mesh, params, num_slots=2, max_len=24,
                         prompt_pad=8, kv_block_size=8,
                         spec_draft_cfg=cfg, spec_draft_params=params,
                         spec_k=2, spec_draft_quant=None)
    engine.plan_warmup()
    m = engine.run(_requests([(8, 4), (4, 6), (6, 3)]))
    d = json.loads(m.to_json(str(tmp_path / "metrics.json")))
    assert d["engine"]["spec"] is True
    assert d["engine"]["spec_k"] == 2
    sp = d["speculation"]
    assert sp["enabled"] is True and sp["spec_k"] == 2
    assert sp["proposed_tokens"] == sp["rounds"] * 2
    assert 0.0 <= sp["acceptance_rate"] <= 1.0
    assert sp["committed_tokens"] == (sp["accepted_tokens"]
                                      + sp["bonus_tokens"])
    assert sp["draft_arch"] == cfg.name
    assert d["plan_cache"]["steady_state"] is True


def test_engine_metrics_prefix_cache_consistency(dense_setup, tmp_path):
    """Semantic checks for the prefix_cache counters (key/type coverage
    lives in tests/test_metrics_schema.py's golden walker)."""
    cfg, mesh, params = dense_setup
    engine = ServeEngine(cfg, mesh, params, num_slots=2, max_len=16,
                         prompt_pad=8, kv_block_size=4, num_kv_blocks=33,
                         prefix_cache=True, prefix_cache_blocks=8)
    engine.plan_warmup()
    m = engine.run(_requests([(8, 4), (4, 2), (6, 3)]))
    d = json.loads(m.to_json(str(tmp_path / "metrics.json")))
    assert d["engine"]["prefix_cache"] is True
    assert d["engine"]["prefix_cache_blocks"] == 8
    px = d["prefix_cache"]
    assert px["lookups"] == 3
    assert px["lookup_tokens"] == 18
    assert 0.0 <= px["hit_rate"] <= 1.0
    assert px["inserted_blocks"] >= 1        # the 8- and 4-token prompts
    assert px["max_cached_blocks"] == 8
    bp = d["block_pool"]
    assert "cached_idle_blocks" in bp and "reclaimed_blocks" in bp
    assert "increfs" in bp
    assert d["plan_cache"]["steady_state"] is True


def test_engine_respects_stop_ids_and_budget(dense_setup):
    cfg, mesh, params = dense_setup
    engine = ServeEngine(cfg, mesh, params, num_slots=2, max_len=20,
                         prompt_pad=8)
    # stop on every token id: each request must finish with exactly 1 token
    reqs = _requests([(4, 5), (6, 5)], stop=tuple(range(cfg.vocab_size)))
    engine.run(reqs)
    for st in engine.finished:
        assert st.finish_reason == "stop" and len(st.tokens) == 1
    engine.reset()
    engine.run(_requests([(4, 3), (6, 2)], stop=()))
    assert sorted(len(s.tokens) for s in engine.finished) == [2, 3]
    assert all(s.finish_reason == "length" for s in engine.finished)


# --------------------------------------------------------- static EOS fix
def test_serve_batch_stops_per_sequence_on_eos(dense_setup):
    """With eos_id, generation for a row ends at its first stop token and
    the tail is pad — rows are independent (engine-comparable outputs)."""
    cfg, mesh, params = dense_setup
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size, (3, 6)), jnp.int32)
    plain = np.asarray(serve_batch(cfg, mesh, params, prompts,
                                   gen_len=8, max_len=15))
    # pick an eos that actually occurs mid-stream in some row
    counts = {}
    for row in plain:
        for t in row[:-1]:
            counts[int(t)] = counts.get(int(t), 0) + 1
    eos = max(counts, key=counts.get)
    stopped = np.asarray(serve_batch(cfg, mesh, params, prompts,
                                     gen_len=8, max_len=15, eos_id=eos))
    assert stopped.shape == plain.shape
    for row_p, row_s in zip(plain, stopped):
        lp = row_p.tolist()
        if eos in lp:
            cut = lp.index(eos) + 1
            assert row_s.tolist()[:cut] == lp[:cut]
            assert all(t == 0 for t in row_s.tolist()[cut:])
        else:
            assert row_s.tolist() == lp


# ------------------------------------------------------------ moe engine
def test_engine_on_prequantized_moe():
    """The engine runs a pre-quantized MoE model (expert tables as
    QuantizedLinear leaves) and stays plan-warm."""
    from repro.quant import prequant

    cfg = C.smoke(C.get_config("olmoe-1b-7b"))
    mesh = make_local_mesh()
    params = prequant.quantize_params(models.init(jax.random.PRNGKey(0), cfg))
    axes = prequant.quantize_axes(models.axes(cfg))
    with use_context(plan_cache=PlanCache(), quant_mode="int8"):
        engine = ServeEngine(cfg, mesh, params, num_slots=2, max_len=14,
                             prompt_pad=6, param_axes=axes)
        engine.plan_warmup()
        m = engine.run(_requests([(6, 4), (3, 2), (5, 3)], stop=()))
        assert m.plan_cache["steady_state"] is True
        assert sorted(len(s.tokens) for s in engine.finished) == [2, 3, 4]


# -------------------------------------------------- slo: preempt/resume
def test_engine_preempt_resume_token_parity(dense_setup):
    """The tentpole regression: a decode preempted by a higher-priority
    arrival, requeued, and resumed produces *exactly* the tokens of an
    unpreempted run — the KV it re-prefills (trie prefix + tail replay)
    is bit-equivalent to the KV it lost."""
    from repro.serve import SimClock

    cfg, mesh, params = dense_setup
    rng = np.random.default_rng(11)
    lo_prompt = rng.integers(0, 503, size=6, dtype=np.int32)
    hi_prompt = rng.integers(0, 503, size=6, dtype=np.int32)
    common = dict(num_slots=1, max_len=24, prompt_pad=8, kv_block_size=4,
                  num_kv_blocks=13)

    engine = ServeEngine(cfg, mesh, params, sched_policy="priority",
                         clock=SimClock(1e-4), **common)
    engine.plan_warmup()
    lo = Request(prompt=lo_prompt, max_new_tokens=10, priority=0)
    hi = Request(prompt=hi_prompt, max_new_tokens=3, priority=5,
                 arrival_s=0.002)
    m = engine.run([lo, hi])
    assert m.preemptions >= 1 and m.resumes == m.preemptions
    assert m.plan_cache["steady_state"] is True
    by_id = {st.request.request_id: st for st in engine.finished}
    assert by_id[lo.request_id].preemptions >= 1
    assert by_id[hi.request_id].preemptions == 0
    preempted_tokens = by_id[lo.request_id].tokens

    engine.reset()          # fresh pool/trie/scheduler, same compiled fns
    alone = Request(prompt=lo_prompt, max_new_tokens=10, priority=0)
    engine.run([alone])
    assert engine.finished[0].tokens == preempted_tokens


def test_engine_preemptive_policy_requires_paged():
    cfg = C.smoke(C.get_config("qwen1.5-4b"))
    mesh = make_local_mesh()
    params = models.init(jax.random.PRNGKey(3), cfg)
    with pytest.raises(ValueError, match="paged"):
        ServeEngine(cfg, mesh, params, num_slots=2, max_len=16,
                    prompt_pad=8, sched_policy="edf")


def test_engine_deadline_miss_and_arrivals(dense_setup):
    """Arrival-aware run(): a request is held until its arrival_s; an
    unmeetable deadline is cancelled (queued or mid-decode) and lands in
    the metrics as a per-class deadline miss, not an exception."""
    from repro.serve import SimClock

    cfg, mesh, params = dense_setup
    engine = ServeEngine(cfg, mesh, params, num_slots=1, max_len=24,
                         prompt_pad=8, kv_block_size=4, num_kv_blocks=13,
                         sched_policy="edf", clock=SimClock(1e-3))
    engine.plan_warmup()
    rng = np.random.default_rng(5)
    mk = lambda g, **kw: Request(
        prompt=rng.integers(0, 503, size=6, dtype=np.int32),
        max_new_tokens=g, **kw)
    long = mk(12, priority=0)                       # hogs the single lane
    doomed = mk(4, priority=2, deadline_s=0.004, arrival_s=0.002)
    m = engine.run([long, doomed])
    assert m.deadline_missed == 1
    assert m.plan_cache["steady_state"] is True
    d = m.to_dict()
    missed = [r for r in d["requests"]
              if r["finish_reason"] == "deadline_missed"]
    assert len(missed) == 1 and missed[0]["priority"] == 2
    assert d["slo"]["2"]["miss_rate"] == 1.0
    assert d["slo"]["0"]["miss_rate"] == 0.0
    by_id = {st.request.request_id: st for st in engine.finished}
    assert len(by_id[long.request_id].tokens) == 12  # untouched by the miss


def test_engine_budget_controller_reacts(dense_setup):
    """--ttft-target-ms feedback: an unmeetably tight target drives the
    prefill budget to its ceiling; chunk accounting stays plan-warm."""
    from repro.serve import SimClock, synthetic_trace

    cfg, mesh, params = dense_setup
    engine = ServeEngine(cfg, mesh, params, num_slots=2, max_len=24,
                         prompt_pad=8, kv_block_size=4, num_kv_blocks=25,
                         prefill_chunk=4, ttft_target_ms=1e-3,
                         max_prefill_chunks=3, clock=SimClock(1e-3))
    engine.plan_warmup()
    m = engine.run(synthetic_trace(6, vocab_size=503, prompt_lens=[8, 6],
                                   max_new_tokens=[4, 3], seed=2))
    assert m.plan_cache["steady_state"] is True
    assert m.budget["observations"] == 6
    assert m.budget["raises"] >= 1
    assert m.budget["final_chunks"] == 3
    assert len(engine.finished) == 6


def test_synthetic_trace_shapes():
    tr = synthetic_trace(5, vocab_size=100, prompt_lens=[4, 8],
                         max_new_tokens=[2, 3], stop_ids=(1,))
    assert [r.prompt_len for r in tr] == [4, 8, 4, 8, 4]
    assert [r.max_new_tokens for r in tr] == [2, 3, 2, 3, 2]
    assert all(r.stop_ids == (1,) for r in tr)
    assert all(r.prompt.max() < 100 for r in tr)


# ------------------------------------------------------------ launcher
def test_init_params_in_activation_dtype_and_quantized_in_place():
    """Serving weights are born in the activation dtype (f32 masters are
    training's); with quantize the same program emits int8 projections."""
    import dataclasses

    from repro.launch.serve import init_params
    from repro.quant.int8 import QuantizedLinear

    cfg = dataclasses.replace(C.smoke(C.get_config("qwen1.5-4b")),
                              activation_dtype="bfloat16")
    mesh = make_local_mesh()
    params, axes = init_params(cfg, mesh)
    assert {x.dtype for x in jax.tree.leaves(params)} == {jnp.dtype("bfloat16")}
    assert jax.tree.structure(axes, is_leaf=models.lm.is_axes_leaf) \
        .num_leaves == len(jax.tree.leaves(params))
    qparams, _ = init_params(cfg, mesh, quantize=True)
    wq = qparams["layers"]["mlp"].w_in
    assert isinstance(wq, QuantizedLinear) and wq.w_q.dtype == jnp.int8
    assert qparams["unembed"].dtype == jnp.bfloat16


def test_compile_cache_dir_from_env_or_checkout(monkeypatch):
    import pathlib

    from repro.launch.args import COMPILE_CACHE_DIR, use_compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert use_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == before  # left to JAX
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert use_compile_cache() == str(COMPILE_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(COMPILE_CACHE_DIR)
        checkout = pathlib.Path(__file__).resolve().parents[1]
        assert COMPILE_CACHE_DIR == checkout / ".jax_cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
