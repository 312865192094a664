"""Flight recorder + counter registry: tracer unit behavior, Chrome
export/validation, registry semantics, and the engine integration
invariants (tracing off = bit-identical run; spec span reconciliation;
preempt gaps as async spans)."""
import json

import numpy as np
import pytest
import jax

from repro import configs as C
from repro import models
from repro.core.context import current_context, use_context
from repro.core.plancache import PlanCache
from repro.launch.mesh import make_local_mesh
from repro.obs import (NULL_TRACER, PHASES, PROCESS_TRACER, Registry, Tracer,
                       prom_name, validate_chrome_trace)
from repro.serve import Request, ServeEngine, SimClock, synthetic_trace


class FakeClock:
    """Deterministic tracer clock: advances ``step`` per reading."""

    def __init__(self, step=1.0):
        self.t = 0.0
        self.step = step

    def __call__(self):
        self.t += self.step
        return self.t


# ----------------------------------------------------------------- tracer
def test_tracer_phase_spans_and_summary_are_deterministic():
    tr = Tracer(clock=FakeClock(0.5))
    tr.set_tick(3)
    for _ in range(4):
        with tr.phase("decode", n=2):
            pass
    with tr.phase("sample", slot=1):
        pass
    s = tr.phase_summary()
    # every span is enter->exit = exactly one clock step = 0.5s
    assert s["phases"]["decode"] == {
        "count": 4, "total_s": 2.0, "mean_s": 0.5, "p50_s": 0.5,
        "p99_s": 0.5}
    assert s["phases"]["sample"]["total_s"] == 0.5
    # no host/device split: every span is host time
    assert set(s) == {"phases", "events_recorded", "events_dropped"}
    assert s["events_recorded"] == 5 and s["events_dropped"] == 0
    assert all(e["tick"] == 3 for e in tr.events)


def test_tracer_percentiles_exact():
    tr = Tracer(clock=FakeClock(1.0))
    durs = [1.0, 2.0, 3.0, 4.0]
    for d in durs:
        t0 = 100.0
        tr.phase_span("bind", t0, t0 + d)
    p = tr.phase_summary()["phases"]["bind"]
    assert p["p50_s"] == float(np.percentile(durs, 50))
    assert p["p99_s"] == float(np.percentile(durs, 99))
    assert p["total_s"] == 10.0 and p["mean_s"] == 2.5


def test_tracer_ring_bounds_events_but_not_durations():
    tr = Tracer(ring_events=8, clock=FakeClock())
    for _ in range(20):
        with tr.phase("expire"):
            pass
    assert len(tr.events) == 8
    assert tr.events_dropped == 12
    s = tr.phase_summary()
    # durations are accumulated outside the ring: timing covers all 20
    assert s["phases"]["expire"]["count"] == 20
    assert s["events_recorded"] == 20 and s["events_dropped"] == 12


def test_tracer_reset_clears_state():
    tr = Tracer(clock=FakeClock())
    with tr.phase("decode"):
        pass
    tr.request_event("submit", 7)
    tr.reset()
    assert len(tr.events) == 0 and tr.events_dropped == 0
    assert tr.phase_summary()["phases"] == {}


def test_chrome_export_layout_and_request_gaps():
    tr = Tracer(clock=FakeClock(1.0))
    tr.set_tick(0)
    with tr.phase("decode", slot=0):
        pass
    tr.instant("plan-lazy_solve", key="k")
    tr.request_event("submit", 1)
    tr.request_event("admit", 1, slot=0)
    tr.request_event("first-token", 1)
    tr.request_event("preempt", 1)
    tr.request_event("resume", 1, slot=0)
    tr.request_event("finish", 1, reason="length")
    obj = tr.to_chrome()
    info = validate_chrome_trace(obj, require_phases=("decode",),
                                 min_requests=1, min_preempts=1)
    assert info["completed_requests"] == 1 and info["preempts"] == 1
    evs = obj["traceEvents"]
    # pid 1: phase track with slot tid; pid 2: async request spans
    phase = next(e for e in evs if e.get("cat") == "phase")
    assert (phase["pid"], phase["tid"], phase["ph"]) == (1, 1, "X")
    active = [e for e in evs if e["name"] == "active"]
    # admit->preempt and resume->finish: two begin/end pairs = the gap
    assert [e["ph"] for e in active] == ["b", "e", "b", "e"]
    assert active[2]["ts"] > active[1]["ts"]
    names = {e["name"] for e in evs}
    assert "plan-lazy_solve" in names
    assert {"process_name", "thread_name"} <= names


def test_chrome_export_closes_open_spans():
    tr = Tracer(clock=FakeClock())
    tr.request_event("submit", 1)
    tr.request_event("admit", 1)
    obj = tr.to_chrome()
    # still validates: the export closes open spans at the last ts
    info = validate_chrome_trace(obj)
    assert info["completed_requests"] == 1
    closer = [e for e in obj["traceEvents"]
              if e["ph"] == "e" and (e.get("args") or {}).get("open_at_export")]
    assert len(closer) == 2


def test_validate_chrome_trace_rejects_malformed():
    with pytest.raises(ValueError, match="traceEvents"):
        validate_chrome_trace({"foo": []})
    bad_dur = {"traceEvents": [
        {"name": "x", "ph": "X", "ts": 0, "dur": -1, "cat": "phase"}]}
    with pytest.raises(ValueError, match="dur"):
        validate_chrome_trace(bad_dur)
    unbalanced = {"traceEvents": [
        {"name": "request", "ph": "e", "ts": 0, "id": "1"}]}
    with pytest.raises(ValueError, match="without begin"):
        validate_chrome_trace(unbalanced)
    ok = {"traceEvents": []}
    with pytest.raises(ValueError, match="required phases"):
        validate_chrome_trace(ok, require_phases=("decode",))
    with pytest.raises(ValueError, match="request spans"):
        validate_chrome_trace(ok, min_requests=1)


def test_null_tracer_is_inert():
    assert NULL_TRACER.enabled is False
    span = NULL_TRACER.phase("decode", slot=1, n=3)
    assert span is NULL_TRACER.phase("sample")  # one shared no-op span
    with span:
        pass
    NULL_TRACER.set_tick(5)
    NULL_TRACER.instant("x")
    NULL_TRACER.request_event("submit", 1)
    NULL_TRACER.phase_span("decode", 0.0, 1.0)
    NULL_TRACER.counter("pool", {"in_use": 3})
    assert NULL_TRACER.phase_summary() == {}
    assert NULL_TRACER.phase_durations() == {}


def test_null_tracer_never_reads_a_clock_or_allocates(monkeypatch):
    """The zero-cost contract, enforced: with every clock source booby-
    trapped, the NullTracer's whole surface still runs, and it retains no
    per-call state (nothing to allocate, nothing to leak)."""
    import time as _time

    def bomb():
        raise AssertionError("NullTracer read a clock")

    monkeypatch.setattr(_time, "perf_counter", bomb)
    monkeypatch.setattr(_time, "monotonic", bomb)
    monkeypatch.setattr(_time, "time", bomb)
    NULL_TRACER.reset()
    NULL_TRACER.set_tick(9)
    with NULL_TRACER.phase("decode", slot=0, n=4):
        pass
    NULL_TRACER.phase_span("spec-draft", 1.0, 2.0)
    NULL_TRACER.instant("plan-miss", key="k")
    NULL_TRACER.counter("attrib", {"compute": 1.0})
    NULL_TRACER.request_event("submit", 1)
    NULL_TRACER.request_event("finish", 1, reason="stop")
    # a singleton with no instance state: nothing accumulated anywhere
    assert NULL_TRACER.__dict__ == {}
    assert NULL_TRACER.phase_durations() == {}


def test_process_tracer_off_records_nothing_and_reads_no_clock(monkeypatch):
    """The zero-cost contract for the process-wide tracer: with no
    profiler session its whole hot-path surface runs with every clock
    source booby-trapped, returns the shared no-op span and records
    nothing."""
    import time as _time

    from jax.profiler import TraceAnnotation

    assert not TraceAnnotation.is_enabled()
    before = PROCESS_TRACER.phase_summary()["events_recorded"]

    def bomb():
        raise AssertionError("the process-wide tracer read a clock")

    monkeypatch.setattr(_time, "perf_counter", bomb)
    monkeypatch.setattr(_time, "monotonic", bomb)
    monkeypatch.setattr(_time, "time", bomb)
    PROCESS_TRACER.set_tick(9)
    span = PROCESS_TRACER.phase("tick")
    assert span is NULL_TRACER.phase("tick")      # the shared no-op span
    with span:
        with PROCESS_TRACER.phase("decode", n=4):
            pass
    PROCESS_TRACER.phase_span("spec-draft", 1.0, 2.0)
    PROCESS_TRACER.instant("plan-miss", key="k")
    PROCESS_TRACER.counter("attrib", {"compute": 1.0})
    PROCESS_TRACER.request_event("submit", 1)
    PROCESS_TRACER.request_event("finish", 1, reason="stop")
    assert PROCESS_TRACER.phase_summary()["events_recorded"] == before


class _FakeAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: a profiler session
    runs while ``on`` is set, and every entered annotation is logged."""

    on = False
    log: list = []

    def __init__(self, name, **kw):
        self.name, self.kw = name, kw

    @classmethod
    def is_enabled(cls):
        return cls.on

    def __enter__(self):
        self.log.append(("enter", self.name, self.kw))

    def __exit__(self, *exc):
        self.log.append(("exit", self.name, self.kw))


def test_spans_annotate_the_profiler_only_inside_a_session(monkeypatch):
    from repro.obs import trace as trace_mod

    monkeypatch.setattr(trace_mod, "TraceAnnotation", _FakeAnnotation)
    monkeypatch.setattr(_FakeAnnotation, "log", [])
    gated = Tracer(clock=FakeClock(), profiled_only=True)
    always = Tracer(clock=FakeClock())
    for on, tick in ((False, 0), (True, 1), (False, 2)):
        _FakeAnnotation.on = on
        for tr in (gated, always):
            tr.set_tick(tick)
            with tr.phase("tick"):
                with tr.phase("wait"):
                    pass
    _FakeAnnotation.on = False
    # the gated recorder keeps only the profiled tick; the caller's
    # tracer keeps all three; both annotate only the profiled one
    assert [e["tick"] for e in gated.events] == [1, 1]
    assert [e["tick"] for e in always.events] == [0, 0, 1, 1, 2, 2]
    assert _FakeAnnotation.log == [
        ("enter", "serve.tick", {"tick": 1}),
        ("enter", "serve.wait", {"tick": 1}),
        ("exit", "serve.wait", {"tick": 1}),
        ("exit", "serve.tick", {"tick": 1}),
    ] * 2


def test_tracer_spans_query_by_name_and_window():
    tr = Tracer(clock=FakeClock(1.0))          # reset reads 1.0
    for tick in range(3):
        tr.set_tick(tick)
        with tr.phase("tick"):                 # 2 + 6k .. 7 + 6k
            with tr.phase("wait"):             # 3 + 6k .. 4 + 6k
                pass
            tr.phase_span("fetch", 4.5 + 6 * tick, 4.75 + 6 * tick)
            with tr.phase("sample"):           # 5 + 6k .. 6 + 6k
                pass
    ticks = tr.spans("tick", 0.0, 100.0)
    assert ticks == [("tick", 2.0 + 6 * k, 7.0 + 6 * k, k) for k in range(3)]
    assert (ticks[1].start, ticks[1].end, ticks[1].tick) == (8.0, 13.0, 1)
    # only spans lying wholly inside the window
    assert [s.tick for s in tr.spans("tick", 7.5, 13.0)] == [1]
    assert [s.tick for s in tr.spans("tick", 7.5, 12.5)] == []
    assert [(s.start, s.end) for s in tr.spans("fetch", 14.0, 19.0)] \
        == [(16.5, 16.75)]
    assert [s.name for s in tr.spans("wait", 0.0, 100.0)] == ["wait"] * 3
    assert tr.spans("reclaim", 0.0, 100.0) == []


def test_tracer_ring_wraparound_still_exports_valid_chrome_json():
    """Once the ring wraps, begin events may be gone while ends survive;
    the export must degrade those to balanced instants, not emit a file
    viewers reject."""
    tr = Tracer(ring_events=16, clock=FakeClock(0.25))
    for i in range(30):
        tr.set_tick(i)
        tr.request_event("submit", i)
        with tr.phase("decode", slot=i % 2):
            pass
        tr.counter("pool", {"in_use": float(i)})
        tr.request_event("finish", i, reason="stop")
    assert tr.events_dropped > 0
    obj = tr.to_chrome()
    # round-trips through JSON and validates despite the dropped begins
    info = validate_chrome_trace(json.loads(json.dumps(obj)))
    assert obj["otherData"]["events_dropped"] == tr.events_dropped
    assert info["counter_samples"] > 0
    # durations accumulate outside the ring: nothing timed was lost
    assert tr.phase_summary()["phases"]["decode"]["count"] == 30


def test_counter_tracks_export_and_validate():
    tr = Tracer(clock=FakeClock(1.0))
    tr.set_tick(2)
    tr.counter("attrib_device_s", {"compute": 0.5, "memory": 1.5,
                                   "drifted": 0})
    tr.counter("attrib_device_s", {"compute": 0.75, "memory": 2.0,
                                   "drifted": 0})
    obj = tr.to_chrome()
    cs = [e for e in obj["traceEvents"] if e["ph"] == "C"]
    assert len(cs) == 2
    # args are pure numeric series — no tick smuggled in, floats only
    assert cs[0]["args"] == {"compute": 0.5, "memory": 1.5, "drifted": 0.0}
    assert cs[1]["ts"] > cs[0]["ts"]
    assert all(e["pid"] == 1 and e["tid"] == 0 for e in cs)
    info = validate_chrome_trace(obj)
    assert info["counter_samples"] == 2
    bad = {"traceEvents": [{"name": "c", "ph": "C", "ts": 0,
                            "args": {"x": "oops"}}]}
    with pytest.raises(ValueError, match="numeric series"):
        validate_chrome_trace(bad)


def test_phase_glossary_covers_engine_phases():
    # a plain glossary: every name says what it covers, with no kind
    assert all(isinstance(v, str) and v not in ("host", "device")
               for v in PHASES.values())
    for name in ("tick", "admit", "bind", "prefill-chunk", "spec-draft",
                 "spec-verify", "decode", "wait", "fetch", "sample",
                 "account", "expire", "reclaim"):
        assert name in PHASES


# --------------------------------------------------------------- registry
def test_registry_counter_gauge_histogram():
    reg = Registry()
    c = reg.counter("repro_test_total", "help text")
    c.inc()
    c.inc(3)
    assert c.collect() == 4
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("repro_test_gauge")
    g.set(2.5)
    g.inc()
    g.dec(0.5)
    assert g.collect() == 3.0
    h = reg.histogram("repro_test_seconds", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    col = h.collect()
    # cumulative Prometheus semantics: le="1" counts <=0.1 too, +Inf = count
    assert col["buckets"] == {"0.1": 1, "1": 2, "+Inf": 3}
    assert col["count"] == 3 and col["sum"] == pytest.approx(5.55)
    # same name, same kind -> same object; different kind -> TypeError
    assert reg.counter("repro_test_total") is c
    with pytest.raises(TypeError):
        reg.gauge("repro_test_total")


def test_registry_ingest_flattens_and_skips_non_numeric():
    reg = Registry()
    n = reg.ingest("serve_sched", {
        "admissions": 3,
        "policy": "edf",                      # skipped: string
        "evictions": {"finished": {"stop": 2}, "preempted": 1},
        "steady": True,
        "nothing": None,                      # skipped
    })
    assert n == 4
    flat = reg.collect()
    assert flat["repro_serve_sched_admissions"] == 3.0
    assert flat["repro_serve_sched_evictions_finished_stop"] == 2.0
    assert flat["repro_serve_sched_evictions_preempted"] == 1.0
    assert flat["repro_serve_sched_steady"] == 1.0
    assert "repro_serve_sched_policy" not in flat


def test_registry_snapshot_and_prometheus_text():
    reg = Registry()
    reg.gauge("repro_x").set(1)
    reg.snapshot(tick=4)
    reg.gauge("repro_x").set(2)
    reg.histogram("repro_y_seconds").observe(0.5)
    reg.snapshot(tick=8)
    assert [s["tick"] for s in reg.snapshots] == [4, 8]
    assert [s["repro_x"] for s in reg.snapshots] == [1.0, 2.0]
    hs = reg.snapshots[1]["repro_y_seconds"]
    # snapshots carry the full cumulative bucket vector, not a collapsed
    # sum/count pair — they must round-trip the same distribution the
    # text exposition serves
    assert hs["sum"] == 0.5 and hs["count"] == 1
    assert hs["buckets"]["+Inf"] == 1
    assert hs["buckets"]["0.1"] == 0 and hs["buckets"]["1"] == 1
    text = reg.to_prometheus_text()
    assert "# TYPE repro_x gauge" in text
    assert "# TYPE repro_y_seconds histogram" in text
    assert 'repro_y_seconds_bucket{le="+Inf"} 1' in text
    assert "repro_y_seconds_count 1" in text


def test_histogram_prometheus_exposition_is_cumulative_and_complete():
    """A scraper-valid histogram: one bucket line per edge plus +Inf,
    counts monotone non-decreasing, +Inf equal to _count."""
    reg = Registry()
    h = reg.histogram("repro_z_seconds", "phase time",
                      buckets=(0.001, 0.01, 0.1, 1.0))
    for v in (0.0005, 0.05, 0.05, 0.5, 50.0):
        h.observe(v)
    text = reg.to_prometheus_text()
    bucket_lines = [ln for ln in text.splitlines()
                    if ln.startswith("repro_z_seconds_bucket")]
    assert len(bucket_lines) == 5  # 4 edges + +Inf
    counts = [int(ln.rsplit(" ", 1)[1]) for ln in bucket_lines]
    assert counts == sorted(counts)              # cumulative => monotone
    assert 'le="+Inf"} 5' in bucket_lines[-1]    # +Inf == count
    assert counts == [1, 1, 3, 4, 5]
    assert "repro_z_seconds_sum" in text and "repro_z_seconds_count 5" in text
    # HELP lines escape newlines/backslashes per the exposition format
    reg.gauge("repro_esc", "line1\nline2\\x")
    assert r"# HELP repro_esc line1\nline2\\x" in reg.to_prometheus_text()


def test_prom_name_sanitizes():
    assert prom_name("prefill-chunk") == "prefill_chunk"
    assert prom_name("9lives") == "_9lives"
    assert prom_name("ok_name:x") == "ok_name:x"


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


def test_traced_run_is_bit_identical_to_untraced(dense_setup):
    """The zero-cost-when-off contract under SimClock: the tracer never
    reads the engine clock, so attaching one changes neither the tokens
    nor a single byte of the (untimed-section) metrics JSON."""
    cfg, mesh, params = dense_setup
    common = dict(num_slots=2, max_len=24, prompt_pad=8, kv_block_size=4,
                  num_kv_blocks=17, prefill_chunk=4)
    spec = [(8, 4), (4, 6), (6, 2), (5, 5)]

    def go(tracer):
        engine = ServeEngine(cfg, mesh, params, clock=SimClock(1e-3),
                             tracer=tracer, **common)
        engine.plan_warmup()
        m = engine.run(_reqs(spec))
        toks = sorted((st.request.prompt.tobytes(), tuple(st.tokens))
                      for st in engine.finished)
        d = m.to_dict()
        # request_id is a process-global counter — the only legitimate
        # difference between the two runs
        for r in d["requests"]:
            r.pop("request_id")
        return engine, toks, d

    recorded = PROCESS_TRACER.phase_summary()["events_recorded"]
    off_engine, off_toks, off_d = go(None)
    assert off_engine.tracer is PROCESS_TRACER
    # no profiler session: the process-wide tracer recorded nothing
    assert PROCESS_TRACER.phase_summary()["events_recorded"] == recorded
    tr = Tracer()
    _, on_toks, on_d = go(tr)
    assert on_toks == off_toks
    assert "timing" not in off_d
    assert "attribution" not in off_d   # the auditor is traced-only too
    timing = on_d.pop("timing")
    on_d.pop("attribution")
    assert on_d == off_d    # bit-identical modulo the traced-only sections
    assert timing["phases"]["decode"]["count"] > 0
    for name in ("tick", "expire", "bind", "prefill-chunk", "wait", "fetch",
                 "sample", "account"):
        assert name in timing["phases"], name


def test_traced_engine_exports_valid_chrome_trace(dense_setup, tmp_path):
    cfg, mesh, params = dense_setup
    tr = Tracer()
    engine = ServeEngine(cfg, mesh, params, num_slots=2, max_len=16,
                         prompt_pad=8, kv_block_size=4, num_kv_blocks=17,
                         tracer=tr, metrics_interval_ticks=4)
    engine.plan_warmup()
    engine.run(_reqs([(8, 4), (4, 2), (6, 3)]))
    obj = tr.save(tmp_path / "trace.json")
    assert obj == json.loads((tmp_path / "trace.json").read_text())
    info = validate_chrome_trace(
        obj, require_phases=("expire", "bind", "prefill-chunk", "decode",
                             "sample"),
        min_requests=3)
    assert info["completed_requests"] == 3
    # registry: periodic snapshots plus phase histograms at end of run
    assert len(engine.registry.snapshots) >= 2
    text = engine.registry.to_prometheus_text()
    assert "repro_serve_phase_decode_seconds_bucket" in text
    assert "repro_serve_generated_tokens" in text
    assert "repro_plan_cache_lazy_solves 0" in text


def test_preempt_gap_renders_as_split_active_spans(dense_setup):
    """A preempted request exports as one outer async span with >= 2
    inner 'active' spans — the gap between them is the preempted
    stretch (the timeline the flight recorder exists to show)."""
    cfg, mesh, params = dense_setup
    rng = np.random.default_rng(11)
    tr = Tracer()
    engine = ServeEngine(cfg, mesh, params, sched_policy="priority",
                         clock=SimClock(1e-4), tracer=tr, num_slots=1,
                         max_len=24, prompt_pad=8, kv_block_size=4,
                         num_kv_blocks=13)
    engine.plan_warmup()
    lo = Request(prompt=rng.integers(0, 503, size=6, dtype=np.int32),
                 max_new_tokens=10, priority=0)
    hi = Request(prompt=rng.integers(0, 503, size=6, dtype=np.int32),
                 max_new_tokens=3, priority=5, arrival_s=0.002)
    m = engine.run([lo, hi])
    assert m.preemptions >= 1
    obj = tr.to_chrome()
    validate_chrome_trace(obj, min_requests=2, min_preempts=1)
    lo_active = [e for e in obj["traceEvents"]
                 if e["name"] == "active" and e.get("id") == str(lo.request_id)]
    begins = [e for e in lo_active if e["ph"] == "b"]
    ends = [e for e in lo_active if e["ph"] == "e"]
    assert len(begins) >= 2 and len(begins) == len(ends)
    # the resume begins strictly after the preempt ended the first span
    assert begins[1]["ts"] > ends[0]["ts"]


def test_spec_phase_spans_reconcile_with_spec_stats(dense_setup):
    """spec-draft/spec-verify spans carry the *same* perf_counter stamps
    that feed SpecStats.draft_s/verify_s — the two views must agree."""
    cfg, mesh, params = dense_setup
    tr = Tracer()
    engine = ServeEngine(cfg, mesh, params, num_slots=2, max_len=24,
                         prompt_pad=8, kv_block_size=8, tracer=tr,
                         spec_draft_cfg=cfg, spec_draft_params=params,
                         spec_k=2, spec_draft_quant=None)
    engine.plan_warmup()
    m = engine.run(_reqs([(8, 4), (4, 6), (6, 3)]))
    sp = m.speculation
    assert sp["rounds"] > 0
    durs = tr.phase_durations()
    assert sum(durs["spec-draft"]) == pytest.approx(sp["draft_s"], rel=1e-9)
    assert sum(durs["spec-verify"]) == pytest.approx(sp["verify_s"], rel=1e-9)
    # one draft span and one verify span per speculative dispatch round
    assert len(durs["spec-draft"]) == len(durs["spec-verify"])
    t = m.timing["phases"]
    assert t["spec-draft"]["total_s"] == pytest.approx(sp["draft_s"],
                                                       rel=1e-9)
    assert t["spec-verify"]["total_s"] == pytest.approx(sp["verify_s"],
                                                        rel=1e-9)


def test_plan_cache_events_land_on_the_timeline(dense_setup):
    """An unwarmed engine's first run consults signatures the (cold)
    cache has never seen; with a tracer attached each one is a
    'plan-miss' instant ON the timeline — the cause of the slow tick,
    not just an end-of-run counter."""
    cfg, mesh, params = dense_setup
    with use_context(plan_cache=PlanCache()):
        tr = Tracer()
        engine = ServeEngine(cfg, mesh, params, num_slots=2, max_len=16,
                             prompt_pad=8, tracer=tr)
        m = engine.run(_reqs([(8, 2), (4, 2)]))
        assert m.plan_cache["steady_state"] is False
        assert m.plan_cache["misses"] > 0
        miss = [e for e in tr.events
                if e["kind"] == "instant" and e["name"] == "plan-miss"]
        assert len(miss) == m.plan_cache["misses"]
        assert all("key" in (e["args"] or {}) for e in miss)
        # and the listener is removed after run(): no leak into the cache
        assert current_context().plan_cache._listeners == []


def test_reclaim_phase_spans_under_prefix_cache_pressure(dense_setup):
    """Prefix-cache block reclaims (the allocator's slow path) show up as
    'reclaim' phase spans attributed to the tick that paid for them."""
    cfg, mesh, params = dense_setup
    tr = Tracer()
    engine = ServeEngine(cfg, mesh, params, num_slots=2, max_len=16,
                         prompt_pad=8, kv_block_size=4, num_kv_blocks=9,
                         prefix_cache=True, tracer=tr)
    engine.plan_warmup()
    m = engine.run(synthetic_trace(6, vocab_size=503, prompt_lens=[8, 6],
                                   max_new_tokens=[4, 3], seed=2))
    # a tight pool + retained prefixes forces at least one reclaim sweep
    assert "reclaim" in tr.phase_durations()
    assert m.timing["phases"]["reclaim"]["count"] >= 1


def test_profiled_run_puts_serve_spans_on_the_profiler_clock(dense_setup,
                                                             tmp_path):
    """Inside a profiler session an engine given no tracer writes its
    spans to the profiler trace's host plane as ``serve.<phase>`` and to
    the process-wide recorder, each child inside its tick, and serves the
    same tokens as without a session."""
    import glob
    import time

    from jax.profiler import ProfileData

    cfg, mesh, params = dense_setup
    common = dict(num_slots=2, max_len=24, prompt_pad=8, kv_block_size=4,
                  num_kv_blocks=17, prefill_chunk=4)
    spec = [(8, 4), (4, 6), (6, 2)]

    def go():
        engine = ServeEngine(cfg, mesh, params, **common)
        engine.plan_warmup()
        m = engine.run(_reqs(spec))
        toks = sorted((st.request.prompt.tobytes(), tuple(st.tokens))
                      for st in engine.finished)
        return toks, m.ticks

    off_toks, _ = go()
    t0 = time.perf_counter()
    jax.profiler.start_trace(str(tmp_path))
    try:
        on_toks, n_ticks = go()
    finally:
        jax.profiler.stop_trace()
    t1 = time.perf_counter()
    assert on_toks == off_toks

    children = ("expire", "bind", "prefill-chunk", "decode", "wait",
                "fetch", "sample", "account")
    # the recorder: every tick of the run, stamped inside the session
    ticks = {t.tick: t for t in PROCESS_TRACER.spans("tick", t0, t1)}
    assert sorted(ticks) == list(range(n_ticks))
    for name in children:
        spans = PROCESS_TRACER.spans(name, t0, t1)
        assert spans, name
        for s in spans:
            t = ticks[s.tick]
            assert t.start <= s.start <= s.end <= t.end, (name, s, t)
    for d in PROCESS_TRACER.spans("decode", t0, t1):
        # each decode holds its own wait and fetch, one of each
        for name in ("wait", "fetch"):
            assert len(PROCESS_TRACER.spans(name, d.start, d.end)) == 1

    # the profiler trace: the same spans on the host plane
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = ProfileData.from_file(path)
    host = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
             int(dict(ev.stats)["tick"]))
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith("serve.")]
    names = {h[0] for h in host}
    assert {f"serve.{n}" for n in ("tick",) + children} <= names, names
    xticks = {h[3]: h for h in host if h[0] == "serve.tick"}
    assert sorted(xticks) == list(range(n_ticks))
    for name, start, end, tick in host:
        t = xticks[tick]
        assert t[1] <= start <= end <= t[2], (name, tick)
