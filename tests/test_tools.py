"""CLI tooling: metrics_diff nested-section comparison, serve_doctor
report/gates, and benchmark provenance stamps."""
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "tools"))
sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "benchmarks"))

import metrics_diff  # noqa: E402
import serve_doctor  # noqa: E402
import provenance  # noqa: E402


# ------------------------------------------------------------ metrics_diff
def test_diff_nested_compares_numeric_leaves_within_tolerance():
    cur = {"phases": {"decode": {"total_s": 1.0, "count": 4}},
           "host_s": 0.5}
    base = {"phases": {"decode": {"total_s": 1.05, "count": 4}},
            "host_s": 0.5}
    assert metrics_diff.diff_nested(cur, base, tolerance=0.10) == []
    probs = metrics_diff.diff_nested(cur, base, tolerance=0.01, path="timing")
    assert len(probs) == 1 and "timing.phases.decode.total_s" in probs[0]


def test_diff_nested_skips_none_missing_and_non_numeric():
    cur = {"a": None, "b": 1.0, "kind": "device", "extra": 7,
           "rows": [{"x": 1.0}, {"x": None}]}
    base = {"a": 2.0, "b": None, "kind": "host",
            "rows": [{"x": 1.0}, {"x": 3.0}]}
    # None on either side, strings, and keys missing from one side are
    # all skipped — never spurious failures
    assert metrics_diff.diff_nested(cur, base, tolerance=0.0) == []
    # a whole section absent on one side (traced vs untraced) is skipped
    assert metrics_diff.diff_nested(None, {"x": 1}, tolerance=0.0) == []
    assert metrics_diff.diff_nested({"x": 1}, None, tolerance=0.0) == []
    # bools are not numbers: steady_state True vs False is not a "diff
    # within tolerance" question and stays out of the numeric gate
    assert metrics_diff.diff_nested(
        {"s": True}, {"s": False}, tolerance=9.0) == []


def test_metrics_diff_cli_sections(tmp_path):
    cur = {"aggregate": {"tokens_per_tick": 2.0},
           "plan_cache": {"steady_state": True},
           "timing": {"device_s": 1.0},
           "attribution": {"reconciliation_error": 0.0}}
    base = {"aggregate": {"tokens_per_tick": 2.0},
            "plan_cache": {"steady_state": True}}    # untraced baseline
    a, b = tmp_path / "cur.json", tmp_path / "base.json"
    a.write_text(json.dumps(cur))
    b.write_text(json.dumps(base))
    rc = metrics_diff.main([str(a), str(b), "--sections",
                            "timing,attribution"])
    assert rc == 0
    # and a real numeric regression in a shared section still fails
    base["timing"] = {"device_s": 2.0}
    b.write_text(json.dumps(base))
    rc = metrics_diff.main([str(a), str(b), "--sections", "timing",
                            "--tolerance", "0.1"])
    assert rc == 1


# ------------------------------------------------------------ serve_doctor
def _metrics(drifted=False, recon=0.0):
    row = {"key": "hw|8|64|128|f32|f32|row", "hw": "hw", "m": 8, "k": 64,
           "n": 128, "in_dtype": "f32", "out_dtype": "f32", "layout": "row",
           "bm": 8, "bk": 128, "bn": 128, "calls": 10, "device_s": 1.0,
           "share": 1.0, "t_comp_s": 1e-7, "t_mem_s": 2e-7,
           "t_total_s": 2e-7, "balance_ratio": 0.5, "snapshot_ratio": 0.5,
           "snapshot_t_total_s": 2e-7, "ratio_deviation": 0.0,
           "time_deviation": 0.9 if drifted else 0.0, "bound": "memory",
           "drifted": drifted, "measured_per_call_s": 0.1,
           "measured_vs_modeled": 5.0,
           "suggested_bm": 8 if drifted else None,
           "suggested_bk": 256 if drifted else None,
           "suggested_bn": 128 if drifted else None,
           "suggested_gain": 2.0 if drifted else None}
    return {
        "engine": {"arch": "smoke", "hw": "hw", "backend": "xla",
                   "num_slots": 2, "paged": True},
        "aggregate": {"ticks": 10, "generated_tokens": 20,
                      "tokens_per_tick": 2.0, "admissions": 4,
                      "preemptions": 0, "deadline_missed": 0,
                      "deferred_admissions": 0, "policy": "fifo"},
        "timing": {"phases": {
            "tick": {"count": 10, "total_s": 1.2, "mean_s": 0.12,
                     "p50_s": 0.12, "p99_s": 0.12},
            "decode": {"count": 10, "total_s": 1.0, "mean_s": 0.1,
                       "p50_s": 0.1, "p99_s": 0.1},
            "wait": {"count": 10, "total_s": 0.9, "mean_s": 0.09,
                     "p50_s": 0.09, "p99_s": 0.09}},
            "events_recorded": 30, "events_dropped": 0},
        "attribution": {
            "signatures": 1, "attributed_device_s": 1.0 - recon,
            "traced_device_s": 1.0, "reconciliation_error": recon,
            "bound_share": {"compute": 0.0, "memory": 1.0, "drifted": 0.0},
            "drifted_count": int(drifted),
            "drifted": [row["key"]] if drifted else [],
            "by_device_s": [row]},
        "block_pool": {"num_blocks": 17, "peak_in_use": 8,
                       "peak_utilization": 0.5, "failed_allocs": 0,
                       "peak_fragmentation_tokens": 12},
        "prefix_cache": {},
        "plan_cache": {"hits": 5, "misses": 0, "lazy_solves": 0,
                       "steady_state": True},
        "slo_burn": {"target_ttft_s": 0.05, "window": 32,
                     "budget_miss_rate": 0.1,
                     "classes": {"0": {"n": 4, "window_n": 4,
                                       "misses_in_window": 2,
                                       "rolling_miss_rate": 0.5,
                                       "burn_rate": 5.0, "alert": True}}},
    }


def test_serve_doctor_report_and_findings(tmp_path, capsys):
    path = tmp_path / "m.json"
    path.write_text(json.dumps(_metrics()))
    rc = serve_doctor.main([str(path), "--report", str(tmp_path / "r.txt")])
    assert rc == 0
    text = (tmp_path / "r.txt").read_text()
    for section in ("Phase bottlenecks", "Balance attribution",
                    "Pool / cache pressure", "SLO burn", "Diagnosis"):
        assert section in text
    assert "burning its SLO budget at 5.0x" in text
    assert "memory-bound" in text
    assert "top phase: decode" in text
    assert "host-bound" not in text     # 0.9 s of 1.2 s waits on the device


def test_serve_doctor_gates(tmp_path):
    clean = tmp_path / "clean.json"
    clean.write_text(json.dumps(_metrics()))
    assert serve_doctor.main(
        [str(clean), "--fail-on-drift",
         "--max-reconciliation-error", "0.05"]) == 0
    drifted = tmp_path / "drift.json"
    drifted.write_text(json.dumps(_metrics(drifted=True)))
    assert serve_doctor.main([str(drifted)]) == 0       # report-only: passes
    assert serve_doctor.main([str(drifted), "--fail-on-drift"]) == 1
    bad = tmp_path / "recon.json"
    bad.write_text(json.dumps(_metrics(recon=0.2)))
    assert serve_doctor.main(
        [str(bad), "--max-reconciliation-error", "0.05"]) == 1
    # the reconciliation gate demands a traced run to gate on
    untraced = tmp_path / "untraced.json"
    m = _metrics()
    del m["timing"], m["attribution"]
    untraced.write_text(json.dumps(m))
    assert serve_doctor.main(
        [str(untraced), "--max-reconciliation-error", "0.05"]) == 1
    assert serve_doctor.main([str(untraced)]) == 0


def test_serve_doctor_drift_suggestion_in_report(tmp_path, capsys):
    path = tmp_path / "m.json"
    path.write_text(json.dumps(_metrics(drifted=True)))
    assert serve_doctor.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "drifted plan hw|8|64|128|f32|f32|row" in out
    assert "re-solve to bm=8 bk=256 bn=128" in out
    assert "--rebalance-drifted" in out


# -------------------------------------------------------------- provenance
def test_provenance_stamp_schema():
    s = provenance.stamp(hw="tpu_v6e", backend="xla")
    assert set(s) == {"git_sha", "dirty", "hw", "backend", "jax",
                      "jaxlib", "timestamp"}
    assert s["hw"] == "tpu_v6e" and s["backend"] == "xla"
    assert isinstance(s["dirty"], (bool, type(None)))
    # in-repo: sha and dirty agree (legacy -dirty suffix kept for humans)
    if s["git_sha"] is not None:
        assert s["git_sha"].endswith("-dirty") == s["dirty"]
    import jax as jax_mod
    assert s["jax"] == jax_mod.__version__
    assert s["timestamp"].endswith("+00:00") or "T" in s["timestamp"]
    assert json.dumps(s)    # JSON-embeddable verbatim
