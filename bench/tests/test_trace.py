"""The trace reduction, on a small trace recorded on a v5e, and the work
counts it is held against.

``data/tiny.xplane.pb.gz`` is a ``--trace 1`` run of the tiny cell
(``helpers.TINY_CONFIG``: 2 layers, 4 lanes) on one TPU v5e, recorded by
the harness itself (``record_trace.py``; jax 0.9, libtpu 0.0.34): 0.15 s
of the window's end, with the benchmark's host spans and the device's
module and op lines.
"""
import gzip
import json
import shutil

import pytest

import helpers
from bench import trace, work
from bench.models import dense
from bench.rundata import RunData
from bench.spec import load_module

DATA = helpers.BENCH / "tests" / "data" / "tiny.xplane.pb.gz"
LAYERS, SLOTS = 2, 4


@pytest.fixture(scope="module")
def xplane(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "tiny.xplane.pb"
    with gzip.open(DATA, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(path)


@pytest.fixture(scope="module")
def summary(xplane):
    return trace.reduce(xplane, layers=LAYERS, slots=SLOTS)


@pytest.fixture(scope="module")
def planes(xplane):
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane)
    yield list(pd.planes)
    del pd


def test_union_and_holes():
    merged = trace.union([(5, 7), (1, 3), (2, 4), (7, 8)])
    assert merged == [[1, 4], [5, 8]]
    assert trace.holes(merged, 0, 10) == [(0, 1), (4, 5), (8, 10)]
    assert trace.holes([], 0, 10) == [(0, 10)]


def test_gemm_ops_are_told_by_name():
    mm = ('%matmul.47 = bf16[16,6912]{1,0} custom-call(bf16[16,2560] %a, '
          'bf16[2560,6912] %b), custom_call_target="tpu_custom_call"')
    mv = mm.replace("%matmul.47", "%decode_matvec.36")
    other = mm.replace("%matmul.47", "%paged_attention.3")
    assert trace.is_gemm(mm) and trace.is_gemm(mv)
    assert not trace.is_gemm(other)
    assert not trace.is_gemm("%matmul.2 = f32[8,8]{1,0} dot(f32[8,8] %a)")
    assert trace.instruction(mm) == ("matmul.47", "bf16[16,6912]")
    assert trace.weight_operand(mm) == "b"


def test_busy_time_is_the_union_of_device_ops(planes, summary):
    host = [ev for p in planes if p.name.startswith("/host:")
            for ln in p.lines for ev in ln.events if ev.name == "bench.traced"]
    assert len(host) == 1
    w0, w1 = host[0].start_ns, host[0].start_ns + host[0].duration_ns
    assert summary.window_s == pytest.approx((w1 - w0) * 1e-9)
    # independent recount: mark every device-op nanosecond in the window
    dev = next(p for p in planes if p.name == "/device:TPU:0")
    ops = next(ln for ln in dev.lines if ln.name == "XLA Ops")
    edges = sorted((max(e.start_ns, w0), min(e.start_ns + e.duration_ns, w1))
                   for e in ops.events
                   if e.start_ns < w1 and e.start_ns + e.duration_ns > w0)
    busy, end = 0.0, w0
    for s, e in edges:
        if e > end:
            busy += e - max(s, end)
            end = e
    assert summary.busy_s == pytest.approx(busy * 1e-9)
    assert 0 < summary.busy_s < summary.window_s


def test_step_executions_match_the_host_dispatches(planes, summary):
    spans = [ev for p in planes if p.name.startswith("/host:")
             for ln in p.lines for ev in ln.events]
    traced = next(ev for ev in spans if ev.name == "bench.traced")
    w0, w1 = traced.start_ns, traced.start_ns + traced.duration_ns
    n = {k: sum(1 for ev in spans if ev.name == f"bench.{k}"
                and w0 <= ev.start_ns <= w1) for k in ("prefill", "decode")}
    for kind, prog in summary.programs.items():
        assert prog.count == n[kind] > 0
        assert prog.gemm_mismatch is None
        assert 0 < prog.gemm_s < prog.device_s
        assert prog.kernel_s > 0 and prog.staging_s > 0
        assert prog.gemm_s == pytest.approx(prog.kernel_s + prog.staging_s)
    assert {r for r, _ in summary.programs["prefill"].rows} <= {4, 8, 16}
    assert set(summary.programs["decode"].rows) == {(SLOTS, SLOTS)}


def test_gemm_weights_are_staged_into_vmem(planes):
    """Each GEMM kernel's weight operand is made by an op of the same
    module whose result lives in VMEM (memory space S(1)), so the weights'
    trip from HBM is that op's time, not the kernel's."""
    dev = next(p for p in planes if p.name == "/device:TPU:0")
    lines = {ln.name: ln for ln in dev.lines}
    mods = sorted((e.start_ns, e.start_ns + e.duration_ns)
                  for e in lines[trace.MODULES_LINE].events)
    by_module = {}
    for e in lines[trace.OPS_LINE].events:
        m = next((m for m in mods if m[0] <= e.start_ns < m[1]), None)
        if m is not None:
            by_module.setdefault(m, {})[trace.instruction(e.name)[0]] = e.name
    staged = 0
    for names in by_module.values():
        for name in names.values():
            if trace.is_gemm(name):
                op = names[trace.weight_operand(name)]
                assert "S(1)}" in op.split(" = ", 1)[1].split(" ", 1)[0], op
                staged += 1
    assert staged > 0


def test_a_step_of_other_gemms_leaves_the_roofline_silent(xplane):
    s = trace.reduce(xplane, layers=LAYERS + 1, slots=SLOTS)
    for prog in s.programs.values():
        assert prog.gemm_s is None
        assert "expected 22" in prog.gemm_mismatch


def test_breakdown(summary):
    ops, gaps = summary.breakdown["device_ops"], summary.breakdown["idle_gaps"]
    assert 0 < len(ops) <= trace.TOP and 0 < len(gaps) <= trace.TOP
    assert [v for _, v in ops] == sorted((v for _, v in ops), reverse=True)
    assert [v for _, v in gaps] == sorted((v for _, v in gaps), reverse=True)
    assert all("/" in name for name, _ in ops)
    assert not any(name.split("/")[1].startswith("while") for name, _ in ops)
    assert all(name.startswith("bench.") or name == "outside any bench span"
               for name, _ in gaps)
    assert sum(v for _, v in gaps) <= summary.window_s - summary.busy_s + 1e-9
    json.dumps(summary.breakdown)


def test_trace_metrics_read_within_their_ranges(summary):
    peak = json.loads((helpers.BENCH / "peaks.json").read_text())["TPU v5 lite"]
    run = RunData(records={}, ticks=[], window=(0.0, 1.0), setup_s=0.0,
                  dims=dense.dims(helpers.TINY_CONFIG), peak=peak,
                  trace=summary)
    read = {name: load_module(helpers.BENCH / "metrics" / f"{name}.py").read
            for name in ("gemm_roofline.prefill", "gemm_roofline.decode",
                         "decode_step_ms", "device_idle_share")}
    for name in ("gemm_roofline.prefill", "gemm_roofline.decode"):
        assert 0 < read[name](run) <= 100
    prog = summary.programs["decode"]
    assert read["decode_step_ms"](run) == pytest.approx(
        prog.device_s * 1e3 / prog.count)
    assert 0 < read["device_idle_share"](run) < 100
    # the decode roofline by hand: 16 GEMMs of 4 rows, from the sizes
    least = prog.count * work.least_time_s(
        work.step_gemms(run.dims, SLOTS, SLOTS), peak)
    assert read["gemm_roofline.decode"](run) == pytest.approx(
        100 * least / prog.gemm_s)


def test_work_counts_from_the_sizes():
    m = dense.dims(helpers.TINY_CONFIG)
    assert work.span_flops(m, 5, 3) == pytest.approx(
        sum(work.span_flops(m, p, 1) for p in (5, 6, 7)))
    assert len(work.step_gemms(m, 16, 1)) == 7 * m["layers"] + 1
    peak = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}
    M, K, N = 4, 64, 128
    assert work.least_time_s([(M, K, N, 2)], peak) == max(
        2 * M * N * K / 1e12, (2 * M * K + 2 * K * N + 2 * M * N) / 1e9)
