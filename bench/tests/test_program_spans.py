"""The metrics read inside the program: ``tick_host_ms``, ``sample_ms`` and
``logits_fetch_ms`` read the process-wide tracer of ``repro.obs.trace``,
here filled from a fake clock, over the traced stretch of a run."""
import pytest

import helpers
from bench.rundata import RunData
from bench.spec import load_module

READERS = ("tick_host_ms", "sample_ms", "logits_fetch_ms")


class Clock:
    """A clock that stands still until the test moves it."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _tick(tr, clock, tick, *, wait, fetch, sample, other, first=None):
    """One engine tick's spans as the engine nests them, each lasting the
    given milliseconds: a final prefill chunk's dispatch, then its wait,
    fetch and first-token sample (``first``, if given); a decode that holds
    its wait and fetch, then the sample loop; ``other`` ms of host work
    around them."""
    def span(name, ms):
        with tr.phase(name):
            clock.t += ms / 1e3

    tr.set_tick(tick)
    with tr.phase("tick"):
        clock.t += other / 2e3
        if first:
            span("prefill-chunk", 0)
            for name, ms in zip(("wait", "fetch", "sample"), first):
                span(name, ms)
        with tr.phase("decode"):
            span("wait", wait)
            span("fetch", fetch)
        span("sample", sample)
        clock.t += other / 2e3


@pytest.fixture
def read():
    return {name: load_module(helpers.BENCH / "metrics" / f"{name}.py").read
            for name in READERS}


@pytest.fixture
def tracer(monkeypatch):
    from repro.obs import trace

    clock = Clock()
    tr = trace.Tracer(clock=clock)
    monkeypatch.setattr(trace, "PROCESS_TRACER", tr)
    return tr, clock


def _run(traced):
    return RunData(records={}, ticks=[], window=(0.0, 100.0), setup_s=0.0,
                   dims={}, peak={}, traced=traced)


def test_readers_average_over_the_traced_ticks(read, tracer):
    tr, clock = tracer
    clock.t = 1.0
    _tick(tr, clock, 0, wait=100, fetch=50, sample=50, other=50)
    t0 = clock.t
    _tick(tr, clock, 1, wait=120, fetch=2, sample=4, other=2)
    _tick(tr, clock, 2, wait=118, fetch=4, sample=6, other=4)
    _tick(tr, clock, 3, wait=121, fetch=3, sample=5, other=3,
          first=(20, 1, 1))
    t1 = clock.t
    _tick(tr, clock, 4, wait=100, fetch=50, sample=50, other=50)
    # ticks 0 and 4 lie outside the stretch; 4 runs across its end
    run = _run((t0, t1 + 0.1))
    assert read["tick_host_ms"](run) == pytest.approx((8 + 14 + 13) / 3)
    assert read["sample_ms"](run) == pytest.approx((4 + 6 + 6) / 3)
    assert read["logits_fetch_ms"](run) == pytest.approx((2 + 4 + 4) / 3)
    assert (read["sample_ms"](run) + read["logits_fetch_ms"](run)
            <= read["tick_host_ms"](run))


def test_spans_outside_the_traced_stretch_are_left_out(read, tracer):
    tr, clock = tracer
    clock.t = 5.0
    _tick(tr, clock, 0, wait=10, fetch=1, sample=2, other=3)
    run = _run((0.0, 4.0))
    assert all(read[name](run) is None for name in READERS)
    run = _run((5.0, clock.t))
    assert read["tick_host_ms"](run) == pytest.approx(6)


def test_an_untraced_run_reads_nothing(read, tracer):
    tr, clock = tracer
    _tick(tr, clock, 0, wait=10, fetch=1, sample=2, other=3)
    assert all(read[name](_run(None)) is None for name in READERS)


def test_a_program_without_the_recorder_reads_nothing(read, monkeypatch):
    from repro.obs import trace

    monkeypatch.delattr(trace, "PROCESS_TRACER")
    assert all(read[name](_run((0.0, 1.0))) is None for name in READERS)
