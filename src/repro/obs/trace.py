"""Flight recorder: bounded ring-buffer tracer with Chrome-trace export.

The tracer records two families of events (phase glossary in
docs/observability.md):

* **Phase spans** — how a tick's wall time decomposes on the host:
  ``tick`` around the whole of it, and inside it ``expire``, ``bind``,
  ``prefill-chunk``, ``decode``, ``wait``, ``fetch``, ``sample``,
  ``account`` and the rest of :data:`PHASES`.  Each span carries a host
  timestamp pair (``clock()`` at enter/exit, default
  ``time.perf_counter``) and the engine tick it ran under.
* **Request lifecycle events** — what one request experienced:
  ``submit``, ``admit``, ``chunk``, ``first-token``, ``preempt``,
  ``resume``, ``rewind``, ``finish``.  These export as Chrome *async*
  spans so a request renders as one horizontal bar with visible gaps
  while preempted.

Design constraints (why it looks the way it does):

* **On the profiler's clock.**  While a JAX profiler session runs
  (``jax.profiler.start_trace`` / ``jax.profiler.trace``), every phase
  span also enters a ``jax.profiler.TraceAnnotation("serve.<phase>",
  tick=...)``, so the span lands on the host plane of the profiler
  trace, on the same clock as the device's ops.  The tracer asks the
  profiler once per tick, in :meth:`Tracer.set_tick`.
* **Zero cost when off.**  An engine given no tracer writes to
  :data:`PROCESS_TRACER`, which records only while a profiler session
  runs: with none, ``phase()`` returns the shared no-op span and no
  method reads a clock.  :data:`NULL_TRACER` never records at all.  So
  an untraced run makes exactly the same clock-read sequence as a build
  without tracing — this matters under ``SimClock``, where *reading*
  the engine clock advances it.
* **The tracer never reads the engine clock.**  All tracer timestamps
  come from its own injected ``clock`` (host ``perf_counter`` by
  default); engine-time ordering is carried by the integer ``tick``
  stamped on every event via :meth:`Tracer.set_tick`.
* **Bounded memory.**  Events live in a ``deque(maxlen=ring_events)``;
  old events fall off the front and are counted in ``events_dropped``.
  Per-phase *durations* are additionally accumulated outside the ring
  so the ``timing`` summary covers the whole run even after the ring
  wraps.
"""
from __future__ import annotations

import json
import time
from collections import deque
from typing import Callable, NamedTuple

import numpy as np
from jax.profiler import TraceAnnotation

# Phase glossary: span name -> what it covers.  Every span is host time;
# the device's time is read from the profiler trace's device plane, which
# the spans share a clock with while a profiler session runs.  ``wait``
# and ``fetch`` nest inside ``decode`` (and ``admit``); every other span
# of a tick nests directly inside ``tick``.
PHASES: dict[str, str] = {
    "tick": "one whole engine tick",
    "expire": "deadline expiry sweep",
    "bind": "paged admission: slot binding and block allocation",
    "admit": "one-shot admission prefill (contiguous engine): dispatch, "
             "wait and fetch; or a draft-model admission's dispatch",
    "prefill-chunk": "dispatch of one chunked-prefill step",
    "decode": "dispatch of the masked batched decode step, then its "
              "wait and fetch",
    "wait": "the host blocked until a step's logits exist on the device",
    "fetch": "the copy of a step's logits to the host, as far as it is "
             "left once the wait ends",
    "sample": "host sampling / acceptance and the token append loop",
    "account": "block-pool and metrics bookkeeping, registry publish",
    "spec-draft": "draft chain proposing k tokens, to proposals on the host",
    "spec-verify": "batched (slots, k+1) target verify, to logits on the "
                   "host",
    "reclaim": "prefix-cache LRU block reclaim",
}

REQUEST_EVENTS = (
    "submit", "admit", "chunk", "first-token",
    "preempt", "resume", "rewind", "finish",
)

_US = 1e6  # seconds -> Chrome trace microseconds

# Fixed pid/tid layout for the Chrome export: pid 1 holds phase tracks
# (tid 0 = engine tick loop, tid 1+slot = per-slot work), pid 2 holds
# request async spans.
_PID_PHASES = 1
_PID_REQUESTS = 2


class Span(NamedTuple):
    """One recorded phase span, as :meth:`Tracer.spans` returns it."""

    name: str
    start: float    # the tracer's clock (time.perf_counter by default), s
    end: float
    tick: int


class _PhaseSpan:
    """Context manager recording one phase span on ``__exit__``; while a
    profiler session runs it also enters a ``serve.<name>`` annotation."""

    __slots__ = ("_tr", "name", "tick", "slot", "args", "_t0", "_ann")

    def __init__(self, tr: "Tracer", name: str, tick: int, slot, args):
        self._tr = tr
        self.name = name
        self.tick = tick
        self.slot = slot
        self.args = args
        self._t0 = 0.0
        self._ann = None

    def __enter__(self):
        if self._tr._annotate:
            self._ann = TraceAnnotation("serve." + self.name, tick=self.tick)
            self._ann.__enter__()
        self._t0 = self._tr._clock()
        return self

    def __exit__(self, exc_type, exc, tb):
        self._tr._end_phase(self)
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        return False


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False


_NULL_SPAN = _NullSpan()


class Tracer:
    """Ring-buffer event recorder with Chrome trace-event export.

    ``profiled_only=True`` makes a recorder that keeps spans and events
    only while a profiler session runs (:data:`PROCESS_TRACER`); a
    default tracer records always.  Either writes its phase spans into
    the profiler trace while a session runs.
    """

    enabled = True

    def __init__(self, ring_events: int = 65536,
                 clock: Callable[[], float] = time.perf_counter, *,
                 profiled_only: bool = False):
        self._clock = clock
        self._ring_events = int(ring_events)
        self._profiled_only = profiled_only
        # refreshed once per tick by set_tick: whether a profiler session
        # runs (spans annotate the profiler trace), and whether to record
        self._annotate = False
        self._record = not profiled_only
        self.reset()

    # -- recording ---------------------------------------------------

    def reset(self) -> None:
        """Drop all recorded state; t=0 becomes "now"."""
        self.events: deque = deque(maxlen=self._ring_events)
        self._durations: dict[str, list[float]] = {}
        self._n_events = 0
        self._tick = 0
        self._t0 = self._clock()
        # async bookkeeping: which request ids have an open outer span /
        # an open "active" (admitted) span.
        self._begun: set = set()
        self._active: set = set()

    def set_tick(self, tick: int) -> None:
        """Default engine tick stamped on events that don't pass one.

        The engine calls this once per tick so deep callees (e.g. the
        prefix cache's reclaimer) don't need tick plumbing.  It is also
        where the tracer asks whether a profiler session runs.
        """
        self._tick = tick
        self._annotate = TraceAnnotation.is_enabled()
        if self._profiled_only:
            self._record = self._annotate

    @property
    def tick(self) -> int:
        return self._tick

    def phase(self, name: str, slot: int | None = None, **args):
        """Span context manager: ``with tr.phase("decode"): ...``."""
        if not self._record:
            return _NULL_SPAN
        return _PhaseSpan(self, name, self._tick, slot, args or None)

    def _end_phase(self, span: _PhaseSpan) -> None:
        t1 = self._clock()
        self._push({
            "kind": "phase", "name": span.name, "tick": span.tick,
            "slot": span.slot, "ts": span._t0, "end": t1,
            "args": span.args,
        })
        self._durations.setdefault(span.name, []).append(t1 - span._t0)

    def phase_span(self, name: str, t_start: float, t_end: float,
                   slot: int | None = None, **args) -> None:
        """Record an externally timed span.

        ``t_start``/``t_end`` must come from the same clock family as
        the tracer's clock (``time.perf_counter`` by default) — the
        engine uses this for spec draft/verify so the spans carry the
        *same* stamps that feed ``SpecStats.draft_s``/``verify_s`` and
        the two reconcile exactly.
        """
        if not self._record:
            return
        self._push({
            "kind": "phase", "name": name, "tick": self._tick,
            "slot": slot, "ts": t_start, "end": t_end, "args": args or None,
        })
        self._durations.setdefault(name, []).append(t_end - t_start)

    def spans(self, name: str, t_start: float, t_end: float) -> list[Span]:
        """The phase spans called ``name`` in the ring that lie within
        [``t_start``, ``t_end``] on the tracer's clock, in the order they
        ended."""
        return [Span(r["name"], r["ts"], r["end"], r["tick"])
                for r in self.events
                if r["kind"] == "phase" and r["name"] == name
                and t_start <= r["ts"] and r["end"] <= t_end]

    def instant(self, name: str, **args) -> None:
        """Point-in-time marker on the engine track (e.g. plan events)."""
        if not self._record:
            return
        self._push({
            "kind": "instant", "name": name, "tick": self._tick,
            "slot": None, "ts": self._clock(), "args": args or None,
        })

    def counter(self, name: str, values: dict) -> None:
        """Record a counter-track sample (Perfetto/Chrome "C" event).

        ``values`` maps series name -> number; successive samples of the
        same ``name`` render as stacked counter tracks (e.g. attributed
        device seconds by bound class, pool blocks in use).
        """
        if not self._record:
            return
        self._push({
            "kind": "counter", "name": name, "tick": self._tick,
            "slot": None, "ts": self._clock(),
            "args": {k: float(v) for k, v in values.items()},
        })

    def request_event(self, event: str, request_id, **args) -> None:
        """Record one lifecycle event for ``request_id``.

        ``submit``/``finish`` open/close the outer async span;
        ``admit``/``resume`` and ``preempt``/``finish`` open/close the
        inner "active" span, so a preempted request shows a gap between
        its active segments.  Everything else is an async instant.
        """
        if not self._record:
            return
        ts = self._clock()
        rec = {
            "kind": "request", "event": event, "req": request_id,
            "tick": self._tick, "ts": ts, "args": args or None,
        }
        if event == "submit":
            self._begun.add(request_id)
        elif event in ("admit", "resume"):
            self._active.add(request_id)
        elif event == "preempt":
            self._active.discard(request_id)
        elif event == "finish":
            rec["was_active"] = request_id in self._active
            rec["was_begun"] = request_id in self._begun
            self._active.discard(request_id)
            self._begun.discard(request_id)
        self._push(rec)

    def _push(self, rec: dict) -> None:
        self._n_events += 1
        self.events.append(rec)

    # -- summaries ---------------------------------------------------

    @property
    def events_dropped(self) -> int:
        return self._n_events - len(self.events)

    def phase_durations(self) -> dict[str, list[float]]:
        """Full-run per-phase duration lists (not ring-bounded)."""
        return self._durations

    def phase_summary(self) -> dict:
        """The metrics ``timing`` section: per-phase stats.

        Percentiles are ``np.percentile`` over the complete duration
        list, so they are an exact, deterministic function of the
        recorded durations (inject a fake ``clock`` for fully
        deterministic tests).
        """
        phases = {}
        for name in sorted(self._durations):
            durs = np.asarray(self._durations[name], dtype=np.float64)
            phases[name] = {
                "count": int(durs.size),
                "total_s": float(durs.sum()),
                "mean_s": float(durs.mean()),
                "p50_s": float(np.percentile(durs, 50)),
                "p99_s": float(np.percentile(durs, 99)),
            }
        return {
            "phases": phases,
            "events_recorded": self._n_events,
            "events_dropped": self.events_dropped,
        }

    # -- Chrome trace export -----------------------------------------

    def to_chrome(self) -> dict:
        """Export the ring as a Chrome trace-event JSON object.

        Layout: pid 1 carries phase spans ("X" complete events; tid 0 is
        the engine tick loop, tid 1+slot a per-slot track), pid 2 carries
        request lifecycles as async spans — an outer ``request`` span
        (submit→finish) plus inner ``active`` spans (admit→preempt /
        resume→finish) whose gaps are the preempted stretches.
        """
        out: list[dict] = []
        tids_seen: set[int] = set()
        # Async span state replayed from the (possibly wrapped) ring:
        # req -> begin ts for outer/inner spans.
        outer_open: dict = {}
        active_open: dict = {}

        def async_ev(ph, name, req, ts, args=None):
            ev = {
                "name": name, "cat": "request", "ph": ph,
                "ts": ts * _US, "pid": _PID_REQUESTS,
                "id": str(req),
            }
            if args:
                ev["args"] = args
            out.append(ev)

        for rec in self.events:
            ts = rec["ts"] - self._t0     # seconds since reset
            args = dict(rec.get("args") or {})
            if rec["tick"] is not None:
                args["tick"] = rec["tick"]
            if rec["kind"] == "phase":
                tid = 0 if rec["slot"] is None else 1 + int(rec["slot"])
                tids_seen.add(tid)
                out.append({
                    "name": rec["name"], "cat": "phase", "ph": "X",
                    "ts": ts * _US,
                    "dur": max(rec["end"] - rec["ts"], 0.0) * _US,
                    "pid": _PID_PHASES, "tid": tid, "args": args,
                })
            elif rec["kind"] == "instant":
                tids_seen.add(0)
                out.append({
                    "name": rec["name"], "cat": "engine", "ph": "i",
                    "s": "t", "ts": ts * _US,
                    "pid": _PID_PHASES, "tid": 0, "args": args,
                })
            elif rec["kind"] == "counter":
                # counter args must stay numeric series values — no tick
                tids_seen.add(0)
                out.append({
                    "name": rec["name"], "cat": "counter", "ph": "C",
                    "ts": ts * _US,
                    "pid": _PID_PHASES, "tid": 0,
                    "args": dict(rec.get("args") or {}),
                })
            else:  # request lifecycle
                event, req = rec["event"], rec["req"]
                if event == "submit":
                    async_ev("b", "request", req, ts, args)
                    outer_open[req] = ts
                elif event in ("admit", "resume"):
                    async_ev("b", "active", req, ts, args)
                    active_open[req] = ts
                elif event == "preempt":
                    if req in active_open:
                        async_ev("e", "active", req, ts, args)
                        active_open.pop(req, None)
                    async_ev("n", "request", req, ts, {"event": event, **args})
                elif event == "finish":
                    if rec.get("was_active") and req in active_open:
                        async_ev("e", "active", req, ts)
                        active_open.pop(req, None)
                    if rec.get("was_begun") and req in outer_open:
                        async_ev("e", "request", req, ts, args)
                        outer_open.pop(req, None)
                    else:
                        # begin fell off the ring (or was never recorded):
                        # degrade to an async instant so the file stays
                        # balanced.
                        async_ev("n", "request", req, ts,
                                 {"event": event, **args})
                else:  # chunk / first-token / rewind / ...
                    async_ev("n", "request", req, ts, {"event": event, **args})

        # Close spans still open at export time at the last known ts so
        # viewers don't render them as unbounded.
        t_end = max((ev["ts"] for ev in out), default=0.0) / _US
        for req in list(active_open):
            async_ev("e", "active", req, t_end, {"open_at_export": True})
        for req in list(outer_open):
            async_ev("e", "request", req, t_end, {"open_at_export": True})

        meta = [
            {"name": "process_name", "ph": "M", "pid": _PID_PHASES,
             "args": {"name": "engine phases"}},
            {"name": "process_name", "ph": "M", "pid": _PID_REQUESTS,
             "args": {"name": "requests"}},
        ]
        for tid in sorted(tids_seen):
            label = "tick loop" if tid == 0 else f"slot {tid - 1}"
            meta.append({"name": "thread_name", "ph": "M",
                         "pid": _PID_PHASES, "tid": tid,
                         "args": {"name": label}})

        return {
            "traceEvents": meta + out,
            "displayTimeUnit": "ms",
            "otherData": {
                "events_recorded": self._n_events,
                "events_dropped": self.events_dropped,
            },
        }

    def save(self, path) -> dict:
        """Write the Chrome trace JSON to ``path``; returns the object."""
        obj = self.to_chrome()
        with open(path, "w") as f:
            json.dump(obj, f)
        return obj


class NullTracer:
    """Inert tracer: every method is a no-op and no clock is ever read."""

    enabled = False

    _SPAN = _NULL_SPAN

    def reset(self) -> None:
        pass

    def set_tick(self, tick: int) -> None:
        pass

    def phase(self, name: str, slot: int | None = None, **args):
        return self._SPAN

    def phase_span(self, name, t_start, t_end, slot=None, **args) -> None:
        pass

    def instant(self, name: str, **args) -> None:
        pass

    def counter(self, name: str, values: dict) -> None:
        pass

    def request_event(self, event: str, request_id, **args) -> None:
        pass

    def phase_durations(self) -> dict:
        return {}

    def phase_summary(self) -> dict:
        return {}


NULL_TRACER = NullTracer()

# The process-wide recorder: what an engine given no tracer writes to.  It
# records only while a profiler session runs, so the benchmark's traced
# stretch can read it (``spans``) with no tracer passed in.
PROCESS_TRACER = Tracer(profiled_only=True)


def validate_chrome_trace(obj, require_phases=(), min_requests: int = 0,
                          min_preempts: int = 0) -> dict:
    """Validate a Chrome trace-event JSON object; raise ``ValueError``.

    Checks structural well-formedness (every event has name/ph/ts; "X"
    events have a non-negative ``dur``; async begin/end balance per
    ``(id, name)``), then the content floor: every phase named in
    ``require_phases`` has at least one span, at least ``min_requests``
    distinct requests have a complete submit→finish span, and at least
    ``min_preempts`` preempt markers are present.  Returns a summary
    dict (phase span counts, request count, preempt count).
    """
    if not isinstance(obj, dict) or not isinstance(obj.get("traceEvents"), list):
        raise ValueError("not a Chrome trace: top-level 'traceEvents' list missing")
    phase_spans: dict[str, int] = {}
    async_depth: dict[tuple, int] = {}
    completed_requests: set = set()
    preempts = 0
    counter_samples = 0
    for i, ev in enumerate(obj["traceEvents"]):
        if not isinstance(ev, dict):
            raise ValueError(f"event {i} is not an object")
        ph = ev.get("ph")
        if not isinstance(ev.get("name"), str) or ph is None:
            raise ValueError(f"event {i} missing name/ph")
        if ph == "M":
            continue
        if not isinstance(ev.get("ts"), (int, float)):
            raise ValueError(f"event {i} ({ev['name']!r}) missing numeric ts")
        if ph == "C":
            vals = ev.get("args")
            if not isinstance(vals, dict) or not all(
                    isinstance(v, (int, float)) for v in vals.values()):
                raise ValueError(
                    f"counter event {i} ({ev['name']!r}) args must be "
                    f"numeric series values: {vals!r}")
            counter_samples += 1
        elif ph == "X":
            dur = ev.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                raise ValueError(f"event {i} ({ev['name']!r}) bad dur: {dur!r}")
            if ev.get("cat") == "phase":
                phase_spans[ev["name"]] = phase_spans.get(ev["name"], 0) + 1
        elif ph in ("b", "e", "n"):
            if "id" not in ev:
                raise ValueError(f"async event {i} ({ev['name']!r}) missing id")
            key = (ev["id"], ev["name"])
            if ph == "b":
                async_depth[key] = async_depth.get(key, 0) + 1
            elif ph == "e":
                depth = async_depth.get(key, 0)
                if depth <= 0:
                    raise ValueError(
                        f"async end without begin for id={ev['id']!r} "
                        f"name={ev['name']!r}")
                async_depth[key] = depth - 1
                if ev["name"] == "request":
                    completed_requests.add(ev["id"])
            else:
                if (ev.get("args") or {}).get("event") == "preempt":
                    preempts += 1
    unbalanced = {k: d for k, d in async_depth.items() if d != 0}
    if unbalanced:
        raise ValueError(f"unbalanced async spans: {sorted(unbalanced)[:5]}")
    missing = [p for p in require_phases if phase_spans.get(p, 0) < 1]
    if missing:
        raise ValueError(
            f"required phases with no spans: {missing} "
            f"(present: {sorted(phase_spans)})")
    if len(completed_requests) < min_requests:
        raise ValueError(
            f"only {len(completed_requests)} completed request spans, "
            f"need >= {min_requests}")
    if preempts < min_preempts:
        raise ValueError(f"only {preempts} preempt markers, need >= {min_preempts}")
    return {
        "events": len(obj["traceEvents"]),
        "phase_spans": phase_spans,
        "completed_requests": len(completed_requests),
        "preempts": preempts,
        "counter_samples": counter_samples,
    }
