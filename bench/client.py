"""The benchmark's client loop and request log.

The loop submits what the traffic source says is due, calls the engine's
``tick()``, and then stamps, on the host clock, every token that tick
produced: a client sees a token when the tick that made it returns. All
times are ``time.perf_counter()`` seconds.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np


@dataclasses.dataclass
class Record:
    """What one request did, as the client saw it."""

    rid: int
    due_s: float
    prompt: np.ndarray
    max_new_tokens: int
    bind_s: float | None = None           # tick start at which a lane took it
    token_s: list[float] = dataclasses.field(default_factory=list)
    tokens: list[int] = dataclasses.field(default_factory=list)
    finished_s: float | None = None


@dataclasses.dataclass
class TickRecord:
    start_s: float
    end_s: float
    decode_lanes: int                     # lanes the decode step served
    prefill_tokens: list[tuple[int, int]]  # (first position, count)
    decode_positions: list[int]
    kv_tokens: int = 0                    # KV positions the live lanes hold
    kv_blocks: int = 0                    # pool blocks reserved for them


class Client:
    """Drives ``engine`` with requests from ``source`` and logs them."""

    def __init__(self, engine, source, *, request_cls, annotate=False):
        self.engine = engine
        self.source = source
        self.request_cls = request_cls
        self.annotate = annotate
        self.records: dict[int, Record] = {}
        self.ticks: list[TickRecord] = []
        self._of_req: dict[int, int] = {}     # engine request id -> rid
        self._done_idx = 0
        self._occ = engine.metrics.occupancy_sum

    def _span(self, name: str):
        return (jax.profiler.TraceAnnotation(name) if self.annotate
                else _NULL_SPAN)

    def submit_due(self) -> None:
        with self._span("bench.submit"):
            now = time.perf_counter()
            for p in self.source.due(now):
                req = self.request_cls(prompt=p.prompt,
                                       max_new_tokens=p.max_new_tokens)
                self._of_req[req.request_id] = p.rid
                self.records[p.rid] = Record(p.rid, p.due_s, p.prompt,
                                             p.max_new_tokens)
                self.engine.submit(req)

    def tick(self) -> None:
        eng = self.engine
        live = [st for st in eng.sched.slots if st is not None]
        before = {id(st): (st.prefill_done, len(st.tokens)) for st in live}
        start = time.perf_counter()
        with self._span("bench.tick"):
            eng.tick()
        end = time.perf_counter()
        with self._span("bench.log"):
            self._log(start, end, before)

    def _log(self, start: float, end: float, before: dict) -> None:
        eng = self.engine
        done = eng.sched.finished[self._done_idx:]
        self._done_idx = len(eng.sched.finished)
        seen = [st for st in eng.sched.slots if st is not None] + done
        prefill, decode = [], []
        for st in seen:
            rec = self.records[self._of_req[st.request.request_id]]
            if rec.bind_s is None:
                rec.bind_s = start
            pd0, nt0 = before.get(id(st), (0, 0))
            if st.prefill_done > pd0:
                prefill.append((pd0, st.prefill_done - pd0))
            new = st.tokens[nt0:]
            # token 0 comes from the prefill's logits; token j >= 1 from a
            # decode step over token j - 1, at position prompt + j - 1
            decode.extend(st.prefill_done + j - 1
                          for j in range(max(nt0, 1), len(st.tokens)))
            rec.tokens.extend(new)
            rec.token_s.extend([end] * len(new))
        for st in done:
            rec = self.records[self._of_req[st.request.request_id]]
            rec.finished_s = end
            self.source.finished(rec.rid, end)
        occ = eng.metrics.occupancy_sum
        live = [st for st in eng.sched.slots if st is not None]
        kv_tokens = sum(st.prefill_done + max(len(st.tokens) - 1, 0)
                        for st in live)
        pool = eng.sched.pool
        self.ticks.append(TickRecord(
            start, end, occ - self._occ, prefill, decode, kv_tokens,
            pool.blocks_in_use if pool is not None else 0))
        self._occ = occ

    def step(self) -> None:
        self.submit_due()
        self.tick()


class _NullSpan:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()
