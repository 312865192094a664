"""What one run measured, as the metric readers (``bench/metrics``) see it."""
from __future__ import annotations

import dataclasses

import numpy as np

from bench import work


@dataclasses.dataclass
class RunData:
    records: dict                   # rid -> client.Record
    ticks: list                     # client.TickRecord, in order
    window: tuple[float, float]     # open, close (host clock, s)
    setup_s: float
    dims: dict                      # bench.models.<family>.dims(config)
    peak: dict                      # this chip's row of bench/peaks.json
    trace: object = None            # trace.TraceSummary of a traced run
    traced: tuple[float, float] | None = None   # host span of the trace
    pool: tuple[int, int] | None = None   # KV pool: usable blocks, block size

    @staticmethod
    def percentile(values, q, scale=1.0):
        if not len(values):
            return None
        return float(np.percentile(np.asarray(values, np.float64), q)) * scale

    def attempted(self) -> list:
        t0, t1 = self.window
        return [r for r in self.records.values() if t0 <= r.due_s < t1]

    def ttfts_s(self) -> list[float]:
        return [r.token_s[0] - r.due_s for r in self.attempted() if r.token_s]

    def token_gaps_s(self) -> list[float]:
        """Every gap between consecutive output tokens of a request that
        ends in the window."""
        t0, t1 = self.window
        return [b - a for r in self.records.values()
                for a, b in zip(r.token_s, r.token_s[1:]) if t0 < b <= t1]

    def window_ticks(self) -> list:
        t0, t1 = self.window
        return [t for t in self.ticks if t0 <= t.start_s and t.end_s <= t1]

    def traced_ticks(self) -> list:
        if self.traced is None:
            return []
        t0, t1 = self.traced
        return [t for t in self.ticks if t0 <= t.start_s and t.end_s <= t1]

    def model_flops(self, ticks) -> float:
        """Model FLOPs of the real tokens the given ticks processed: each
        prefill chunk's tokens at their positions plus one logits row, and
        each decode token at its position plus its logits row."""
        m, total = self.dims, 0.0
        for t in ticks:
            for first, count in t.prefill_tokens:
                total += work.span_flops(m, first, count)
                total += work.unembed_flops(m)
            for pos in t.decode_positions:
                total += work.span_flops(m, pos, 1) + work.unembed_flops(m)
        return total

    def gemm_roofline(self, program: str) -> float | None:
        """Least time of the weight GEMMs of every execution of
        ``program`` in the trace over the device time of the kernels that
        ran them (%)."""
        if self.trace is None:
            return None
        prog = self.trace.programs.get(program)
        if prog is None or not prog.count or prog.gemm_s is None:
            return None
        least = sum(work.least_time_s(work.step_gemms(self.dims, rows, logit_rows),
                                      self.peak)
                    for rows, logit_rows in prog.rows)
        return 100.0 * least / prog.gemm_s
