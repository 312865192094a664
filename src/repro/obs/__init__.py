"""repro.obs — zero-cost-when-off observability for the serving stack.

Two complementary instruments (docs/observability.md):

* ``trace`` — a bounded ring-buffer event tracer recording per-tick
  **phase spans** (tick, expire, bind, prefill-chunk, decode, wait,
  fetch, sample, account, ...; also written into the profiler trace as
  ``serve.<phase>`` while a profiler session runs) and per-request
  **lifecycle events** (submit, admit, chunk, first-token, preempt,
  resume, rewind, finish), each stamped with both a host
  ``perf_counter`` time and the engine tick. Exports Chrome trace-event JSON that loads directly in Perfetto
  or ``chrome://tracing`` — slots as tracks, requests as async spans.
* ``registry`` — a counter/gauge/histogram registry with Prometheus
  text exposition and periodic snapshots, onto which the engine's
  subsystem counters (block pool, prefix cache, plan cache, SpecStats,
  budget controller) are published.
* ``attrib`` — the balance auditor: a per-signature GEMM attribution
  ledger joining traced phase seconds against the analytic balance
  model (compute-/memory-bound vs drifted plans; metrics.json
  ``attribution`` section, ``repro_attrib_*`` gauges, re-solve
  candidates for ``--rebalance-drifted``).

Both are off by default: an engine given no tracer writes to the
``PROCESS_TRACER``, which records only while a JAX profiler session runs
and otherwise never reads a clock, so an untraced run is bit-identical
(output *and* metrics JSON) to a build without this package.
"""
from repro.obs.attrib import GEMM_PHASES, AttributionLedger
from repro.obs.registry import (Counter, Gauge, Histogram, Registry,
                                prom_name)
from repro.obs.trace import (NULL_TRACER, PHASES, PROCESS_TRACER,
                             NullTracer, Span, Tracer, validate_chrome_trace)

__all__ = [
    "Tracer", "NullTracer", "NULL_TRACER", "PROCESS_TRACER", "PHASES",
    "Span",
    "validate_chrome_trace",
    "Registry", "Counter", "Gauge", "Histogram", "prom_name",
    "AttributionLedger", "GEMM_PHASES",
]
