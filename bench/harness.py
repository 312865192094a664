"""One run of one cell: set-up, the measured window, the check, the result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up makes the configuration's weights on the device from the seed,
builds the serving engine under test, warms its GEMM plans and every
program the window runs (each prefill bucket and the decode step), and
fills the lanes with the cell's traffic: until every client's first
request has its first token, and the mix's ``fill_completions`` requests
have finished. The window then drives
``ServeEngine.tick()`` from the benchmark's client loop for ``--seconds``
seconds; after it, the loop runs on until every request due in the window
has its first token (or the mix's drain cap passes). Then the peak device
memory is read, the engine's state is freed, and the float32 reference
checks a seeded sample of the requests finished in the window.

``--trace 1`` records a profiler trace of the window's last few seconds
and reports the cell's per-layer metrics; ``--trace 0`` its end-to-end
metrics. The last line of standard output is one JSON object.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

from bench import check, spec
from bench.client import Client
from bench.rundata import RunData

TRACE_S = 3.0           # length of the traced stretch at the window's end


class CompileClock:
    """Seconds and count of JAX backend compiles or persistent-cache reads
    since construction, and how many of them missed the cache."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration_secs, **kwargs):
        if event == self.EVENT:
            self.seconds += duration_secs
            self.count += 1

    def _on_event(self, event, **kwargs):
        if event == self.MISS:
            self.misses += 1


def process_start_perf() -> float:
    """This process's start time on the ``time.perf_counter`` clock."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    age = uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    return time.perf_counter() - age


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def use_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    when set, else a fixed directory inside the checkout."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".bench_cache" / "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def chip(cell, peaks: dict):
    """The device this cell runs on and its peaks; raises without a TPU,
    with fewer chips than the cell asks for, or on a chip missing from
    the peaks table."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"no TPU: JAX's device 0 is {dev.platform!r}; "
                         f"this benchmark runs only on the chip")
    if len(devices) < cell.chips:
        raise SystemExit(f"cell {cell.name} needs {cell.chips} chips, "
                         f"JAX finds {len(devices)}")
    if dev.device_kind not in peaks:
        raise SystemExit(f"device_kind {dev.device_kind!r} is not in "
                         f"bench/peaks.json (known: {sorted(peaks)})")
    return dev, peaks[dev.device_kind]


def main(argv=None, *, root: Path | None = None, require_tpu: bool = True,
         fault=None, keep_trace: Path | None = None) -> int:
    """Run one cell once; returns the exit code. ``require_tpu=False``,
    ``fault`` and ``keep_trace`` are for the benchmark's own tests: they
    skip the look for a chip, break the timed path underneath, and keep a
    copy of the traced run's ``.xplane.pb``."""
    t_process = process_start_perf()
    args = parse_args(argv)
    root = root or spec.BENCH_DIR.parent
    if not (root / "src" / "repro").is_dir():
        print(f"bench: no program under test at {root / 'src' / 'repro'}",
              file=sys.stderr)
        return 1
    cell = spec.find_cell(args.workload, root)
    with open(cell.bench_dir / "peaks.json") as f:
        peaks = json.load(f)
    import jax

    use_compile_cache(root)
    if require_tpu:
        try:
            dev, peak = chip(cell, peaks)
        except SystemExit as e:
            print(f"bench: {e}", file=sys.stderr)
            return 1
    else:
        dev, peak = jax.devices()[0], next(iter(peaks.values()))
    result = run(cell, args, dev, peak, t_process=t_process, root=root,
                 fault=fault, keep_trace=keep_trace)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


def _trace_options():
    """Device ops and the benchmark's own host spans; no Python call
    tracing, which would slow the host loop and fill the trace."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def _limits(cell) -> dict:
    with open(cell.bench_dir / "limits" / f"{cell.name}.json") as f:
        return json.load(f)


def run(cell, args, dev, peak, *, t_process, root, fault=None,
        quantize=None, keep_trace=None) -> dict:
    """One run of ``cell``; ``quantize`` serves the program's own int8
    path instead of the configuration's precision (the control)."""
    import jax

    fam = cell.family
    clock = CompileClock()
    split = {"start_s": time.perf_counter() - t_process}

    t = time.perf_counter()
    weights = fam.init_weights(cell.config, args.seed)
    jax.block_until_ready(weights)
    split["weights_s"] = time.perf_counter() - t

    t = time.perf_counter()
    program = fam.program(cell.config, weights, quantize=quantize)
    if quantize is not None:
        # the quantized tree replaces the bf16 one on the device; the
        # reference gets the same bf16 weights again from the seed
        weights = None
    served = fam.Served(
        cell.config, cell.mix, program,
        device_kind=dev.device_kind if dev.platform == "tpu" else None,
        plan_path=root / ".bench_cache" / f"plans-{cell.config['name']}.json",
        generators=cell.generator, quantize=quantize)
    del program
    with served:
        return _serve(cell, args, dev, peak, served, weights, clock, split,
                      t, t_process, fault, keep_trace)


def _serve(cell, args, dev, peak, served, weights, clock, split, t,
           t_process, fault, keep_trace):
    import jax

    from bench import trace as trace_lib

    fam, engine = cell.family, served.engine
    plans = served.plan_warmup()
    split["engine_and_plans_s"] = time.perf_counter() - t

    t = time.perf_counter()
    served.warm_programs()
    split["program_warmup_s"] = time.perf_counter() - t

    if fault is not None:
        fault(served)
    source = cell.generator.Source(cell.mix, seed=args.seed,
                                   vocab_size=fam.dims(cell.config)["vocab"])
    client = Client(engine, source, request_cls=served.request_cls,
                    annotate=bool(args.trace))
    if args.trace:
        trace_lib.annotate_steps(engine)

    t = time.perf_counter()
    source.start(t)
    # the first step submits every client's first request
    while (not client.records
           or len(engine.sched.finished) < cell.mix["fill_completions"]
           or any(not r.token_s for r in client.records.values())):
        client.step()
    split["lane_fill_s"] = time.perf_counter() - t
    split["compile_s"] = clock.seconds
    split["compiles"] = clock.count
    split["compile_cache_misses"] = clock.misses
    compiles0, plan0 = clock.count, served.lazy_solves()

    t_open = time.perf_counter()
    setup_s = t_open - t_process
    t_close = t_open + args.seconds
    trace_s = min(TRACE_S, args.seconds / 2)
    tmp, traced, span = None, None, None
    while time.perf_counter() < t_close:
        if (args.trace and tmp is None
                and time.perf_counter() >= t_close - trace_s):
            tmp = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(tmp, profiler_options=_trace_options())
            span = jax.profiler.TraceAnnotation("bench.traced")
            span.__enter__()
            traced = [time.perf_counter(), None]
        client.step()
    if span is not None:
        traced[1] = time.perf_counter()
        span.__exit__(None, None, None)
        jax.profiler.stop_trace()
    t_end = client.ticks[-1].end_s
    compiles = clock.count - compiles0
    lazy = served.lazy_solves() - plan0

    due = [r for r in client.records.values() if t_open <= r.due_s < t_end]
    cap = time.perf_counter() + cell.mix["drain_cap_s"]
    while any(not r.token_s for r in due) and time.perf_counter() < cap:
        client.step()
    source.stop()
    failed = sum(1 for r in due if not r.token_s)

    stats = dev.memory_stats() or {}
    memory_peak = stats.get("peak_bytes_in_use")
    kv = engine.sched.pool
    pool = (kv.usable_blocks, kv.block_size) if kv is not None else None
    served.release()
    del engine
    gc.collect()

    summary, notes = None, []
    if tmp is not None:
        try:
            xplane = trace_lib.find_xplane(tmp)
            if keep_trace is not None:
                Path(keep_trace).parent.mkdir(parents=True, exist_ok=True)
                shutil.copy(xplane, keep_trace)
            summary = trace_lib.reduce(xplane,
                                       layers=fam.dims(cell.config)["layers"],
                                       slots=cell.mix["clients"])
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        for prog in summary.programs.values():
            if prog.gemm_mismatch:
                notes.append(f"GEMM roofline left out: {prog.gemm_mismatch}")
                print(f"bench: {notes[-1]}", file=sys.stderr)

    data = RunData(records=client.records, ticks=client.ticks,
                   window=(t_open, t_end), setup_s=setup_s,
                   dims=fam.dims(cell.config), peak=peak, trace=summary,
                   traced=tuple(traced) if traced else None, pool=pool)
    wanted = cell.per_layer if args.trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = cell.reader(m["name"])(data)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    t = time.perf_counter()
    if weights is None:
        weights = fam.init_weights(cell.config, args.seed)
    finished = [r for r in client.records.values()
                if r.finished_s is not None and t_open <= r.finished_s <= t_end]
    sample = check.sample(finished, args.seed, cell.mix["check_tokens"])
    gap = check.logit_gaps(cell.reference, weights, fam.dims(cell.config),
                           sample)
    check_s = time.perf_counter() - t
    limits = _limits(cell)
    checks = {
        "mean_logit_gap": {"value": gap["mean_gap"],
                           "limit": limits["mean_logit_gap"]["limit"]},
        "compiles_in_window": {"value": compiles, "limit": 0},
        "lazy_plan_solves": {"value": lazy, "limit": 0},
    }
    correct = bool(sample) and all(c["value"] <= c["limit"]
                                   for c in checks.values())
    result = {
        "correct": correct,
        "attempted": len(due),
        "failed": failed,
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices()),
                   "memory_peak_bytes": memory_peak},
    }
    if summary is not None:
        result["device"].update(busy_s=summary.busy_s,
                                window_s=summary.window_s)
        result["breakdown"] = summary.breakdown
        result["trace"] = _programs(summary)
    if notes:
        result["notes"] = notes
    result["setup"] = {**split, "setup_s": setup_s,
                       "plan_signatures": plans["signatures"],
                       "plans_solved": plans["solved"]}
    result["run"] = {"window_s": t_end - t_open,
                     "requests_finished_in_window": len(finished),
                     "tokens_in_window": sum(
                         1 for r in client.records.values()
                         for s in r.token_s if t_open < s <= t_end),
                     "widest_logit_gap": gap["gap"],
                     "checked_requests": gap["requests"],
                     "checked_tokens": gap["tokens"],
                     "argmax_agree": gap["argmax_agree"],
                     "check_s": check_s,
                     **_spread(data)}
    result["checks"] = checks
    return result


def _programs(summary) -> dict:
    """Per step program: executions, and device, GEMM-kernel and
    weight-staging milliseconds per execution in the traced stretch."""
    out = {}
    for kind, prog in summary.programs.items():
        n = max(prog.count, 1)
        out[kind] = {"executions": prog.count,
                     "device_ms": 1e3 * prog.device_s / n,
                     "gemm_kernel_ms": 1e3 * prog.kernel_s / n,
                     "weight_staging_ms": 1e3 * prog.staging_s / n,
                     "gemm_mismatch": prog.gemm_mismatch}
    return out


def _spread(data) -> dict:
    """Percentiles of the window's first-token times and token gaps, and
    the KV the window's ticks held: positions written and blocks reserved,
    mean and most, against the pool."""
    gaps = data.token_gaps_s()
    ttft = data.ttfts_s()
    ticks = data.window_ticks()
    kv_tok = [t.kv_tokens for t in ticks] or [0]
    kv_blk = [t.kv_blocks for t in ticks] or [0]
    return {
        "ttft_ms": {q: data.percentile(ttft, q, 1e3) for q in (50, 90, 95)},
        "itl_ms": {q: data.percentile(gaps, q, 1e3)
                   for q in (50, 90, 95, 98, 99)},
        "kv_tokens_mean": sum(kv_tok) / len(kv_tok),
        "kv_tokens_max": max(kv_tok),
        "kv_blocks_mean": sum(kv_blk) / len(kv_blk),
        "kv_blocks_max": max(kv_blk),
        "kv_pool_blocks": data.pool[0] if data.pool else None,
    }
