#!/usr/bin/env python
"""serve-doctor: offline bottleneck report over serve metrics + trace.

Ingests the metrics JSON a serve-engine run wrote (``--metrics-json``)
and, optionally, its flight-recorder Chrome trace (``--trace-out``), and
prints a ranked diagnosis: where the tick time went per phase, which
GEMM signatures dominate and whether the balance auditor considers them
compute-bound, memory-bound or *drifted* (with the suggested re-solve),
how hard the block pool / prefix trie are being pressed, and which SLO
classes are burning their error budget::

  PYTHONPATH=src python tools/serve_doctor.py serve_metrics.json \\
      --trace serve_trace.json --report serve_doctor.txt

CI gates on it: ``--max-reconciliation-error`` fails the build when the
auditor's per-signature attribution stops reconciling with the traced
phase totals (the join is broken or a phase went unattributable), and
``--fail-on-drift`` fails when any warm plan reads as drifted (a stale
or perturbed plan cache survived into the smoke).
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path


def _fmt(v, spec: str = ".3f", none: str = "n/a") -> str:
    return none if v is None else format(v, spec)


def _section(lines: list[str], title: str) -> None:
    lines.append("")
    lines.append(title)
    lines.append("-" * len(title))


def _phase_report(lines: list[str], timing: dict) -> list[str]:
    """Ranked per-phase time table; returns diagnosis strings.

    Spans nest (every phase inside ``tick``; ``wait`` and ``fetch``
    inside ``decode``), so shares are of the ticks' total time."""
    findings: list[str] = []
    phases = timing.get("phases", {})
    if not phases:
        lines.append("(untraced run: no timing section — rerun with "
                     "--trace-out for phase and attribution analysis)")
        return findings
    tick_s = phases.get("tick", {}).get("total_s")
    total = tick_s or sum(p["total_s"] for p in phases.values()) or 1.0
    ranked = sorted(phases.items(), key=lambda kv: -kv[1]["total_s"])
    lines.append(f"{'phase':<16} {'count':>7} {'total_s':>9} "
                 f"{'share':>6} {'mean_s':>10} {'p99_s':>10}")
    for name, p in ranked:
        lines.append(
            f"{name:<16} {p['count']:>7} "
            f"{p['total_s']:>9.3f} {p['total_s']/total:>6.2f} "
            f"{p['mean_s']:>10.5f} {p['p99_s']:>10.5f}")
    lines.append(f"{timing.get('events_dropped', 0)} events dropped")
    top_name, top = next(((n, p) for n, p in ranked if n != "tick"),
                         ranked[0])
    findings.append(
        f"top phase: {top_name} with {top['total_s']:.3f}s "
        f"({top['total_s']/total:.0%} of tick time)")
    if timing.get("events_dropped", 0):
        findings.append(
            f"tracer dropped {timing['events_dropped']} events — raise "
            f"--trace-ring-events for a complete timeline")
    if tick_s:
        # the host's part of a tick: everything but blocking on the device
        wait_s = phases.get("wait", {}).get("total_s", 0.0)
        host_s = tick_s - wait_s
        if host_s > wait_s:
            findings.append(
                f"host-bound: {host_s:.3f}s of {tick_s:.3f}s tick time "
                f"not waiting on the device — dispatch, sampling and "
                f"bookkeeping outweigh the wait for logits")
    return findings


def _attrib_report(lines: list[str], attrib: dict, top: int) -> list[str]:
    findings: list[str] = []
    if not attrib:
        lines.append("(no attribution section — traced runs only)")
        return findings
    recon = attrib.get("reconciliation_error")
    lines.append(
        f"{attrib['signatures']} signatures, attributed "
        f"{attrib['attributed_device_s']:.3f}s of "
        f"{attrib['traced_device_s']:.3f}s traced GEMM-phase device time "
        f"(reconciliation error {_fmt(recon)})")
    shares = attrib.get("bound_share", {})
    lines.append("bound shares: " + ", ".join(
        f"{k}={_fmt(shares.get(k), '.2f')}"
        for k in ("compute", "memory", "drifted")))
    rows = attrib.get("by_device_s", [])[:top]
    if rows:
        lines.append(f"{'signature':<40} {'device_s':>9} {'share':>6} "
                     f"{'calls':>7} {'bound':>8} {'ratio':>7} {'drift':>6}")
        for r in rows:
            lines.append(
                f"{r['key']:<40} {r['device_s']:>9.3f} "
                f"{_fmt(r['share'], '.2f'):>6} {r['calls']:>7} "
                f"{r['bound']:>8} {_fmt(r['balance_ratio'], '.2f'):>7} "
                f"{'YES' if r['drifted'] else '-':>6}")
    for key in attrib.get("drifted", []):
        row = next((r for r in attrib.get("by_device_s", [])
                    if r["key"] == key), None)
        msg = f"drifted plan {key}"
        if row is not None:
            msg += (f": cached bm={row['bm']} bk={row['bk']} bn={row['bn']}"
                    f" (ratio dev {_fmt(row['ratio_deviation'])}, time dev "
                    f"{_fmt(row['time_deviation'])})")
            if row.get("suggested_bm") is not None:
                msg += (f" — re-solve to bm={row['suggested_bm']} "
                        f"bk={row['suggested_bk']} bn={row['suggested_bn']} "
                        f"(x{_fmt(row['suggested_gain'], '.2f')} modeled); "
                        f"run with --rebalance-drifted")
        lines.append(msg)
        findings.append(msg)
    if recon is not None and recon > 0.05:
        findings.append(
            f"attribution reconciliation error {recon:.3f} — a GEMM phase "
            f"went unattributable (missing warm-up profile or dispatch "
            f"counts)")
    share = shares.get("memory")
    if share is not None and share > 0.75:
        findings.append(
            f"{share:.0%} of attributed device time is memory-bound — "
            f"quantization (--quantize/--kv-quantize) moves this directly")
    return findings


def _pressure_report(lines: list[str], m: dict) -> list[str]:
    findings: list[str] = []
    bp = m.get("block_pool", {})
    agg = m.get("aggregate", {})
    if bp:
        cap = bp.get("num_blocks", 0) - 1
        lines.append(
            f"block pool: peak {bp.get('peak_in_use')}/{cap} blocks "
            f"({_fmt(bp.get('peak_utilization'), '.2f')} util), "
            f"{bp.get('failed_allocs', 0)} failed allocs, "
            f"{agg.get('deferred_admissions', 0)} deferred admissions, "
            f"peak frag {bp.get('peak_fragmentation_tokens', 0)} tokens")
        util = bp.get("peak_utilization")
        if util is not None and util >= 0.95:
            findings.append(
                f"block pool peaked at {util:.0%} utilization with "
                f"{agg.get('deferred_admissions', 0)} deferred admissions — "
                f"grow --num-kv-blocks or enable --kv-quantize")
    else:
        lines.append("block pool: n/a (contiguous KV layout)")
    px = m.get("prefix_cache", {})
    if px:
        lines.append(
            f"prefix cache: hit {px.get('hit_tokens')}/"
            f"{px.get('lookup_tokens')} tokens "
            f"(rate {_fmt(px.get('hit_rate'), '.2f')}), "
            f"{px.get('inserted_blocks')} cached, "
            f"{px.get('reclaimed_blocks')} reclaimed")
        rate = px.get("hit_rate")
        if rate is not None and rate < 0.1 and px.get("lookup_tokens"):
            findings.append(
                f"prefix cache hit rate {rate:.2f} — the trie is overhead "
                f"on this traffic; drop --prefix-cache or check header "
                f"sharing")
    plan = m.get("plan_cache", {})
    if plan:
        lines.append(
            f"plan cache: hits={plan.get('hits')} "
            f"misses={plan.get('misses')} "
            f"lazy_solves={plan.get('lazy_solves')} "
            f"steady_state={plan.get('steady_state')}")
        if plan.get("steady_state") is False:
            findings.append(
                f"plan cache fell out of steady state "
                f"({plan.get('lazy_solves')} lazy solves) — warm-up missed "
                f"signatures; the decode loop is paying solver latency")
    return findings


def _slo_report(lines: list[str], m: dict) -> list[str]:
    findings: list[str] = []
    burn = m.get("slo_burn", {})
    classes = burn.get("classes", {})
    if not classes:
        lines.append("(no finished requests)")
        return findings
    lines.append(
        f"target_ttft_s={_fmt(burn.get('target_ttft_s'))} "
        f"window={burn.get('window')} "
        f"budget_miss_rate={_fmt(burn.get('budget_miss_rate'), '.2f')}")
    for prio in sorted(classes, key=int):
        c = classes[prio]
        lines.append(
            f"priority {prio}: {c['misses_in_window']}/{c['window_n']} "
            f"misses in window (rate {_fmt(c['rolling_miss_rate'], '.2f')}, "
            f"burn {_fmt(c['burn_rate'], '.2f')})"
            + ("  ** ALERT **" if c["alert"] else ""))
        if c["alert"]:
            findings.append(
                f"priority {prio} burning its SLO budget at "
                f"{c['burn_rate']:.1f}x — raise --max-prefill-chunks, "
                f"shrink the TTFT target, or shed class load")
    return findings


def _trace_check(lines: list[str], trace_path: str) -> list[str]:
    """Validate the Chrome trace and summarize what it carries."""
    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
    from repro.obs.trace import validate_chrome_trace
    with open(trace_path) as f:
        obj = json.load(f)
    try:
        info = validate_chrome_trace(obj)
    except ValueError as e:
        lines.append(f"trace INVALID: {e}")
        return [f"trace file {trace_path} failed validation: {e}"]
    spans = sum(info["phase_spans"].values())
    lines.append(
        f"trace OK: {info['events']} events, {spans} phase spans across "
        f"{len(info['phase_spans'])} phases, {info['completed_requests']} "
        f"completed requests, {info['counter_samples']} counter samples")
    return []


def doctor(m: dict, *, trace: str | None = None, top: int = 8) -> tuple[str, list[str]]:
    """Build the report text and the ranked diagnosis list."""
    lines: list[str] = []
    findings: list[str] = []
    eng = m.get("engine", {})
    agg = m.get("aggregate", {})
    tps = agg.get("tokens_per_tick")
    lines.append("serve-doctor report")
    lines.append("===================")
    lines.append(
        f"engine: arch={eng.get('arch')} hw={eng.get('hw')} "
        f"backend={eng.get('backend')} slots={eng.get('num_slots')} "
        f"paged={eng.get('paged')} policy={agg.get('policy')}")
    lines.append(
        f"run: {agg.get('ticks')} ticks, {agg.get('generated_tokens')} "
        f"tokens ({_fmt(tps, '.2f')} tok/tick), "
        f"{agg.get('admissions')} admissions, "
        f"{agg.get('preemptions')} preemptions, "
        f"{agg.get('deadline_missed')} deadline misses")
    if trace:
        _section(lines, "Trace")
        findings += _trace_check(lines, trace)
    _section(lines, "Phase bottlenecks")
    findings += _phase_report(lines, m.get("timing", {}))
    _section(lines, "Balance attribution")
    findings += _attrib_report(lines, m.get("attribution", {}), top)
    _section(lines, "Pool / cache pressure")
    findings += _pressure_report(lines, m)
    _section(lines, "SLO burn")
    findings += _slo_report(lines, m)
    _section(lines, "Diagnosis")
    if findings:
        for i, f_ in enumerate(findings, 1):
            lines.append(f"{i}. {f_}")
    else:
        lines.append("no findings — the run looks healthy")
    return "\n".join(lines) + "\n", findings


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("metrics", help="serve metrics JSON (--metrics-json)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="the run's Chrome trace JSON (--trace-out): "
                         "validated and summarized in the report")
    ap.add_argument("--top", type=int, default=8, metavar="N",
                    help="attribution rows to print (default 8)")
    ap.add_argument("--report", default=None, metavar="PATH",
                    help="also write the report text here")
    ap.add_argument("--fail-on-drift", action="store_true",
                    help="exit 1 if the balance auditor flagged any "
                         "drifted warm plan")
    ap.add_argument("--max-reconciliation-error", type=float, default=None,
                    metavar="FRAC",
                    help="exit 1 if the attribution reconciliation error "
                         "exceeds FRAC (CI gate)")
    args = ap.parse_args(argv)

    with open(args.metrics) as f:
        m = json.load(f)
    text, _ = doctor(m, trace=args.trace, top=args.top)
    print(text, end="")
    if args.report:
        with open(args.report, "w") as f:
            f.write(text)
        print(f"[serve-doctor] report written to {args.report}")

    rc = 0
    attrib = m.get("attribution", {})
    if args.fail_on_drift and attrib.get("drifted_count"):
        print(f"FAIL: {attrib['drifted_count']} drifted warm plan(s): "
              + ", ".join(attrib.get("drifted", [])), file=sys.stderr)
        rc = 1
    if args.max_reconciliation_error is not None:
        recon = attrib.get("reconciliation_error")
        if not attrib:
            print("FAIL: --max-reconciliation-error needs an attribution "
                  "section (traced run)", file=sys.stderr)
            rc = 1
        elif recon is not None and recon > args.max_reconciliation_error:
            print(f"FAIL: reconciliation error {recon:.4f} > "
                  f"{args.max_reconciliation_error}", file=sys.stderr)
            rc = 1
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
