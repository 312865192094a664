#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from, on the chip.

    python3 bench/control.py --workload <cell> --seconds <s> \
        --seeds <n,n,...> --control-seeds <n,n,...> [--out <file>]

In one process (the set-up is long), each seed runs the cell as the
benchmark does, with a window of ``--seconds`` (the cell's own
``run_seconds``: a shorter window finishes, and so samples, only shorter
requests), and reads the numbers its check compares. ``--seeds`` run the
program as the configuration states it (bf16): the largest of their mean
logit gaps is the lower reading. ``--control-seeds`` run the control, the
program's own int8 (W8A8) path, the precision below bf16: the smallest of
their mean gaps is the upper reading. The limit in
``bench/limits/<cell>.json`` sits between the two; the readings it was
set from are kept beside it, in ``bench/limits/<cell>.readings.json``.
The benchmark's own runs never run this.
"""
import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))


def readings(cell_name: str, seconds: float, seeds, control_seeds) -> dict:
    from bench import harness, spec

    t_process = harness.process_start_perf()
    cell = spec.find_cell(cell_name, ROOT)
    with open(cell.bench_dir / "peaks.json") as f:
        peaks = json.load(f)
    harness.use_compile_cache(ROOT)
    dev, peak = harness.chip(cell, peaks)
    out = {"cell": cell_name, "seconds": seconds, "program": [],
           "control": []}
    for kind, quantize, chosen in (("program", None, seeds),
                                   ("control", "int8", control_seeds)):
        for seed in chosen:
            args = harness.parse_args(["--workload", cell_name, "--seed",
                                       str(seed), "--seconds", str(seconds)])
            r = harness.run(cell, args, dev, peak, t_process=t_process,
                            root=ROOT, quantize=quantize)
            row = {"seed": seed,
                   "mean_logit_gap": r["checks"]["mean_logit_gap"]["value"],
                   **{k: r["run"][k] for k in ("widest_logit_gap",
                                               "checked_requests",
                                               "checked_tokens",
                                               "argmax_agree")}}
            out[kind].append(row)
            print(json.dumps({kind: row}), flush=True)
            del r
            gc.collect()
    if out["program"]:
        out["lower"] = max(r["mean_logit_gap"] for r in out["program"])
    if out["control"]:
        out["upper"] = min(r["mean_logit_gap"] for r in out["control"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    ints = lambda s: [int(x) for x in s.split(",") if x]  # noqa: E731
    out = readings(args.workload, args.seconds, ints(args.seeds),
                   ints(args.control_seeds))
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(out, indent=1))
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
