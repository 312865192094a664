#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Cells are the ``workloads`` of ``BENCHMARK.json``; see ``bench/harness.py``.
Exits non-zero, printing no result, without a TPU.
"""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

if __name__ == "__main__":
    from bench import harness

    sys.exit(harness.main(sys.argv[1:]))
