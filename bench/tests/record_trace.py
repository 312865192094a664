#!/usr/bin/env python3
"""Record ``data/tiny.xplane.pb.gz``, the trace ``test_trace.py`` reduces.

    python3 bench/tests/record_trace.py [--seed N]

Run on a TPU host from the root of a checkout. It runs the tiny cell of
``helpers`` once through the harness with ``--trace 1`` and a 0.4 s window
(so the trace covers its last 0.2 s), and keeps the trace the harness
recorded.
"""
import argparse
import gzip
import shutil
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import helpers  # noqa: E402  (puts the repo and src on the path)
from bench import harness  # noqa: E402

OUT = helpers.BENCH / "tests" / "data" / "tiny.xplane.pb.gz"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=3000000004)
    args = ap.parse_args(argv)
    with tempfile.TemporaryDirectory(prefix="bench-record-") as tmp:
        root = helpers.make_root(Path(tmp))
        kept = Path(tmp) / "tiny.xplane.pb"
        rc = harness.main(["--workload", helpers.TINY_CELL, "--seed",
                           str(args.seed), "--seconds", "0.4", "--trace", "1"],
                          root=root, keep_trace=kept)
        if rc:
            return rc
        with open(kept, "rb") as src, gzip.open(OUT, "wb") as dst:
            shutil.copyfileobj(src, dst)
    print(f"wrote {OUT}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
