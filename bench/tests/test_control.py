"""The correctness check against its control, on the CPU at a tiny size.

The configuration states bf16; the control is the program's own int8
(W8A8) serving path, the precision below it. On the same seeds the
program as configured stays within the tiny cell's limit
(``helpers.TINY_GAP_LIMIT``, set from tiny readings) and the control
exceeds it.
``bench/control.py`` takes the same readings on the chip at each cell's
own size, where the cells' limits come from.
"""
import json

import jax
import pytest

import helpers
from bench import harness, spec

SEEDS = [2**33 + 1, 2**33 + 2, 2**33 + 3]


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    root = helpers.make_root(tmp_path_factory.mktemp("checkout"))
    harness.use_compile_cache(root)
    return spec.find_cell(helpers.TINY_CELL, root), root


def _run(cell, root, seed, quantize):
    with open(cell.bench_dir / "peaks.json") as f:
        peak = next(iter(json.load(f).values()))
    args = harness.parse_args(["--workload", cell.name, "--seed", str(seed),
                               "--seconds", "1"])
    return harness.run(cell, args, jax.devices()[0], peak,
                       t_process=harness.process_start_perf(), root=root,
                       quantize=quantize)


@pytest.mark.parametrize("seed", SEEDS)
def test_program_is_correct_and_control_is_not(cell, seed):
    cell, root = cell
    program = _run(cell, root, seed, None)
    control = _run(cell, root, seed, "int8")
    assert program["run"]["checked_tokens"] >= helpers.TINY_MIX["check_tokens"]
    assert program["correct"] is True, program["checks"]
    assert control["correct"] is False, control["checks"]
