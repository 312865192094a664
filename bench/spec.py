"""Finds a cell's pieces by name: ``BENCHMARK.json`` names each cell's
configuration, traffic mix and metrics, and each lives in a file of its
own under ``bench/``:

- configuration  ``bench/configs/<config>.json`` (its ``family`` picks
  ``bench/models/<family>.py``: ``dims``, ``init_weights``, ``Served``;
  and ``bench/reference/<family>.py``: ``logits``);
- traffic mix    ``bench/traffic/<mix>.json`` (its ``kind`` picks
  ``bench/generators/<kind>.py``: ``Source``, ``max_lengths``);
- correctness    ``bench/limits/<cell>.json``, the limit of each number
  the check compares;
- metric         ``bench/metrics/<metric>.py``, a ``read(run)`` function of
  ``bench.rundata.RunData`` that returns a number, or None where the run
  has nothing to read.

A new cell, configuration, mix or metric is new files and new entries in
``BENCHMARK.json``; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent


def load_module(path: Path):
    """Import the Python file at ``path`` as a fresh module."""
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    name = f"bench_{path.parent.name}_{path.stem}".replace(
        ".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod     # dataclasses look their module up here
    spec.loader.exec_module(mod)
    return mod


def _load_json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    name: str
    chips: int
    config: dict            # the configuration file's contents
    mix: dict               # the traffic mix file's contents
    end_to_end: list[dict]  # the metric entries this cell reports
    per_layer: list[dict]
    bench_dir: Path

    _modules: dict = dataclasses.field(default_factory=dict, repr=False)

    def module(self, kind: str, name: str):
        key = (kind, name)
        if key not in self._modules:
            self._modules[key] = load_module(self.bench_dir / kind / f"{name}.py")
        return self._modules[key]

    @property
    def family(self):
        """The configuration's model family module (weights, engine)."""
        return self.module("models", self.config["family"])

    @property
    def reference(self):
        return self.module("reference", self.config["family"])

    @property
    def generator(self):
        return self.module("generators", self.mix["kind"])

    def reader(self, metric: str):
        return self.module("metrics", metric).read


def _applies(metric: dict, cell: str, e2e_names: set[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e_names


def find_cell(name: str, root: Path) -> Cell:
    """The cell called ``name`` in ``<root>/BENCHMARK.json``."""
    bench = _load_json(root / "BENCHMARK.json")
    bench_dir = root / bench["paths"][0]
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(known: {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(root / configs[w["config"]]["file"])
    mix = _load_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in bench["end_to_end"] if _applies(m, name, set())]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, name, e2e_names)]
    return Cell(name=name, chips=int(w["chips"]), config=config, mix=mix,
                end_to_end=e2e, per_layer=per_layer, bench_dir=bench_dir)
