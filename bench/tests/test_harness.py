"""The harness as data, on the CPU at a tiny size.

It finds a configuration, a traffic mix and a metric that were added as
new files, by the names ``BENCHMARK.json`` gives them; it refuses to run
without a TPU, on a chip missing from its peaks table, and without the
program under test, printing no result; and a run whose timed path is
broken underneath comes out as not correct.
"""
import json

import jax
import jax.numpy as jnp
import pytest

import helpers
from bench import harness, spec

NEW_METRIC = '''"""Requests due in the window (a test's metric)."""


def read(run):
    return float(len(run.attempted()))
'''


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = helpers.make_root(tmp_path_factory.mktemp("checkout"))
    (root / "bench" / "metrics" / "due_requests.py").write_text(NEW_METRIC)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["end_to_end"].append({
        "name": "due_requests", "unit": "requests", "better": "higher",
        "bound": 0.25, "source": "host_clock",
        "workloads": [helpers.TINY_CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def _args(seed=2**33 + 17, seconds=1.0, cell=helpers.TINY_CELL):
    return ["--workload", cell, "--seed", str(seed), "--seconds", str(seconds),
            "--trace", "0"]


def _result(capsys) -> dict:
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


def test_cell_pieces_are_found_by_name(root):
    cell = spec.find_cell(helpers.TINY_CELL, root)
    assert cell.config["name"] == "tiny-dense"
    assert cell.mix == helpers.TINY_MIX
    assert callable(cell.generator.Source)
    assert cell.reader("due_requests") is not None
    assert "due_requests" in [m["name"] for m in cell.end_to_end]
    other = spec.find_cell("qwen1.5-4b.chat-c16", root)
    assert "due_requests" not in [m["name"] for m in other.end_to_end]
    with pytest.raises(KeyError):
        spec.find_cell("no-such-cell", root)


def test_run_reports_every_metric(root, capsys):
    assert harness.main(_args(), root=root, require_tpu=False) == 0
    res = _result(capsys)
    cell = spec.find_cell(helpers.TINY_CELL, root)
    assert res["correct"] is True, res["checks"]
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert res["metrics"]["due_requests"]["value"] == res["attempted"] > 0
    assert res["failed"] == 0
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "checks"
    assert res["checks"]["compiles_in_window"]["value"] == 0
    assert res["checks"]["lazy_plan_solves"]["value"] == 0


def test_refuses_a_cpu(root, capsys):
    assert harness.main(_args(), root=root) != 0
    out = capsys.readouterr()
    assert out.out == ""
    assert "no TPU" in out.err


class _Device:
    platform = "tpu"
    device_kind = "TPU v99"


def test_refuses_an_unknown_device_kind(root, capsys, monkeypatch):
    monkeypatch.setattr(jax, "devices", lambda *a: [_Device()])
    assert harness.main(_args(), root=root) != 0
    out = capsys.readouterr()
    assert out.out == ""
    assert "TPU v99" in out.err


def test_refuses_without_the_program(tmp_path, capsys):
    root = helpers.make_root(tmp_path)
    (root / "src").unlink()
    assert harness.main(_args(), root=root, require_tpu=False) != 0
    assert capsys.readouterr().out == ""


def _token_altered(served):
    """Every sampled token is the next id after the one chosen."""
    eng = served.engine
    sample = eng._sample
    eng._sample = lambda row, st: (sample(row, st) + 1) % eng.cfg.vocab_size


def _state_unchanged(served):
    """The decode step hands back the state it was given."""
    art = served.engine.art
    decode = art.decode_fn

    def decode_fn(params, state, tokens, active):
        kept = jax.tree.map(jnp.copy, state)
        logits, _ = decode(params, state, tokens, active)
        return logits, kept

    art.decode_fn = decode_fn


def _half_batch(served):
    """The decode step serves the lower half of the lanes; the upper half
    gets the lower half's logits."""
    art = served.engine.art
    decode = art.decode_fn

    def decode_fn(params, state, tokens, active):
        logits, state = decode(params, state, tokens, active)
        h = logits.shape[0] // 2
        return logits.at[h:].set(logits[:logits.shape[0] - h]), state

    art.decode_fn = decode_fn


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged,
                                   _half_batch],
                         ids=["token_altered", "state_unchanged",
                              "half_batch"])
def test_a_broken_timed_path_is_not_correct(root, capsys, fault):
    assert harness.main(_args(), root=root, require_tpu=False,
                        fault=fault) == 0
    res = _result(capsys)
    assert res["correct"] is False, res["checks"]
    assert (res["checks"]["mean_logit_gap"]["value"]
            > res["checks"]["mean_logit_gap"]["limit"])
