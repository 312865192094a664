"""A tiny copy of the benchmark for tests on the CPU: the real harness,
generators, references and metric readers, with one small dense
configuration and one short closed-loop mix added as files of their own."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_CONFIG = {
    "name": "tiny-dense",
    "source": "test configuration",
    "family": "dense",
    "hidden_size": 64,
    "intermediate_size": 128,
    "num_attention_heads": 4,
    "num_key_value_heads": 2,
    "num_hidden_layers": 2,
    "vocab_size": 512,
    "rms_norm_eps": 1e-6,
    "rope_theta": 10000.0,
    "torch_dtype": "bfloat16",
    "reduced": [],
    "assumed": {"head_dim": 16, "attention_bias": True},
    "serving": {"engine": "paged", "kv_block_size": 4, "prefill_chunk": 16,
                "matmul_backend": "auto", "kv_dtype": "bf16",
                "sched_policy": "fifo", "prefix_cache": False,
                "speculation": False, "sampling": "greedy"},
}

TINY_MIX = {
    "kind": "closed", "clients": 4, "think_s": 0.0,
    "prompt_tokens": {"median": 12, "sigma": 0.6, "min": 4, "max": 40},
    "output_tokens": {"median": 6, "sigma": 0.5, "min": 3, "max": 12},
    "base_set": 16, "pairing_seed": 0, "fill_completions": 4,
    "drain_cap_s": 30.0, "check_tokens": 800,
}

TINY_CELL = "tiny-dense.chat"


# Mean logit gap of the tiny cell over ~800 served tokens, seeds
# 2**33 + 1 .. 8: the program read 1.6e-5 to 9.3e-5; its int8 control
# (seeds 2**33 + 1 .. 4) 6.0e-4 to 8.5e-4. The limit sits between.
TINY_GAP_LIMIT = 2.5e-4


def make_root(tmp: Path, *, gap_limit: float = TINY_GAP_LIMIT) -> Path:
    """A checkout-like directory holding the benchmark plus the tiny cell,
    and the program under test (a link to this checkout's ``src``)."""
    shutil.copytree(BENCH, tmp / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (tmp / "src").symlink_to(ROOT / "src")
    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    (tmp / "bench" / "configs" / "tiny-dense.json").write_text(
        json.dumps(TINY_CONFIG))
    (tmp / "bench" / "traffic" / "tiny-chat.json").write_text(
        json.dumps(TINY_MIX))
    (tmp / "bench" / "limits" / f"{TINY_CELL}.json").write_text(
        json.dumps({"mean_logit_gap": {"limit": gap_limit}}))
    bench["configs"].append({"name": "tiny-dense", "source": "test",
                             "file": "bench/configs/tiny-dense.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": TINY_CELL, "config": "tiny-dense",
                               "traffic": "tiny-chat", "chips": 1,
                               "why": "test"})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp
