"""The closed-loop generator on the committed chat mix: the seed orders a
fixed set of sizes, and every block of ``clients`` consecutive requests
carries one prompt from each stratum of prompt length."""
import json

import helpers
from bench.generators import closed

MIX = json.loads((helpers.BENCH / "traffic" / "chat-c16.json").read_text())


def _stream(mix, seed, n):
    src = closed.Source(mix, seed=seed, vocab_size=100)
    return [src._lengths() for _ in range(n)]


def test_every_seed_sends_the_base_set():
    base = sorted(closed.base_set(MIX))
    for seed in (0, 2**33 + 5):
        assert sorted(_stream(MIX, seed, len(base))) == base
    assert _stream(MIX, 1, 64) != _stream(MIX, 2, 64)


def test_each_block_holds_one_prompt_of_each_stratum():
    k, base = MIX["clients"], closed.base_set(MIX)
    prompts = sorted(p for p, _ in base)
    n = len(base)
    for seed in (3, 2**33 + 7):
        stream = _stream(MIX, seed, 2 * n)
        for b in range(0, 2 * n, k):
            got = sorted(p for p, _ in stream[b:b + k])
            for j, p in enumerate(got):
                assert prompts[j * n // k] <= p <= prompts[(j + 1) * n // k - 1]


def test_lengths_keep_to_the_cut():
    lengths = closed.base_set(MIX)
    assert max(p for p, _ in lengths) <= MIX["prompt_tokens"]["max"]
    assert max(o for _, o in lengths) <= MIX["output_tokens"]["max"]
    assert closed.max_lengths(MIX) == (512, 384)
