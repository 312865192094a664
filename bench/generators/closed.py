"""Closed-loop traffic: ``clients`` callers, each sending its next request
when its last one finishes (plus ``think_s``).

Lengths are drawn once, not per seed: ``base_set`` (prompt, output) pairs
sit at the quantiles of two clipped lognormals, paired by a fixed
permutation (``pairing_seed``). The run's seed only orders them: the
request stream is the base set, shuffled anew for every pass through it,
in blocks of ``clients`` requests that each hold one pair from each of
``clients`` strata of prompt length (the base set sorted by prompt and
cut into equal runs). Any ``clients`` consecutive requests then carry
nearly the same prompt lengths whatever the seed, so a window that sees
only a few tens of requests sees the same sizes. Prompt token ids are
uniform over the vocabulary, from the seed.

A mix file (``bench/traffic/<mix>.json`` with ``"kind": "closed"``) holds::

    clients, think_s, base_set (a multiple of clients), pairing_seed,
    prompt_tokens / output_tokens: {median, sigma, min, max}
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Planned:
    """One request as the client sends it."""

    rid: int
    client: int
    due_s: float
    prompt: np.ndarray
    max_new_tokens: int


def quantile_lengths(spec: dict, n: int) -> list[int]:
    """``n`` lengths at the mid-quantiles of a lognormal with the given
    median and sigma, rounded and clipped to [min, max]."""
    mu, sigma = math.log(spec["median"]), spec["sigma"]
    out = []
    for i in range(n):
        z = NormalDist().inv_cdf((i + 0.5) / n)
        out.append(int(min(spec["max"], max(spec["min"],
                                            round(math.exp(mu + sigma * z))))))
    return out


def base_set(mix: dict) -> list[tuple[int, int]]:
    n = mix["base_set"]
    prompts = quantile_lengths(mix["prompt_tokens"], n)
    outputs = quantile_lengths(mix["output_tokens"], n)
    perm = np.random.default_rng(mix["pairing_seed"]).permutation(n)
    return [(prompts[i], outputs[int(perm[i])]) for i in range(n)]


def max_lengths(mix: dict) -> tuple[int, int]:
    """(longest prompt, longest output) the mix can send."""
    return mix["prompt_tokens"]["max"], mix["output_tokens"]["max"]


class Source:
    """The request source for one run.

    ``due(now)`` returns the requests due by ``now``; ``finished(rid, now)``
    tells the source that request ``rid`` completed at ``now``, so its
    client sends again ``think_s`` later. ``stop()`` ends sending.
    """

    def __init__(self, mix: dict, *, seed: int, vocab_size: int):
        self.mix = mix
        self.vocab_size = vocab_size
        self._rng = np.random.default_rng(seed)
        self._base = base_set(mix)
        self._queue: list[tuple[int, int]] = []
        self._next_rid = 0
        self._client_of: dict[int, int] = {}
        # (due time, client) of every client waiting to send
        self._ready: list[tuple[float, int]] = [
            (0.0, c) for c in range(mix["clients"])]
        self._stopped = False

    def _pass(self) -> list[int]:
        """The order of the base set's pairs for one pass."""
        n, k = len(self._base), self.mix["clients"]
        if n % k:
            raise ValueError(f"base_set {n} is not a multiple of clients {k}")
        by_prompt = sorted(range(n), key=lambda i: (self._base[i][0], i))
        strata = [by_prompt[j * n // k:(j + 1) * n // k] for j in range(k)]
        picks = [[s[int(i)] for i in self._rng.permutation(len(s))]
                 for s in strata]
        order = []
        for b in range(n // k):
            block = [picks[j][b] for j in range(k)]
            order += [block[int(i)] for i in self._rng.permutation(k)]
        return order

    def _lengths(self) -> tuple[int, int]:
        if not self._queue:
            self._queue = [self._base[i] for i in self._pass()]
        return self._queue.pop(0)

    def start(self, now: float) -> None:
        """Every client sends its first request at ``now``."""
        self._ready = [(now, c) for _, c in self._ready]

    def due(self, now: float) -> list[Planned]:
        if self._stopped:
            return []
        out, keep = [], []
        for due_s, client in self._ready:
            if due_s > now:
                keep.append((due_s, client))
                continue
            plen, olen = self._lengths()
            rid = self._next_rid
            self._next_rid += 1
            self._client_of[rid] = client
            prompt = self._rng.integers(0, self.vocab_size, size=plen,
                                        dtype=np.int32)
            out.append(Planned(rid, client, due_s, prompt, olen))
        self._ready = keep
        return out

    def finished(self, rid: int, now: float) -> None:
        client = self._client_of.pop(rid)
        self._ready.append((now + self.mix["think_s"], client))

    def stop(self) -> None:
        self._stopped = True
