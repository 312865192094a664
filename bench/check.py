"""Whether what the timed path served is correct.

After the window a sample of the requests the engine finished in it is
drawn from the seed: the request with the longest sequence, then others
in a seeded order until ``check_tokens`` served tokens are covered. The
float32 reference runs once over each prompt followed by its served
tokens. For each served token it reads the gap by which that token's
reference logit lies below the reference's best logit at the same
position (0 where the engine chose the reference's own argmax); greedy
decoding is what makes it meaningful. The number compared is the mean gap
over all served tokens of the sample. The widest gap is reported beside
it: with random weights a vocabulary of 10^5 logits holds near-ties that
bf16 rounding alone flips, so the widest gap of a sound run and of the
int8 control lie within 3x of each other, while the mean, which counts
how often and how far tokens flip, separates them.
"""
from __future__ import annotations

import numpy as np


def sample(records: list, seed: int, check_tokens: int) -> list:
    """The requests to compare: the longest (prompt + served tokens)
    first, then a seeded order until ``check_tokens`` tokens."""
    if not records:
        return []
    recs = sorted(records, key=lambda r: r.rid)
    longest = max(recs, key=lambda r: (len(r.prompt) + len(r.tokens), r.rid))
    order = np.random.default_rng(seed).permutation(len(recs))
    out, n = [longest], len(longest.tokens)
    for i in order:
        r = recs[int(i)]
        if n >= check_tokens:
            break
        if r is not longest:
            out.append(r)
            n += len(r.tokens)
    return out


def logit_gaps(reference, weights: dict, dims: dict, records: list) -> dict:
    """Widest and mean reference gap over every served token of
    ``records``, with the counts they were taken over."""
    worst, total, tokens, agree = 0.0, 0.0, 0, 0
    for r in records:
        served = np.asarray(r.tokens, np.int32)
        seq = np.concatenate([np.asarray(r.prompt, np.int32), served[:-1]])
        rows = len(r.prompt) - 1 + np.arange(len(served))
        ref = reference.logits(weights, dims, seq, rows)
        best = ref.max(-1)
        got = ref[np.arange(len(served)), served]
        gap = best - got
        worst = max(worst, float(gap.max()))
        total += float(gap.sum())
        tokens += len(served)
        agree += int((ref.argmax(-1) == served).sum())
    return {"gap": worst, "mean_gap": total / max(tokens, 1),
            "tokens": tokens, "requests": len(records),
            "argmax_agree": agree}
