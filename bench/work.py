"""The work a step of the dense family needs, from its sizes alone: the
weight GEMMs' least time on a chip and the model FLOPs of the tokens
served. Nothing here reads the kernels' tiles or the compiled program, so
the numbers stay put whatever implements the step.
"""
from __future__ import annotations


def step_gemms(m: dict, rows: int, logit_rows: int) -> list[tuple]:
    """(M, K, N, out_bytes) of every weight GEMM in one step that carries
    ``rows`` token rows and computes logits for ``logit_rows`` of them;
    ``m`` is ``bench.models.dense.dims(cfg)``. Projections write bf16, the
    unembedding float32."""
    d, f = m["d"], m["f"]
    qd, kvd = m["heads"] * m["head_dim"], m["kv_heads"] * m["head_dim"]
    layer = [(d, qd), (d, kvd), (d, kvd), (qd, d), (d, f), (d, f), (f, d)]
    out = [(rows, K, N, 2) for K, N in layer] * m["layers"]
    out.append((logit_rows, d, m["vocab"], 4))
    return out


def least_time_s(gemms: list[tuple], peak: dict) -> float:
    """Sum over GEMMs of the larger of 2MNK over the bf16 peak and the
    bytes of A, B (bf16) and C, each moved once, over HBM bandwidth."""
    total = 0.0
    for M, K, N, out_bytes in gemms:
        flops = 2.0 * M * N * K
        moved = 2.0 * M * K + 2.0 * K * N + out_bytes * M * N
        total += max(flops / peak["bf16_flops"],
                     moved / peak["hbm_bytes_per_s"])
    return total


def span_flops(m: dict, first: int, count: int) -> float:
    """Model FLOPs of the tokens at positions ``first .. first+count-1``
    (0-based): every weight matmul, plus attention over the ``position + 1``
    keys each token sees."""
    d, f, L = m["d"], m["f"], m["layers"]
    qd, kvd = m["heads"] * m["head_dim"], m["kv_heads"] * m["head_dim"]
    weights = 2.0 * (2 * d * qd + 2 * d * kvd + 3 * d * f)
    keys = count * first + count * (count + 1) / 2   # sum of position + 1
    return L * (count * weights + 4.0 * qd * keys)


def unembed_flops(m: dict) -> float:
    """FLOPs of one token's logits."""
    return 2.0 * m["d"] * m["vocab"]
