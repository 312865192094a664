"""Plain float32 forward of the dense decoder family, from the published
description (Qwen2 / InternLM2): token embedding; per layer RMSNorm,
grouped-query attention with rotary embeddings (rotate-half convention,
base ``rope_theta``) and optional q/k/v biases, residual, RMSNorm, gated
SiLU MLP ``down(silu(gate(x)) * up(x))``, residual; final RMSNorm and an
untied unembedding.

It reads the benchmark's weight dict (``bench/models/dense.py``) and
imports nothing of the program under test. Every matmul runs at
``precision="highest"`` in float32. It runs one sequence at a time, one
layer at a time, attention in blocks of query rows, and the unembedding
only at the positions asked for, so it fits beside the weights once the
engine's state is freed. Sequences are padded to a multiple of
``PAD_TO`` (causal attention keeps the padding out of every real
position), so a handful of programs serve every length.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PAD_TO = 256
Q_BLOCK = 256


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    """x (S, H, D); rotate-half rotary embedding at positions ``pos``."""
    half = x.shape[-1] // 2
    freqs = 1.0 / theta ** (jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs        # (S, D/2)
    s, c = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _mm(a, b):
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnames=("m",))
def _layer(x, w, i, *, m):
    """One decoder layer over the whole (padded) sequence x (S, d)."""
    S = x.shape[0]
    H, Hkv, D = m["heads"], m["kv_heads"], m["head_dim"]
    f32 = lambda name: jax.lax.dynamic_index_in_dim(  # noqa: E731
        w[name], i, keepdims=False).astype(jnp.float32)
    pos = jnp.arange(S)
    h = _rms(x, f32("ln1"), m["eps"])
    q, k, v = _mm(h, f32("wq")), _mm(h, f32("wk")), _mm(h, f32("wv"))
    if m["qkv_bias"]:
        q, k, v = q + f32("bq"), k + f32("bk"), v + f32("bv")
    q = _rope(q.reshape(S, H, D), pos, m["rope_theta"])
    k = _rope(k.reshape(S, Hkv, D), pos, m["rope_theta"])
    v = v.reshape(S, Hkv, D)
    k = jnp.repeat(k, H // Hkv, axis=1)
    v = jnp.repeat(v, H // Hkv, axis=1)

    def block(qi):
        qb = jax.lax.dynamic_slice_in_dim(q, qi * Q_BLOCK, Q_BLOCK)
        s = jnp.einsum("qhd,khd->hqk", qb, k,
                       precision=jax.lax.Precision.HIGHEST) * D ** -0.5
        qpos = qi * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(qpos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, -1)
        return jnp.einsum("hqk,khd->qhd", p, v,
                          precision=jax.lax.Precision.HIGHEST)

    o = jax.lax.map(block, jnp.arange(S // Q_BLOCK)).reshape(S, H * D)
    x = x + _mm(o, f32("wo"))
    h = _rms(x, f32("ln2"), m["eps"])
    g = _mm(h, f32("w_gate"))
    x = x + _mm(jax.nn.silu(g) * _mm(h, f32("w_up")), f32("w_down"))
    return x


@functools.partial(jax.jit, static_argnames=("m",))
def _head(x, rows, final_norm, unembed, *, m):
    h = _rms(x[rows], final_norm.astype(jnp.float32), m["eps"])
    logits = _mm(h, unembed.astype(jnp.float32))
    return logits[:, : m["vocab"]]


def logits(w: dict, m: dict, tokens: np.ndarray,
           rows: np.ndarray) -> np.ndarray:
    """float32 logits (len(rows), vocab) of the sequence ``tokens`` at
    positions ``rows``; ``m`` is ``bench.models.dense.dims(cfg)``."""
    S = len(tokens)
    Sp = -(-S // PAD_TO) * PAD_TO
    ids = np.zeros((Sp,), np.int32)
    ids[:S] = tokens
    mh = _hashable(m)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(w["embed"], jnp.asarray(ids), axis=0).astype(jnp.float32)
        for i in range(m["layers"]):
            x = _layer(x, w, jnp.int32(i), m=mh)
        out = _head(x, jnp.asarray(rows, jnp.int32), w["final_norm"],
                    w["unembed"], m=mh)
    return np.asarray(out, np.float32)


class _hashable(dict):
    """A dict usable as a static jit argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))
