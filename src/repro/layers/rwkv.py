"""RWKV-6 "Finch" block: time-mix with data-dependent decay + channel-mix.

The WKV recurrence is elementwise/outer-product state math — *not* a GEMM —
so the paper's tile-balance technique does not apply to it (DESIGN.md
§Arch-applicability); it runs as a ``lax.scan`` over time. The projections
(R, K, V, G, O, channel-mix), which dominate FLOPs, do route through the
balanced-GEMM substrate.

State per head is (head_dim × head_dim): O(1) in sequence length — this is
why rwkv6 runs the long_500k decode cell that full-attention archs skip.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.layers import common as cm

LORA_R = 32


class RwkvTimeMixParams(NamedTuple):
    mu: jax.Array        # (5, d) token-shift mixing for (w, k, v, r, g)
    lora_a: jax.Array    # (d, 5*LORA_R) data-dependent mix tower (down)
    lora_b: jax.Array    # (5, LORA_R, d) data-dependent mix tower (up)
    w0: jax.Array        # (d,) decay base
    w_lora_a: jax.Array  # (d, LORA_R)
    w_lora_b: jax.Array  # (LORA_R, d)
    u: jax.Array         # (d,) bonus
    wr: jax.Array        # (d, d)
    wk: jax.Array        # (d, d)
    wv: jax.Array        # (d, d)
    wg: jax.Array        # (d, d)
    wo: jax.Array        # (d, d)
    ln_g: jax.Array      # (d,) per-head group-norm gamma
    ln_b: jax.Array      # (d,)


class RwkvChannelMixParams(NamedTuple):
    mu_k: jax.Array      # (d,)
    mu_r: jax.Array      # (d,)
    wk: jax.Array        # (d, f)
    wv: jax.Array        # (f, d)
    wr: jax.Array        # (d, d)


def init_time_mix(key, d, dtype=jnp.float32):
    ks = cm.split_keys(key, 9)
    return RwkvTimeMixParams(
        mu=jnp.full((5, d), 0.5, dtype),
        lora_a=cm.normal_init(ks[0], (d, 5 * LORA_R), dtype, scale=0.01),
        lora_b=cm.normal_init(ks[1], (5, LORA_R, d), dtype, scale=0.01),
        w0=jnp.full((d,), -6.0, dtype),
        w_lora_a=cm.normal_init(ks[2], (d, LORA_R), dtype, scale=0.01),
        w_lora_b=cm.normal_init(ks[3], (LORA_R, d), dtype, scale=0.01),
        u=jnp.zeros((d,), dtype),
        wr=cm.normal_init(ks[4], (d, d), dtype),
        wk=cm.normal_init(ks[5], (d, d), dtype),
        wv=cm.normal_init(ks[6], (d, d), dtype),
        wg=cm.normal_init(ks[7], (d, d), dtype),
        wo=cm.normal_init(ks[8], (d, d), dtype),
        ln_g=jnp.ones((d,), dtype),
        ln_b=jnp.zeros((d,), dtype),
    )


def time_mix_axes():
    return RwkvTimeMixParams(
        mu=(None, "embed"), lora_a=("embed", "lora"),
        lora_b=(None, "lora", "embed"), w0=("embed",),
        w_lora_a=("embed", "lora"), w_lora_b=("lora", "embed"),
        u=("embed",), wr=("embed", "heads"), wk=("embed", "heads"),
        wv=("embed", "heads"), wg=("embed", "heads"), wo=("heads", "embed"),
        ln_g=("embed",), ln_b=("embed",),
    )


def init_channel_mix(key, d, f, dtype=jnp.float32):
    ks = cm.split_keys(key, 3)
    return RwkvChannelMixParams(
        mu_k=jnp.full((d,), 0.5, dtype),
        mu_r=jnp.full((d,), 0.5, dtype),
        wk=cm.normal_init(ks[0], (d, f), dtype),
        wv=cm.normal_init(ks[1], (f, d), dtype),
        wr=cm.normal_init(ks[2], (d, d), dtype),
    )


def channel_mix_axes():
    return RwkvChannelMixParams(
        mu_k=("embed",), mu_r=("embed",), wk=("embed", "ffn"),
        wv=("ffn", "embed"), wr=("embed", "embed"),
    )


def _token_shift(x: jax.Array, x_prev: jax.Array | None = None) -> jax.Array:
    """Previous-token values; x_prev supplies the value before position 0."""
    shifted = jnp.roll(x, 1, axis=1)
    first = jnp.zeros_like(x[:, :1]) if x_prev is None else x_prev[:, None]
    return shifted.at[:, 0].set(first[:, 0])


def _ddlerp(p: RwkvTimeMixParams, x, sx):
    """Finch data-dependent token-shift: 5 mixed inputs (w, k, v, r, g)."""
    # shared tower: tanh(x @ lora_a) -> (B,T,5,R) -> per-stream up-proj
    low = jnp.tanh(cm.dense(x + 0.5 * sx, p.lora_a))
    B, T, _ = low.shape
    low = low.reshape(B, T, 5, LORA_R)
    delta = jnp.einsum("btkr,krd->btkd", low, p.lora_b.astype(x.dtype))
    mix = p.mu.astype(x.dtype)[None, None] + delta          # (B,T,5,d)
    return x[:, :, None, :] + sx[:, :, None, :] * mix       # (B,T,5,d)


def wkv_chunk_parallel(r, k, v, wlog, u, state, chunk: int = 32):
    """Chunk-parallel WKV (the §Perf cell-1 optimization).

    The token-by-token recurrence makes the (B,H,N,N) state cross the HLO
    boundary every token (T·L state round-trips — the worst memory term in
    the roofline table). This block form materializes the state once per
    chunk and does the intra-chunk work as matmuls:

      y_t = (r_t ⊙ D_t) · S0                         (inter-chunk, matmul)
          + Σ_{s<t} (Σ_n r_t D_t k_s / D_{s+1}) v_s  (intra, C×C matmul)
          + (r_t·u·k_t) v_t                          (bonus diagonal)
      S' = diag(D_C) S0 + (k ⊙ D_C/D_{s+1})ᵀ v

    with D_t = exp(Σ_{s<t} log w_s). All decay ratios are computed as
    exp(negative differences) — numerically safe for any w ∈ (0,1).

    Shapes: r/k/v/wlog (B,H,T,N) f32, u (H,N), state (B,H,N,N).
    Returns (y (B,H,T,N), new_state). T must be a multiple of ``chunk``.
    """
    B, H, T, N = r.shape
    C = chunk
    nc = T // C
    rs = r.reshape(B, H, nc, C, N)
    ks = k.reshape(B, H, nc, C, N)
    vs = v.reshape(B, H, nc, C, N)
    wl = wlog.reshape(B, H, nc, C, N)
    # clog[t] = sum_{s<t} log w_s  (within chunk);  cend = full-chunk sum
    clog = jnp.cumsum(wl, axis=3) - wl          # exclusive cumsum
    cend = clog[..., -1, :] + wl[..., -1, :]    # (B,H,nc,N)

    causal = jnp.tril(jnp.ones((C, C)), -1)     # strictly lower
    u_bh = u[None, :, None, :]                  # (1,H,1,N)

    def body(S, inp):
        rc, kc, vc, cl, wlc, ce = inp           # (B,H,C,N)... ce (B,H,N)
        y1 = jnp.einsum("bhtn,bhnm->bhtm", rc * jnp.exp(cl), S)
        # A[t,s] = Σ_n r_t k_s exp(clog_t - clog_{s+1}): factored — the
        # O(C²·N) pairwise-decay tensor of the first iteration dominated
        # the byte traffic (§Perf cell-1 iter 2). Midpoint re-centering
        # bounds both factors' exponents by (C/2)·|log w| so neither over-
        # nor underflows f32 for any realistic decay spectrum.
        mid = cl[..., C // 2, :][..., None, :]
        rDm = rc * jnp.exp(cl - mid)
        kinv = kc * jnp.exp(jnp.clip(mid - (cl + wlc), max=60.0))
        A = jnp.einsum("bhtn,bhsn->bhts", rDm, kinv)
        A = A * causal
        diag = jnp.sum(rc * u_bh * kc, axis=-1)   # bonus term (B,H,C)
        y2 = jnp.einsum("bhts,bhsm->bhtm", A, vc) + diag[..., None] * vc
        # state update
        kdec = kc * jnp.exp(
            jnp.clip(ce[..., None, :] - (cl + wlc), max=0.0))
        S_new = jnp.exp(ce)[..., :, None] * S + jnp.einsum(
            "bhsn,bhsm->bhnm", kdec, vc)
        return S_new, y1 + y2

    xs = (rs.transpose(2, 0, 1, 3, 4), ks.transpose(2, 0, 1, 3, 4),
          vs.transpose(2, 0, 1, 3, 4), clog.transpose(2, 0, 1, 3, 4),
          wl.transpose(2, 0, 1, 3, 4), cend.transpose(2, 0, 1, 3))
    new_state, ys = jax.lax.scan(body, state, xs)
    y = ys.transpose(1, 2, 0, 3, 4).reshape(B, H, T, N)
    return y, new_state


def _wkv_step(state, inputs):
    """state: (B,H,N,N); one recurrence step.

    y_t = (S + diag(u) k v^T)^T r ;  S' = diag(w) S + k v^T
    """
    r, k, v, w, u = inputs  # r,k,w,u: (B,H,N); v: (B,H,N)
    kv = k[..., :, None] * v[..., None, :]                  # (B,H,N,N)
    y = jnp.einsum("bhnm,bhn->bhm", state + u[..., None] * kv, r)
    new_state = w[..., None] * state + kv
    return new_state, y


def time_mix(
    p: RwkvTimeMixParams, x: jax.Array, *, n_heads: int,
    state: jax.Array | None = None, x_prev: jax.Array | None = None,
    eps: float = 1e-5,
):
    """x: (B,T,d). Returns (out, (new_state, last_x)) for recurrent reuse."""
    B, T, d = x.shape
    N = d // n_heads
    sx = _token_shift(x, x_prev) - x
    mixed = _ddlerp(p, x, sx)
    xw, xk, xv, xr, xg = [mixed[:, :, i] for i in range(5)]

    r = cm.dense(xr, p.wr).reshape(B, T, n_heads, N)
    k = cm.dense(xk, p.wk).reshape(B, T, n_heads, N)
    v = cm.dense(xv, p.wv).reshape(B, T, n_heads, N)
    g = jax.nn.silu(cm.dense(xg, p.wg))
    # data-dependent decay w_t in (0, 1): exp(-exp(w0 + lora(xw)))
    wlog = p.w0.astype(jnp.float32) + cm.dense(
        jnp.tanh(cm.dense(xw, p.w_lora_a)), p.w_lora_b
    ).astype(jnp.float32)
    w = jnp.exp(-jnp.exp(wlog)).reshape(B, T, n_heads, N)
    u = p.u.astype(jnp.float32).reshape(n_heads, N)

    if state is None:
        state = jnp.zeros((B, n_heads, N, N), jnp.float32)

    # §Perf cell-1: chunk-parallel WKV (state crosses the HLO boundary once
    # per chunk; intra-chunk work is matmuls). Falls back to the token scan
    # for short/ragged sequences (decode) — bit-compatible up to f32
    # accumulation order.
    chunk = 32
    if T % chunk == 0 and T > chunk:
        to_bh = lambda x: x.astype(jnp.float32).transpose(0, 2, 1, 3)
        log_w = (-jnp.exp(wlog)).reshape(B, T, n_heads, N)  # log of decay
        ys_bh, new_state = wkv_chunk_parallel(
            to_bh(r), to_bh(k), to_bh(v),
            log_w.transpose(0, 2, 1, 3),
            u, state, chunk=chunk)
        y = ys_bh.transpose(0, 2, 1, 3).reshape(B, T, d)
    else:
        seq = (
            r.astype(jnp.float32).transpose(1, 0, 2, 3),
            k.astype(jnp.float32).transpose(1, 0, 2, 3),
            v.astype(jnp.float32).transpose(1, 0, 2, 3),
            w.transpose(1, 0, 2, 3),
            jnp.broadcast_to(u, (T, B, n_heads, N)),
        )
        new_state, ys = jax.lax.scan(_wkv_step, state, seq)
        y = ys.transpose(1, 0, 2, 3).reshape(B, T, d)
    # per-head group norm
    yh = y.reshape(B, T, n_heads, N)
    mu = yh.mean(-1, keepdims=True)
    var = yh.var(-1, keepdims=True)
    yh = (yh - mu) * jax.lax.rsqrt(var + eps)
    y = yh.reshape(B, T, d) * p.ln_g.astype(jnp.float32) + p.ln_b.astype(
        jnp.float32
    )
    out = cm.dense((y.astype(x.dtype)) * g, p.wo)
    return out, (new_state, x[:, -1])


def channel_mix(
    p: RwkvChannelMixParams, x: jax.Array, x_prev: jax.Array | None = None,
):
    sx = _token_shift(x, x_prev) - x
    xk = x + sx * p.mu_k.astype(x.dtype)
    xr = x + sx * p.mu_r.astype(x.dtype)
    k = cm.dense(xk, p.wk, activation="relu")
    kv = cm.dense(k * k, p.wv)  # squared ReLU
    r = jax.nn.sigmoid(cm.dense(xr, p.wr))
    return r * kv, x[:, -1]
