"""Decoder-only LM families: dense (GQA), MoE, RWKV-6, Mamba2-hybrid.

Layers are *stacked* (leading L dim) and executed with ``jax.lax.scan`` —
this keeps the HLO size O(1) in depth (essential for 512-device dry-run
compiles) and is the standard production layout (MaxText-style). Parameter
trees are plain dicts/NamedTuples; a parallel ``axes`` tree carries logical
sharding axes for the partitioner.

Entry points:
  init_lm / lm_axes                 parameters + sharding metadata
  forward            (B,S) tokens -> hidden (training/prefill compute)
  lm_loss            sequence-chunked CE (never materializes (B,S,V) logits)
  init_decode_state / prefill / decode_step
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.layers import attention as attn
from repro.quant.kvcache import KVCacheDtype
from repro.layers import common as cm
from repro.layers import mamba as mb
from repro.layers import mlp as mlp_lib
from repro.layers import moe as moe_lib
from repro.layers import rwkv as rwkv_lib
from repro.core.gemm import balanced_gemm


# ---------------------------------------------------------------- norms
def init_norm(cfg: ModelConfig, d: int):
    if cfg.norm_type == "layernorm":
        return {"g": jnp.ones((d,), cfg.pdtype), "b": jnp.zeros((d,), cfg.pdtype)}
    return {"g": jnp.ones((d,), cfg.pdtype)}


def norm_axes(cfg: ModelConfig):
    if cfg.norm_type == "layernorm":
        return {"g": ("embed",), "b": ("embed",)}
    return {"g": ("embed",)}


def apply_norm(cfg: ModelConfig, p, x):
    if cfg.norm_type == "layernorm":
        return cm.layer_norm(x, p["g"], p["b"], cfg.norm_eps)
    return cm.rms_norm(x, p["g"], cfg.norm_eps)


def _stack_init(fn, key, n: int):
    """vmap an init function over n layer keys -> stacked (n, ...) leaves."""
    return jax.vmap(fn)(jax.random.split(key, n))


def is_axes_leaf(a) -> bool:
    """An axes leaf is a plain tuple of axis names (str/None). None is NOT a
    leaf — like absent (None) params it is an empty subtree, so axes trees
    flatten in lockstep with param trees. NamedTuple containers are tuple
    subclasses — excluded by the ``type() is tuple`` check."""
    return type(a) is tuple and all(x is None or isinstance(x, str) for x in a)


def _prefix_axes(tree, prefix: str = "layers"):
    return jax.tree.map(
        lambda a: (prefix, *a), tree, is_leaf=is_axes_leaf,
    )


# ---------------------------------------------------------------- init
def init_lm(key, cfg: ModelConfig):
    cfg.validate()
    keys = cm.split_keys(key, 8)
    d, dt = cfg.d_model, cfg.pdtype
    Vp = cfg.padded_vocab
    params: dict[str, Any] = {
        "embed": cm.normal_init(keys[0], (Vp, d), dt, scale=0.02),
        "final_norm": init_norm(cfg, d),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = cm.normal_init(keys[1], (d, Vp), dt)

    L = cfg.n_layers
    if cfg.family in ("dense", "moe"):
        layers = {
            "ln1": _stack_init(lambda k: init_norm(cfg, d), keys[2], L),
            "ln2": _stack_init(lambda k: init_norm(cfg, d), keys[3], L),
            "attn": _stack_init(
                lambda k: attn.init_attn(
                    k, d, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
                    qkv_bias=cfg.qkv_bias, dtype=dt),
                keys[4], L),
        }
        if cfg.family == "dense":
            layers["mlp"] = _stack_init(
                lambda k: mlp_lib.init_mlp(
                    k, d, cfg.d_ff, gated=cfg.gated_mlp,
                    bias=False, dtype=dt),
                keys[5], L)
        else:
            layers["moe"] = _stack_init(
                lambda k: moe_lib.init_moe(
                    k, d, cfg.d_ff, cfg.n_experts, gated=cfg.gated_mlp,
                    dtype=dt),
                keys[5], L)
            if cfg.dense_residual:  # arctic: parallel dense FFN
                layers["mlp"] = _stack_init(
                    lambda k: mlp_lib.init_mlp(
                        k, d, cfg.d_ff, gated=cfg.gated_mlp, dtype=dt),
                    keys[6], L)
        params["layers"] = layers
    elif cfg.family == "rwkv":
        params["layers"] = {
            "ln1": _stack_init(lambda k: init_norm(cfg, d), keys[2], L),
            "ln2": _stack_init(lambda k: init_norm(cfg, d), keys[3], L),
            "tmix": _stack_init(
                lambda k: rwkv_lib.init_time_mix(k, d, dtype=dt), keys[4], L),
            "cmix": _stack_init(
                lambda k: rwkv_lib.init_channel_mix(k, d, cfg.d_ff, dtype=dt),
                keys[5], L),
        }
    elif cfg.family == "hybrid":
        params["layers"] = {
            "ln1": _stack_init(lambda k: init_norm(cfg, d), keys[2], L),
            "mamba": _stack_init(
                lambda k: mb.init_mamba(
                    k, d, cfg.ssm_state, expand=cfg.ssm_expand,
                    head_dim=cfg.ssm_head_dim, dtype=dt),
                keys[4], L),
        }
        # single shared attention+MLP block (zamba2)
        params["shared"] = {
            "ln1": init_norm(cfg, d),
            "ln2": init_norm(cfg, d),
            "attn": attn.init_attn(
                keys[5], d, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
                qkv_bias=cfg.qkv_bias, dtype=dt),
            "mlp": mlp_lib.init_mlp(
                keys[6], d, cfg.d_ff, gated=cfg.gated_mlp, dtype=dt),
        }
    else:
        raise ValueError(f"init_lm does not handle family {cfg.family!r}")
    return params


def lm_axes(cfg: ModelConfig):
    ax: dict[str, Any] = {
        "embed": ("vocab", None),
        "final_norm": norm_axes(cfg),
    }
    if not cfg.tie_embeddings:
        ax["unembed"] = (None, "vocab")
    if cfg.family in ("dense", "moe"):
        layers = {
            "ln1": _prefix_axes(norm_axes(cfg)),
            "ln2": _prefix_axes(norm_axes(cfg)),
            "attn": _prefix_axes(attn.attn_axes(cfg.qkv_bias)),
        }
        if cfg.family == "dense":
            layers["mlp"] = _prefix_axes(mlp_lib.mlp_axes(cfg.gated_mlp))
        else:
            layers["moe"] = _prefix_axes(moe_lib.moe_axes(cfg.gated_mlp))
            if cfg.dense_residual:
                layers["mlp"] = _prefix_axes(mlp_lib.mlp_axes(cfg.gated_mlp))
        ax["layers"] = layers
    elif cfg.family == "rwkv":
        ax["layers"] = {
            "ln1": _prefix_axes(norm_axes(cfg)),
            "ln2": _prefix_axes(norm_axes(cfg)),
            "tmix": _prefix_axes(rwkv_lib.time_mix_axes()),
            "cmix": _prefix_axes(rwkv_lib.channel_mix_axes()),
        }
    elif cfg.family == "hybrid":
        ax["layers"] = {
            "ln1": _prefix_axes(norm_axes(cfg)),
            "mamba": _prefix_axes(mb.mamba_axes()),
        }
        ax["shared"] = {
            "ln1": norm_axes(cfg), "ln2": norm_axes(cfg),
            "attn": attn.attn_axes(cfg.qkv_bias),
            "mlp": mlp_lib.mlp_axes(cfg.gated_mlp),
        }
    return ax


# ---------------------------------------------------------------- blocks
def _attn_kw(cfg: ModelConfig):
    return dict(
        n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        rope_theta=cfg.rope_theta, chunk=cfg.attn_chunk,
    )


def _dense_block(cfg, lp, x):
    x = x + attn.self_attention(lp["attn"], apply_norm(cfg, lp["ln1"], x),
                                **_attn_kw(cfg))
    x = x + mlp_lib.mlp(lp["mlp"], apply_norm(cfg, lp["ln2"], x),
                        activation=cfg.activation)
    return cm.hint(x, "dp", None, "model")


def _moe_block(cfg, lp, x, mesh):
    x = x + attn.self_attention(lp["attn"], apply_norm(cfg, lp["ln1"], x),
                                **_attn_kw(cfg))
    h = apply_norm(cfg, lp["ln2"], x)
    y, aux = moe_lib.moe_ffn(
        lp["moe"], h, mesh=mesh, top_k=cfg.top_k,
        capacity_factor=cfg.capacity_factor, activation=cfg.activation,
    )
    if cfg.dense_residual:
        y = y + mlp_lib.mlp(lp["mlp"], h, activation=cfg.activation)
    return cm.hint(x + y, "dp", None, "model"), aux


def _rwkv_block(cfg, lp, x, tmix_state=None, shifts=(None, None)):
    h = apply_norm(cfg, lp["ln1"], x)
    y, (new_state, last_att) = rwkv_lib.time_mix(
        lp["tmix"], h, n_heads=cfg.n_heads, state=tmix_state,
        x_prev=shifts[0],
    )
    x = x + y
    h2 = apply_norm(cfg, lp["ln2"], x)
    y2, last_ffn = rwkv_lib.channel_mix(lp["cmix"], h2, x_prev=shifts[1])
    return cm.hint(x + y2, "dp", None, "model"), (new_state, last_att, last_ffn)


def _shared_block(cfg, sp, x, cache: attn.KVCache | None = None, mode="full"):
    h = apply_norm(cfg, sp["ln1"], x)
    if mode == "full":
        y = attn.self_attention(sp["attn"], h, **_attn_kw(cfg))
        new_cache = cache
    elif mode == "prefill":
        y, new_cache = attn.prefill_attention(
            sp["attn"], h, cache, rope_theta=cfg.rope_theta,
            chunk=cfg.attn_chunk)
    else:  # decode
        y, new_cache = attn.decode_attention(
            sp["attn"], h, cache, rope_theta=cfg.rope_theta)
    x = x + y
    x = x + mlp_lib.mlp(sp["mlp"], apply_norm(cfg, sp["ln2"], x),
                        activation=cfg.activation)
    return x, new_cache


def _cached_block(cfg, lp, x, attend, mesh, token_mask=None):
    """A dense or MoE layer around its cached attention ``attend(h) ->
    (y, cache)``; for MoE only ``token_mask``'s tokens compete for expert
    capacity. Returns (x, cache)."""
    y, cache = attend(apply_norm(cfg, lp["ln1"], x))
    x = x + y
    h2 = apply_norm(cfg, lp["ln2"], x)
    if cfg.family == "moe":
        y2, _ = moe_lib.moe_ffn(
            lp["moe"], h2, mesh=mesh, top_k=cfg.top_k,
            capacity_factor=cfg.capacity_factor, activation=cfg.activation,
            token_mask=token_mask)
        if cfg.dense_residual:
            y2 = y2 + mlp_lib.mlp(lp["mlp"], h2, activation=cfg.activation)
    else:
        y2 = mlp_lib.mlp(lp["mlp"], h2, activation=cfg.activation)
    return cm.hint(x + y2, "dp", None, "model"), cache


# ---------------------------------------------------------------- forward
def _maybe_remat(cfg, fn):
    if not cfg.remat:
        return fn
    if cfg.remat_policy == "dots":
        # selective remat: matmul outputs are saved, elementwise is
        # recomputed — cuts the backward's recompute FLOPs and the
        # associated HBM traffic at a bounded activation-memory cost
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


def forward(params, tokens, cfg: ModelConfig, mesh=None):
    """tokens (B, S) -> (hidden (B, S, d), aux_loss scalar)."""
    cm.set_activation_mesh(mesh)
    x = cm.embed_lookup(params["embed"], tokens, mesh).astype(cfg.dtype)
    L = cfg.n_layers

    if cfg.family == "dense":
        def body(carry, lp):
            return _dense_block(cfg, lp, carry), None
        x, _ = jax.lax.scan(_maybe_remat(cfg, body), x, params["layers"])
        aux = jnp.zeros((), jnp.float32)
    elif cfg.family == "moe":
        def body(carry, lp):
            x, aux = carry
            x, a = _moe_block(cfg, lp, x, mesh)
            return (x, aux + a), None
        (x, aux), _ = jax.lax.scan(
            _maybe_remat(cfg, body), (x, jnp.zeros((), jnp.float32)),
            params["layers"])
        aux = aux / L
    elif cfg.family == "rwkv":
        def body(carry, lp):
            y, _ = _rwkv_block(cfg, lp, carry)
            return y, None
        x, _ = jax.lax.scan(_maybe_remat(cfg, body), x, params["layers"])
        aux = jnp.zeros((), jnp.float32)
    elif cfg.family == "hybrid":
        shared = params["shared"]
        k_every = cfg.shared_attn_every or (L + 1)

        def body(carry, inp):
            i, lp = inp
            x = carry
            h = apply_norm(cfg, lp["ln1"], x)
            y, _ = mb.mamba_block(
                lp["mamba"], h, d_state=cfg.ssm_state, expand=cfg.ssm_expand,
                head_dim=cfg.ssm_head_dim)
            x = x + y
            x = jax.lax.cond(
                i % k_every == 0,
                lambda v: _shared_block(cfg, shared, v)[0],
                lambda v: v,
                x,
            )
            return cm.hint(x, "dp", None, "model"), None

        x, _ = jax.lax.scan(
            _maybe_remat(cfg, body), x, (jnp.arange(L), params["layers"]))
        aux = jnp.zeros((), jnp.float32)
    else:
        raise ValueError(cfg.family)

    x = apply_norm(cfg, params["final_norm"], x)
    return x, aux


def _logits(params, cfg: ModelConfig, h):
    """Unembed. Tied embeddings use the (V, d) table as a column-major B —
    the paper's B-col-major GEMM case, no transpose materialized."""
    if cfg.tie_embeddings:
        return balanced_gemm(
            h, params["embed"], b_layout="col", out_dtype=jnp.float32,
            backend=cm.get_matmul_backend())
    return cm.dense(h, params["unembed"], out_dtype=jnp.float32)


def lm_loss(params, hidden, labels, cfg: ModelConfig):
    """Sequence-chunked CE: logits are materialized only (B, chunk, V) at a
    time (the (B,S,V) tensor for command-r@4k would be half a TB)."""
    B, S, d = hidden.shape
    c = min(cfg.loss_chunk, S)
    if S % c:
        c = S  # fallback: uneven chunks
    n = S // c
    hs = hidden.reshape(B, n, c, d).transpose(1, 0, 2, 3)
    ls = labels.reshape(B, n, c).transpose(1, 0, 2)

    def body(carry, inp):
        tot, cnt = carry
        h, lab = inp
        logits = _logits(params, cfg, h)
        mask = (lab >= 0) & (lab < cfg.vocab_size)
        lab_c = jnp.clip(lab, 0, cfg.padded_vocab - 1)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lab_c[..., None], axis=-1)[..., 0]
        nll = (lse - gold) * mask
        return (tot + nll.sum(), cnt + mask.sum()), None

    (tot, cnt), _ = jax.lax.scan(
        jax.checkpoint(body),
        (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)), (hs, ls))
    return tot / jnp.maximum(cnt, 1)


# ---------------------------------------------------------------- decode
def init_decode_state(cfg: ModelConfig, batch: int, max_len: int,
                      per_slot: bool = False, kv_block_size: int | None = None,
                      num_kv_blocks: int | None = None, kv_dtype=None):
    """``per_slot=True`` makes the KV length a (batch,) vector — one decode
    position per slot lane, the continuous-batching engine's cache layout
    (dense/moe only; other families keep their scalar/implicit clocks).

    ``kv_block_size`` switches the per-slot cache from contiguous
    ``(batch, max_len)`` regions to the paged block-pool layout
    (:class:`repro.layers.attention.PagedKVCache`): ``num_kv_blocks`` pool
    blocks of ``kv_block_size`` tokens (block 0 reserved as the null
    block), a ``(batch, ceil(max_len/block))`` block table, and per-slot
    lengths. Pool capacity then tracks admitted tokens, not
    ``batch * max_len``.

    ``kv_dtype`` (:class:`repro.quant.KVCacheDtype` or its string name)
    selects the paged pool's storage format: int8 allocates the K/V pool
    in int8 plus ``(L, num_kv_blocks, n_kv_heads)`` f32 scale arrays
    (initialized to 1.0 — a zero block dequantizes to zero at any scale).
    Paged layout only; the contiguous cache stays ``cfg.dtype``."""
    L, d = cfg.n_layers, cfg.d_model
    kvd = KVCacheDtype.parse(kv_dtype)
    if kvd.quantized and not kv_block_size:
        raise ValueError(
            f"kv_dtype={kvd.value} needs the paged layout (kv_block_size)")
    if kv_block_size:
        if cfg.family not in ("dense", "moe"):
            raise ValueError(
                f"paged KV needs a KV-cache family, not {cfg.family!r}")
        if not per_slot:
            raise ValueError("paged KV is a per-slot (engine) layout")
        if num_kv_blocks is None or num_kv_blocks < 2:
            raise ValueError(
                f"paged KV needs num_kv_blocks >= 2 (block 0 is the null "
                f"block), got {num_kv_blocks}")
        max_blocks = -(-max_len // kv_block_size)
        sd = kvd.storage_dtype if kvd.quantized else cfg.dtype
        kv = attn.PagedKVCache(
            k=jnp.zeros((L, num_kv_blocks, kv_block_size, cfg.n_kv_heads,
                         cfg.head_dim), sd),
            v=jnp.zeros((L, num_kv_blocks, kv_block_size, cfg.n_kv_heads,
                         cfg.head_dim), sd),
            table=jnp.zeros((batch, max_blocks), jnp.int32),
            length=jnp.zeros((batch,), jnp.int32),
            k_scale=(jnp.ones((L, num_kv_blocks, cfg.n_kv_heads),
                              jnp.float32) if kvd.quantized else None),
            v_scale=(jnp.ones((L, num_kv_blocks, cfg.n_kv_heads),
                              jnp.float32) if kvd.quantized else None),
        )
        return {"kv": kv}
    if cfg.family in ("dense", "moe"):
        kv = attn.KVCache(
            k=jnp.zeros((L, batch, max_len, cfg.n_kv_heads, cfg.head_dim),
                        cfg.dtype),
            v=jnp.zeros((L, batch, max_len, cfg.n_kv_heads, cfg.head_dim),
                        cfg.dtype),
            length=jnp.zeros((batch,) if per_slot else (), jnp.int32),
        )
        return {"kv": kv}
    if per_slot:
        raise ValueError(
            f"per-slot decode state needs a KV-cache family, not {cfg.family!r}")
    if cfg.family == "rwkv":
        H, N = cfg.n_heads, d // cfg.n_heads
        return {
            "wkv": jnp.zeros((L, batch, H, N, N), jnp.float32),
            "att_shift": jnp.zeros((L, batch, d), cfg.dtype),
            "ffn_shift": jnp.zeros((L, batch, d), cfg.dtype),
        }
    if cfg.family == "hybrid":
        d_inner, n_heads = mb.dims(
            d, cfg.ssm_state, expand=cfg.ssm_expand,
            head_dim=cfg.ssm_head_dim)
        d_conv = d_inner + 2 * cfg.ssm_state
        return {
            "ssm": jnp.zeros(
                (L, batch, n_heads, cfg.ssm_head_dim, cfg.ssm_state),
                jnp.float32),
            "conv": jnp.zeros((L, batch, mb.CONV_K - 1, d_conv), cfg.dtype),
            "kv": attn.KVCache(
                k=jnp.zeros(
                    (L, batch, max_len, cfg.n_kv_heads, cfg.head_dim),
                    cfg.dtype),
                v=jnp.zeros(
                    (L, batch, max_len, cfg.n_kv_heads, cfg.head_dim),
                    cfg.dtype),
                length=jnp.zeros((), jnp.int32),
            ),
        }
    raise ValueError(cfg.family)


def prefill(params, tokens, cfg: ModelConfig, state, mesh=None,
            last_pos=None):
    """Full-sequence prefill populating the decode state.

    Returns (last-token logits (B, Vp), new state). ``last_pos`` (scalar or
    (B,) int32) selects which position's logits to return instead of the
    final one — the serving engine right-pads every prompt to one fixed
    length (one compiled prefill, one GEMM signature set) and reads logits
    at each request's true last token; trailing pads are causally invisible
    to it."""
    cm.set_activation_mesh(mesh)
    x = cm.embed_lookup(params["embed"], tokens, mesh).astype(cfg.dtype)
    S = tokens.shape[1]
    L = cfg.n_layers

    if cfg.family in ("dense", "moe"):
        kv = state["kv"]

        def body(carry, inp):
            x = carry
            lp, ck, cv = inp
            h = apply_norm(cfg, lp["ln1"], x)
            cache = attn.KVCache(k=ck, v=cv, length=kv.length)
            y, new_cache = attn.prefill_attention(
                lp["attn"], h, cache, rope_theta=cfg.rope_theta,
                chunk=cfg.attn_chunk)
            x = x + y
            h2 = apply_norm(cfg, lp["ln2"], x)
            if cfg.family == "moe":
                y2, _ = moe_lib.moe_ffn(
                    lp["moe"], h2, mesh=mesh, top_k=cfg.top_k,
                    capacity_factor=cfg.capacity_factor,
                    activation=cfg.activation)
                if cfg.dense_residual:
                    y2 = y2 + mlp_lib.mlp(lp["mlp"], h2,
                                          activation=cfg.activation)
            else:
                y2 = mlp_lib.mlp(lp["mlp"], h2, activation=cfg.activation)
            return cm.hint(x + y2, "dp", None, "model"), (new_cache.k, new_cache.v)

        x, (nk, nv) = jax.lax.scan(
            body, x, (params["layers"], kv.k, kv.v))
        new_state = {"kv": attn.KVCache(
            k=nk, v=nv, length=jnp.asarray(S, jnp.int32))}
    elif cfg.family == "rwkv":
        def body(carry, inp):
            x = carry
            lp = inp
            x, (wkv, att_s, ffn_s) = _rwkv_block(cfg, lp, x)
            return x, (wkv, att_s, ffn_s)

        x, (wkv, att_s, ffn_s) = jax.lax.scan(body, x, params["layers"])
        new_state = {"wkv": wkv, "att_shift": att_s, "ffn_shift": ffn_s}
    elif cfg.family == "hybrid":
        shared = params["shared"]
        k_every = cfg.shared_attn_every or (L + 1)
        kv = state["kv"]

        def body(carry, inp):
            x = carry
            i, lp, ck, cv = inp
            h = apply_norm(cfg, lp["ln1"], x)
            y, mstate = mb.mamba_block(
                lp["mamba"], h, d_state=cfg.ssm_state, expand=cfg.ssm_expand,
                head_dim=cfg.ssm_head_dim)
            x = x + y
            cache = attn.KVCache(k=ck, v=cv, length=kv.length)

            def with_shared(v):
                out, nc = _shared_block(cfg, shared, v, cache, mode="prefill")
                return out, nc.k, nc.v

            def without(v):
                return v, cache.k, cache.v

            x, nk, nv = jax.lax.cond(i % k_every == 0, with_shared, without, x)
            return cm.hint(x, "dp", None, "model"), (mstate.ssm, mstate.conv, nk, nv)

        x, (ssm, conv, nk, nv) = jax.lax.scan(
            body, x, (jnp.arange(L), params["layers"], kv.k, kv.v))
        new_state = {
            "ssm": ssm, "conv": conv,
            "kv": attn.KVCache(k=nk, v=nv, length=jnp.asarray(S, jnp.int32)),
        }
    else:
        raise ValueError(cfg.family)

    if last_pos is None:
        h_last = x[:, -1:]
    else:
        lp = jnp.broadcast_to(
            jnp.asarray(last_pos, jnp.int32), (x.shape[0],))
        h_last = jnp.take_along_axis(x, lp[:, None, None], axis=1)
    h_last = apply_norm(cfg, params["final_norm"], h_last)
    return _logits(params, cfg, h_last)[:, 0], new_state


def prefill_chunk(params, tokens, cfg: ModelConfig, state, *, slot, start,
                  true_len, blocks, mesh=None):
    """One chunked-prefill step into a *paged* decode state.

    ``tokens`` is (1, C): the next chunk of one request's prompt, right-
    padded to the bucket length C. ``slot`` is the lane the request
    occupies, ``start`` how many prompt tokens earlier chunks wrote,
    ``true_len`` how many of this chunk's tokens are real, and ``blocks``
    the (max_blocks,) int32 block-table row the allocator assigned (null-
    padded) — installed idempotently on every chunk, so the first chunk
    binds the lane and later chunks are no-ops on the table.

    Returns (logits (1, Vp) at the chunk's last real token, new state); the
    engine only samples the logits of a prompt's final chunk. Slot, start
    and true_len are traced scalars: one compiled program per bucket length
    serves every admission (the fixed-signature property the plan cache is
    built around).

    Parity: for the dense family this is bit-exact against whole-prompt
    prefill (each chunk attends to the identical key set, position for
    position). For MoE it matches only while expert capacity does not
    bind: ``moe_ffn`` derives capacity from the tokens in the call, so a
    chunk's tokens compete for a chunk-sized capacity rather than a
    prompt-sized one — when routing overflows, chunked and whole-prompt
    prefill can drop different tokens. Chunk-wise exactness under
    overflow is structurally impossible (capacity competition is
    per-call); docs/serving.md states the same caveat for operators.
    """
    cm.set_activation_mesh(mesh)
    if cfg.family not in ("dense", "moe"):
        raise ValueError(
            f"chunked prefill needs a KV-cache family, not {cfg.family!r}")
    kv = state["kv"]
    if not isinstance(kv, attn.PagedKVCache):
        raise ValueError("prefill_chunk requires a paged decode state "
                         "(init_decode_state with kv_block_size)")
    x = cm.embed_lookup(params["embed"], tokens, mesh).astype(cfg.dtype)
    C = tokens.shape[1]
    # pad positions must not compete for expert capacity
    real = (jnp.arange(C) < true_len)[None, :]

    # the stacked pools are the scan's carry, written in place (as in
    # decode_step)
    def body(carry, inp):
        x, cache = carry
        lp, li = inp
        return _cached_block(
            cfg, lp, x, lambda h: attn.paged_prefill_attention(
                lp["attn"], h, cache, layer=li, slot=slot, start=start,
                true_len=true_len, rope_theta=cfg.rope_theta),
            mesh, real), None

    (x, kv), _ = jax.lax.scan(
        body, (x, kv._replace(table=kv.table.at[slot].set(blocks))),
        (params["layers"], jnp.arange(cfg.n_layers, dtype=jnp.int32)))
    new_state = {**state, "kv": kv._replace(length=kv.length.at[slot].set(
        jnp.asarray(start + true_len, jnp.int32)))}
    lp = jnp.broadcast_to(
        jnp.asarray(true_len - 1, jnp.int32), (x.shape[0],))
    h_last = jnp.take_along_axis(x, lp[:, None, None], axis=1)
    h_last = apply_norm(cfg, params["final_norm"], h_last)
    return _logits(params, cfg, h_last)[:, 0], new_state


def verify_step(params, tokens, cfg: ModelConfig, state, mesh=None,
                active=None):
    """Speculative-verify step: S candidate tokens per lane in one pass.

    ``tokens`` is (B, S): per slot, the last committed token followed by
    the draft model's S-1 proposals. Returns (logits (B, S, Vp), new
    state) — the logits at *every* fed position, so the engine's greedy
    acceptance can compare each proposal against the target's own argmax
    at the same position. All S keys/values are written through the block
    table (``attn.paged_verify_attention``) and every active lane's
    length advances by S; the engine rewinds the rejected tail host-side
    (blocks were allocated at budget, so rewind never touches the
    allocator).

    Paged per-slot state only — speculation rides the paged engine. For
    MoE the active-lane mask broadcasts over the S candidate positions
    (all fed tokens of a live lane are real; a vacant lane's pads must
    not compete for expert capacity, same rule as ``decode_step``).
    """
    cm.set_activation_mesh(mesh)
    if cfg.family not in ("dense", "moe"):
        raise ValueError(
            f"speculative verify needs a KV-cache family, not {cfg.family!r}")
    kv = state["kv"]
    if not isinstance(kv, attn.PagedKVCache):
        raise ValueError("verify_step requires a paged decode state "
                         "(init_decode_state with kv_block_size)")
    x = cm.embed_lookup(params["embed"], tokens, mesh).astype(cfg.dtype)
    B, S = tokens.shape
    quantized = kv.k_scale is not None

    def body(carry, inp):
        if quantized:
            lp, ck, cv, cks, cvs = inp
        else:
            (lp, ck, cv), cks, cvs = inp, None, None
        cache = attn.PagedKVCache(k=ck, v=cv, table=kv.table,
                                  length=kv.length,
                                  k_scale=cks, v_scale=cvs)
        x, nc = _cached_block(
            cfg, lp, carry, lambda h: attn.paged_verify_attention(
                lp["attn"], h, cache, rope_theta=cfg.rope_theta,
                active=active),
            mesh, None if active is None
            else jnp.broadcast_to((active > 0)[:, None], (B, S)))
        out = ((nc.k, nc.v, nc.k_scale, nc.v_scale) if quantized
               else (nc.k, nc.v))
        return x, out

    if quantized:
        x, (nk, nv, nks, nvs) = jax.lax.scan(
            body, x, (params["layers"], kv.k, kv.v, kv.k_scale, kv.v_scale))
    else:
        x, (nk, nv) = jax.lax.scan(body, x, (params["layers"], kv.k, kv.v))
        nks = nvs = None
    step = S if active is None else S * active.astype(kv.length.dtype)
    new_state = {**state, "kv": attn.PagedKVCache(
        k=nk, v=nv, table=kv.table, length=kv.length + step,
        k_scale=nks, v_scale=nvs)}
    h = apply_norm(cfg, params["final_norm"], x)
    return _logits(params, cfg, h), new_state


def decode_step(params, tokens, cfg: ModelConfig, state, mesh=None,
                active=None):
    """One decode step. tokens (B, 1) -> (logits (B, Vp), new state).

    ``active`` (B,) marks which rows are live decode lanes: the KV length of
    an inactive slot does not advance (its pad-token write lands beyond the
    valid prefix and is reclaimed by the next admission). Requires a per-KV-
    cache family; the engine only schedules dense/moe models."""
    cm.set_activation_mesh(mesh)
    if active is not None and cfg.family not in ("dense", "moe"):
        raise ValueError(
            f"per-slot active masks need a KV-cache family, not {cfg.family!r}")
    x = cm.embed_lookup(params["embed"], tokens, mesh).astype(cfg.dtype)
    L = cfg.n_layers

    if cfg.family in ("dense", "moe"):
        kv = state["kv"]

        # vacant slot lanes must not compete for expert capacity: a live
        # request's routing would otherwise depend on unrelated slot
        # occupancy (engine determinism)
        real = None if active is None else (active > 0)[:, None]
        step = 1 if active is None else active.astype(kv.length.dtype)
        if isinstance(kv, attn.PagedKVCache):
            # the stacked pools are the scan's carry: each layer writes its
            # new rows into them in place and attends at its own index, so
            # no per-layer pool slice or whole-pool copy exists
            def body(carry, inp):
                x, cache = carry
                lp, li = inp
                return _cached_block(
                    cfg, lp, x, lambda h: attn.paged_decode_attention(
                        lp["attn"], h, cache, layer=li,
                        rope_theta=cfg.rope_theta, active=active),
                    mesh, real), None

            (x, kv), _ = jax.lax.scan(
                body, (x, kv),
                (params["layers"], jnp.arange(L, dtype=jnp.int32)))
            new_state = {"kv": kv._replace(length=kv.length + step)}
        else:
            def body(carry, inp):
                lp, ck, cv = inp
                x, nc = _cached_block(
                    cfg, lp, carry, lambda h: attn.decode_attention(
                        lp["attn"], h,
                        attn.KVCache(k=ck, v=cv, length=kv.length),
                        rope_theta=cfg.rope_theta),
                    mesh, real)
                return x, (nc.k, nc.v)

            x, (nk, nv) = jax.lax.scan(
                body, x, (params["layers"], kv.k, kv.v))
            new_state = {"kv": attn.KVCache(
                k=nk, v=nv, length=kv.length + step)}
    elif cfg.family == "rwkv":
        def body(carry, inp):
            x = carry
            lp, wkv, att_s, ffn_s = inp
            x, (nw, na, nf) = _rwkv_block(
                cfg, lp, x, tmix_state=wkv, shifts=(att_s, ffn_s))
            return x, (nw, na, nf)

        x, (wkv, att_s, ffn_s) = jax.lax.scan(
            body, x,
            (params["layers"], state["wkv"], state["att_shift"],
             state["ffn_shift"]))
        new_state = {"wkv": wkv, "att_shift": att_s, "ffn_shift": ffn_s}
    elif cfg.family == "hybrid":
        shared = params["shared"]
        k_every = cfg.shared_attn_every or (L + 1)
        kv = state["kv"]

        def body(carry, inp):
            x = carry
            i, lp, ssm, conv, ck, cv = inp
            h = apply_norm(cfg, lp["ln1"], x)
            y, mstate = mb.mamba_block(
                lp["mamba"], h, d_state=cfg.ssm_state, expand=cfg.ssm_expand,
                head_dim=cfg.ssm_head_dim,
                state=mb.MambaState(ssm=ssm, conv=conv))
            x = x + y
            cache = attn.KVCache(k=ck, v=cv, length=kv.length)

            def with_shared(v):
                out, nc = _shared_block(cfg, shared, v, cache, mode="decode")
                return out, nc.k, nc.v

            def without(v):
                return v, cache.k, cache.v

            x, nk, nv = jax.lax.cond(i % k_every == 0, with_shared, without, x)
            return cm.hint(x, "dp", None, "model"), (mstate.ssm, mstate.conv, nk, nv)

        x, (ssm, conv, nk, nv) = jax.lax.scan(
            body, x,
            (jnp.arange(L), params["layers"], state["ssm"], state["conv"],
             kv.k, kv.v))
        new_state = {
            "ssm": ssm, "conv": conv,
            "kv": attn.KVCache(k=nk, v=nv, length=kv.length + 1),
        }
    else:
        raise ValueError(cfg.family)

    h = apply_norm(cfg, params["final_norm"], x)
    return _logits(params, cfg, h)[:, 0], new_state
