"""Attention layers: GQA self-attention (train/prefill/decode), cross-attention.

Memory posture: full-sequence training/prefill uses an online-softmax scan
over KV chunks (``chunked_attention``) so the S×S score matrix is never
materialized — the pure-JAX flash-attention formulation. Decode attends one
query against the whole KV cache (linear per step).

All projections route through :func:`repro.layers.common.dense` — i.e. the
paper's balanced-GEMM substrate.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.context import current_context
from repro.kernels import paged_attention as pa
from repro.kernels.ops import resolve_backend
from repro.layers import common as cm
from repro.quant import int8 as q8

NEG_INF = -1e30


class AttnParams(NamedTuple):
    wq: jax.Array           # (d, H*Dh)
    wk: jax.Array           # (d, Hkv*Dh)
    wv: jax.Array           # (d, Hkv*Dh)
    wo: jax.Array           # (H*Dh, d)
    bq: jax.Array | None
    bk: jax.Array | None
    bv: jax.Array | None


def init_attn(key, d_model, n_heads, n_kv_heads, head_dim, *, qkv_bias=False,
              dtype=jnp.float32):
    ks = cm.split_keys(key, 4)
    q_dim, kv_dim = n_heads * head_dim, n_kv_heads * head_dim
    zeros = lambda n: jnp.zeros((n,), dtype)
    return AttnParams(
        wq=cm.normal_init(ks[0], (d_model, q_dim), dtype),
        wk=cm.normal_init(ks[1], (d_model, kv_dim), dtype),
        wv=cm.normal_init(ks[2], (d_model, kv_dim), dtype),
        wo=cm.normal_init(ks[3], (q_dim, d_model), dtype),
        bq=zeros(q_dim) if qkv_bias else None,
        bk=zeros(kv_dim) if qkv_bias else None,
        bv=zeros(kv_dim) if qkv_bias else None,
    )


def attn_axes(qkv_bias=False):
    """Logical sharding axes mirroring AttnParams."""
    return AttnParams(
        wq=("embed", "heads"), wk=("embed", "kv"), wv=("embed", "kv"),
        wo=("heads", "embed"),
        bq=("heads",) if qkv_bias else None,
        bk=("kv",) if qkv_bias else None,
        bv=("kv",) if qkv_bias else None,
    )


def _attn_mode(n_heads: int, seq: int) -> str:
    """How to parallelize attention activations over the 'model' axis.

    'heads'  — classic TP: heads divide the model axis;
    'seq'    — context parallelism: heads don't divide (qwen 20H, arctic 56H,
               whisper 8H on a 16-way axis) but the query sequence does;
    'none'   — tiny shapes (smoke tests).
    """
    tp = cm.axis_size("model")
    if tp <= 1:
        return "none"
    if n_heads % tp == 0:
        return "heads"
    if seq % tp == 0:
        return "seq"
    return "none"


def _hint_qkv(q, k, v):
    """Apply activation sharding to (B, S, H, D) q/k/v (post repeat_kv)."""
    mode = _attn_mode(q.shape[2], q.shape[1])
    if mode == "heads":
        q = cm.hint(q, "dp", None, "model", None)
        k = cm.hint(k, "dp", None, "model", None)
        v = cm.hint(v, "dp", None, "model", None)
    elif mode == "seq":
        # context parallel: queries sharded along S; KV replicated over model
        q = cm.hint(q, "dp", "model", None, None)
        k = cm.hint(k, "dp", None, None, None)
        v = cm.hint(v, "dp", None, None, None)
    return q, k, v


def _repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    """(B, S, Hkv, D) -> (B, S, Hkv*n_rep, D)."""
    if n_rep == 1:
        return x
    b, s, h, d = x.shape
    return jnp.broadcast_to(
        x[:, :, :, None, :], (b, s, h, n_rep, d)
    ).reshape(b, s, h * n_rep, d)


def plain_attention(q, k, v, *, causal: bool, q_offset: int = 0):
    """Reference attention, materializes scores. q: (B,Sq,H,D), k/v (B,Sk,H,D)."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q * jnp.asarray(scale, q.dtype), k,
                   preferred_element_type=jnp.float32)
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        qpos = jnp.arange(sq) + q_offset
        mask = qpos[:, None] >= jnp.arange(sk)[None, :]
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def chunked_attention(q, k, v, *, causal: bool, chunk: int = 1024,
                      q_offset: int = 0):
    """Online-softmax attention, scanning KV chunks (flash formulation).

    Never materializes more than (B, H, Sq, chunk) scores. Exact (up to f32
    accumulation order) vs plain_attention.
    """
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    if Sk % chunk:
        pad = chunk - Sk % chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kpad_mask = jnp.arange(Sk + pad) < Sk
    else:
        kpad_mask = None
    n_chunks = k.shape[1] // chunk
    scale = D ** -0.5
    qf = q * jnp.asarray(scale, q.dtype)
    kc = k.reshape(B, n_chunks, chunk, H, D)
    vc = v.reshape(B, n_chunks, chunk, H, D)
    mode = _attn_mode(H, Sq)
    carry_spec = {
        "heads": ("dp", "model", None, None),
        "seq": ("dp", None, "model", None),
        "none": ("dp", None, None, None),
    }[mode]  # carries are (B, H, Sq, ...)

    qpos = jnp.arange(Sq) + q_offset

    def body(carry, inp):
        o, m, l = carry
        idx, kb, vb = inp
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kb,
                       preferred_element_type=jnp.float32)
        s = cm.hint(s, *carry_spec)
        kpos = idx * chunk + jnp.arange(chunk)
        mask = jnp.ones((Sq, chunk), bool)
        if causal:
            mask = qpos[:, None] >= kpos[None, :]
        if kpad_mask is not None:
            mask = mask & (kpos < Sk)[None, :]
        s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + p.sum(-1)
        o_new = o * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(v.dtype), vb,
            preferred_element_type=jnp.float32,
        )
        o_new = cm.hint(o_new, *carry_spec)
        return (o_new, m_new, l_new), None

    o0 = cm.hint(jnp.zeros((B, H, Sq, D), jnp.float32), *carry_spec)
    m0 = cm.hint(jnp.full((B, H, Sq), NEG_INF, jnp.float32), *carry_spec[:3])
    l0 = cm.hint(jnp.zeros((B, H, Sq), jnp.float32), *carry_spec[:3])
    (o, m, l), _ = jax.lax.scan(
        jax.checkpoint(body), (o0, m0, l0),
        (jnp.arange(n_chunks), kc.transpose(1, 0, 2, 3, 4),
         vc.transpose(1, 0, 2, 3, 4)),
    )
    o = o / jnp.maximum(l[..., None], 1e-30)
    return o.transpose(0, 2, 1, 3).astype(q.dtype)


def attention_core(q, k, v, *, causal: bool, chunk: int | None,
                   q_offset: int = 0):
    if chunk is not None and k.shape[1] > chunk:
        return chunked_attention(q, k, v, causal=causal, chunk=chunk,
                                 q_offset=q_offset)
    return plain_attention(q, k, v, causal=causal, q_offset=q_offset)


def self_attention(
    p: AttnParams,
    x: jax.Array,
    *,
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    rope_theta: float = 10000.0,
    causal: bool = True,
    chunk: int | None = 1024,
    positions: jax.Array | None = None,
    use_rope: bool = True,
) -> jax.Array:
    """Full-sequence GQA self-attention (training / prefill without cache)."""
    B, S, _ = x.shape
    q = cm.dense(x, p.wq, p.bq).reshape(B, S, n_heads, head_dim)
    k = cm.dense(x, p.wk, p.bk).reshape(B, S, n_kv_heads, head_dim)
    v = cm.dense(x, p.wv, p.bv).reshape(B, S, n_kv_heads, head_dim)
    if use_rope:
        if positions is None:
            positions = jnp.arange(S)[None, :]
        sin, cos = cm.rotary_embedding(positions, head_dim, rope_theta)
        q = cm.apply_rotary(q, sin, cos)
        k = cm.apply_rotary(k, sin, cos)
    k = _repeat_kv(k, n_heads // n_kv_heads)
    v = _repeat_kv(v, n_heads // n_kv_heads)
    q, k, v = _hint_qkv(q, k, v)
    o = attention_core(q, k, v, causal=causal, chunk=chunk)
    return cm.dense(o.reshape(B, S, n_heads * head_dim), p.wo)


class KVCache(NamedTuple):
    k: jax.Array      # (B, S_max, Hkv, Dh)
    v: jax.Array      # (B, S_max, Hkv, Dh)
    # valid prefix length: scalar int32 (all rows share one position — the
    # static-batch serve path) or (B,) int32 (per-slot positions — the
    # continuous-batching engine, where every lane decodes at its own depth)
    length: jax.Array


def init_kv_cache(batch, max_len, n_kv_heads, head_dim, dtype=jnp.bfloat16):
    shape = (batch, max_len, n_kv_heads, head_dim)
    return KVCache(
        k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
        length=jnp.zeros((), jnp.int32),
    )


def prefill_attention(
    p: AttnParams, x: jax.Array, cache: KVCache, **kw
) -> tuple[jax.Array, KVCache]:
    """Prefill: full self-attention + populate the KV cache prefix."""
    B, S, _ = x.shape
    n_kv, hd = cache.k.shape[2], cache.k.shape[3]
    q = cm.dense(x, p.wq, p.bq).reshape(B, S, -1, hd)
    k = cm.dense(x, p.wk, p.bk).reshape(B, S, n_kv, hd)
    v = cm.dense(x, p.wv, p.bv).reshape(B, S, n_kv, hd)
    if kw.get("use_rope", True):
        sin, cos = cm.rotary_embedding(
            jnp.arange(S)[None, :], hd, kw.get("rope_theta", 10000.0)
        )
        q = cm.apply_rotary(q, sin, cos)
        k = cm.apply_rotary(k, sin, cos)
    new_cache = KVCache(
        k=jax.lax.dynamic_update_slice_in_dim(
            cache.k, k.astype(cache.k.dtype), 0, axis=1),
        v=jax.lax.dynamic_update_slice_in_dim(
            cache.v, v.astype(cache.v.dtype), 0, axis=1),
        length=jnp.asarray(S, jnp.int32),
    )
    n_heads = q.shape[2]
    kr = _repeat_kv(k, n_heads // n_kv)
    vr = _repeat_kv(v, n_heads // n_kv)
    q, kr, vr = _hint_qkv(q, kr, vr)
    o = attention_core(q, kr, vr, causal=True, chunk=kw.get("chunk", 1024))
    return cm.dense(o.reshape(B, S, -1), p.wo), new_cache


def decode_attention(
    p: AttnParams, x: jax.Array, cache: KVCache, *,
    rope_theta: float = 10000.0, use_rope: bool = True,
) -> tuple[jax.Array, KVCache]:
    """One decode step: x (B, 1, d) against the cache; append the new KV.

    The score einsum contracts against the full cache; invalid (future)
    slots are masked by position. With the cache sequence dim sharded over
    the mesh 'data' axis (long_500k), GSPMD turns the masked softmax into
    the distributed flash-decode combine (partial max/sum + all-reduce).

    ``cache.length`` may be a scalar (every row at the same depth — static
    batching) or a (B,) vector of per-slot positions (the serving engine's
    slot lanes). The returned cache advances every position by 1; in the
    per-slot path the caller owns the advance instead (``lm.decode_step``
    masks it by the active lanes and discards the per-layer length) — a
    vacant lane's pad-token KV write lands beyond the valid prefix and is
    overwritten by the next admission.
    """
    B, S1, _ = x.shape
    assert S1 == 1
    n_kv, hd = cache.k.shape[2], cache.k.shape[3]
    q = cm.dense(x, p.wq, p.bq).reshape(B, 1, -1, hd)
    k = cm.dense(x, p.wk, p.bk).reshape(B, 1, n_kv, hd)
    v = cm.dense(x, p.wv, p.bv).reshape(B, 1, n_kv, hd)
    pos = cache.length
    per_slot = pos.ndim == 1
    if use_rope:
        # (B, 1) positions per slot; a scalar broadcasts to every row
        rpos = (pos[:, None] if per_slot else pos[None, None]).astype(
            jnp.float32)
        sin, cos = cm.rotary_embedding(rpos, hd, rope_theta)
        q = cm.apply_rotary(q, sin, cos)
        k = cm.apply_rotary(k, sin, cos)
    if per_slot:
        rows = jnp.arange(B)
        ck = cache.k.at[rows, pos].set(k[:, 0].astype(cache.k.dtype),
                                       mode="drop")
        cv = cache.v.at[rows, pos].set(v[:, 0].astype(cache.v.dtype),
                                       mode="drop")
    else:
        ck = jax.lax.dynamic_update_slice_in_dim(
            cache.k, k.astype(cache.k.dtype), pos, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(
            cache.v, v.astype(cache.v.dtype), pos, axis=1)
    n_heads = q.shape[2]
    scale = hd ** -0.5
    kr = _repeat_kv(ck, n_heads // n_kv)
    vr = _repeat_kv(cv, n_heads // n_kv)
    # contract against the cache in its storage dtype (a f32 .astype would
    # materialize an f32 copy of the whole 32k–512k cache); accumulate f32.
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", (q * jnp.asarray(scale, q.dtype)).astype(kr.dtype),
        kr, preferred_element_type=jnp.float32,
    )
    kpos = jnp.arange(cache.k.shape[1])
    valid = (kpos[None, :] <= pos[:, None] if per_slot
             else jnp.broadcast_to(kpos <= pos, (B, cache.k.shape[1])))
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    prob = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", prob.astype(vr.dtype), vr,
                   preferred_element_type=jnp.float32)
    out = cm.dense(o.reshape(B, 1, -1).astype(x.dtype), p.wo)
    return out, KVCache(k=ck, v=cv, length=pos + 1)


class PagedKVCache(NamedTuple):
    """Block-pool KV cache (vLLM-style paged layout).

    K/V live in a flat pool of fixed-size token blocks shared by every slot
    lane; a per-slot block table maps logical token positions to pool
    blocks. Capacity is proportional to admitted tokens instead of
    ``num_slots * max_len`` — the serving-side rendition of the paper's
    memory-balance argument (``serve/blockpool.py`` is the allocator).
    The table-directed gather back to the logical
    (slots, max_blocks * block_size, ...) layout happens *inside* the
    traced attention functions below — kernel-visible layout, never a
    host-side copy — so the masked contraction is the contiguous cache's,
    byte for byte.

    Block 0 is the reserved null block: vacant table entries point at it and
    redirected (inactive-lane / pad-position) writes land in it, so freed
    blocks are reusable without scrubbing. Every value a gather can read is
    finite, and invalid positions are masked to ``NEG_INF`` before softmax,
    so garbage never reaches a live request's output.

    With ``kv_dtype=int8`` the pool stores K/V as int8 with per-block,
    per-kv-head symmetric absmax scales in the parallel ``k_scale`` /
    ``v_scale`` arrays — quantized at write time, dequantized *inside* the
    table-directed gather, so no bf16 copy of the cache ever exists
    (docs/serving.md). ``k_scale is None`` is the bf16 mode switch: the
    pytree (and every traced graph over it) stays byte-identical to the
    pre-quantization layout.
    """

    # k, v and the scales lead with the layer axis (L, ...) in the decode
    # step and prefill chunk, which carry every layer's pool; the verify
    # step hands its attention one layer's slice
    k: jax.Array        # (num_blocks, block_size, Hkv, Dh) bf16 | int8
    v: jax.Array        # (num_blocks, block_size, Hkv, Dh) bf16 | int8
    table: jax.Array    # (num_slots, max_blocks) int32 pool-block ids
    length: jax.Array   # (num_slots,) int32 tokens written per slot
    k_scale: jax.Array | None = None   # (num_blocks, Hkv) f32, int8 only
    v_scale: jax.Array | None = None   # (num_blocks, Hkv) f32, int8 only


def _requantize(old_q, old_scale, keep_old, write_new, newv):
    """Dequant-merge-requantize of whole int8 pool blocks.

    ``old_q`` (..., block_size, Hkv, Dh) and ``old_scale`` (..., Hkv) are
    the touched blocks as stored; ``keep_old`` / ``write_new`` (...,
    block_size) mask block offsets; ``newv`` (..., block_size, Hkv, Dh)
    holds the incoming values at ``write_new`` positions. Offsets neither
    kept nor written are zeroed, so a block's stored scale depends only on
    the tokens that are actually valid in it — stale tails (rejected
    speculation, reused blocks) can never inflate the grid. Returns the
    blocks and their scales.
    """
    old = q8.dequantize_block(old_q, old_scale)
    merged = jnp.where(write_new[..., None, None], newv.astype(jnp.float32),
                       jnp.where(keep_old[..., None, None], old, 0.0))
    return q8.quantize_block(merged)


def _quantized_scatter(pool, scales, blk, keep_old, write_new, newv):
    """Whole-block write into the int8 pool (:func:`_requantize`).

    ``blk`` (...,) are the touched pool-block ids. Distinct lanes own
    distinct blocks, so duplicate scatter indices only ever collide on
    the null block 0, whose content is never validly read.
    """
    qblk, qs = _requantize(pool[blk], scales[blk], keep_old, write_new,
                           newv)
    return (pool.at[blk].set(qblk, mode="drop"),
            scales.at[blk].set(qs, mode="drop"))


def _write_in_place(pool, update, index):
    """``pool`` with each ``update[i]`` written at ``index(i)``.

    One ``dynamic_update_slice`` each, which XLA does in place on the
    pool the layer scan carries. An XLA scatter into the stacked pool
    would not do: on the TPU it picks a pool layout of its own, and the
    compiled step then relayouts the whole pool around it, inside the
    scan as well (``tests/test_tpu_compile.py``).
    """
    update = update.astype(pool.dtype)
    for i in range(update.shape[0]):
        pool = jax.lax.dynamic_update_slice(pool, update[i], index(i))
    return pool


def _rewrite_blocks(pool, scales, layer, blk, keep_old, write_new, newv):
    """Rewrite the blocks ``blk`` (n,) of layer ``layer`` whole, in place.

    ``keep_old`` / ``write_new`` (n, block_size) mask block offsets and
    ``newv`` (n, block_size, Hkv, Dh) holds the incoming values at
    ``write_new`` positions. A bf16 pool keeps the stored value at every
    other offset; an int8 pool requantizes (:func:`_requantize`), so
    there offsets neither kept nor written are zeroed. Returns the pool
    and the scales (None for bf16).
    """
    old = pool[layer, blk]
    at = lambda i: (layer, blk[i], 0, 0, 0)
    if scales is None:
        new = jnp.where(write_new[..., None, None], newv.astype(pool.dtype),
                        old)
        return _write_in_place(pool, new[:, None, None], at), None
    q, qs = _requantize(old, scales[layer, blk], keep_old, write_new, newv)
    return (_write_in_place(pool, q[:, None, None], at),
            _write_in_place(scales, qs[:, None, None],
                            lambda i: (layer, blk[i], 0)))


def paged_prefill_attention(
    p: AttnParams, x: jax.Array, cache: PagedKVCache, *, layer: jax.Array,
    slot: jax.Array, start: jax.Array, true_len: jax.Array,
    rope_theta: float = 10000.0, use_rope: bool = True,
) -> tuple[jax.Array, PagedKVCache]:
    """One prefill *chunk* of layer ``layer`` for the request occupying
    ``slot``, against the stacked block pools (as in
    :func:`paged_decode_attention`, which also says how they are written).

    ``x`` is (1, C, d): chunk tokens right-padded to the bucket length C;
    ``start`` is how many prompt tokens earlier chunks already wrote, and
    ``true_len`` (<= C) how many of this chunk's tokens are real. The chunk's
    K/V land in the slot's pool blocks at logical positions
    ``start..start+true_len-1``, then queries attend causally to the slot's
    whole written prefix through the block table — so chunked prefill sees
    exactly the key set whole-prompt prefill sees, position for position.
    """
    B, C, _ = x.shape
    assert B == 1
    _, _, bs, n_kv, hd = cache.k.shape
    mb = cache.table.shape[1]
    q = cm.dense(x, p.wq, p.bq).reshape(B, C, -1, hd)
    k = cm.dense(x, p.wk, p.bk).reshape(B, C, n_kv, hd)
    v = cm.dense(x, p.wv, p.bv).reshape(B, C, n_kv, hd)
    pos = start + jnp.arange(C)
    if use_rope:
        sin, cos = cm.rotary_embedding(pos[None, :], hd, rope_theta)
        q = cm.apply_rotary(q, sin, cos)
        k = cm.apply_rotary(k, sin, cos)
    # rewrite every block the chunk touches whole: a chunk of C tokens
    # spans at most C // bs + 2 consecutive table slots from start // bs;
    # out-of-table candidates redirect to the null block. Offsets outside
    # the chunk's real tokens keep what the pool holds (int8: the earlier
    # chunks' tokens, and zero past the chunk).
    row = cache.table[slot]                               # (max_blocks,)
    T = C // bs + 2
    cand_ti = start // bs + jnp.arange(T)
    blk = jnp.where(cand_ti < mb, row[jnp.minimum(cand_ti, mb - 1)], 0)
    bpos = cand_ti[:, None] * bs + jnp.arange(bs)[None, :]   # (T, bs)
    write_new = (bpos >= start) & (bpos < start + true_len)
    keep_old = bpos < start
    src = jnp.clip(bpos - start, 0, C - 1)
    ck, ks = _rewrite_blocks(cache.k, cache.k_scale, layer, blk, keep_old,
                             write_new, k[0][src])
    cv, vs = _rewrite_blocks(cache.v, cache.v_scale, layer, blk, keep_old,
                             write_new, v[0][src])
    new_cache = cache._replace(k=ck, v=cv, k_scale=ks, v_scale=vs)
    # gather the slot's full logical region (prefix + this chunk) and run
    # the same masked contraction plain_attention would
    n_heads = q.shape[2]
    if ks is None:
        kr = ck[layer, row].reshape(1, mb * bs, n_kv, hd)
        vr = cv[layer, row].reshape(1, mb * bs, n_kv, hd)
    else:
        # dequantize inside the gather: the pool is read as int8; the
        # bf16 view exists only as this chunk-sized activation
        kr = q8.dequantize_block(ck[layer, row], ks[layer, row],
                                 q.dtype).reshape(1, mb * bs, n_kv, hd)
        vr = q8.dequantize_block(cv[layer, row], vs[layer, row],
                                 q.dtype).reshape(1, mb * bs, n_kv, hd)
    kr = _repeat_kv(kr, n_heads // n_kv)
    vr = _repeat_kv(vr, n_heads // n_kv)
    scale = hd ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q * jnp.asarray(scale, q.dtype),
                   kr.astype(q.dtype), preferred_element_type=jnp.float32)
    kpos = jnp.arange(mb * bs)
    mask = kpos[None, :] <= pos[:, None]                  # causal, (C, S)
    s = jnp.where(mask[None, None], s, NEG_INF)
    prob = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", prob.astype(vr.dtype), vr,
                   preferred_element_type=jnp.float32).astype(q.dtype)
    return cm.dense(o.reshape(B, C, -1), p.wo), new_cache


def decode_attention_backend(quantized: bool) -> str | None:
    """The kernel backend paged decode attention runs on, or None where it
    takes the gather path.

    The context's matmul backend chooses, as it does for the GEMMs
    (:func:`repro.kernels.ops.resolve_backend`): 'pallas' (or 'auto' on a
    TPU) runs the Pallas kernel, 'interpret' its body in the interpreter,
    'xla' (or 'auto' elsewhere) the gather. An int8 pool always gathers:
    its dequantize lives in the gather.
    """
    backend = resolve_backend(current_context().matmul_backend)
    return None if quantized or backend == "xla" else backend


def paged_decode_attention(
    p: AttnParams, x: jax.Array, cache: PagedKVCache, *, layer: jax.Array,
    rope_theta: float = 10000.0, use_rope: bool = True,
    active: jax.Array | None = None,
) -> tuple[jax.Array, PagedKVCache]:
    """One decode step of layer ``layer`` against the stacked block pools.

    ``cache`` holds every layer's pool, ``(L, num_blocks, block_size, Hkv,
    Dh)`` (with int8, and ``(L, num_blocks, Hkv)`` scales): the pools
    ``lm.decode_step``'s layer scan carries. Each lane's new K and V row
    is written into ``pools[layer]`` in place (:func:`_write_in_place`; an
    int8 pool rewrites the lane's current block whole), and the returned
    cache holds the written pools with the length it was given: the
    caller owns the advance (masked by ``active``).

    ``active`` (num_slots,) marks live decode lanes. An inactive lane's
    write is redirected to the null block — unlike the contiguous layout,
    a vacant lane's table row may reference blocks the allocator has
    already handed to another request, so its pad-token write must never
    reach table-resolved storage.

    Where :func:`decode_attention_backend` names a kernel backend, the
    Pallas kernel (:mod:`repro.kernels.paged_attention`) reads
    ``pools[layer]``, only each active lane's written blocks, and takes
    the current position from this step's k, v. Otherwise the step
    gathers every lane's whole table region from ``pools[layer]``:
    numerics-identical to the contiguous per-slot :func:`decode_attention`
    (same masked contraction over the same logical positions — the gather
    only changes *where* the bytes live); the kernel sums the same terms
    in another order.
    """
    B, S1, _ = x.shape
    assert S1 == 1
    _, _, bs, n_kv, hd = cache.k.shape
    mb = cache.table.shape[1]
    q = cm.dense(x, p.wq, p.bq).reshape(B, 1, -1, hd)
    k = cm.dense(x, p.wk, p.bk).reshape(B, 1, n_kv, hd)
    v = cm.dense(x, p.wv, p.bv).reshape(B, 1, n_kv, hd)
    pos = cache.length                                     # (B,)
    if use_rope:
        sin, cos = cm.rotary_embedding(pos[:, None].astype(jnp.float32),
                                       hd, rope_theta)
        q = cm.apply_rotary(q, sin, cos)
        k = cm.apply_rotary(k, sin, cos)
    ti = jnp.minimum(pos // bs, mb - 1)
    blk = cache.table[jnp.arange(B), ti]
    if active is not None:
        blk = jnp.where(active.astype(bool), blk, 0)       # null-block spill
    off = pos % bs
    quantized = cache.k_scale is not None
    if not quantized:
        row_at = lambda b: (layer, blk[b], off[b], 0, 0)
        ck = _write_in_place(cache.k, k[:, :, None, None], row_at)
        cv = _write_in_place(cache.v, v[:, :, None, None], row_at)
        ks = vs = None
    else:
        # int8 pool: each lane rewrites its current block whole — keep
        # the offsets before the append point, zero the tail past it
        ar = jnp.arange(bs)[None, :]
        keep, new = ar < off[:, None], ar == off[:, None]
        ck, ks = _rewrite_blocks(cache.k, cache.k_scale, layer, blk, keep,
                                 new, k)
        cv, vs = _rewrite_blocks(cache.v, cache.v_scale, layer, blk, keep,
                                 new, v)
    new_cache = cache._replace(k=ck, v=cv, k_scale=ks, v_scale=vs)
    n_heads = q.shape[2]
    scale = hd ** -0.5
    backend = decode_attention_backend(quantized)
    if backend is not None:
        o = pa.paged_decode_attention(
            (q[:, 0] * jnp.asarray(scale, q.dtype)).astype(ck.dtype),
            k[:, 0], v[:, 0], ck, cv, layer, cache.table, pos, active,
            interpret=(backend == "interpret"),
            vmem_limit_bytes=current_context().hw.vmem_limit_bytes)
        out = cm.dense(o.reshape(B, 1, -1).astype(x.dtype), p.wo)
        return out, new_cache
    if ks is None:
        gk = ck[layer, cache.table].reshape(B, mb * bs, n_kv, hd)
        gv = cv[layer, cache.table].reshape(B, mb * bs, n_kv, hd)
    else:
        gk = q8.dequantize_block(ck[layer, cache.table],
                                 ks[layer, cache.table],
                                 q.dtype).reshape(B, mb * bs, n_kv, hd)
        gv = q8.dequantize_block(cv[layer, cache.table],
                                 vs[layer, cache.table],
                                 q.dtype).reshape(B, mb * bs, n_kv, hd)
    kr = _repeat_kv(gk, n_heads // n_kv)
    vr = _repeat_kv(gv, n_heads // n_kv)
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", (q * jnp.asarray(scale, q.dtype)).astype(kr.dtype),
        kr, preferred_element_type=jnp.float32,
    )
    kpos = jnp.arange(mb * bs)
    valid = kpos[None, :] <= pos[:, None]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    prob = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", prob.astype(vr.dtype), vr,
                   preferred_element_type=jnp.float32)
    out = cm.dense(o.reshape(B, 1, -1).astype(x.dtype), p.wo)
    return out, new_cache


def paged_verify_attention(
    p: AttnParams, x: jax.Array, cache: PagedKVCache, *,
    rope_theta: float = 10000.0, use_rope: bool = True,
    active: jax.Array | None = None,
) -> tuple[jax.Array, PagedKVCache]:
    """Speculative-verify step: S candidate tokens per slot in one pass.

    ``x`` is (B, S, d) — for every lane, the last committed token followed
    by the draft's S-1 proposals. All S keys/values scatter into the slot's
    pool blocks at logical positions ``length..length+S-1``, then each
    query attends causally through the block table — so position i's
    scores match what i sequential :func:`paged_decode_attention` steps
    would compute for the same tokens, and greedy acceptance against these
    logits is token-for-token identical to non-speculative decode.

    Write-side safety differs from the single-step path in one way: a
    lane's tail positions can run past the blocks it owns (the last
    committed tokens of a round land within budget, but the rejected tail
    may not). Table rows are null-padded past the owned region, and
    positions beyond the table entirely (``>= max_blocks * block_size``)
    are redirected to the null block explicitly — without that guard the
    ``min(pos // bs, mb - 1)`` clamp would alias an out-of-range write
    onto the last owned block. Causality keeps any committable query from
    ever attending a spilled key. Rejected in-range tails are simply
    overwritten when the next round re-feeds those positions.

    The returned length advances every slot by S; as with decode, the
    caller (``lm.verify_step``) owns the actual advance (masked by
    ``active``) and the engine rewinds rejected tails host-side.
    """
    B, S, _ = x.shape
    nb, bs, n_kv, hd = cache.k.shape
    mb = cache.table.shape[1]
    q = cm.dense(x, p.wq, p.bq).reshape(B, S, -1, hd)
    k = cm.dense(x, p.wk, p.bk).reshape(B, S, n_kv, hd)
    v = cm.dense(x, p.wv, p.bv).reshape(B, S, n_kv, hd)
    pos = cache.length[:, None] + jnp.arange(S)[None, :]   # (B, S)
    if use_rope:
        sin, cos = cm.rotary_embedding(pos.astype(jnp.float32),
                                       hd, rope_theta)
        q = cm.apply_rotary(q, sin, cos)
        k = cm.apply_rotary(k, sin, cos)
    rows = jnp.arange(B)[:, None]
    if cache.k_scale is None:
        ti = jnp.minimum(pos // bs, mb - 1)
        blk = cache.table[rows, ti]                        # (B, S)
        spill = pos >= mb * bs
        if active is not None:
            spill = spill | ~active.astype(bool)[:, None]
        blk = jnp.where(spill, 0, blk)                     # null-block spill
        ck = cache.k.at[blk, pos % bs].set(k.astype(cache.k.dtype),
                                           mode="drop")
        cv = cache.v.at[blk, pos % bs].set(v.astype(cache.v.dtype),
                                           mode="drop")
        ks = vs = None
    else:
        # int8 pool: rewrite the blocks the S-token window touches whole.
        # Candidates past the table (or inactive lanes) redirect to block
        # 0 — the same spill rule as the bf16 single-position writes.
        T = S // bs + 2
        cand_ti = cache.length[:, None] // bs + jnp.arange(T)[None, :]
        ok = cand_ti < mb                                  # (B, T)
        if active is not None:
            ok = ok & active.astype(bool)[:, None]
        cblk = jnp.where(
            ok, cache.table[rows, jnp.minimum(cand_ti, mb - 1)], 0)
        bpos = (cand_ti[:, :, None] * bs
                + jnp.arange(bs)[None, None, :])           # (B, T, bs)
        start_l = cache.length[:, None, None]
        write_new = (bpos >= start_l) & (bpos < start_l + S)
        keep_old = bpos < start_l                          # committed prefix
        src = jnp.clip(bpos - start_l, 0, S - 1)
        newv_k = k[jnp.arange(B)[:, None, None], src]      # (B, T, bs, ...)
        newv_v = v[jnp.arange(B)[:, None, None], src]
        ck, ks = _quantized_scatter(cache.k, cache.k_scale, cblk,
                                    keep_old, write_new, newv_k)
        cv, vs = _quantized_scatter(cache.v, cache.v_scale, cblk,
                                    keep_old, write_new, newv_v)
    new_cache = PagedKVCache(k=ck, v=cv, table=cache.table,
                             length=cache.length + S, k_scale=ks, v_scale=vs)
    if ks is None:
        gk = ck[cache.table].reshape(B, mb * bs, n_kv, hd)
        gv = cv[cache.table].reshape(B, mb * bs, n_kv, hd)
    else:
        gk = q8.dequantize_block(ck[cache.table], ks[cache.table],
                                 q.dtype).reshape(B, mb * bs, n_kv, hd)
        gv = q8.dequantize_block(cv[cache.table], vs[cache.table],
                                 q.dtype).reshape(B, mb * bs, n_kv, hd)
    n_heads = q.shape[2]
    scale = hd ** -0.5
    kr = _repeat_kv(gk, n_heads // n_kv)
    vr = _repeat_kv(gv, n_heads // n_kv)
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", (q * jnp.asarray(scale, q.dtype)).astype(kr.dtype),
        kr, preferred_element_type=jnp.float32,
    )
    kpos = jnp.arange(mb * bs)
    valid = kpos[None, None, :] <= pos[:, :, None]         # (B, S, K) causal
    s = jnp.where(valid[:, None], s, NEG_INF)
    prob = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", prob.astype(vr.dtype), vr,
                   preferred_element_type=jnp.float32)
    out = cm.dense(o.reshape(B, S, -1).astype(x.dtype), p.wo)
    return out, new_cache


def cross_attention(
    p: AttnParams, x: jax.Array, kv_src: jax.Array, *,
    n_heads: int, n_kv_heads: int, head_dim: int, chunk: int | None = None,
) -> jax.Array:
    """Cross-attention: queries from x, keys/values from kv_src (no rope)."""
    B, S, _ = x.shape
    Sk = kv_src.shape[1]
    q = cm.dense(x, p.wq, p.bq).reshape(B, S, n_heads, head_dim)
    k = cm.dense(kv_src, p.wk, p.bk).reshape(B, Sk, n_kv_heads, head_dim)
    v = cm.dense(kv_src, p.wv, p.bv).reshape(B, Sk, n_kv_heads, head_dim)
    k = _repeat_kv(k, n_heads // n_kv_heads)
    v = _repeat_kv(v, n_heads // n_kv_heads)
    q, k, v = _hint_qkv(q, k, v)
    o = attention_core(q, k, v, causal=False, chunk=chunk)
    return cm.dense(o.reshape(B, S, n_heads * head_dim), p.wo)
