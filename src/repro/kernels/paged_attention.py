"""Paged decode attention: one query per lane against its live KV blocks.

The serving engine keeps K and V in a block pool, one stacked pool per
tensor for all layers, and each lane's logical positions map to pool blocks
through its row of the block table. This kernel reads, for every lane, only
the table blocks that hold positions the lane has already written
(``ceil(length / block_size)`` of them) and takes the current position's
K/V from its own inputs, so its result is the same masked softmax over
positions ``<= length`` that scatter-then-gather computes, without the
gather of every lane's whole table region.

Work is a list of (lane, chunk) items, a chunk being ``pages`` consecutive
table blocks of one lane, walked lane after lane in one grid step. Each
item's blocks are DMA'd from HBM into one half of a double buffer while the
previous item is computed from the other half; chunks past a lane's last
live block are never visited, and a lane with no live block (inactive, or
length 0) is one item that reads nothing. The loops over an item's pages
are rolled and its loops over KV heads unrolled: the engine traces the
kernel twice in its set-up, and branches per page made that slow. Per
lane and KV head, the online softmax runs in float32 over the chunks:
scores and the PV product contract in the pool's dtype on the MXU with
float32 accumulation, probabilities are rounded to the pool's dtype for
PV. The query heads of a KV head (``H // Hkv`` of them, padded to the
sublane tile) form the rows of each contraction, so grouped-query
attention needs no repeated K/V.

The pool operands are ``(layers, blocks, kv_heads, block_size, head_dim)``
views. With 20 KV heads the TPU lays a ``(..., block_size, kv_heads,
head_dim)`` pool out with the block offset second-minor, so the view is
the same bytes and a block of one layer is one contiguous run per KV
head; with 8 it keeps the heads second-minor, and the view is a copy.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.matmul import SUBLANE

NEG_INF = -1e30
# positions a chunk covers: a block alone (16 tokens) is too little work
# to amortize an item's DMA issue and softmax bookkeeping
CHUNK_POSITIONS = 128


def chunk_pages(block_size: int, max_blocks: int) -> int:
    """Table blocks per work item."""
    return max(1, min(max_blocks, CHUNK_POSITIONS // block_size))


def _kernel(layer_ref, table_ref, len_ref, nblk_ref,      # scalar prefetch
            q_ref, kn_ref, vn_ref, kp_hbm, vp_hbm,         # inputs
            o_ref,                                         # output
            kbuf, vbuf, sem, m_ref, l_ref, acc_ref,        # scratch
            *, pages: int, max_blocks: int):
    B, Hkv, _, _ = q_ref.shape
    bs = kbuf.shape[3]
    T = pages * bs
    layer = layer_ref[0]

    def n_chunks(b):
        """Items of lane b: one even where it reads no block."""
        return jnp.maximum(1, (nblk_ref[b] + pages - 1) // pages)

    def live(b, c):
        """Pages of item (b, c) that hold written positions."""
        return jnp.clip(nblk_ref[b] - c * pages, 0, pages)

    def copies(b, c, slot, j):
        blk = table_ref[b * max_blocks + c * pages + j]
        return (pltpu.make_async_copy(kp_hbm.at[layer, blk],
                                      kbuf.at[slot, :, j], sem.at[0, slot]),
                pltpu.make_async_copy(vp_hbm.at[layer, blk],
                                      vbuf.at[slot, :, j], sem.at[1, slot]))

    def start(b, c, slot):
        @pl.loop(0, live(b, c))
        def _(j):
            for cp in copies(b, c, slot, j):
                cp.start()

    def wait(b, c, slot):
        n = live(b, c)

        @pl.loop(0, n)
        def _(j):
            for cp in copies(b, c, slot, j):
                cp.wait()

        # a page the item leaves unread holds whatever the buffer held,
        # possibly not a number: its scores are masked, but a zero
        # probability times a NaN value is NaN, so zero its values
        @pl.loop(n, pages)
        def _(j):
            vbuf[slot, :, j] = jnp.zeros(vbuf.shape[1:2] + vbuf.shape[3:],
                                         vbuf.dtype)

    def lane_init(b):
        """The lane's accumulators hold the current position alone."""
        kn = kn_ref[b]                                     # (Hkv, Dh) f32
        vn = vn_ref[b]
        for h in range(Hkv):
            q = q_ref[b, h].astype(jnp.float32)            # (Gp, Dh)
            s0 = jnp.sum(q * kn[h:h + 1, :], axis=1, keepdims=True)
            m_ref[h] = s0
            l_ref[h] = jnp.ones_like(s0)
            acc_ref[h] = jnp.broadcast_to(vn[h:h + 1, :], q.shape)

    def lane_emit(b):
        for h in range(Hkv):
            o_ref[b, h] = (acc_ref[h] / l_ref[h]).astype(o_ref.dtype)

    def compute(b, c, slot):
        length = len_ref[b]
        for h in range(Hkv):
            q = q_ref[b, h]                                # (Gp, Dh)
            k = kbuf[slot, h].reshape(T, -1)               # (T, Dh)
            v = vbuf[slot, h].reshape(T, -1)
            s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            pos = c * T + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(pos < length, s, NEG_INF)
            m_prev, l_prev = m_ref[h], l_ref[h]            # (Gp, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            m_ref[h] = m_new
            l_ref[h] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            pv = jnp.dot(p.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
            acc_ref[h] = alpha * acc_ref[h] + pv

    def step(carry):
        b, c, slot = carry
        last = c + 1 >= n_chunks(b)
        nb = jnp.where(last, b + 1, b)
        nc = jnp.where(last, 0, c + 1)

        @pl.when(nb < B)
        def _():
            start(nb, nc, 1 - slot)

        @pl.when(c == 0)
        def _():
            lane_init(b)

        wait(b, c, slot)

        # a lane that reads no block attends to the current position alone
        @pl.when(live(b, c) > 0)
        def _():
            compute(b, c, slot)

        @pl.when(last)
        def _():
            lane_emit(b)

        return nb, nc, 1 - slot

    start(0, 0, 0)
    lax.while_loop(lambda carry: carry[0] < B, step,
                   (jnp.int32(0), jnp.int32(0), jnp.int32(0)))


@functools.partial(
    jax.jit, static_argnames=("interpret", "vmem_limit_bytes"))
def paged_decode_attention(
    q: jax.Array, k_new: jax.Array, v_new: jax.Array,
    k_pool: jax.Array, v_pool: jax.Array, layer: jax.Array,
    table: jax.Array, length: jax.Array, active: jax.Array | None = None,
    *, interpret: bool | pltpu.InterpretParams = False,
    vmem_limit_bytes: int | None = None,
) -> jax.Array:
    """Attention of each lane's query over its live pool positions and the
    current one.

    q (B, H, Dh), scaled and in the pool's dtype; k_new, v_new (B, Hkv, Dh)
    the current position's key and value; k_pool, v_pool
    (L, blocks, block_size, Hkv, Dh) the stacked pools, with or without
    the current position written (positions ``>= length`` are masked);
    ``layer`` the layer to read; ``table`` (B, max_blocks);
    ``length`` (B,) positions already in the pool per lane; ``active`` (B,)
    lanes to attend for (others read no block). Returns (B, H, Dh) in q's
    dtype; an inactive lane's row is its current value, finite.
    """
    B, H, Dh = q.shape
    L, nb, bs, Hkv, _ = k_pool.shape
    mb = table.shape[1]
    G = H // Hkv
    if G * Hkv != H:
        raise ValueError(f"{H} query heads do not group over {Hkv} KV heads")
    sub = SUBLANE[jnp.dtype(q.dtype).itemsize]
    Gp = -(-G // sub) * sub             # query rows of a KV head, tiled
    pages = chunk_pages(bs, mb)
    qg = q.reshape(B, Hkv, G, Dh)
    if Gp != G:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, Gp - G), (0, 0)))
    length = jnp.minimum(length.astype(jnp.int32), mb * bs)
    nblk = (length + bs - 1) // bs
    if active is not None:
        nblk = jnp.where(active > 0, nblk, 0)
    # the pools as (L, blocks, Hkv, block_size, Dh): on the TPU the same
    # bytes (see the module docstring)
    kp = jnp.swapaxes(k_pool, 2, 3)
    vp = jnp.swapaxes(v_pool, 2, 3)
    kn = k_new.astype(k_pool.dtype).astype(jnp.float32)
    vn = v_new.astype(v_pool.dtype).astype(jnp.float32)
    vmem = pltpu.VMEM
    out = pl.pallas_call(
        functools.partial(_kernel, pages=pages, max_blocks=mb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(1,),
            in_specs=[
                pl.BlockSpec(memory_space=vmem),
                pl.BlockSpec(memory_space=vmem),
                pl.BlockSpec(memory_space=vmem),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(memory_space=vmem),
            scratch_shapes=[
                vmem((2, Hkv, pages, bs, Dh), k_pool.dtype),
                vmem((2, Hkv, pages, bs, Dh), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                vmem((Hkv, Gp, 1), jnp.float32),
                vmem((Hkv, Gp, 1), jnp.float32),
                vmem((Hkv, Gp, Dh), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, Gp, Dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
        name="paged_decode_attention",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      table.reshape(-1).astype(jnp.int32), length, nblk.astype(jnp.int32),
      qg, kn, vn, kp, vp)
    return out[:, :, :G].reshape(B, H, Dh)
