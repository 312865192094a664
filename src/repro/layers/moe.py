"""Mixture-of-Experts FFN with expert parallelism (EP).

Production layout (arctic-480b: 128 experts cannot be replicated):

* expert weights (E, d, f): E sharded over the mesh ``data`` axis (EP),
  f sharded over ``model`` (TP inside each expert);
* tokens stay data-parallel; assignments travel to their expert's shard via
  ``lax.all_to_all`` and come back the same way (GShard-style two-level
  capacity dispatch, argsort-free — slot positions via cumsum of one-hots);
* the whole block runs inside ``shard_map`` so the collectives are explicit
  (they are the MoE entries in the roofline's collective term).

On a 1×1 mesh the same code degenerates to a single-shard MoE (all_to_all
over a size-1 axis is the identity) — tests exploit this to compare against
the dense reference ``moe_ref``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.layers import common as cm
from repro.kernels.ref import apply_activation
from repro.quant.int8 import QuantizedLinear


class MoeParams(NamedTuple):
    w_router: jax.Array        # (d, E)
    w_in: jax.Array            # (E, d, f)
    w_gate: jax.Array | None   # (E, d, f) — gated (SwiGLU) experts
    w_out: jax.Array           # (E, f, d)


def init_moe(key, d_model, d_ff, n_experts, *, gated=True, dtype=jnp.float32):
    ks = cm.split_keys(key, 4)
    shape = (n_experts, d_model, d_ff)
    return MoeParams(
        w_router=cm.normal_init(ks[0], (d_model, n_experts), jnp.float32),
        w_in=cm.normal_init(ks[1], shape, dtype, scale=d_model ** -0.5),
        w_gate=(
            cm.normal_init(ks[2], shape, dtype, scale=d_model ** -0.5)
            if gated else None
        ),
        w_out=cm.normal_init(
            ks[3], (n_experts, d_ff, d_model), dtype, scale=d_ff ** -0.5
        ),
    )


def moe_axes(gated=True):
    return MoeParams(
        w_router=("embed", None),
        w_in=("expert", "embed", "ffn"),
        w_gate=("expert", "embed", "ffn") if gated else None,
        w_out=("expert", "ffn", "embed"),
    )


def _round8(x: int) -> int:
    return max(8, -(-x // 8) * 8)


def _maybe_dequant(w, dtype):
    """Pre-quantized expert table (…, N, K) int8 + (…, N) scales -> float
    (…, K, N) in the einsum's orientation. Runs *inside* the shard_map
    local block, so only int8 bytes cross HBM/ICI; the float copy is a
    transient on-chip value feeding the expert einsum. Float tables pass
    through untouched."""
    if isinstance(w, QuantizedLinear):
        wf = w.w_q.astype(jnp.float32) * w.w_scale[..., :, None]
        return jnp.swapaxes(wf, -1, -2).astype(dtype)
    return w


def _positions_in_bucket(bucket: jax.Array, n_buckets: int) -> jax.Array:
    """For each element, its running index within its bucket (cumsum trick)."""
    onehot = jax.nn.one_hot(bucket, n_buckets, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - 1
    return jnp.take_along_axis(pos, bucket[:, None], axis=1)[:, 0]


def _top_k_gates(logits: jax.Array, top_k: int, norm_topk: bool):
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gates, idx = jax.lax.top_k(probs, top_k)
    if norm_topk:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return probs, gates, idx


def _expert_ffn(xe, w_in, w_gate, w_out, activation, tp_axis,
                scatter: bool = False):
    """xe: (E_l, C, d); weights (E_l, d, f_l)/(E_l, f_l, d).

    TP combine: ``scatter=False`` -> psum (output full d, replicated over
    TP); ``scatter=True`` -> psum_scatter over the d dim (output d/TP —
    half the collective bytes, and the return all-to-all then carries
    TP× less; §Perf cell-2)."""
    h = jnp.einsum("ecd,edf->ecf", xe, w_in.astype(xe.dtype))
    if w_gate is not None:
        g = jnp.einsum("ecd,edf->ecf", xe, w_gate.astype(xe.dtype))
        h = apply_activation(g, activation) * h
    else:
        h = apply_activation(h, activation)
    out = jnp.einsum("ecf,efd->ecd", h, w_out.astype(xe.dtype))
    if tp_axis is not None:
        if scatter:
            out = jax.lax.psum_scatter(
                out, tp_axis, scatter_dimension=2, tiled=True)
        else:
            out = jax.lax.psum(out, tp_axis)
    return out


def moe_ffn(
    p: MoeParams,
    x: jax.Array,
    *,
    mesh: Mesh,
    top_k: int,
    dp_axes: Sequence[str] = ("pod", "data"),
    ep_axis: str = "data",
    tp_axis: str | None = "model",
    capacity_factor: float = 1.25,
    norm_topk: bool = True,
    activation: str = "silu",
    aux_coef: float = 0.01,
    token_mask: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Returns (y, aux_loss). x: (B, S, d) with B sharded over dp_axes.

    ``token_mask`` (B, S) marks live tokens; dead ones (a serving engine's
    vacant pad lanes) are excluded from routing *and capacity* — they must
    not occupy expert-bucket slots, or an active request's expert
    assignment could be dropped depending on unrelated slot occupancy
    (breaking the engine's served-alone determinism). Dead rows return 0.

    ``mesh=None`` (abstract traces: ``plan_model``, shape-only tests) runs
    the same code on a synthetic 1×1 mesh — all collectives are identities
    there, so the traced signature set matches single-shard serving."""
    if mesh is None:
        import numpy as _np

        mesh = Mesh(
            _np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    E = p.w_router.shape[1]
    mesh_axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    D = mesh_axes.get(ep_axis, 1)          # number of expert shards
    E_l = E // D
    if E % D:
        raise ValueError(f"n_experts={E} not divisible by EP degree {D}")
    tp = tp_axis if (tp_axis in mesh_axes and mesh_axes[tp_axis] > 1) else (
        tp_axis if tp_axis in mesh_axes else None
    )

    dp_spec = tuple(a for a in dp_axes if a in mesh_axes)
    dp_spec = dp_spec if dp_spec else None
    tp_size = mesh_axes.get(tp_axis, 1) if tp_axis else 1
    d_model = x.shape[-1]
    # §Perf cell-2: reduce-scatter the expert output over TP and carry
    # d/TP-wide payloads on the return all-to-all (the residual stream is
    # d-sharded between blocks anyway).
    scatter_out = bool(tp and tp_size > 1 and d_model % tp_size == 0)

    def local(x_l, tm_l, w_router, w_in, w_gate, w_out):
        w_in = _maybe_dequant(w_in, x_l.dtype)
        w_gate = _maybe_dequant(w_gate, x_l.dtype)
        w_out = _maybe_dequant(w_out, x_l.dtype)
        B_l, S, d = x_l.shape
        T = B_l * S
        xf = x_l.reshape(T, d)
        tmf = tm_l.reshape(T)
        logits = cm.dense(xf.astype(jnp.float32), w_router)
        probs, gates, idx = _top_k_gates(logits, top_k, norm_topk)

        # ---- load-balancing aux loss (Switch): E * sum_e f_e * P_e
        top1 = idx[:, 0]
        f_e = jnp.mean(jax.nn.one_hot(top1, E, dtype=jnp.float32), axis=0)
        P_e = jnp.mean(probs, axis=0)
        aux = E * jnp.sum(f_e * P_e)
        if dp_spec:
            aux = jax.lax.pmean(aux, dp_spec)

        # ---- level 1: route assignments to their expert's shard
        a_tok = jnp.repeat(jnp.arange(T), top_k)          # (T*k,)
        a_exp = idx.reshape(-1)                           # global expert ids
        a_gate = gates.reshape(-1).astype(jnp.float32)
        # dead tokens route to a phantom shard D: they take no bucket
        # positions (capacity isolation) and every write to shard D falls
        # out of bounds and is dropped
        a_live = tmf[a_tok]
        dest = jnp.where(a_live, a_exp // E_l, D)         # target shard
        Cs = _round8(int(capacity_factor * T * top_k / D))
        pos = _positions_in_bucket(dest, D + 1)
        keep = a_live & (pos < Cs)
        pos_c = jnp.where(keep, pos, Cs - 1)

        send_x = jnp.zeros((D, Cs, d), x_l.dtype)
        send_x = send_x.at[dest, pos_c].set(
            jnp.where(keep[:, None], xf[a_tok], 0).astype(x_l.dtype),
            mode="drop",
        )
        send_e = jnp.full((D, Cs), -1, jnp.int32).at[dest, pos_c].set(
            jnp.where(keep, a_exp % E_l, -1), mode="drop"
        )
        # local return map: which assignment filled slot (dest, c)
        slot_src = jnp.full((D, Cs), -1, jnp.int32).at[dest, pos_c].set(
            jnp.where(keep, jnp.arange(T * top_k), -1), mode="drop"
        )

        if D > 1:
            recv_x = jax.lax.all_to_all(
                send_x, ep_axis, split_axis=0, concat_axis=0, tiled=True)
            recv_e = jax.lax.all_to_all(
                send_e, ep_axis, split_axis=0, concat_axis=0, tiled=True)
        else:
            recv_x, recv_e = send_x, send_e

        # ---- level 2: slot received tokens into per-local-expert buffers
        R = D * Cs
        rx = recv_x.reshape(R, d)
        re = recv_e.reshape(R)
        valid = re >= 0
        re_c = jnp.where(valid, re, 0)
        Ce = _round8(int(capacity_factor * R / E_l))
        pos2 = _positions_in_bucket(re_c, E_l)
        keep2 = valid & (pos2 < Ce)
        pos2_c = jnp.where(keep2, pos2, Ce - 1)
        xe = jnp.zeros((E_l, Ce, d), x_l.dtype).at[re_c, pos2_c].set(
            jnp.where(keep2[:, None], rx, 0), mode="drop"
        )

        ye = _expert_ffn(xe, w_in, w_gate, w_out, activation, tp,
                         scatter=scatter_out)
        d_out = ye.shape[-1]  # d/TP when scattered, d otherwise

        # ---- return trip: expert buffers -> recv slots -> all_to_all back
        yr = ye[re_c, pos2_c] * keep2[:, None].astype(ye.dtype)
        yr = yr.reshape(D, Cs, d_out)
        if D > 1:
            back = jax.lax.all_to_all(
                yr, ep_axis, split_axis=0, concat_axis=0, tiled=True)
        else:
            back = yr

        # ---- combine: weighted scatter-add straight into token rows
        flat = back.reshape(R, d_out)
        src = slot_src.reshape(R)
        ok = src >= 0
        src_c = jnp.where(ok, src, 0)
        w = jnp.where(ok, a_gate[src_c], 0.0).astype(jnp.float32)
        contrib = flat.astype(jnp.float32) * w[:, None]
        y = jnp.zeros((T, d_out), jnp.float32).at[src_c // top_k].add(
            jnp.where(ok[:, None], contrib, 0), mode="drop"
        )
        return y.reshape(B_l, S, d_out).astype(x_l.dtype), aux

    def wspec(w, k_ax, n_ax):
        """Spec for one (E, K, N)-oriented expert table. A pre-quantized
        table stores (E, N, K) int8 + (E, N) scales, so the logical K/N
        mesh axes swap positions on w_q and the scales follow N."""
        if isinstance(w, QuantizedLinear):
            return QuantizedLinear(
                w_q=P(ep_axis, n_ax, k_ax), w_scale=P(ep_axis, n_ax),
                bias=None)
        return P(ep_axis, k_ax, n_ax)

    tp_ax = tp_axis if tp_axis else None
    tm = (jnp.ones(x.shape[:2], bool) if token_mask is None
          else jnp.broadcast_to(token_mask.astype(bool), x.shape[:2]))
    out = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P(dp_spec, None, None),
            P(dp_spec, None),
            P(None, None),
            wspec(p.w_in, None, tp_ax),
            (wspec(p.w_gate, None, tp_ax) if p.w_gate is not None
             else P(None, None, None)),
            wspec(p.w_out, tp_ax, None),
        ),
        out_specs=(P(dp_spec, None, tp_axis if scatter_out else None), P()),
        check_vma=False,
    )(x, tm, p.w_router, p.w_in,
      p.w_gate if p.w_gate is not None else jnp.zeros((1, 1, 1), x.dtype),
      p.w_out)
    y, aux = out
    return y, aux_coef * aux


def moe_ref(
    p: MoeParams, x: jax.Array, *, top_k: int, norm_topk: bool = True,
    activation: str = "silu",
) -> jax.Array:
    """Dense (no-drop, no-comm) reference: y = sum_k gate_k * FFN_{e_k}(x).
    Accepts pre-quantized expert tables like :func:`moe_ffn` does."""
    B, S, d = x.shape
    xf = x.reshape(-1, d)
    logits = xf.astype(jnp.float32) @ p.w_router
    _, gates, idx = _top_k_gates(logits, top_k, norm_topk)
    E = p.w_router.shape[1]
    w_in = _maybe_dequant(p.w_in, xf.dtype)
    w_gate = _maybe_dequant(p.w_gate, xf.dtype)
    w_out = _maybe_dequant(p.w_out, xf.dtype)
    h = jnp.einsum("td,edf->tef", xf, w_in.astype(xf.dtype))
    if w_gate is not None:
        g = jnp.einsum("td,edf->tef", xf, w_gate.astype(xf.dtype))
        h = apply_activation(g, activation) * h
    else:
        h = apply_activation(h, activation)
    y_all = jnp.einsum("tef,efd->ted", h, w_out.astype(xf.dtype))
    y = jnp.zeros_like(xf, dtype=jnp.float32)
    for k in range(top_k):
        sel = jnp.take_along_axis(y_all, idx[:, k][:, None, None], axis=1)[:, 0]
        y = y + gates[:, k][:, None] * sel.astype(jnp.float32)
    return y.reshape(B, S, d).astype(x.dtype)
