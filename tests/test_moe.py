"""MoE layer: EP dispatch vs dense reference, dropping, aux loss."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from hypothesis import given, settings, strategies as st

from repro.layers import moe


def _mesh11():
    dev = np.array(jax.devices()[:1]).reshape(1, 1)
    return Mesh(dev, ("data", "model"))


def _setup(E=4, d=32, f=64, T=24, top_k=2, seed=0):
    rng = np.random.default_rng(seed)
    p = moe.init_moe(jax.random.PRNGKey(seed), d, f, E)
    x = jnp.asarray(rng.normal(size=(2, T // 2, d)), jnp.float32)
    return p, x


def test_ep_matches_dense_reference_when_no_drops():
    """With generous capacity the EP path must equal the dense reference
    (same gates, same experts, different data movement)."""
    p, x = _setup()
    mesh = _mesh11()
    with mesh:
        y, aux = moe.moe_ffn(p, x, mesh=mesh, top_k=2, capacity_factor=8.0,
                             aux_coef=1.0)
    want = moe.moe_ref(p, x, top_k=2)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    assert float(aux) > 0


def test_capacity_dropping_reduces_output_norm():
    """Tiny capacity drops tokens: output is a strict subset of the full
    computation (dropped tokens contribute zero)."""
    p, x = _setup(T=32)
    mesh = _mesh11()
    with mesh:
        y_full, _ = moe.moe_ffn(p, x, mesh=mesh, top_k=2,
                                capacity_factor=8.0)
        y_tight, _ = moe.moe_ffn(p, x, mesh=mesh, top_k=2,
                                 capacity_factor=0.25)
    n_full = float(jnp.linalg.norm(y_full))
    n_tight = float(jnp.linalg.norm(y_tight))
    assert n_tight < n_full


def test_grad_flows_through_ep():
    p, x = _setup()
    mesh = _mesh11()

    def loss(p):
        with mesh:
            y, aux = moe.moe_ffn(p, x, mesh=mesh, top_k=2,
                                 capacity_factor=4.0)
        return jnp.sum(y * y) + aux

    g = jax.grad(loss)(p)
    for name, leaf in zip(p._fields, g):
        if leaf is None:
            continue
        assert np.all(np.isfinite(np.asarray(leaf, np.float32))), name
    assert float(jnp.abs(g.w_in).max()) > 0
    assert float(jnp.abs(g.w_router).max()) > 0  # router learns


@settings(max_examples=10, deadline=None)
@given(
    T=st.sampled_from([8, 16, 40]),
    E=st.sampled_from([2, 4, 8]),
    k=st.integers(1, 3),
)
def test_property_positions_in_bucket(T, E, k):
    rng = np.random.default_rng(T * 31 + E * 7 + k)
    bucket = jnp.asarray(rng.integers(0, E, size=(T * k,)), jnp.int32)
    pos = moe._positions_in_bucket(bucket, E)
    pos = np.asarray(pos)
    b = np.asarray(bucket)
    for e in range(E):
        got = pos[b == e]
        np.testing.assert_array_equal(np.sort(got), np.arange(len(got)))


def test_topk_gate_normalization():
    logits = jnp.asarray(np.random.default_rng(0).normal(size=(6, 8)),
                         jnp.float32)
    _, gates, _ = moe._top_k_gates(logits, 3, norm_topk=True)
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 1.0, rtol=1e-5)


def test_moe_token_mask_isolates_live_tokens():
    """Engine determinism: dead tokens (vacant pad lanes) must not route,
    must not consume expert capacity, and must return zero rows — a live
    token's output is identical whether or not it shares the batch with
    any number of identical dead tokens."""
    from repro import configs as C
    from repro import models
    from repro.launch.mesh import make_local_mesh
    from repro.layers import moe as moe_lib

    cfg = C.smoke(C.get_config("olmoe-1b-7b"))
    p = jax.tree.map(lambda x: x[0],
                     models.init(jax.random.PRNGKey(0), cfg)["layers"]["moe"])
    mesh = make_local_mesh()
    rng = np.random.default_rng(3)
    live = jnp.asarray(rng.normal(size=(1, 1, cfg.d_model)), jnp.float32)
    # 32 identical dead rows: unmasked they would flood one expert's
    # capacity bucket and could evict the live token's assignment
    dead = jnp.broadcast_to(jnp.asarray(
        rng.normal(size=(1, 1, cfg.d_model)), jnp.float32),
        (32, 1, cfg.d_model))
    x = jnp.concatenate([live, dead], axis=0)
    mask = jnp.asarray([[True]] + [[False]] * 32)
    y_masked, _ = moe_lib.moe_ffn(
        p, x, mesh=mesh, top_k=cfg.top_k,
        capacity_factor=cfg.capacity_factor, token_mask=mask)
    y_alone, _ = moe_lib.moe_ffn(
        p, live, mesh=mesh, top_k=cfg.top_k,
        capacity_factor=cfg.capacity_factor)
    np.testing.assert_allclose(np.asarray(y_masked[0]),
                               np.asarray(y_alone[0]), rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(y_masked[1:]), 0.0)
