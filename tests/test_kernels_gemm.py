"""Pallas GEMM kernel vs pure-jnp oracle: shape/dtype sweeps + properties.

All kernels run with ``backend='interpret'`` (Pallas interpret mode executes
the kernel body on CPU; the BlockSpec pipeline semantics are preserved).
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.matmul import vmem_bytes

RNG = np.random.default_rng(1234)


def _rand(shape, dtype):
    if jnp.issubdtype(dtype, jnp.integer):
        return jnp.asarray(RNG.integers(-100, 100, size=shape), dtype)
    return jnp.asarray(RNG.normal(size=shape), dtype)


def _tol(dtype):
    if dtype == jnp.bfloat16:
        return dict(rtol=5e-2, atol=5e-2)
    return dict(rtol=5e-3, atol=1e-4)


# ---------------------------------------------------------------- sweeps
SHAPES = [
    # aligned to blocks
    (128, 256, 128),
    (256, 512, 384),
    # ragged in every dimension (exercise zero-padding to native size)
    (100, 300, 200),
    (33, 520, 65),
    (1, 128, 128),
    (130, 1, 7),
]
FLOAT_CASES = [
    (jnp.bfloat16, jnp.bfloat16),
    (jnp.bfloat16, jnp.float32),
    (jnp.float32, jnp.float32),
]
INT_CASES = [
    (jnp.int8, jnp.int32),
    (jnp.int8, jnp.int16),
    (jnp.int8, jnp.int8),
]


@pytest.mark.parametrize("M,K,N", SHAPES)
@pytest.mark.parametrize("in_dtype,out_dtype", FLOAT_CASES + INT_CASES)
@pytest.mark.parametrize("b_layout", ["row", "col"])
def test_matmul_matches_oracle(M, K, N, in_dtype, out_dtype, b_layout):
    a = _rand((M, K), in_dtype)
    b = _rand((N, K) if b_layout == "col" else (K, N), in_dtype)
    plan = ops.GemmPlan(bm=64, bk=128, bn=128)
    got = ops.balanced_matmul(
        a, b, plan=plan, out_dtype=out_dtype, b_layout=b_layout,
        backend="interpret",
    )
    want = ref.matmul_ref(a, b, out_dtype=out_dtype, b_layout=b_layout)
    assert got.shape == (M, N) and got.dtype == want.dtype
    if jnp.issubdtype(out_dtype, jnp.integer):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    else:
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            **_tol(out_dtype),
        )


@pytest.mark.parametrize("activation", ["relu", "relu2", "gelu", "silu"])
def test_matmul_fused_epilogue(activation):
    a = _rand((96, 256), jnp.bfloat16)
    b = _rand((256, 192), jnp.bfloat16)
    bias = _rand((192,), jnp.float32)
    got = ops.balanced_matmul(
        a, b, bias, plan=ops.GemmPlan(32, 128, 128), out_dtype=jnp.float32,
        activation=activation, backend="interpret",
    )
    want = ref.matmul_ref(
        a, b, bias=bias, out_dtype=jnp.float32, activation=activation,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=5e-2, atol=5e-2,
    )


def test_int8_saturation():
    # Force accumulator values far outside int8/int16 range.
    a = jnp.full((32, 512), 100, jnp.int8)
    b = jnp.full((512, 128), 100, jnp.int8)
    for od in (jnp.int8, jnp.int16):
        got = ops.balanced_matmul(
            a, b, plan=ops.GemmPlan(32, 128, 128), out_dtype=od,
            backend="interpret",
        )
        assert np.all(np.asarray(got) == np.iinfo(od).max)


@pytest.mark.parametrize(
    "plan",
    [ops.GemmPlan(32, 128, 128), ops.GemmPlan(128, 256, 256),
     ops.GemmPlan(64, 512, 128)],
)
def test_block_shape_invariance(plan):
    """Different tiling plans compute the same GEMM (paper §5.3.1: only the
    grid counts change across problem sizes, results are identical)."""
    a = _rand((192, 640), jnp.float32)
    b = _rand((640, 256), jnp.float32)
    got = ops.balanced_matmul(a, b, plan=plan, backend="interpret")
    want = ref.matmul_ref(a, b)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4,
    )


# ------------------------------------------------------------- decode gemv
@pytest.mark.parametrize("B", [1, 4, 17, 128])
@pytest.mark.parametrize("in_dtype", [jnp.bfloat16, jnp.int8])
@pytest.mark.parametrize("w_layout", ["row", "col"])
def test_decode_matvec(B, in_dtype, w_layout):
    out_dtype = jnp.int32 if in_dtype == jnp.int8 else jnp.float32
    x = _rand((B, 768), in_dtype)
    w = _rand((512, 768) if w_layout == "col" else (768, 512), in_dtype)
    got = ops.decode_matvec(
        x, w, out_dtype=out_dtype, w_layout=w_layout, backend="interpret",
    )
    want = ref.gemv_ref(x, w, out_dtype=out_dtype, w_layout=w_layout)
    if jnp.issubdtype(out_dtype, jnp.integer):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    else:
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), **_tol(in_dtype),
        )


# ---------------------------------------------------------- property tests
@settings(max_examples=25, deadline=None)
@given(
    M=st.integers(1, 200),
    K=st.integers(1, 300),
    N=st.integers(1, 200),
    col=st.booleans(),
)
def test_property_int8_exact(M, K, N, col):
    """int8 GEMM through the kernel is bit-exact vs the i32 oracle for any
    shape (zero-padding must never change the result)."""
    rng = np.random.default_rng(M * 7 + K * 13 + N * 29 + col)
    a = jnp.asarray(rng.integers(-128, 128, size=(M, K)), jnp.int8)
    b = jnp.asarray(
        rng.integers(-128, 128, size=(N, K) if col else (K, N)), jnp.int8
    )
    layout = "col" if col else "row"
    got = ops.balanced_matmul(
        a, b, plan=ops.GemmPlan(32, 128, 128), out_dtype=jnp.int32,
        b_layout=layout, backend="interpret",
    )
    want = ref.matmul_ref(a, b, out_dtype=jnp.int32, b_layout=layout)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@settings(max_examples=50, deadline=None)
@given(
    bm=st.sampled_from([32, 64, 128, 256]),
    bk=st.sampled_from([128, 256, 512, 1024]),
    bn=st.sampled_from([128, 256, 512]),
)
def test_property_vmem_model_positive_and_monotone(bm, bk, bn):
    v = vmem_bytes(bm, bk, bn, ty_in=2, ty_out=2)
    assert v > 0
    # doubling any block dim strictly increases the working set
    assert vmem_bytes(2 * bm, bk, bn, 2, 2) > v
    assert vmem_bytes(bm, 2 * bk, bn, 2, 2) > v
    assert vmem_bytes(bm, bk, 2 * bn, 2, 2) > v


def test_pallas_backend_refuses_a_non_tpu_platform():
    a = _rand((64, 128), jnp.bfloat16)
    b = _rand((128, 64), jnp.bfloat16)
    with pytest.raises(ValueError, match="TPU"):
        ops.balanced_matmul(a, b, backend="pallas")
    with pytest.raises(ValueError, match="TPU"):
        ops.decode_matvec(a[:8], b, backend="pallas")
    assert ops.resolve_backend("auto") == "xla"


def test_xla_fallback_matches_oracle():
    a = _rand((64, 128), jnp.bfloat16)
    b = _rand((128, 64), jnp.bfloat16)
    got = ops.balanced_matmul(a, b, backend="xla", out_dtype=jnp.float32)
    want = ref.matmul_ref(a, b, out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
