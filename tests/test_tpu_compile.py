"""Main-path Pallas kernels compile for a described TPU v5e at qwen1.5-4b
widths, with the tile plans the solver picks for v5e.

Nothing runs: the TPU compiler, installed with jax, compiles for a chip
that is described and not attached, and refuses what the chip would
refuse — a tile plan whose working set overflows the kernel's scoped VMEM,
a block not aligned to the tiling. The topology is described inside a
fixture, never at import, so only the worker that runs this file loads the
TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import hwregistry
from repro.core.gemm import plan_for
from repro.core.plancache import PlanCache
from repro.kernels import matmul as mm
from repro.kernels.decode_matvec import decode_matvec

V5E = hwregistry.get_hw("tpu_v5e")
D, FF, VP = 2560, 6912, 151936      # qwen1.5-4b d_model, d_ff, padded vocab

# (name, kernel, M, K, N, in dtype, out dtype, b layout, bias, out_scale).
# Prefill rows at 2048 tokens; decode rows at the engine's slot counts. The
# first four are the shapes whose plans, under the old Eq. 5 (output block
# counted once, no dot temporary), overflowed the compiler's default scoped
# VMEM at 14.75-15.5 MiB. The unembed plan still needs the kernel's own
# limit: it overflows the 16 MiB default.
CASES = [
    ("mlp_up_bf16", "matmul", 2048, D, FF, "bfloat16", "bfloat16", "row",
     False, False),
    ("unembed_bf16_f32", "matmul", 2048, D, VP, "bfloat16", "float32", "row",
     False, False),
    ("mlp_up_int8_col_scale", "matmul", 2048, D, FF, "int8", "bfloat16",
     "col", True, True),
    ("square_4096_bf16", "matmul", 4096, 4096, 4096, "bfloat16", "bfloat16",
     "row", False, False),
    ("mlp_down_bf16", "matmul", 2048, FF, D, "bfloat16", "bfloat16", "row",
     False, False),
    ("qkv_bias_bf16", "matmul", 2048, D, D, "bfloat16", "bfloat16", "row",
     True, False),
    ("decode_mlp_up_bf16", "decode_matvec", 16, D, FF, "bfloat16",
     "bfloat16", "row", False, False),
    ("decode_unembed_bf16_f32", "decode_matvec", 4, D, VP, "bfloat16",
     "float32", "row", False, False),
]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile is written to a persistent cache but cannot
    # be read back without the chip; keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.mark.parametrize(
    "name,kernel,M,K,N,din,dout,layout,bias,scale", CASES,
    ids=[c[0] for c in CASES])
def test_main_path_kernel_compiles_for_v5e(
        one_chip, name, kernel, M, K, N, din, dout, layout, bias, scale):
    din, dout = jnp.dtype(din), jnp.dtype(dout)
    plan = plan_for(M, K, N, in_dtype=din, out_dtype=dout, b_layout=layout,
                    hw=V5E, cache=PlanCache())
    Mp, Kp, Np = plan.native_size(M, K, N)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    b_shape = (Np, Kp) if layout == "col" else (Kp, Np)
    if kernel == "matmul":
        a = arg((Mp, Kp), din)
        extra = [arg((Np,), jnp.float32) if bias else None,
                 arg((Np,), jnp.float32) if scale else None]
        fn = jax.jit(lambda a, b, bi, sc: mm.matmul(
            a, b, bi, sc, bm=plan.bm, bk=plan.bk, bn=plan.bn,
            out_dtype=dout, b_layout=layout,
            vmem_limit_bytes=V5E.vmem_limit_bytes))
    else:
        sub = mm.SUBLANE[din.itemsize]
        a = arg((-(-M // sub) * sub, Kp), din)
        extra = []
        fn = jax.jit(lambda a, b: decode_matvec(
            a, b, bk=plan.bk, bn=plan.bn, out_dtype=dout, w_layout=layout,
            vmem_limit_bytes=V5E.vmem_limit_bytes))
    compiled = fn.lower(a, arg(b_shape, din), *extra).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert mm.vmem_bytes(plan.bm, plan.bk, plan.bn, din.itemsize,
                         dout.itemsize) <= V5E.vmem_bytes
