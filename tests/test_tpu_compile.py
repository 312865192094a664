"""Main-path Pallas kernels compile for a described TPU v5e at qwen1.5-4b
widths, with the tile plans the solver picks for v5e.

Nothing runs: the TPU compiler, installed with jax, compiles for a chip
that is described and not attached, and refuses what the chip would
refuse — a tile plan whose working set overflows the kernel's scoped VMEM,
a block not aligned to the tiling. The topology is described inside a
fixture, never at import, so only the worker that runs this file loads the
TPU library.
"""
import re

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


@pytest.mark.parametrize(
    "H,Hkv,view_is_bitcast", [(20, 20, True), (48, 8, False)],
    ids=["qwen1.5-4b_mha", "internlm2-20b_gqa"])
def test_paged_decode_attention_compiles_for_v5e(one_chip, H, Hkv,
                                                 view_is_bitcast):
    """The decode attention kernel over a 40-layer, 600-block bf16 pool of
    16-token blocks, 16 lanes of 56 blocks: Mosaic takes the strided block
    DMAs and the per-head contractions. With 20 KV heads the TPU keeps the
    block offset second-minor, and the kernel's head-major view of the pool
    is a bitcast; with 8 it keeps the heads second-minor, and the view is a
    transposed copy (PERF.md, open questions)."""
    from repro.kernels.paged_attention import paged_decode_attention

    B, Dh, L, nb, bs, mb = 16, 128, 40, 600, 16, 56
    bf = jnp.bfloat16

    def arg(shape, dtype=bf):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn = jax.jit(lambda *a: paged_decode_attention(
        *a, vmem_limit_bytes=V5E.vmem_limit_bytes))
    pool = arg((L, nb, bs, Hkv, Dh))
    compiled = fn.lower(
        arg((B, H, Dh)), arg((B, Hkv, Dh)), arg((B, Hkv, Dh)), pool, pool,
        arg((), jnp.int32), arg((B, mb), jnp.int32), arg((B,), jnp.int32),
        arg((B,), jnp.int32)).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    pool_ops = re.findall(
        rf"= bf16\[{L},{nb},{Hkv},{bs},{Dh}\]\S* (\w+)\(", hlo)
    assert pool_ops
    assert (set(pool_ops) == {"bitcast"}) == view_is_bitcast


@pytest.mark.parametrize("chunk", [None, 512], ids=["decode", "prefill_512"])
def test_paged_step_writes_the_pool_in_place_for_v5e(one_chip, monkeypatch,
                                                      chunk):
    """The paged decode step, and a prefill chunk of 512, at qwen1.5-4b
    widths on 4 layers, a pool of 600 blocks of 16 and 16 lanes, compiled
    as the engine compiles them (state donated, Pallas backend): the
    layer scan carries the stacked pools and writes them in place. No op
    makes a pool-shaped or per-layer-slice-shaped array but the
    parameters, their tuple elements, bitcasts (the kernel's head-major
    view) and dynamic-update-slices (alone or fused), and the program's
    temporaries do not grow with the pool: the same program over 300
    blocks has as many. A whole-pool copy or relayout would add a pool
    (197 MB at 600 blocks) beside the largest temporary that remains, the
    unembedding's padded weight (780 MB)."""
    import dataclasses

    from repro import configs as C
    from repro import models
    from repro.core.context import GemmContext, use_context

    # the kernels' backend checks the platform; the compile is for a TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    L, bs, B, max_len = 4, 16, 16, 896
    cfg = dataclasses.replace(C.get_config("qwen1.5-4b"), n_layers=L,
                              param_dtype="bfloat16")
    Hkv, Dh = cfg.n_kv_heads, cfg.head_dim

    def shapes(fn):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip),
            jax.eval_shape(fn))

    def i32(shape=()):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    if chunk is None:
        fn = jax.jit(lambda p, s, t, a: models.decode_step(
            p, t, cfg, s, active=a), donate_argnums=(1,))
        args = (i32((B, 1)), i32((B,)))
    else:
        fn = jax.jit(lambda p, s, c, sl, st, n, b: models.prefill_chunk(
            p, c, cfg, s, slot=sl, start=st, true_len=n, blocks=b),
            donate_argnums=(1,))
        args = (i32((1, chunk)), i32(), i32(), i32(), i32((max_len // bs,)))

    def compile_for(nb):
        ctx = GemmContext(hw=V5E, matmul_backend="pallas",
                          plan_cache=PlanCache())
        with use_context(ctx):
            params = shapes(lambda: models.init(jax.random.PRNGKey(0), cfg))
            state = shapes(lambda: models.init_decode_state(
                cfg, B, max_len, per_slot=True, kv_block_size=bs,
                num_kv_blocks=nb))
            return fn.lower(params, state, *args).compile()

    nb = 600
    compiled = compile_for(nb)
    hlo = compiled.as_text()
    dims = [f"{L},{nb},{bs},{Hkv},{Dh}", f"{L},{nb},{Hkv},{bs},{Dh}",
            f"1,{nb},{bs},{Hkv},{Dh}", f"{nb},{bs},{Hkv},{Dh}"]
    ops = re.findall(rf"= bf16\[(?:{'|'.join(dims)})\]\S* ([\w-]+)\(", hlo)
    assert "dynamic-update-slice" in ops
    assert set(ops) <= {"parameter", "get-tuple-element", "bitcast",
                        "dynamic-update-slice", "fusion"}, sorted(set(ops))
    # a copy of the pool, or of one layer's slice, would differ by at
    # least one 300-block layer (24.6 MB) between the two
    temps = [c.memory_analysis().temp_size_in_bytes
             for c in (compiled, compile_for(nb // 2))]
    assert abs(temps[0] - temps[1]) < (nb // 2) * bs * Hkv * Dh * 2, temps
