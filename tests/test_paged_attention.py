"""Paged decode attention through the Pallas kernel (interpret mode on the
CPU) against the XLA gather path it replaces: the layer on one pool and
table, ``lm.decode_step`` over a few greedy steps, and the engine's
counters of which path each decode step took."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro import configs as C
from repro import models
from repro.core.context import use_context
from repro.core.plancache import PlanCache
from repro.launch.mesh import make_local_mesh
from repro.layers import attention as attn
from repro.serve import Request, ServeEngine


def _kernel_in(jaxpr) -> bool:
    return "paged_decode_attention" in str(jaxpr)


# ---------------------------------------------------------------- layer
@pytest.mark.parametrize(
    "n_heads,n_kv,dtype,bs",
    [(4, 4, jnp.bfloat16, 16), (8, 2, jnp.bfloat16, 16),
     (4, 4, jnp.float32, 8), (8, 2, jnp.float32, 4)],
    ids=["mha-bf16", "gqa-bf16", "mha-f32", "gqa-f32"])
def test_paged_decode_kernel_matches_gather(n_heads, n_kv, dtype, bs,
                                            monkeypatch):
    """One layer of a stacked pool, the same table, kernel against gather.
    Active lanes hold 1, bs-1, bs, bs+1 and max_len-1 positions on
    shuffled, non-contiguous blocks; one inactive lane's row points at
    blocks other lanes own, another's is all null block."""
    rng = np.random.default_rng(11)
    hd, L, mb, nb = 32, 2, 12, 64
    max_len = mb * bs
    lengths = np.array([1, bs - 1, bs, bs + 1, max_len - 1, 9, 3], np.int32)
    active = np.array([1, 1, 1, 1, 1, 0, 0], np.int32)
    B = len(lengths)
    ids = iter(rng.permutation(np.arange(1, nb)))
    table = np.zeros((B, mb), np.int32)
    for b in range(5):                       # blocks up to the write slot
        n = min(mb, lengths[b] // bs + 1)
        table[b, :n] = [next(ids) for _ in range(n)]
    table[5] = table[4]                      # another lane's blocks
    d = n_heads * hd
    k0, k1, k2, k3 = jax.random.split(jax.random.PRNGKey(5), 4)
    p = attn.init_attn(k0, d, n_heads, n_kv, hd, qkv_bias=True, dtype=dtype)
    p = p._replace(wo=jnp.eye(d, dtype=dtype))    # output = attention
    shape = (L, nb, bs, n_kv, hd)
    kp = jax.random.normal(k1, shape, jnp.float32).astype(dtype)
    vp = jax.random.normal(k2, shape, jnp.float32).astype(dtype)
    x = jax.random.normal(k3, (B, 1, d), jnp.float32).astype(dtype)
    cache = attn.PagedKVCache(k=kp, v=vp, table=jnp.asarray(table),
                              length=jnp.asarray(lengths))
    act = jnp.asarray(active)
    layer = jnp.int32(1)
    with use_context(matmul_backend="interpret", plan_cache=PlanCache()):
        got, gc = attn.paged_decode_attention(p, x, cache, layer=layer,
                                              active=act)
        # the same GEMMs, the gather in place of the kernel
        monkeypatch.setattr(attn, "decode_attention_backend",
                            lambda quantized: None)
        want, wc = attn.paged_decode_attention(p, x, cache, layer=layer,
                                               active=act)
    # the write is the same either way
    np.testing.assert_array_equal(np.asarray(gc.k), np.asarray(wc.k))
    np.testing.assert_array_equal(np.asarray(gc.v), np.asarray(wc.v))
    got = np.asarray(got[:, 0], np.float32)
    want = np.asarray(want[:, 0], np.float32)
    on = active.astype(bool)
    # bf16 output: one rounding of a value the two paths sum in different
    # orders; f32: summation order alone
    eps = float(jnp.finfo(dtype).eps)
    tol = (2 if dtype == jnp.bfloat16 else 64) * eps * np.abs(want[on]).max()
    np.testing.assert_allclose(got[on], want[on], rtol=0, atol=tol)
    assert np.isfinite(got[~on]).all()


def test_paged_decode_kernel_unread_pages_and_dma_races(capsys):
    """The TPU interpreter fills fresh VMEM with NaN and checks every DMA
    against the loads and stores around it: lanes whose last chunk leaves
    pages unread still come out finite and as the plain interpreter has
    them, and no race is reported."""
    from jax.experimental.pallas import tpu as pltpu
    from repro.kernels import paged_attention as pa

    rng = np.random.default_rng(3)
    B, H, Hkv, hd, L, nb, bs, mb = 4, 4, 2, 32, 2, 64, 4, 12
    lengths = jnp.asarray([3, 13, 0, 37], jnp.int32)   # 1, 4, 0, 10 blocks
    table = jnp.asarray(rng.permutation(np.arange(1, nb))[:B * mb]
                        .reshape(B, mb), jnp.int32)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.bfloat16)
    args = (f(B, H, hd), f(B, Hkv, hd), f(B, Hkv, hd), f(L, nb, bs, Hkv, hd),
            f(L, nb, bs, Hkv, hd), jnp.int32(1), table, lengths,
            jnp.asarray([1, 1, 1, 0], jnp.int32))
    assert pa.chunk_pages(bs, mb) == mb      # one chunk, mostly unread
    want = pa.paged_decode_attention(*args, interpret=True)
    got = pa.paged_decode_attention(*args, interpret=pltpu.InterpretParams(
        uninitialized_memory="nan", detect_races=True))
    assert np.isfinite(np.asarray(got, np.float32)).all()
    np.testing.assert_array_equal(np.asarray(got, np.float32)[:3],
                                  np.asarray(want, np.float32)[:3])
    assert "RACE" not in capsys.readouterr().out


# ----------------------------------------------------------- model step
def _decode_run(cfg, params, backend, kv_dtype=None, steps=6):
    """Chunked prefill of two lanes, then greedy decode steps with lane 2
    vacant; returns per-step logits and tokens."""
    rng = np.random.default_rng(2)
    bs, max_len, lanes = 4, 32, 3
    with use_context(matmul_backend=backend, plan_cache=PlanCache()):
        state = models.init_decode_state(
            cfg, lanes, max_len, per_slot=True, kv_block_size=bs,
            num_kv_blocks=24, kv_dtype=kv_dtype)
        prefill = jax.jit(lambda p, c, s, *a: models.prefill_chunk(
            p, c, cfg, s, slot=a[0], start=a[1], true_len=a[2],
            blocks=a[3]))
        decode = jax.jit(lambda p, t, s, a: models.decode_step(
            p, t, cfg, s, active=a))
        first = []
        for slot, plen in ((0, 9), (1, 4)):
            prompt = rng.integers(0, cfg.vocab_size, size=plen)
            # shuffled, disjoint blocks: 1..8 for lane 0, 9..16 for lane 1
            blocks = (1 + slot * 8 + rng.permutation(max_len // bs)).astype(
                np.int32)
            chunk = np.zeros((1, 12), np.int32)
            chunk[0, :plen] = prompt
            lp, state = prefill(
                params, jnp.asarray(chunk), state, jnp.int32(slot),
                jnp.int32(0), jnp.int32(plen), jnp.asarray(blocks))
            first.append(int(jnp.argmax(lp[0, : cfg.vocab_size])))
        active = jnp.asarray([1, 1, 0], jnp.int32)
        tok = np.array(first + [0], np.int32)
        logits, toks = [], []
        for _ in range(steps):
            ld, state = decode(params, jnp.asarray(tok[:, None]), state,
                               active)
            ld = np.asarray(ld[:2, : cfg.vocab_size], np.float32)
            tok = np.array(list(ld.argmax(-1)) + [0], np.int32)
            logits.append(ld)
            toks.append(tok[:2].tolist())
        jaxpr = jax.make_jaxpr(decode)(params, jnp.asarray(tok[:, None]),
                                       state, active)
    return logits, toks, _kernel_in(jaxpr)


@pytest.mark.parametrize("arch", ["qwen1.5-4b", "internlm2-20b"],
                         ids=["mha", "gqa"])
def test_decode_step_kernel_matches_xla_path(arch):
    """A tiny dense model for a few greedy steps on a paged pool: the
    interpret backend runs the kernel and matches the xla backend's gather
    (close logits, the same tokens); an int8 pool takes the gather."""
    cfg = C.smoke(C.get_config(arch))
    params = models.init(jax.random.PRNGKey(4), cfg)
    want, want_toks, xla_kernel = _decode_run(cfg, params, "xla")
    got, got_toks, int_kernel = _decode_run(cfg, params, "interpret")
    assert not xla_kernel and int_kernel
    assert got_toks == want_toks
    scale = max(np.abs(w).max() for w in want)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=0, atol=1e-4 * scale)
    with use_context(matmul_backend="interpret"):
        assert attn.decode_attention_backend(quantized=True) is None
    _, _, q_kernel = _decode_run(cfg, params, "interpret", kv_dtype="int8",
                                 steps=1)
    assert not q_kernel


# --------------------------------------------------------------- engine
def _engine_counts(cfg, params, backend, kv_quantize=None):
    with use_context(matmul_backend=backend, plan_cache=PlanCache()):
        eng = ServeEngine(cfg, make_local_mesh(), params, num_slots=2,
                          max_len=24, prompt_pad=8, kv_block_size=4,
                          num_kv_blocks=16, prefill_chunk=8,
                          kv_quantize=kv_quantize)
        rng = np.random.default_rng(3)
        m = eng.run([Request(prompt=rng.integers(0, cfg.vocab_size, size=5,
                                                 dtype=np.int32),
                             max_new_tokens=6, stop_ids=())])
    # the engine's copy of the lane lengths is the device's
    np.testing.assert_array_equal(eng._kv_len,
                                  np.asarray(eng.state["kv"].length))
    agg = m.to_dict()["aggregate"]
    gauges = eng.registry.collect()
    return agg, gauges, eng.art.max_blocks


def test_engine_counts_decode_attention_paths():
    """One request (prompt 5, 6 tokens: the first from prefill, then 5
    decode steps) on block size 4: the kernel reads ceil(len / 4) blocks
    for lengths 5..9, that is 2+2+2+2+3; the gather paths count steps."""
    cfg = C.smoke(C.get_config("qwen1.5-4b"))
    params = models.init(jax.random.PRNGKey(4), cfg)
    agg, gauges, mb = _engine_counts(cfg, params, "interpret")
    assert agg["decode_attn_kernel_steps"] == 5
    assert agg["decode_attn_fallback_steps"] == 0
    assert agg["decode_attn_blocks_read"] == 11
    assert agg["decode_attn_blocks_table"] == 5 * mb
    assert gauges["repro_serve_decode_attn_kernel_steps"] == 5
    assert gauges["repro_serve_decode_attn_blocks_read"] == 11
    for backend, kvq in (("xla", None), ("interpret", "int8")):
        agg, gauges, _ = _engine_counts(cfg, params, backend, kvq)
        assert agg["decode_attn_kernel_steps"] == 0
        assert agg["decode_attn_fallback_steps"] == 5
        assert agg["decode_attn_blocks_read"] == 0
        assert agg["decode_attn_blocks_table"] == 0
        assert gauges["repro_serve_decode_attn_fallback_steps"] == 5


def test_decode_attention_backend_follows_the_context():
    """The GEMMs' backend picks the path; 'auto' off a TPU gathers."""
    for backend, want in (("xla", None), ("auto", None),
                          ("interpret", "interpret")):
        with use_context(matmul_backend=backend):
            assert attn.decode_attention_backend(quantized=False) == want
            assert attn.decode_attention_backend(quantized=True) is None
