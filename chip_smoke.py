#!/usr/bin/env python3
"""Bring-up smoke for one TPU chip: serve full-width qwen1.5-4b through the
Pallas GEMMs from the serving CLI.

  python chip_smoke.py

Everything runs in this one process on one device, ``jax.devices()[0]``;
the weights are random, made from a fixed seed. Phases:

1. checks — a TPU must be attached; its ``device_kind`` picks the hardware
   spec (an unmodelled chip fails);
2. bf16 engine — ``repro.launch.serve.main`` with the paged engine, 8
   requests, ``--matmul-backend pallas``; it must finish plan-warm
   (steady state, zero lazy plan solves);
3. pallas vs xla — the same weights and prompts: each prompt's prefill
   logits at its last position and ``DECODE_STEPS`` greedy decode steps'
   logits under the Pallas kernels and under XLA, within ``LOGIT_TOL``;
4. int8 engine — phase 2 with ``--quantize int8`` (W8A8, fused requantize
   epilogue), then its prefill logits against XLA on the same int8 tree.

Each phase prints one line: compile seconds (XLA compiles or persistent
cache reads), wall seconds, tokens generated, the largest logit difference
and the device's ``peak_bytes_in_use`` so far. The last line of the output
is one JSON object naming the device; it is printed only when every phase
passed. Without a TPU, or when a phase fails, the script exits non-zero.
The times are a smoke's, not a benchmark's.
"""
from __future__ import annotations

import gc
import importlib.metadata
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen1.5-4b"
SLOTS, PROMPT_LEN, GEN, BLOCK = 4, 128, 32, 16
DECODE_STEPS = 4
ENGINE_ARGV = [
    "--arch", ARCH, "--engine", "--kv-block-size", str(BLOCK),
    "--num-slots", str(SLOTS), "--prompt-len", str(PROMPT_LEN),
    "--gen", str(GEN), "--matmul-backend", "pallas", "--plan-cache", "",
]
# Largest |pallas - xla| logit, as a fraction of the largest |xla| logit,
# per weight format. A wrong tile, a dropped K slab or a misplaced epilogue
# moves logits by their own size; these bounds sit well below that.
LOGIT_TOL = {
    # Both backends feed bf16 operands to the MXU and accumulate in f32,
    # but reduce K in a different order (the kernel in bk-wide slabs, XLA
    # in its own tiling), and XLA fuses the non-GEMM ops of the two
    # programs differently. A last-bit f32 difference flips the bf16
    # rounding of a few activations by one step (2^-8 relative), and the
    # residual layers compound the flips: at full width with interpret-mode
    # kernels on a CPU the fraction was 0.0042, 0.0097 and 0.013 at 2, 6
    # and 12 layers, about depth^0.6 — near 0.03 at 40 layers.
    None: 0.08,
    # The int8 GEMMs are bit-exact between the backends (i32 accumulation;
    # checked on a v5e for the MLP block, the unembed and a 128x2560x6912
    # projection), but activations
    # are requantized per tensor: one activation moving by one bf16 step
    # can re-round the whole tensor. On a CPU with XLA alone, one
    # embedding entry scaled by 1 + 2^-7 moved one-layer int8 logits by
    # 0.044 of the largest, ten times the bf16 response (0.0049). The
    # upstream flips above thus give int8 a gap that does not grow with
    # depth: 0.069 at 1 layer, 0.094-0.100 at 5 to 40 on a v5e.
    "int8": 0.2,
}


class CompileClock:
    """Seconds JAX spends compiling, or reading a compiled program from the
    persistent cache, since construction."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration_secs, **kwargs):
        if event == self.EVENT:
            self.seconds += duration_secs


def _peak_bytes(dev) -> int | None:
    return (dev.memory_stats() or {}).get("peak_bytes_in_use")


def _report(name, clock, c0, t0, dev, tokens, diff) -> None:
    diff_s = "n/a" if diff is None else f"{diff:.6g}"
    print(f"[{name}] compile_s={clock.seconds - c0:.2f} "
          f"wall_s={time.perf_counter() - t0:.2f} tokens={tokens} "
          f"max_logit_diff={diff_s} peak_bytes_in_use={_peak_bytes(dev)}",
          flush=True)


def _engine_phase(serve, argv) -> int:
    """serve.main through the CLI; returns the tokens generated."""
    m = serve.main(argv)
    pc = m.plan_cache
    if not pc["steady_state"] or pc["lazy_solves"]:
        raise RuntimeError(f"engine was not plan-warm: {pc}")
    return m.generated_tokens


def _prompts(vocab_size: int):
    """The engine trace's first ``SLOTS`` prompts (same seed, same lengths)."""
    from repro.serve import synthetic_trace

    trace = synthetic_trace(
        SLOTS, vocab_size=vocab_size,
        prompt_lens=[PROMPT_LEN, PROMPT_LEN // 2, 3 * PROMPT_LEN // 4],
        max_new_tokens=[GEN], seed=0)
    return [r.prompt for r in trace]


def _logits(cfg, mesh, hw, params, axes, prompts, *, backend, quant,
            decode_steps, feed=None):
    """Prefill each prompt into its own lane through the paged engine's
    step functions, then ``decode_steps`` decode steps over all lanes.

    Returns ``(rows, fed)``: the prefill logits (SLOTS, Vp) followed by one
    (SLOTS, Vp) array per decode step, and the tokens fed to each step —
    greedy from this run's own logits, or ``feed`` when given, so a second
    backend is compared on the same inputs.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import models
    from repro.core.context import GemmContext, use_context
    from repro.core.plancache import PlanCache
    from repro.train.servestep import make_paged_engine_step

    max_len = PROMPT_LEN + GEN + 1
    # the engine's own pool geometry, so the pallas programs are the ones
    # the engine phase compiled
    num_blocks = -(-SLOTS * max_len // BLOCK) + 1
    per_lane = -(-(PROMPT_LEN + decode_steps) // BLOCK)
    ctx = GemmContext(hw=hw, matmul_backend=backend, quant_mode=quant,
                      plan_cache=PlanCache())
    with use_context(ctx):
        art = make_paged_engine_step(
            cfg, mesh, num_slots=SLOTS, max_len=max_len, kv_block_size=BLOCK,
            num_kv_blocks=num_blocks, chunk_buckets=(PROMPT_LEN,),
            param_shapes=jax.eval_shape(lambda: params), param_axes=axes)
        state = jax.jit(
            lambda: models.init_decode_state(
                cfg, SLOTS, max_len, per_slot=True, kv_block_size=BLOCK,
                num_kv_blocks=num_blocks),
            out_shardings=art.state_shardings)()
        prefill = []
        for slot, prompt in enumerate(prompts):
            chunk = np.zeros((1, PROMPT_LEN), np.int32)
            chunk[0, :len(prompt)] = prompt
            table_row = np.zeros((art.max_blocks,), np.int32)
            table_row[:per_lane] = 1 + slot * per_lane + np.arange(per_lane)
            logits, state = art.prefill_fn(
                params, state, jnp.asarray(chunk), jnp.int32(slot),
                jnp.int32(0), jnp.int32(len(prompt)), jnp.asarray(table_row))
            prefill.append(np.asarray(logits, np.float32))
        rows, fed = [np.stack(prefill)], []
        active = jnp.ones((SLOTS,), jnp.int32)
        for step in range(decode_steps):
            tok = (feed[step] if feed is not None else
                   rows[-1][:, :cfg.vocab_size].argmax(-1).astype(np.int32))
            fed.append(tok)
            logits, state = art.decode_fn(
                params, state, jnp.asarray(tok[:, None]), active)
            rows.append(np.asarray(logits, np.float32))
    return rows, fed


def _compare(cfg, mesh, hw, params, axes, prompts, *, quant, decode_steps):
    """Largest relative logit difference, pallas against xla."""
    import numpy as np

    got, fed = _logits(cfg, mesh, hw, params, axes, prompts,
                       backend="pallas", quant=quant,
                       decode_steps=decode_steps)
    want, _ = _logits(cfg, mesh, hw, params, axes, prompts, backend="xla",
                      quant=quant, decode_steps=decode_steps, feed=fed)
    diff = 0.0
    for g, w in zip(got, want):
        if not (np.isfinite(g).all() and np.isfinite(w).all()):
            raise RuntimeError("non-finite logits")
        diff = max(diff, float(np.abs(g - w).max() / np.abs(w).max()))
    if diff > LOGIT_TOL[quant]:
        raise RuntimeError(
            f"pallas vs xla logits differ by {diff:.4g} of the largest "
            f"logit (tolerance {LOGIT_TOL[quant]})")
    return diff, len(got) * SLOTS


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU attached (JAX's device 0 is "
              f"{dev.platform!r}); this smoke runs only on a TPU",
              file=sys.stderr)
        return 1

    from repro import configs as C
    from repro.core import hwregistry
    from repro.launch import serve
    from repro.launch.args import use_compile_cache
    from repro.launch.mesh import make_local_mesh

    cache_dir = use_compile_cache()
    clock = CompileClock()
    hw = hwregistry.hw_for_device_kind(dev.device_kind)
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    print(f"[checks] platform={dev.platform} device_kind={dev.device_kind!r} "
          f"devices={len(jax.devices())} jax={jax.__version__} "
          f"jaxlib={importlib.metadata.version('jaxlib')} libtpu={libtpu} "
          f"hw={hw.name} compile_cache={cache_dir}", flush=True)

    cfg = C.get_config(ARCH)
    mesh = make_local_mesh()
    prompts = _prompts(cfg.vocab_size)

    c0, t0 = clock.seconds, time.perf_counter()
    tokens = _engine_phase(serve, ENGINE_ARGV)
    gc.collect()
    _report("bf16-engine", clock, c0, t0, dev, tokens, None)

    c0, t0 = clock.seconds, time.perf_counter()
    params, axes = serve.init_params(cfg, mesh)
    diff, tokens = _compare(cfg, mesh, hw, params, axes, prompts, quant=None,
                            decode_steps=DECODE_STEPS)
    del params
    gc.collect()
    _report("pallas-vs-xla", clock, c0, t0, dev, tokens, diff)

    c0, t0 = clock.seconds, time.perf_counter()
    tokens = _engine_phase(serve, ENGINE_ARGV + ["--quantize", "int8"])
    gc.collect()
    params, axes = serve.init_params(cfg, mesh, quantize=True)
    diff, _ = _compare(cfg, mesh, hw, params, axes, prompts, quant="int8",
                       decode_steps=0)
    del params
    _report("int8-engine", clock, c0, t0, dev, tokens, diff)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
