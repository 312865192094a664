"""Shared launcher arguments: one --hw/--matmul-backend/--quantize layer.

Every entry point under ``launch/`` (serve, train, dryrun) builds its
:class:`repro.core.context.GemmContext` through here, so hardware
generation, kernel backend, quantization mode and plan-cache location are
selected the same way everywhere:

  --hw tpu_v6e --matmul-backend pallas --quantize int8 --plan-cache p.json

``--hw`` defaults to the ``REPRO_HW`` env var, else the attached TPU's spec
(an unmodelled chip raises), else tpu_v5e as the modelling default off the
TPU; ``--plan-cache ''`` disables persistence (in-memory cache only).

``use_compile_cache`` is for entry points only: importing a module never
configures JAX.
"""
from __future__ import annotations

import argparse
import os
import pathlib

import jax

from repro.core.context import BACKENDS, GemmContext
from repro.core.hwregistry import default_hw, list_hw
from repro.core.plancache import PlanCache, default_cache_path
from repro.kernels.ops import resolve_backend


def add_context_args(
    ap: argparse.ArgumentParser,
    *,
    backend_default: str = "xla",
    include_quant: bool = True,
) -> argparse.ArgumentParser:
    g = ap.add_argument_group("execution context")
    g.add_argument(
        "--hw", default=None, metavar="GEN",
        help=f"hardware generation for the GEMM planner/perf model "
             f"({', '.join(list_hw())}; default: $REPRO_HW, else the "
             f"attached TPU, else tpu_v5e)")
    g.add_argument(
        "--matmul-backend", default=backend_default, choices=list(BACKENDS),
        help="kernel backend for every dense()/balanced_gemm")
    if include_quant:
        g.add_argument(
            "--quantize", default="none", choices=["none", "int8"],
            help="int8: route every projection through the W8A8 "
                 "balanced-GEMM path (fused requantize epilogue)")
    g.add_argument(
        "--plan-cache", default=None, metavar="PATH",
        help="persistent GEMM plan cache JSON (default: "
             "$REPRO_PLAN_CACHE or ~/.cache/repro/plancache.json; "
             "'' = in-memory only)")
    return ap


def add_serve_engine_args(ap: argparse.ArgumentParser) -> argparse.ArgumentParser:
    """The serving-engine argument layer (continuous batching; docs/serving.md)."""
    g = ap.add_argument_group("serving engine")
    g.add_argument(
        "--engine", action="store_true",
        help="serve with the continuous-batching slot engine instead of "
             "static batching (repro.serve)")
    g.add_argument(
        "--num-slots", type=int, default=4, metavar="N",
        help="fixed decode lanes: every decode tick is one (N, 1) step "
             "regardless of traffic (default 4)")
    g.add_argument(
        "--max-new-tokens", type=int, default=None, metavar="N",
        help="per-request generation budget for the engine trace "
             "(default: --gen)")
    g.add_argument(
        "--eos-id", type=int, default=None, metavar="ID",
        help="stop id: requests/sequences end early on this token "
             "(both engine and static paths)")
    g.add_argument(
        "--kv-block-size", type=int, default=0, metavar="B",
        help="paged KV: pool the engine cache in B-token blocks behind "
             "per-slot block tables (0 = contiguous per-slot regions); "
             "enables chunked prefill")
    g.add_argument(
        "--num-kv-blocks", type=int, default=None, metavar="N",
        help="paged KV pool size in blocks incl. the reserved null block "
             "(default: full num_slots*max_len capacity; shrink it to make "
             "footprint track admitted tokens — short admissions defer)")
    g.add_argument(
        "--kv-quantize", default="none", choices=["none", "int8"],
        help="quantize the paged KV pool's block storage (needs "
             "--kv-block-size): int8 blocks plus per-block/per-kv-head "
             "f32 scales, dequantized inside the table-walking gather — "
             "~0.5x pool bytes, so an equal-byte budget holds ~2x the "
             "blocks (greedy decode parity is tolerance-gated, see "
             "docs/serving.md)")
    g.add_argument(
        "--prefill-chunk", type=int, default=None, metavar="C",
        help="chunked prefill: admit prompts at most C tokens per tick, "
             "interleaved with decode (paged engine only; default: "
             "prompt pad). Chunks round up to <=3 bucket lengths "
             "{C/4, C/2, C} so prefill stays plan-warm")
    g.add_argument(
        "--prefix-cache", action="store_true",
        help="share prompt-prefix KV across requests through a radix "
             "trie over the paged pool (needs --kv-block-size; blocks "
             "become ref-counted, full-block prefixes are cached at "
             "retirement and matched at admission — zero prefill for "
             "shared headers, token-for-token identical output)")
    g.add_argument(
        "--prefix-cache-blocks", type=int, default=None, metavar="N",
        help="cap the prefix cache at N pool blocks (LRU leaves are "
             "trimmed past it; default: unbounded — cached-idle blocks "
             "are reclaimed on demand before the pool reports OOM)")
    g.add_argument(
        "--sched-policy", default="fifo",
        choices=["fifo", "priority", "edf", "prefix"],
        help="admission-ordering policy (serve/policy.py). priority/edf "
             "preempt lower-ranked decodes under lane/block pressure "
             "(paged engine only); prefix admits the longest cached "
             "prefix first (pairs with --prefix-cache)")
    g.add_argument(
        "--ttft-target-ms", type=float, default=None, metavar="MS",
        help="TTFT SLO target for the dynamic prefill/decode budget: the "
             "engine adapts prefill chunks per tick (1..--max-prefill-"
             "chunks) from observed submit-to-first-token EWMA vs this "
             "target (default: off — fixed 1 chunk/tick)")
    g.add_argument(
        "--max-prefill-chunks", type=int, default=4, metavar="N",
        help="budget controller ceiling: at most N prefill chunks per "
             "tick (default 4)")
    g.add_argument(
        "--sim-clock", type=float, default=None, metavar="DT",
        help="drive the engine with a deterministic simulated clock "
             "advancing DT seconds per reading instead of wall time "
             "(reproducible TTFT/deadline metrics; benchmarks and CI)")
    g.add_argument(
        "--bursty-trace", action="store_true",
        help="use the seeded bursty mixed-priority trace (interactive "
             "high-priority + background low-priority classes, arrivals "
             "in bursts) instead of the uniform synthetic trace — the "
             "traffic shape --sched-policy exists for")
    g.add_argument(
        "--burst-size", type=int, default=4, metavar="N",
        help="requests per burst in --bursty-trace (default 4)")
    g.add_argument(
        "--burst-gap-s", type=float, default=0.05, metavar="S",
        help="gap between bursts on the engine clock (default 0.05)")
    g.add_argument(
        "--temperature", type=float, default=0.0, metavar="T",
        help="sampling temperature (0 = greedy; host-side, per-request "
             "seeded streams)")
    g.add_argument(
        "--top-p", type=float, default=1.0, metavar="P",
        help="nucleus sampling mass (with --temperature > 0)")
    g.add_argument(
        "--spec-draft-config", default=None, metavar="ARCH",
        help="enable speculative decoding: draft-model architecture "
             "(repro.configs name) that proposes tokens for the target to "
             "verify in one batched pass (paged engine, greedy only; "
             "--smoke shrinks the draft alongside the target)")
    g.add_argument(
        "--spec-k", type=int, default=4, metavar="K",
        help="speculation depth: draft proposes K tokens per lane per "
             "round, target verifies K+1 positions (default 4)")
    g.add_argument(
        "--spec-draft-quantize", default="int8", choices=["none", "int8"],
        help="quantize the draft's weights once at load (int8 prequant, "
             "same path as --quantize; default int8 — the draft exists "
             "to be cheap)")
    g.add_argument(
        "--metrics-json", default=None, metavar="PATH",
        help="write the engine's serve metrics JSON here")
    g.add_argument(
        "--trace-out", default=None, metavar="PATH",
        help="flight recorder: write a Chrome trace-event JSON of the "
             "run (phase spans + per-request async spans; load in "
             "Perfetto or chrome://tracing — docs/observability.md). "
             "Also adds the `timing` section to the metrics JSON")
    g.add_argument(
        "--trace-ring-events", type=int, default=65536, metavar="N",
        help="tracer ring-buffer capacity in events; oldest events drop "
             "past it (default 65536 ~ 16k ticks of phase spans)")
    g.add_argument(
        "--metrics-interval-ticks", type=int, default=None, metavar="N",
        help="snapshot the counter registry every N engine ticks and "
             "write its Prometheus text exposition next to "
             "--metrics-json (default: end-of-run publish only)")
    g.add_argument(
        "--measure-plans", action="store_true",
        help="refine warm-up plans in place with wall-clock measurement "
             "(core.autotune) and persist the refined plans")
    g.add_argument(
        "--attrib-tol", type=float, default=0.25, metavar="F",
        help="balance-auditor drift tolerance: a cached plan whose "
             "current model evaluation deviates from its solve-time "
             "snapshot by more than F (relative t_total or balance "
             "ratio) is flagged drifted (default 0.25)")
    g.add_argument(
        "--rebalance-drifted", action="store_true",
        help="after a traced run, feed the warm plans the balance "
             "auditor flagged as drifted into autotune.refine_cached_"
             "plans(resolve=True) — model re-solve + hillclimb — and "
             "persist the restored plans (needs --trace-out)")
    return ap


# <checkout>/.jax_cache: a fixed path, since the path is part of the key.
COMPILE_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, places it and JAX reads it
    itself; otherwise the cache lives in the checkout's ``.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)


def context_from_args(args: argparse.Namespace) -> GemmContext:
    """Build (and load) the execution context an argparse namespace asks for."""
    resolve_backend(args.matmul_backend)  # 'pallas' off a TPU fails here
    path = args.plan_cache
    if path is None:
        path = default_cache_path()
    cache = PlanCache(path=path or None)
    cache.load()
    return GemmContext(
        hw=args.hw if args.hw is not None else default_hw(),
        matmul_backend=args.matmul_backend,
        quant_mode=getattr(args, "quantize", None),
        plan_cache=cache,
    )
