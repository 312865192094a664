"""Batched serving driver: continuous-batching-style prefill + decode loop.

Requests arrive with different prompt lengths; the server right-pads to the
batch maximum, prefills once, then decodes step-by-step with the sharded KV
cache. Greedy sampling (deterministic; good for tests/examples).

Start-up follows the production recipe the GemmContext subsystem enables:

1. build the execution context from the shared --hw/--matmul-backend/
   --quantize arg layer, loading previously solved plans from the
   persistent cache; keep JAX's compilation cache in
   ``$JAX_COMPILATION_CACHE_DIR`` or the checkout (``args.use_compile_cache``);
2. make the seeded weights on the mesh in one jit (``init_params``), in
   the activation dtype; with --quantize int8 the same program quantizes
   them (quant.prequant) so decode streams int8 weights — not the in-graph
   re-quantization demo path;
3. warm up: ``plan_model`` pre-solves every GEMM signature the model will
   issue (prefill + decode, all projections) and persists them, so steady-
   state traffic performs zero lazy plan solves and the *next* process
   start solves nothing at all.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-4b --smoke \
      --hw tpu_v6e --quantize int8 --batch 4 --prompt-len 12 --gen 16

``--engine`` swaps static batching for the continuous-batching slot engine
(``repro.serve``): requests admit/retire mid-flight while the decode batch
stays at ``--num-slots`` fixed lanes, so every tick replays one plan-cached
GEMM signature set (docs/serving.md):

  PYTHONPATH=src python -m repro.launch.serve --smoke --engine \
      --num-slots 4 --prompt-len 12 --gen 16 --metrics-json serve.json

``--kv-block-size`` switches the engine's cache to the paged block-pool
layout (per-slot block tables, chunked prefill via ``--prefill-chunk``,
pool sized by ``--num-kv-blocks``); ``--temperature``/``--top-p`` enable
host-side per-request-seeded sampling. ``--prefix-cache`` (paged only)
shares prompt-prefix KV across requests through the radix trie
(``--prefix-cache-blocks`` caps it) and serves a shared-header trace so
the dedup is visible in the metrics. ``--spec-draft-config`` (paged,
greedy only) adds speculative decoding lanes: an int8-prequantized draft
proposes ``--spec-k`` tokens per slot, the target verifies them in one
batched pass, rejected tails rewind in place. See docs/serving.md.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs as C
from repro import models
from repro.core.context import use_context
from repro.core.gemm import plan_model
from repro.launch.args import (add_context_args, add_serve_engine_args,
                               context_from_args, use_compile_cache)
from repro.launch.mesh import make_local_mesh, make_production_mesh
from repro.parallel import sharding as shd
from repro.quant import prequant
from repro.train.servestep import make_serve_step


def init_params(cfg, mesh, *, quantize: bool = False, seed: int = 0):
    """Seeded random serving weights, made on ``mesh`` by one jit.

    Serving holds weights in the activation dtype (bf16 for the published
    configs — their checkpoints' dtype; training keeps f32 masters), and
    the jit's out_shardings place each leaf where the partitioner wants it,
    so no f32 copy of the model exists on the host or the device. With
    ``quantize`` the same program quantizes every projection
    (quant.prequant): the float tree never lives beside the int8 one.
    Returns ``(params, param_axes)``.
    """
    cfg = dataclasses.replace(cfg, param_dtype=cfg.activation_dtype)
    axes = models.axes(cfg)
    if quantize:
        axes = prequant.quantize_axes(axes)

    def make():
        params = models.init(jax.random.PRNGKey(seed), cfg)
        return prequant.quantize_params(params) if quantize else params

    shardings = shd.param_shardings(axes, jax.eval_shape(make), mesh)
    return jax.jit(make, out_shardings=shardings)(), axes


def serve_batch(cfg, mesh, params, prompts, *, gen_len: int, max_len: int,
                extras=None, param_axes=None, eos_id: int | None = None,
                pad_id: int = 0):
    """prompts: (B, P) int32. Returns (B, gen_len) generated ids.

    With ``eos_id``, generation stops *per sequence* at the first stop
    token: the stop token is kept, the tail is ``pad_id``, and a finished
    row keeps feeding ``pad_id`` (so its outputs are reproducible and
    engine-comparable). The batch still decodes until every row finishes or
    ``gen_len`` — that whole-batch tail is exactly the waste the
    continuous-batching engine (repro.serve) exists to reclaim.
    """
    B = prompts.shape[0]
    art = make_serve_step(
        cfg, mesh, batch=B, max_len=max_len,
        param_shapes=(None if param_axes is None
                      else jax.eval_shape(lambda: params)),
        param_axes=param_axes)
    with mesh:
        state = jax.jit(
            lambda: models.init_decode_state(cfg, B, max_len),
            out_shardings=art.state_shardings)()
        batch_in = {"tokens": prompts, **(extras or {})}
        logits, state = art.prefill_fn(params, state, batch_in)
        out = []
        finished = jnp.zeros((B,), bool)
        tok = jnp.argmax(logits[:, : cfg.vocab_size], -1).astype(jnp.int32)
        for _ in range(gen_len):
            if eos_id is not None:
                tok = jnp.where(finished, jnp.int32(pad_id), tok)
            out.append(tok)
            if eos_id is not None:
                finished = finished | (tok == eos_id)
                if bool(finished.all()):
                    break
            logits, state = art.decode_fn(params, state, tok[:, None])
            tok = jnp.argmax(logits[:, : cfg.vocab_size], -1).astype(jnp.int32)
    gen = jnp.stack(out, axis=1)
    if gen.shape[1] < gen_len:  # every row hit EOS early: pad the tail
        gen = jnp.pad(gen, ((0, 0), (0, gen_len - gen.shape[1])),
                      constant_values=pad_id)
    return gen


def _report_warmup(ctx, warm: dict, seconds: float, label: str) -> None:
    """Persist the warmed plans and print one warm-up summary line."""
    saved = ctx.plan_cache.save()
    print(f"[plan-cache] {label} {seconds:.2f}s: "
          f"{warm['signatures']} signatures, {warm['solved']} solved, "
          f"{warm['from_cache']} from cache (hw={ctx.hw.name}"
          + (f", persisted to {saved}" if saved else "") + ")")


def _measure_plans(ctx, args) -> None:
    """--measure-plans: refine the warm-up's plans with wall-clock feedback
    (core.autotune) and persist the refined set (ROADMAP item)."""
    from repro.core.autotune import refine_cached_plans

    t0 = time.perf_counter()
    stats = refine_cached_plans(ctx.plan_cache)
    saved = ctx.plan_cache.save()
    print(f"[plan-cache] measured refinement {time.perf_counter()-t0:.2f}s: "
          f"{stats['measured']} measurements, {stats['refined']} plans "
          f"refined, {stats['kept']} kept"
          + (f", persisted to {saved}" if saved else ""))


def _report_attrib(ctx, engine, m, *, rebalance: bool) -> None:
    """Print the balance auditor's verdict and, with --rebalance-drifted,
    feed the drifted warm plans through a model re-solve + hillclimb.

    The metrics JSON keeps the *audited* (pre-rebalance) attribution: the
    re-solve restores the cache for the next run, it does not rewrite the
    evidence that triggered it.
    """
    a = m.attribution
    if not a:
        return
    recon = a.get("reconciliation_error")
    print(f"[attrib] {a['signatures']} signatures: attributed "
          f"{a['attributed_device_s']:.3f}s of {a['traced_device_s']:.3f}s "
          f"traced GEMM-phase device time (recon err "
          + (f"{recon:.3f}" if recon is not None else "n/a")
          + f"), bound shares "
          + ", ".join(f"{k}={v:.2f}" if v is not None else f"{k}=n/a"
                      for k, v in sorted(a["bound_share"].items()))
          + f", drifted={a['drifted_count']}")
    rows = a.get("by_device_s") or []
    if rows:
        top = rows[0]
        print(f"[attrib] top signature {top['key']}: "
              f"{top['device_s']:.3f}s ({top['share']:.2f} share, "
              f"{top['calls']} calls, bound={top['bound']})")
    for k in a.get("drifted", []):
        row = next((r for r in rows if r["key"] == k), None)
        sug = ""
        if row is not None and row.get("suggested_bm") is not None:
            sug = (f" -> suggest bm={row['suggested_bm']} "
                   f"bk={row['suggested_bk']} bn={row['suggested_bn']} "
                   f"(x{row['suggested_gain']:.2f} modeled)")
        print(f"[attrib] drifted: {k}{sug}")
    if not rebalance:
        return
    keys = engine.attrib.drifted_keys()
    if not keys:
        print("[attrib] rebalance: no drifted warm plans, nothing to do")
        return
    from repro.core.autotune import model_measure_fn, refine_cached_plans

    t0 = time.perf_counter()
    stats = refine_cached_plans(
        ctx.plan_cache, keys=keys, resolve=True,
        measure_factory=lambda M, K, N, **kw: model_measure_fn(
            M, K, N, hw=ctx.hw, **kw))
    saved = ctx.plan_cache.save()
    print(f"[attrib] rebalanced {len(keys)} drifted plans in "
          f"{time.perf_counter()-t0:.2f}s: {stats['refined']} refined, "
          f"{stats['kept']} kept"
          + (f", persisted to {saved}" if saved else ""))


def _run_engine(args, ctx, cfg, mesh, params, param_axes):
    """--engine: continuous batching over a mixed-length synthetic trace
    (with --prefix-cache: a shared-header trace, so the radix cache has
    prefixes to dedupe; with --bursty-trace: bursts of mixed-priority
    traffic, the shape --sched-policy and --ttft-target-ms exist for)."""
    from repro.obs.trace import Tracer
    from repro.serve import (ServeEngine, SimClock, bursty_trace,
                             shared_prefix_trace, synthetic_trace)

    if args.prefix_cache and not args.kv_block_size:
        raise SystemExit("--prefix-cache needs the paged engine: pass "
                         "--kv-block-size too")
    if args.sched_policy in ("priority", "edf") and not args.kv_block_size:
        raise SystemExit(f"--sched-policy {args.sched_policy} preempts via "
                         "the paged pool: pass --kv-block-size too")
    if args.kv_quantize != "none" and not args.kv_block_size:
        raise SystemExit("--kv-quantize stores per-block scales alongside "
                         "the block pool: pass --kv-block-size too")
    spec_kwargs = {}
    if args.spec_draft_config:
        if not args.kv_block_size:
            raise SystemExit("--spec-draft-config needs the paged engine: "
                             "pass --kv-block-size too")
        if args.temperature > 0:
            raise SystemExit("speculative decoding verifies greedy argmax "
                             "chains: --temperature must be 0")
        dcfg = C.get_config(args.spec_draft_config)
        if args.smoke:
            dcfg = C.smoke(dcfg)
        # same once-at-load prequant recipe as the target's --quantize
        dquant = "int8" if args.spec_draft_quantize == "int8" else None
        dparams, daxes = init_params(dcfg, mesh, quantize=dquant == "int8")
        spec_kwargs = dict(
            spec_draft_cfg=dcfg, spec_draft_params=dparams,
            spec_k=args.spec_k, spec_draft_param_axes=daxes,
            spec_draft_quant=dquant)
    gen = args.max_new_tokens or args.gen
    plen = args.prompt_len
    stop = (args.eos_id,) if args.eos_id is not None else ()
    n_requests = max(args.batch, 2 * args.num_slots)
    prompt_pad = plen
    if args.bursty_trace:
        # interactive class: short prompts, short answers, a deadline a
        # few bursts out; background class: long prompts, long answers,
        # no deadline — one queue, mixed
        header = plen if args.prefix_cache else 0
        classes = [
            dict(priority=2, prompt_lens=(max(1, plen // 2), plen),
                 max_new_tokens=(max(1, gen // 4), max(1, gen // 2)),
                 deadline_slack_s=10 * args.burst_gap_s, weight=1.0),
            dict(priority=0, prompt_lens=(2 * plen,),
                 max_new_tokens=(gen,), deadline_slack_s=None, weight=1.0),
        ]
        trace = bursty_trace(
            n_requests, vocab_size=cfg.vocab_size,
            burst_size=args.burst_size, burst_gap_s=args.burst_gap_s,
            classes=classes, header_len=header, stop_ids=stop, seed=0)
        prompt_pad = header + 2 * plen
        max_len = prompt_pad + gen + 1
    elif args.prefix_cache:
        # every request repeats a plen-token header + a short unique tail
        tails = [1, 3, 5]
        trace = shared_prefix_trace(
            n_requests, vocab_size=cfg.vocab_size, header_len=plen,
            tail_lens=tails,
            max_new_tokens=[gen, max(1, gen // 2), max(1, gen // 4)],
            stop_ids=stop, seed=0)
        max_len = plen + max(tails) + gen + 1
    else:
        trace = synthetic_trace(
            n_requests, vocab_size=cfg.vocab_size,
            prompt_lens=[plen, max(1, plen // 2), max(1, (3 * plen) // 4)],
            max_new_tokens=[gen, max(1, gen // 2), max(1, gen // 4)],
            stop_ids=stop, seed=0)
        max_len = plen + gen + 1
    tracer = (Tracer(ring_events=args.trace_ring_events)
              if args.trace_out else None)
    engine = ServeEngine(
        cfg, mesh, params, num_slots=args.num_slots,
        max_len=max_len, prompt_pad=prompt_pad, param_axes=param_axes,
        kv_block_size=args.kv_block_size or None,
        num_kv_blocks=args.num_kv_blocks,
        kv_quantize=args.kv_quantize,
        prefill_chunk=args.prefill_chunk,
        prefix_cache=args.prefix_cache,
        prefix_cache_blocks=args.prefix_cache_blocks,
        temperature=args.temperature, top_p=args.top_p,
        sched_policy=args.sched_policy,
        ttft_target_ms=args.ttft_target_ms,
        max_prefill_chunks=args.max_prefill_chunks,
        clock=(SimClock(args.sim_clock) if args.sim_clock else None),
        tracer=tracer,
        metrics_interval_ticks=args.metrics_interval_ticks,
        attrib_tol=args.attrib_tol,
        **spec_kwargs)
    if not args.no_warmup:
        t0 = time.perf_counter()
        warm = engine.plan_warmup()
        _report_warmup(ctx, warm, time.perf_counter() - t0, "engine warm-up")
        if args.measure_plans:
            _measure_plans(ctx, args)

    m = engine.run(trace)
    qtag = f" quant={ctx.quant_mode}" if ctx.quant_mode else ""
    ptag = (f" paged(block={engine.kv_block_size},"
            f"pool={engine.num_kv_blocks})" if engine.paged else "")
    # rate properties are None when their denominator never moved (e.g.
    # a SimClock run finishing inside one resolution step)
    tps = (f"{m.tokens_per_sec:.1f} tok/s" if m.tokens_per_sec is not None
           else f"{m.tokens_per_tick:.2f} tok/tick")
    occ = (f"{m.mean_occupancy:.2f}" if m.mean_occupancy is not None
           else "n/a")
    print(f"[engine]{ptag} arch={cfg.name}{qtag} hw={ctx.hw.name} "
          f"backend={ctx.matmul_backend} slots={args.num_slots}: "
          f"{len(trace)} requests, {m.generated_tokens} tokens in "
          f"{m.wall_s:.2f}s ({tps} incl. compile), "
          f"mean occupancy {occ}/{args.num_slots}, "
          f"{m.ticks} ticks")
    if engine.paged:
        bp = m.block_pool
        print(f"[block-pool] peak {bp['peak_in_use']}/{bp['num_blocks'] - 1} "
              f"blocks ({bp['peak_utilization']:.2f} util), memory ratio "
              f"{bp['memory_ratio']:.2f}x contiguous, "
              f"{m.deferred_admissions} deferred admissions, "
              f"peak internal frag {bp['peak_fragmentation_tokens']} tokens")
        kv = m.kv_cache
        if kv.get("quantized"):
            print(f"[kv-quant] {kv['kv_dtype']}: "
                  f"{kv['bytes_per_block']} B/block "
                  f"({kv['bytes_ratio']:.3f}x bf16, pool "
                  f"{kv['pool_bytes']} vs {kv['bf16_pool_bytes']} B), "
                  f"max scale k={kv['scale_k_max']:.4g} "
                  f"v={kv['scale_v_max']:.4g}")
    if m.speculation.get("enabled"):
        sp = m.speculation
        print(f"[spec] draft={sp['draft_arch']}"
              + (f"({sp['draft_quant']})" if sp.get("draft_quant") else "")
              + f" k={sp['spec_k']}: {sp['rounds']} rounds, accepted "
              f"{sp['accepted_tokens']}/{sp['proposed_tokens']} proposals "
              f"({sp['acceptance_rate']:.2f}), "
              f"{sp['mean_committed_per_round']:.2f} tokens/round, "
              f"draft {sp['draft_s']:.2f}s / verify {sp['verify_s']:.2f}s")
    if m.prefix_cache:
        px = m.prefix_cache
        print(f"[prefix-cache] hit {px['hit_tokens']}/{px['lookup_tokens']} "
              f"prompt tokens ({px['hit_rate']:.2f} hit rate), "
              f"{px['inserted_blocks']} blocks cached, "
              f"{px['reclaimed_blocks']} reclaimed")
    if m.policy != "fifo" or m.preemptions or m.deadline_missed:
        print(f"[sched] policy={m.policy} preemptions={m.preemptions} "
              f"resumes={m.resumes} deadline_missed={m.deadline_missed} "
              f"deferred={m.deferred_admissions}")
        for prio, s in m.slo_summary().items():
            p99t = s["p99_ttft_ticks"]
            print(f"[slo] priority={prio}: n={s['n']} "
                  f"finished={s['finished']} "
                  f"missed={s['deadline_missed']} "
                  f"(rate {s['miss_rate']:.2f}), "
                  f"p99 ttft "
                  + (f"{p99t:.0f} ticks" if p99t is not None else "n/a")
                  + f", {s['preemptions']} preemptions")
    if m.budget.get("target_ttft_s"):
        b = m.budget
        print(f"[budget] target {1e3 * b['target_ttft_s']:.1f}ms: "
              f"{b['observations']} TTFT observations, ema "
              + (f"{1e3 * b['ema_ttft_s']:.1f}ms"
                 if b["ema_ttft_s"] is not None else "n/a")
              + f", {b['raises']} raises / {b['drops']} drops, final "
              f"{b['final_chunks']} chunks/tick")
    pc = m.plan_cache
    print(f"[plan-cache] serving: hits={pc['hits']} misses={pc['misses']} "
          f"lazy_solves={pc['lazy_solves']} "
          f"steady_state={pc['steady_state']}")
    first = engine.finished[0]
    print(f"first finished: id={first.request.request_id} "
          f"reason={first.finish_reason} tokens={first.tokens[:12]} ...")
    if tracer is not None:
        obj = tracer.save(args.trace_out)
        t = m.timing
        print(f"[trace] {len(obj['traceEvents'])} events "
              f"({t.get('events_dropped', 0)} dropped) -> {args.trace_out}; "
              f"host {t.get('host_s', 0.0):.3f}s / device "
              f"{t.get('device_s', 0.0):.3f}s across "
              f"{len(t.get('phases', {}))} phases")
        _report_attrib(ctx, engine, m, rebalance=args.rebalance_drifted)
    elif args.rebalance_drifted:
        raise SystemExit("--rebalance-drifted needs the balance auditor's "
                         "traced attribution: pass --trace-out too")
    if args.metrics_json:
        m.to_json(args.metrics_json)
        print(f"[engine] metrics written to {args.metrics_json}")
        if args.metrics_interval_ticks:
            prom_path = args.metrics_json + ".prom"
            with open(prom_path, "w") as f:
                f.write(engine.registry.to_prometheus_text())
            print(f"[registry] {len(engine.registry.snapshots)} snapshots, "
                  f"exposition written to {prom_path}")
    # steady state needs no guard here: a warmed engine's run() itself
    # raises PlanCacheColdError on any lazy solve or unseen signature
    return m


def main(argv: list[str] | None = None):
    """CLI entry point; ``argv`` defaults to ``sys.argv[1:]``. Returns the
    engine's :class:`repro.serve.EngineMetrics` with ``--engine``, else
    None."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-4b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--no-warmup", action="store_true",
                    help="skip the plan pre-solve (plans solve lazily)")
    add_context_args(ap)
    add_serve_engine_args(ap)
    args = ap.parse_args(argv)

    use_compile_cache()
    ctx = context_from_args(args)
    with use_context(ctx):
        cfg = C.get_config(args.arch)
        if args.smoke:
            cfg = C.smoke(cfg)
        mesh = (make_production_mesh() if args.production_mesh
                else make_local_mesh())

        # int8: quantized once at load — decode streams int8 weights, the
        # dequantize rides the GEMM epilogue (§5.1 traffic win)
        params, param_axes = init_params(
            cfg, mesh, quantize=ctx.quant_mode == "int8")

        if args.engine:
            return _run_engine(args, ctx, cfg, mesh, params, param_axes)

        rng = np.random.default_rng(0)
        prompts = jnp.asarray(
            rng.integers(0, cfg.vocab_size, size=(args.batch, args.prompt_len)),
            jnp.int32)
        extras = {}
        if cfg.family == "encdec":
            extras["frames"] = jnp.asarray(rng.standard_normal(
                (args.batch, cfg.encoder_len, cfg.d_model)), jnp.float32)
        if cfg.family == "vlm":
            extras["image_embeds"] = jnp.asarray(rng.standard_normal(
                (args.batch, cfg.n_image_tokens, cfg.d_model)), jnp.float32)

        max_len = args.prompt_len + args.gen + 1
        if not args.no_warmup:
            t0 = time.perf_counter()
            warm = plan_model(
                cfg, batch=args.batch, prompt_len=args.prompt_len,
                max_len=max_len, params=params, extras=extras)
            _report_warmup(ctx, warm, time.perf_counter() - t0, "warm-up")
            if args.measure_plans:
                _measure_plans(ctx, args)
        warm_stats = ctx.plan_cache.stats.snapshot()

        t0 = time.perf_counter()
        out = serve_batch(cfg, mesh, params, prompts,
                          gen_len=args.gen, max_len=max_len,
                          extras=extras, param_axes=param_axes,
                          eos_id=args.eos_id)
        dt = time.perf_counter() - t0
        toks = args.batch * args.gen
        qtag = f" quant={ctx.quant_mode}" if ctx.quant_mode else ""
        print(f"[serve] arch={cfg.name}{qtag} hw={ctx.hw.name} "
              f"backend={ctx.matmul_backend} generated {toks} tokens in "
              f"{dt:.2f}s ({toks/dt:.1f} tok/s incl. compile)")
        print("first row:", np.asarray(out[0])[:12], "...")

        st = ctx.plan_cache.stats
        lazy = st.lazy_solves - warm_stats.lazy_solves
        missed = st.misses - warm_stats.misses
        print(f"[plan-cache] serving: hits={st.hits - warm_stats.hits} "
              f"misses={missed} lazy_solves={lazy} ({st})")
        if not args.no_warmup and (lazy or missed):
            raise SystemExit(
                f"plan warm-up incomplete: {missed} unseen signatures, "
                f"{lazy} lazy solves during serving")


if __name__ == "__main__":
    main()
