"""Continuous-batching serving engine: fixed slot lanes, streaming requests.

The step loop decouples request lifecycle (host-side scheduler) from the
compiled step functions (device-side, fixed shapes):

* every tick runs ONE masked decode step for all ``num_slots`` lanes —
  vacant lanes are fed the pad token and excluded from sampling, and their
  cache position does not advance;
* admissions interleave between ticks. On the contiguous layout a
  single-request prefill (prompt right-padded to one fixed ``prompt_pad``)
  writes the slot's cache region in one shot. On the **paged** layout
  (``kv_block_size``) admission only binds a lane and allocates KV blocks;
  the prompt then prefills chunk by chunk — at most one bucket-padded
  chunk per tick — interleaved with decode, so a long admit never stalls
  the running batch;
* eviction on stop-id / max-new-tokens frees the lane (and, paged, returns
  the request's blocks to the pool) for the queue head;
* admission order is a pluggable policy (``sched_policy``: fifo /
  priority / edf / prefix — serve/policy.py); preemptive policies evict
  and requeue strictly lower-ranked decodes under lane/block pressure,
  and resumed requests re-prefill only what the prefix trie no longer
  holds. A per-tick **prefill budget** (``ttft_target_ms``) adapts how
  many chunked-prefill calls run per tick from observed TTFT — all of it
  host-side policy code over the same warm chunk-bucket signatures;
* with the **prefix cache** on (``prefix_cache=True``, paged only),
  admission first maps any cached prompt prefix's blocks straight into the
  slot's block table — chunked prefill then starts at the first uncached
  token (zero prefill GEMMs for the shared header), and retirement indexes
  the request's full-block prefixes for the next arrival. Decode output is
  token-for-token identical to cache-off (serve/prefixcache.py);
* with **KV quantization** on (``kv_quantize="int8"``, paged only) the
  pool stores int8 blocks plus per-block/per-kv-head f32 scales
  (layers/attention.py) — same step-loop shapes, roughly half the pool
  bytes, so an equal-byte budget holds ~2x the blocks. The block pool
  carries ``bytes_per_block`` so OOM decisions and metrics account in
  bytes, and ``metrics.kv_cache`` reports the bytes ratio + scale stats.

Because slot count, chunk buckets, max_len and model dims are all fixed at
engine build, every tick issues the identical GEMM signature set. The
engine warms the plan cache by abstractly tracing its own step functions
(``plan_warmup``), then *asserts* the serving loop performs zero lazy plan
solves (``PlanCache.expect_steady_state``) — the steady state the
GemmContext/PlanCache subsystem exists to provide.

Sampling is host-side and per-request: greedy at ``temperature=0``
(default), else temperature + top-p nucleus sampling from a per-request
seeded stream — the device step functions never see randomness, so the
fixed-signature property is untouched.
"""
from __future__ import annotations

import time
from typing import Iterable

import jax
import jax.numpy as jnp
import numpy as np

from repro import models
from repro.configs.base import ModelConfig
from repro.core.context import current_context
from repro.layers import attention as attn
from repro.obs.attrib import AttributionLedger
from repro.obs.registry import Registry, prom_name
from repro.obs.trace import PROCESS_TRACER
from repro.quant.kvcache import KVCacheDtype, kv_block_bytes
from repro.serve.blockpool import BlockPool
from repro.serve.metrics import EngineMetrics
from repro.serve.policy import BudgetController, SchedPolicy, get_policy
from repro.serve.prefixcache import PrefixCache
from repro.serve.request import Request, RequestState
from repro.serve.scheduler import SlotScheduler
from repro.serve import spec as spec_lib
from repro.train.servestep import (
    make_engine_step, make_paged_engine_step, make_spec_step)


def chunk_buckets(chunk: int) -> tuple[int, ...]:
    """Bucket lengths chunked prefill pads to: {chunk/4, chunk/2, chunk}.

    Full chunks use the largest bucket; a prompt's tail rounds up to the
    smallest covering bucket — so prefill issues at most 3 distinct GEMM
    signatures instead of one per prompt length.
    """
    return tuple(sorted({max(1, chunk // 4), max(1, chunk // 2), chunk}))


class ServeEngine:
    def __init__(
        self,
        cfg: ModelConfig,
        mesh,
        params,
        *,
        num_slots: int,
        max_len: int,
        prompt_pad: int,
        pad_id: int = 0,
        param_axes=None,
        kv_block_size: int | None = None,
        num_kv_blocks: int | None = None,
        kv_quantize: str | KVCacheDtype | None = None,
        prefill_chunk: int | None = None,
        prefix_cache: bool = False,
        prefix_cache_blocks: int | None = None,
        temperature: float = 0.0,
        top_p: float = 1.0,
        seed: int = 0,
        sched_policy: str | SchedPolicy | None = None,
        ttft_target_ms: float | None = None,
        max_prefill_chunks: int = 4,
        clock=None,
        spec_draft_cfg: ModelConfig | None = None,
        spec_draft_params=None,
        spec_k: int = 4,
        spec_draft_param_axes=None,
        spec_draft_quant: str | None = None,
        tracer=None,
        registry: Registry | None = None,
        metrics_interval_ticks: int | None = None,
        attrib_tol: float = 0.25,
    ):
        self.cfg = cfg
        self.mesh = mesh
        self.params = params
        self.num_slots = num_slots
        self.max_len = max_len
        self.prompt_pad = prompt_pad
        self.pad_id = pad_id
        self.temperature = temperature
        self.top_p = top_p
        self.seed = seed
        self.sched_policy = get_policy(sched_policy)
        self.ttft_target_ms = ttft_target_ms
        self.max_prefill_chunks = max_prefill_chunks
        # injectable clock (policy.SimClock in tests/benchmarks): TTFT,
        # deadlines and burst arrivals become deterministic functions of
        # the event sequence
        self._now = clock if clock is not None else time.perf_counter
        # observability (repro.obs): the tracer keeps its own host clock
        # and never reads self._now — under SimClock a clock *read*
        # advances time, so tracing on/off must not change the engine's
        # read sequence. A caller's tracer records the whole run and
        # feeds the metrics' timing and attribution sections; without
        # one the engine writes to the process-wide tracer, which records
        # only while a profiler session runs and is never reset here.
        self.tracer = tracer if tracer is not None else PROCESS_TRACER
        self._traced_run = tracer is not None and tracer.enabled
        self.registry = registry if registry is not None else Registry()
        self.metrics_interval_ticks = metrics_interval_ticks
        # balance auditor: phase→signature profiles are captured during
        # plan_warmup; runtime dispatch counting is a plain int add (no
        # clock reads), and the join against traced phase seconds happens
        # once at end of run — only when a real tracer is attached
        self.attrib = AttributionLedger(tol=attrib_tol)
        self.paged = bool(kv_block_size)
        self.kv_dtype = KVCacheDtype.parse(kv_quantize)
        if self.kv_dtype.quantized and not self.paged:
            raise ValueError(
                "KV quantization stores per-block scales alongside the "
                "block pool — it needs the paged engine (kv_block_size)")
        self.spec = spec_draft_cfg is not None
        self.spec_k = int(spec_k) if self.spec else 0
        self.spec_draft_cfg = spec_draft_cfg
        self.spec_draft_params = spec_draft_params
        self.spec_draft_quant = spec_draft_quant
        if self.spec:
            if spec_draft_params is None:
                raise ValueError(
                    "speculative decoding needs draft params "
                    "(spec_draft_params) alongside spec_draft_cfg")
            if not self.paged:
                raise ValueError(
                    "speculative decoding rewinds per-slot lengths over "
                    "budget-allocated blocks — it needs the paged engine "
                    "(kv_block_size)")
            if temperature and temperature > 0.0:
                raise ValueError(
                    f"speculative decoding verifies greedily; engine "
                    f"temperature={temperature} is incompatible (submit-"
                    f"time validation rejects per-request sampling too)")
            if self.sched_policy.preemptive:
                raise ValueError(
                    f"policy {self.sched_policy.name!r} preempts mid-"
                    f"decode; speculative lanes don't support preemption "
                    f"yet — use a non-preemptive policy (fifo/prefix)")
            if spec_draft_cfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft vocab_size={spec_draft_cfg.vocab_size} != "
                    f"target vocab_size={cfg.vocab_size} — proposals must "
                    f"share the token space")
        if prefix_cache and not self.paged:
            raise ValueError(
                "the prefix cache shares KV at block granularity — it "
                "needs the paged engine (kv_block_size)")
        if self.sched_policy.preemptive and not self.paged:
            raise ValueError(
                f"policy {self.sched_policy.name!r} preempts via block "
                f"refcounts — it needs the paged engine (kv_block_size)")
        self.prefix_cache_enabled = bool(prefix_cache)
        self.prefix_cache_blocks = prefix_cache_blocks
        param_shapes = (None if param_axes is None
                        else jax.eval_shape(lambda: params))
        if self.paged:
            self.kv_block_size = int(kv_block_size)
            # default pool: full contiguous capacity (+ the null block) —
            # shrink num_kv_blocks to make footprint track admitted tokens
            full = -(-num_slots * max_len // self.kv_block_size) + 1
            self.num_kv_blocks = int(num_kv_blocks or full)
            self.prefill_chunk = int(prefill_chunk or prompt_pad)
            self.chunk_buckets = chunk_buckets(self.prefill_chunk)
            self.art = make_paged_engine_step(
                cfg, mesh, num_slots=num_slots, max_len=max_len,
                kv_block_size=self.kv_block_size,
                num_kv_blocks=self.num_kv_blocks,
                chunk_buckets=self.chunk_buckets,
                param_shapes=param_shapes, param_axes=param_axes,
                kv_dtype=self.kv_dtype)
            self._init_fn = jax.jit(
                lambda: models.init_decode_state(
                    cfg, num_slots, max_len, per_slot=True,
                    kv_block_size=self.kv_block_size,
                    num_kv_blocks=self.num_kv_blocks,
                    kv_dtype=self.kv_dtype),
                out_shardings=self.art.state_shardings)
        else:
            self.kv_block_size = None
            self.num_kv_blocks = None
            self.prefill_chunk = None
            self.chunk_buckets = None
            self.art = make_engine_step(
                cfg, mesh, num_slots=num_slots, max_len=max_len,
                prompt_pad=prompt_pad,
                param_shapes=param_shapes, param_axes=param_axes)
            self._init_fn = jax.jit(
                lambda: models.init_decode_state(cfg, num_slots, max_len,
                                                 per_slot=True),
                out_shardings=self.art.state_shardings)
        if self.spec:
            draft_shapes = (None if spec_draft_param_axes is None
                            else jax.eval_shape(lambda: spec_draft_params))
            self.spec_art = make_spec_step(
                cfg, spec_draft_cfg, mesh, num_slots=num_slots,
                max_len=max_len, prompt_pad=prompt_pad, spec_k=self.spec_k,
                target_art=self.art, draft_param_shapes=draft_shapes,
                draft_param_axes=spec_draft_param_axes)
            self._draft_init_fn = jax.jit(
                lambda: models.init_decode_state(
                    spec_draft_cfg, num_slots, max_len, per_slot=True),
                out_shardings=self.spec_art.draft_state_shardings)
        else:
            self.spec_art = None
            self._draft_init_fn = None
        self._warmed = False
        self.reset()

    def _rel_now(self) -> float:
        """Seconds on the engine clock since the last reset — the time
        base every stamp, deadline and ``arrival_s`` lives in."""
        return self._now() - self._t0

    # ------------------------------------------------------------ state
    def reset(self) -> None:
        """Fresh scheduler/state/metrics; compiled functions are kept (the
        benchmark times a second run to measure steady state, not XLA)."""
        ctx = current_context()
        if self._traced_run:
            self.tracer.reset()
        self.attrib.reset_run()
        # the engine's time base: every stamp (submit, admission, TTFT,
        # deadlines, trace arrival_s) is seconds since this reset, so
        # absolute deadline_s/arrival_s values in a trace mean what they
        # say regardless of the clock's epoch
        self._t0 = self._now()
        with self.mesh:
            self.state = self._init_fn()
        pool = (BlockPool(self.num_kv_blocks, self.kv_block_size,
                          bytes_per_block=kv_block_bytes(
                              self.kv_block_size, self.cfg.n_kv_heads,
                              self.cfg.head_dim, self.kv_dtype,
                              n_layers=self.cfg.n_layers))
                if self.paged else None)
        cache = (PrefixCache(pool, max_cached_blocks=self.prefix_cache_blocks,
                             tracer=self.tracer)
                 if self.prefix_cache_enabled else None)
        self.sched = SlotScheduler(self.num_slots, max_len=self.max_len,
                                   pool=pool, prefix_cache=cache,
                                   policy=self.sched_policy,
                                   spec=self.spec, tracer=self.tracer)
        if self.spec:
            with self.mesh:
                self.draft_state = self._draft_init_fn()
            # per-lane draft bookkeeping: lag marks lanes whose draft KV
            # is one token behind the committed stream (a fully-accepted
            # round's last proposal was never fed back); catch_tok is
            # that token, re-ingested by the next propose call
            self._lag = np.zeros((self.num_slots,), bool)
            self._catch_tok = np.full((self.num_slots,), self.pad_id,
                                      np.int64)
            self.spec_stats = spec_lib.SpecStats(spec_k=self.spec_k)
        self.budget = BudgetController(
            None if self.ttft_target_ms is None
            else self.ttft_target_ms / 1e3,
            max_chunks=self.max_prefill_chunks)
        self._next_tok = np.full((self.num_slots,), self.pad_id, np.int64)
        # the device's per-lane KV lengths (positions written), set where
        # the engine's step programs set them
        self._kv_len = [0] * self.num_slots
        # which path the paged decode program's attention takes under this
        # context (layers/attention.decode_attention_backend)
        self._attn_kernel = self.paged and attn.decode_attention_backend(
            self.kv_dtype.quantized) is not None
        engine_info = {
            "arch": self.cfg.name,
            "num_slots": self.num_slots,
            "max_len": self.max_len,
            "prompt_pad": self.prompt_pad,
            "hw": ctx.hw.name,
            "backend": ctx.matmul_backend,
            "quant": ctx.quant_mode,
            "paged": self.paged,
            "temperature": self.temperature,
            "top_p": self.top_p,
            "sched_policy": self.sched_policy.name,
            "ttft_target_ms": self.ttft_target_ms,
        }
        if self.paged:
            engine_info.update(
                kv_block_size=self.kv_block_size,
                num_kv_blocks=self.num_kv_blocks,
                kv_dtype=self.kv_dtype.value,
                prefill_chunk=self.prefill_chunk,
                chunk_buckets=list(self.chunk_buckets),
                prefix_cache=self.prefix_cache_enabled,
                prefix_cache_blocks=self.prefix_cache_blocks)
        engine_info["spec"] = self.spec
        if self.spec:
            engine_info.update(
                spec_k=self.spec_k,
                spec_draft_arch=self.spec_draft_cfg.name,
                spec_draft_quant=self.spec_draft_quant)
        self.metrics = EngineMetrics(engine=engine_info)

    # ------------------------------------------------------------ warm-up
    def plan_warmup(self) -> dict[str, int]:
        """Pre-solve every GEMM signature the engine's compiled step
        functions issue by abstractly tracing them — the engine-shaped
        analogue of ``core.gemm.plan_model``. The paged engine traces one
        chunked-prefill signature per bucket (<= 3) plus the decode tick.
        Marks the engine warm: subsequent ``run`` calls assert steady state.
        """
        cache = current_context().plan_cache
        before = cache.stats.snapshot()
        scalar = jax.ShapeDtypeStruct((), jnp.int32)
        toks = jax.ShapeDtypeStruct((self.num_slots, 1), jnp.int32)
        active = jax.ShapeDtypeStruct((self.num_slots,), jnp.int32)
        # each abstract trace runs under an attribution capture: the
        # ledger records which GEMM signatures one execution of that phase
        # function consults (and how often) — the phase→signature profile
        # the balance auditor joins against traced phase seconds
        with cache.warmup():
            if self.paged:
                blocks = jax.ShapeDtypeStruct((self.art.max_blocks,),
                                              jnp.int32)
                for bucket in self.chunk_buckets:
                    chunk = jax.ShapeDtypeStruct((1, bucket), jnp.int32)
                    with self.attrib.capture(f"prefill-chunk@{bucket}"):
                        jax.eval_shape(self.art.prefill_raw, self.params,
                                       self.art.state_shapes, chunk, scalar,
                                       scalar, scalar, blocks)
            else:
                prompt = jax.ShapeDtypeStruct((1, self.prompt_pad), jnp.int32)
                with self.attrib.capture("admit"):
                    jax.eval_shape(self.art.admit_raw, self.params,
                                   self.art.state_shapes, prompt, scalar,
                                   scalar)
            with self.attrib.capture("decode"):
                jax.eval_shape(self.art.decode_raw, self.params,
                               self.art.state_shapes, toks, active)
            if self.spec:
                # the draft is a second GemmContext-resolved model sharing
                # the tick loop: its admit + fused propose signatures and
                # the target's (num_slots, k+1) verify pass all join the
                # warm set, so zero lazy solves holds with speculation on
                vtoks = jax.ShapeDtypeStruct(
                    (self.num_slots, self.spec_k + 1), jnp.int32)
                with self.attrib.capture("spec-verify"):
                    jax.eval_shape(self.spec_art.verify_raw, self.params,
                                   self.art.state_shapes, vtoks, active)
                dprompt = jax.ShapeDtypeStruct((1, self.prompt_pad),
                                               jnp.int32)
                # draft admission runs inside the engine's "admit" phase
                # spans (spec requires paged, so the contiguous admit tag
                # is never live at the same time)
                with self.attrib.capture("admit"):
                    jax.eval_shape(self.spec_art.draft_admit_raw,
                                   self.spec_draft_params,
                                   self.spec_art.draft_state_shapes,
                                   dprompt, scalar, scalar)
                with self.attrib.capture("spec-draft"):
                    jax.eval_shape(self.spec_art.propose_raw,
                                   self.spec_draft_params,
                                   self.spec_art.draft_state_shapes,
                                   toks, active, toks, active)
        self._warmed = True
        solved = cache.stats.warm_solves - before.warm_solves
        signatures = len(cache.warm_keys)
        return {"signatures": signatures, "solved": solved,
                "from_cache": signatures - solved}

    # ------------------------------------------------------------ intake
    def submit(self, request: Request, now_s: float | None = None) -> Request:
        if not self.paged and request.prompt_len > self.prompt_pad:
            raise ValueError(
                f"prompt_len={request.prompt_len} exceeds the engine's "
                f"prompt_pad={self.prompt_pad}")
        if self.spec and request.prompt_len > self.prompt_pad:
            raise ValueError(
                f"prompt_len={request.prompt_len} exceeds prompt_pad="
                f"{self.prompt_pad}: the speculative draft model admits "
                f"prompts in one padded shot even on the paged engine")
        return self.sched.submit(
            request, now_s if now_s is not None else self._rel_now())

    # ------------------------------------------------------------ sampling
    def _sample(self, logits_row: np.ndarray, st: RequestState) -> int:
        """Sample one token for ``st`` from its lane's logits (host-side;
        the padded vocab tail is never sampled). Greedy at temperature 0,
        else temperature + top-p nucleus sampling from the request's seeded
        stream."""
        req = st.request
        logits = np.asarray(logits_row[: self.cfg.vocab_size], np.float64)
        temp = (req.temperature if req.temperature is not None
                else self.temperature)
        if temp is None or temp <= 0.0:
            return int(np.argmax(logits))
        top_p = req.top_p if req.top_p is not None else self.top_p
        z = logits / temp
        z -= z.max()
        probs = np.exp(z)
        probs /= probs.sum()
        if top_p < 1.0:
            order = np.argsort(-probs, kind="stable")
            csum = np.cumsum(probs[order])
            # smallest prefix with mass >= top_p (the boundary token stays)
            cut = int(np.searchsorted(csum, top_p)) + 1
            keep = order[:cut]
            mask = np.zeros_like(probs)
            mask[keep] = probs[keep]
            probs = mask / mask.sum()
        if st.rng is None:
            st.rng = np.random.default_rng(
                req.seed if req.seed is not None
                else [self.seed, st.admission_index])
        return int(st.rng.choice(probs.shape[0], p=probs))

    # ------------------------------------------------------------ ticking
    def _finish(self, st: RequestState, reason: str, now: float) -> None:
        self.sched.evict(st.slot, reason, now)
        self.metrics.record_request(st)

    def _budget(self, st: RequestState) -> int:
        """Effective generation budget (``Request.budget`` — shared with
        the scheduler's block-allocation sizing, so the engine can never
        decode past the blocks a paged request owns)."""
        return st.request.budget(self.max_len)

    def _first_token(self, st: RequestState, logits: np.ndarray,
                     now: float) -> None:
        """Record the first token falling out of a completed prefill.

        A resumed prefill also lands here (its final chunk's logits yield
        the next token of the stream) — only a genuinely first token
        feeds the budget controller's TTFT loop."""
        first_ever = st.first_token_s is None
        with self.tracer.phase("sample", slot=st.slot, n=1):
            tok = self._sample(logits, st)
            st.append(tok, now, tick=self.sched.tick)
            if first_ever:
                self.budget.observe_ttft(now - st.request.submitted_s)
                self.tracer.request_event("first-token",
                                          st.request.request_id,
                                          slot=st.slot)
            self._next_tok[st.slot] = tok
            reason = ("length" if len(st.tokens) >= self._budget(st)
                      else st.should_stop())
            if reason:
                self._finish(st, reason, now)

    def _fetch(self, logits) -> np.ndarray:
        """A step's logits on the host: block until the device has them
        (the ``wait`` span), then copy them over (``fetch``)."""
        # start the copy first, so it follows the step the moment the
        # device finishes (as a bare np.asarray would) rather than after
        # the host has woken from the wait
        logits.copy_to_host_async()
        with self.tracer.phase("wait"):
            jax.block_until_ready(logits)
        with self.tracer.phase("fetch"):
            return np.asarray(logits)

    def _admit_all(self, now: float) -> int:
        """Contiguous path: drain the queue into free lanes; each admission
        prefills in one shot and yields the request's first token. Returns
        tokens produced."""
        n = 0
        while True:
            st = self.sched.admit_next(now)
            if st is None:
                return n
            n += 1
            req = st.request
            prompt = np.full((1, self.prompt_pad), self.pad_id, np.int32)
            prompt[0, : req.prompt_len] = req.prompt
            self.attrib.dispatch("admit")
            with self.tracer.phase("admit", slot=st.slot):
                logits, self.state = self.art.admit_fn(
                    self.params, self.state, jnp.asarray(prompt),
                    jnp.asarray(st.slot, jnp.int32),
                    jnp.asarray(req.prompt_len, jnp.int32))
                np_logits = self._fetch(logits)
            self._first_token(st, np_logits, self._rel_now())

    def _bind_admissions(self, now: float) -> int:
        """Paged path: bind queue heads to free lanes + allocate their KV
        blocks. No device work for the target — prompts prefill chunk by
        chunk over the following ticks. With speculation on, each
        admission also one-shot prefills the *draft* model's contiguous
        per-slot cache (the draft is independent of the target's prefix
        cache — it always ingests the full prompt)."""
        n = 0
        while True:
            st = self.sched.admit_next(now)
            if st is None:
                return n
            n += 1
            if self.spec:
                self._draft_admit(st)

    def _draft_admit(self, st: RequestState) -> None:
        """Prefill the draft model for a newly admitted lane. Overwrites
        whatever the slot's previous occupant left in the draft cache and
        resets the lane's lag bookkeeping."""
        req = st.request
        prompt = np.full((1, self.prompt_pad), self.pad_id, np.int32)
        prompt[0, : req.prompt_len] = req.prompt
        self.attrib.dispatch("admit")
        with self.tracer.phase("admit", slot=st.slot, draft=True):
            _, self.draft_state = self.spec_art.draft_admit_fn(
                self.spec_draft_params, self.draft_state, jnp.asarray(prompt),
                jnp.asarray(st.slot, jnp.int32),
                jnp.asarray(req.prompt_len, jnp.int32))
        self._lag[st.slot] = False

    def _chunk_shape(self, remaining: int) -> tuple[int, int]:
        """(bucket_len, true_len) for the next prefill chunk."""
        if remaining >= self.prefill_chunk:
            return self.prefill_chunk, self.prefill_chunk
        for b in self.chunk_buckets:
            if b >= remaining:
                return b, remaining
        return self.prefill_chunk, remaining  # unreachable; buckets cover it

    def _prefill_tick(self, now: float) -> int:
        """Run ONE chunked-prefill step for the oldest mid-prefill lane.
        The final chunk yields the request's first token (or, resumed, the
        next token of the stream). Returns tokens produced (0 or 1)."""
        st = self.sched.prefill_head()
        if st is None:
            return 0
        # the prefill sequence is the admission snapshot: the bare prompt
        # for a fresh request, prompt + generated-so-far for a resume
        seq = (st.prefill_tokens if st.prefill_tokens is not None
               else st.request.prompt)
        start = st.prefill_done
        bucket, n = self._chunk_shape(st._target - start)
        chunk = np.full((1, bucket), self.pad_id, np.int32)
        chunk[0, :n] = seq[start: start + n]
        blocks = np.zeros((self.art.max_blocks,), np.int32)
        blocks[: len(st.blocks)] = st.blocks
        self.attrib.dispatch(f"prefill-chunk@{bucket}")
        with self.tracer.phase("prefill-chunk", slot=st.slot, n=n,
                               bucket=bucket):
            logits, self.state = self.art.prefill_fn(
                self.params, self.state, jnp.asarray(chunk),
                jnp.asarray(st.slot, jnp.int32),
                jnp.asarray(start, jnp.int32),
                jnp.asarray(n, jnp.int32),
                jnp.asarray(blocks))
        self._kv_len[st.slot] = start + n
        self.sched.prefill_advance(st.slot, n)
        self.tracer.request_event("chunk", st.request.request_id,
                                  slot=st.slot, n=n, done=st.prefill_done)
        if st.prefilling:
            return 0
        self._first_token(st, self._fetch(logits), self._rel_now())
        return 1

    def _spec_round(self, mask: np.ndarray) -> int:
        """One speculative decode round for the decode-ready lanes: a
        fused k-step draft propose, one batched (num_slots, k + 1) target
        verify, then host-side greedy acceptance per lane.

        Commit/rollback per lane (serve/spec.py holds the math): every
        committed token is the target's own argmax at its position, so
        output is token-for-token identical to non-speculative decode;
        the rejected tail rewinds both models' per-slot lengths host-side
        (blocks were allocated at budget — the allocator is untouched).
        Two device dispatches commit up to k + 1 tokens per lane."""
        k = self.spec_k
        self.attrib.dispatch("spec-draft")
        self.attrib.dispatch("spec-verify")
        t0 = time.perf_counter()
        start_toks = np.where(mask, self._next_tok, self.pad_id)
        catch_mask = mask & self._lag
        proposals, self.draft_state = self.spec_art.propose_fn(
            self.spec_draft_params, self.draft_state,
            jnp.asarray(self._catch_tok[:, None], jnp.int32),
            jnp.asarray(catch_mask, jnp.int32),
            jnp.asarray(start_toks[:, None], jnp.int32),
            jnp.asarray(mask, jnp.int32))
        np_props = np.asarray(proposals)                   # (num_slots, k)
        t1 = time.perf_counter()
        fed = np.concatenate(
            [start_toks[:, None],
             np.where(mask[:, None], np_props, self.pad_id)],
            axis=1)                                        # (num_slots, k+1)
        logits, self.state = self.spec_art.verify_fn(
            self.params, self.state, jnp.asarray(fed, jnp.int32),
            jnp.asarray(mask, jnp.int32))
        np_logits = np.asarray(logits)             # (num_slots, k+1, Vp)
        t2 = time.perf_counter()
        self.spec_stats.draft_s += t1 - t0
        self.spec_stats.verify_s += t2 - t1
        if self._traced_run:
            # externally-timed spans carrying the SAME perf_counter stamps
            # that feed SpecStats — summed spans reconcile exactly with
            # draft_s/verify_s
            self.tracer.phase_span("spec-draft", t0, t1)
            self.tracer.phase_span("spec-verify", t1, t2)
        now = self._rel_now()
        with self.tracer.phase("sample", n=int(mask.sum())):
            # post-verify device lengths: every active lane advanced by
            # k+1; the acceptance walk decides how far each rolls back
            tgt_len = np.asarray(self.state["kv"].length).copy()
            drf_len = np.asarray(self.draft_state["kv"].length).copy()
            produced = 0
            for slot in np.flatnonzero(mask):
                st = self.sched.slots[slot]
                self.sched.advance_written(slot, k + 1)
                greedy = spec_lib.greedy_rows(np_logits[slot],
                                              self.cfg.vocab_size)
                committed, n_accepted = spec_lib.accept_prefix(
                    np_props[slot], greedy)
                finished = False
                n_committed = 0
                for i, tok in enumerate(committed):
                    st.append(tok, now, tick=self.sched.tick)
                    self._next_tok[slot] = tok
                    n_committed += 1
                    produced += 1
                    reason = ("length" if len(st.tokens) >= self._budget(st)
                              else st.should_stop())
                    if reason:
                        # committed[0..n_accepted-1] are accepted
                        # proposals, committed[n_accepted] the bonus: a
                        # finish at index i used min(i + 1, n_accepted)
                        # proposals
                        n_accepted = min(n_accepted, i + 1)
                        self._finish(st, reason, now)
                        finished = True
                        break
                self.spec_stats.record_round(k, n_accepted, n_committed)
                if finished:
                    continue
                # target KV must cover all committed tokens except the
                # newest
                rewind = spec_lib.verify_rewind(k, n_accepted)
                self.sched.rewind(slot, rewind)
                tgt_len[slot] -= rewind
                committed_len = st.request.prompt_len + len(st.tokens)
                drf_len[slot], lag = spec_lib.draft_sync(
                    committed_len, n_accepted, k)
                self._lag[slot] = lag
                if lag:
                    self._catch_tok[slot] = st.tokens[-2]
            kv = self.state["kv"]
            self.state["kv"] = kv._replace(
                length=jnp.asarray(tgt_len, jnp.int32))
            self._kv_len = tgt_len.tolist()
            dkv = self.draft_state["kv"]
            self.draft_state["kv"] = dkv._replace(
                length=jnp.asarray(drf_len, jnp.int32))
        return produced

    def tick(self) -> int:
        """One engine tick: deadline sweep, admissions (plus, paged, up to
        ``budget.chunks_per_tick()`` prefill chunks), then one masked
        decode step for the decode-ready lanes, then the bookkeeping.
        Returns the number of tokens generated."""
        tr = self.tracer
        tr.set_tick(self.sched.tick)
        with tr.phase("tick"):
            now = self._rel_now()
            with tr.phase("expire"):
                for st in self.sched.expire_deadlines(now):
                    self.metrics.record_request(st)
            if self.paged:
                with tr.phase("bind"):
                    self._bind_admissions(now)
                produced = 0
                # the budget controller's knob: how much of this tick goes
                # to prefill (TTFT) vs decode (throughput). Same warm chunk
                # signatures either way — only the count changes.
                for _ in range(self.budget.chunks_per_tick()):
                    if self.sched.prefill_head() is None:
                        break
                    produced += self._prefill_tick(now)
            else:
                produced = self._admit_all(now)
            mask = self.sched.decode_mask()
            ready = int(mask.sum())
            if ready and self.spec:
                produced += self._spec_round(mask)
            elif ready:
                produced += self._decode(mask, ready)
            with tr.phase("account"):
                self._account(ready, produced)
        return produced

    def _decode(self, mask: np.ndarray, ready: int) -> int:
        """One masked decode step for the ``ready`` lanes of ``mask`` and
        their sampled tokens. Returns the number of tokens generated."""
        tr = self.tracer
        toks = np.where(mask, self._next_tok, self.pad_id)
        self.attrib.dispatch("decode")
        with tr.phase("decode", n=ready):
            logits, self.state = self.art.decode_fn(
                self.params, self.state,
                jnp.asarray(toks[:, None], jnp.int32),
                jnp.asarray(mask, jnp.int32))
            np_logits = self._fetch(logits)
        now = self._rel_now()
        if self.paged:
            self._count_decode_attn(np.flatnonzero(mask).tolist())
        produced = 0
        with tr.phase("sample", n=ready):
            for slot in np.flatnonzero(mask):
                st = self.sched.slots[slot]
                tok = self._sample(np_logits[slot], st)
                st.append(tok, now, tick=self.sched.tick)
                self._next_tok[slot] = tok
                produced += 1
                reason = ("length" if len(st.tokens) >= self._budget(st)
                          else st.should_stop())
                if reason:
                    self._finish(st, reason, now)
        return produced

    def _count_decode_attn(self, lanes: list[int]) -> None:
        """Count the decode step that ran ``lanes`` by its attention path,
        and on the kernel path the blocks it read: ``ceil(length /
        block_size)`` a lane, the length being the positions written
        before the step; then advance the lanes' lengths."""
        m, lens, bs = self.metrics, self._kv_len, self.kv_block_size
        read = 0
        for s in lanes:
            read += (lens[s] + bs - 1) // bs
            lens[s] += 1
        if self._attn_kernel:
            m.decode_attn_kernel_steps += 1
            m.decode_attn_blocks_read += read
            m.decode_attn_blocks_table += len(lanes) * self.art.max_blocks
        else:
            m.decode_attn_fallback_steps += 1

    def _account(self, ready: int, produced: int) -> None:
        """The tick's bookkeeping: block-pool and tick metrics, the tick
        counter, and the registry publish at its cadence."""
        if self.paged:
            self.metrics.record_block_pool(
                self.sched.pool, self.sched.live_tokens(),
                contiguous_tokens=self.num_slots * self.max_len)
        # occupancy counts lanes that *decoded* this tick (token-steps
        # computed), matching the pre-paging engine and the benchmark's
        # computed_token_steps; mid-prefill lanes are visible separately
        # via deferred/prefill metrics
        self.metrics.record_tick(ready, produced, self.sched.pending)
        self.sched.tick += 1
        if (self.metrics_interval_ticks
                and self.sched.tick % self.metrics_interval_ticks == 0):
            self._publish_registry()
            self.registry.snapshot(tick=self.sched.tick)
            if self._traced_run:
                self._emit_counters()

    def _emit_counters(self) -> None:
        """Perfetto counter tracks at the metrics snapshot cadence
        (traced runs only): engine progress, pool pressure and attributed
        device seconds by bound class — the auditor's running view."""
        tr = self.tracer
        m = self.metrics
        tr.counter("engine_progress", {
            "generated_tokens": m.generated_tokens,
            "queued": self.sched.pending,
        })
        if self.paged:
            tr.counter("block_pool", {
                "blocks_in_use": self.sched.pool.blocks_in_use,
                "free_blocks": self.sched.pool.free_blocks,
            })
        tr.counter("attrib_device_s", self.attrib.class_seconds(
            tr.phase_durations(), cache=current_context().plan_cache))

    # ------------------------------------------------------------ driving
    def run(self, requests: Iterable[Request] = ()) -> EngineMetrics:
        """Run ``requests`` to completion and return the filled metrics.

        Arrival-aware: a request is submitted once the engine clock
        reaches its ``arrival_s`` (0.0, the default, submits before the
        first tick — the pre-SLO behavior), so bursty traces replay with
        their gaps. A request whose deadline passed while it waited to
        arrive is terminal-missed without ever queueing. After
        ``plan_warmup`` the whole loop runs under the zero-lazy-solve
        steady-state assertion."""
        pending = sorted(requests, key=lambda r: (r.arrival_s, r.request_id))
        cache = current_context().plan_cache
        before = cache.stats.snapshot()
        t_start = self._rel_now()

        def step():
            now = self._rel_now()
            while pending and pending[0].arrival_s <= now:
                r = pending.pop(0)
                if r.deadline_s is not None and r.deadline_s <= now:
                    self.metrics.record_request(
                        self.sched.drop_expired(r, now))
                else:
                    self.sched.submit(r, now)
            self.tick()

        listener = None
        if self._traced_run:
            # plan-solve events on the timeline: in steady state none
            # fire; a "plan-lazy_solve" instant IS the regression
            tr = self.tracer
            def listener(event, key):  # noqa: E306
                tr.instant(f"plan-{event}", key="|".join(map(str, key)))
            cache.add_listener(listener)
        try:
            if self._warmed:
                with cache.expect_steady_state("serve-engine loop"):
                    while pending or not self.sched.idle:
                        step()
            else:
                while pending or not self.sched.idle:
                    step()
        finally:
            if listener is not None:
                cache.remove_listener(listener)
        self.metrics.wall_s = self._rel_now() - t_start
        self.metrics.record_plan_cache(before, cache.stats.snapshot())
        counters = self.sched.counters()
        self.metrics.admissions = counters["admissions"]
        self.metrics.evictions = counters["evictions"]
        self.metrics.deferred_admissions = counters["deferred_admissions"]
        self.metrics.preemptions = counters["preemptions"]
        self.metrics.resumes = counters["resumes"]
        self.metrics.deadline_missed = counters["deadline_missed"]
        self.metrics.policy = counters["policy"]
        self.metrics.budget = self.budget.stats()
        if self.paged:
            scale_stats = None
            if self.kv_dtype.quantized:
                # dequant-error gauges: a block's worst-case quantization
                # error is scale/2, so absmax-scale statistics over the
                # pool bound the cache's numeric drift without ever
                # materializing a bf16 reference copy. The scale arrays
                # are (L, num_blocks, Hkv) f32 — tiny; host fetch is cheap.
                # Unwritten blocks still hold the init scale (exactly 1.0;
                # a requantized block's absmax/127 never lands there) and
                # would otherwise pin the max at 1.0 — gauge only the
                # recalibrated slots.
                ks = np.asarray(self.state["kv"].k_scale, np.float64)
                vs = np.asarray(self.state["kv"].v_scale, np.float64)
                ks = ks[ks != 1.0] if (ks != 1.0).any() else ks
                vs = vs[vs != 1.0] if (vs != 1.0).any() else vs
                scale_stats = {
                    "scale_k_mean": float(ks.mean()),
                    "scale_k_max": float(ks.max()),
                    "scale_v_mean": float(vs.mean()),
                    "scale_v_max": float(vs.max()),
                }
            self.metrics.record_kv_cache(
                kv_dtype=self.kv_dtype.value,
                bytes_per_block=self.sched.pool.bytes_per_block,
                num_blocks=self.num_kv_blocks,
                bf16_bytes_per_block=kv_block_bytes(
                    self.kv_block_size, self.cfg.n_kv_heads,
                    self.cfg.head_dim, KVCacheDtype.BF16,
                    n_layers=self.cfg.n_layers),
                scale_stats=scale_stats)
        if self.sched.prefix_cache is not None:
            self.metrics.record_prefix_cache(self.sched.prefix_cache)
        if self.spec:
            self.metrics.record_speculation(
                self.spec_stats, draft_arch=self.spec_draft_cfg.name,
                draft_quant=self.spec_draft_quant)
        self.metrics.slo_burn = self.metrics.slo_burn_summary(
            None if self.ttft_target_ms is None
            else self.ttft_target_ms / 1e3)
        if self._traced_run:
            self.metrics.timing = self.tracer.phase_summary()
            for name, durs in self.tracer.phase_durations().items():
                h = self.registry.histogram(
                    f"repro_serve_phase_{prom_name(name)}_seconds",
                    "engine phase span duration (s)")
                for d in durs:
                    h.observe(d)
            # the balance auditor's join: apportion traced device phase
            # seconds across GEMM signatures and compare each cached plan
            # against the model + its solve-time snapshot. Reads
            # cache.entries directly — steady-state counters untouched.
            self.metrics.attribution = self.attrib.summarize(
                self.tracer.phase_durations(), cache=cache)
            self._publish_attrib(self.metrics.attribution)
        self._publish_registry()
        if self.metrics_interval_ticks:
            self.registry.snapshot(tick=self.sched.tick)
        return self.metrics

    def _publish_attrib(self, a: dict) -> None:
        """Mirror the attribution summary into ``repro_attrib_*`` gauges
        plus a measured-vs-modeled ratio histogram."""
        reg = self.registry
        reg.ingest("attrib", {
            "signatures": a["signatures"],
            "drifted": a["drifted_count"],
            "attributed_device_s": a["attributed_device_s"],
            "traced_device_s": a["traced_device_s"],
            "unattributed_device_s": a["unattributed_device_s"],
            "reconciliation_error": a["reconciliation_error"],
            "bound_s": a["bound_s"],
        })
        h = reg.histogram(
            "repro_attrib_measured_vs_modeled",
            "per-signature measured/modeled device seconds ratio",
            buckets=(0.25, 0.5, 0.9, 1.1, 2.0, 8.0, 64.0, 1024.0))
        for row in a["by_device_s"]:
            if row["measured_vs_modeled"] is not None:
                h.observe(row["measured_vs_modeled"])

    def _publish_registry(self) -> None:
        """Mirror the subsystem counters into the registry (gauges named
        ``repro_serve_*`` / ``repro_plan_cache_*`` — docs/observability.md).
        The dicts the metrics JSON is built from are the source of truth;
        the registry is a uniform re-homing, not a second count."""
        reg = self.registry
        m = self.metrics
        reg.ingest("serve", {
            "ticks": m.ticks,
            "generated_tokens": m.generated_tokens,
            "occupancy_sum": m.occupancy_sum,
            "queue_peak": m.queue_peak,
            "decode_attn_kernel_steps": m.decode_attn_kernel_steps,
            "decode_attn_fallback_steps": m.decode_attn_fallback_steps,
            "decode_attn_blocks_read": m.decode_attn_blocks_read,
            "decode_attn_blocks_table": m.decode_attn_blocks_table,
        })
        reg.ingest("serve_sched", self.sched.counters())
        reg.ingest("serve_budget", self.budget.stats())
        if self.metrics.kv_cache:
            reg.ingest("serve_kv", self.metrics.kv_cache)
        if self.spec:
            self.spec_stats.publish(reg)
        pcs = current_context().plan_cache.stats
        reg.ingest("plan_cache", {
            "hits": pcs.hits, "misses": pcs.misses,
            "warm_solves": pcs.warm_solves, "lazy_solves": pcs.lazy_solves,
        })

    @property
    def finished(self) -> list[RequestState]:
        return self.sched.finished
