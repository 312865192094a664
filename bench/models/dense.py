"""The dense decoder family: the benchmark's seeded weights, and how they
are handed to the serving engine under test.

The weights belong to the benchmark: one jitted call makes every leaf on
the device, in bf16, from the run's seed. The engine gets them as its own
parameter tree (the same buffers, no copy); the float32 reference
(``bench/reference/dense.py``) reads the same dict and nothing the engine
made.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def dims(cfg: dict) -> dict:
    """The sizes the benchmark uses, from a configuration file."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {
        "d": d,
        "f": cfg["intermediate_size"],
        "heads": h,
        "kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["assumed"].get("head_dim", d // h),
        "layers": cfg["num_hidden_layers"],
        "vocab": cfg["vocab_size"],
        "qkv_bias": bool(cfg["assumed"].get("attention_bias",
                                            cfg.get("bias", False))),
        "eps": float(cfg["rms_norm_eps"]),
        "rope_theta": float(cfg["rope_theta"]),
    }


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (the low 32 bits, then the rest
    folded in)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def init_weights(cfg: dict, seed: int) -> dict:
    """Seeded bf16 weights, made on the device by one jit.

    Projections are N(0, 1/fan_in); the embedding is N(0, 1); norm gains
    are 1 + N(0, 0.1) and q/k/v biases N(0, 0.5), so that a path that
    skipped either would move the logits. Stacked layer leaves lead with
    the layer axis, as the engine scans them.
    """
    m = dims(cfg)
    d, f, L, V = m["d"], m["f"], m["layers"], m["vocab"]
    qd, kvd = m["heads"] * m["head_dim"], m["kv_heads"] * m["head_dim"]
    shapes = {
        "embed": ((V, d), 1.0),
        "unembed": ((d, V), d ** -0.5),
        "final_norm": ((d,), None),
        "ln1": ((L, d), None),
        "ln2": ((L, d), None),
        "wq": ((L, d, qd), d ** -0.5),
        "wk": ((L, d, kvd), d ** -0.5),
        "wv": ((L, d, kvd), d ** -0.5),
        "wo": ((L, qd, d), qd ** -0.5),
        "w_gate": ((L, d, f), d ** -0.5),
        "w_up": ((L, d, f), d ** -0.5),
        "w_down": ((L, f, d), f ** -0.5),
    }
    if m["qkv_bias"]:
        shapes.update(bq=((L, qd), 0.5), bk=((L, kvd), 0.5),
                      bv=((L, kvd), 0.5))

    def make(key):
        keys = jax.random.split(key, len(shapes))
        out = {}
        for k, (name, (shape, scale)) in zip(keys, sorted(shapes.items())):
            z = jax.random.normal(k, shape, jnp.float32)
            w = 1.0 + 0.1 * z if scale is None else scale * z
            out[name] = w.astype(jnp.bfloat16)
        return out

    return jax.jit(make)(seed_key(seed))


def model_config(cfg: dict):
    """The engine's ``ModelConfig`` for this configuration."""
    from repro.configs.base import ModelConfig

    m = dims(cfg)
    if m["vocab"] % 128:
        raise ValueError("the engine pads the vocabulary to a multiple of "
                         "128; give the benchmark's weights that width")
    return ModelConfig(
        name=cfg["name"], family="dense", n_layers=m["layers"],
        d_model=m["d"], n_heads=m["heads"], n_kv_heads=m["kv_heads"],
        head_dim=m["head_dim"], d_ff=m["f"], vocab_size=m["vocab"],
        qkv_bias=m["qkv_bias"], rope_theta=m["rope_theta"],
        norm_eps=m["eps"], activation="silu", gated_mlp=True,
        param_dtype=cfg["torch_dtype"], activation_dtype=cfg["torch_dtype"])


def program_params(w: dict, model_cfg):
    """The engine's parameter tree over the benchmark's buffers, and its
    logical sharding axes."""
    from repro import models
    from repro.layers.attention import AttnParams
    from repro.layers.mlp import MlpParams

    params = {
        "embed": w["embed"],
        "unembed": w["unembed"],
        "final_norm": {"g": w["final_norm"]},
        "layers": {
            "ln1": {"g": w["ln1"]},
            "ln2": {"g": w["ln2"]},
            "attn": AttnParams(wq=w["wq"], wk=w["wk"], wv=w["wv"],
                               wo=w["wo"], bq=w.get("bq"), bk=w.get("bk"),
                               bv=w.get("bv")),
            "mlp": MlpParams(w_in=w["w_up"], w_gate=w["w_gate"],
                             w_out=w["w_down"], b_in=None, b_out=None),
        },
    }
    return params, models.axes(model_cfg)


def program(cfg: dict, weights: dict, *, quantize: str | None = None):
    """The engine's model config, parameter tree and its axes over the
    benchmark's weights; ``quantize="int8"`` is the program's own W8A8
    serving path (quant/prequant.py), the control for a configuration
    stated in bf16. Once the caller drops ``weights``, the int8 tree holds
    no bf16 copy of the projections."""
    model_cfg = model_config(cfg)
    params, axes = program_params(weights, model_cfg)
    if quantize == "int8":
        from repro.quant import prequant

        params = jax.jit(prequant.quantize_params)(params)
        axes = prequant.quantize_axes(axes)
    elif quantize is not None:
        raise ValueError(f"no {quantize!r} path in the program")
    return model_cfg, params, axes


class Served:
    """The engine under test, built over ``program(...)``, and the
    execution context it runs in (``with served:``)."""

    def __init__(self, cfg: dict, mix: dict, program, *,
                 device_kind: str | None, plan_path, generators,
                 quantize: str | None = None):
        from repro.core import hwregistry
        from repro.core.context import GemmContext
        from repro.core.plancache import PlanCache
        from repro.launch.mesh import make_local_mesh
        from repro.serve import ServeEngine
        from repro.serve.request import Request

        serving = cfg["serving"]
        self.model_cfg, params, axes = program
        max_prompt, max_output = generators.max_lengths(mix)
        hw = (hwregistry.hw_for_device_kind(device_kind)
              if device_kind is not None else hwregistry.get_hw("tpu_v5e"))
        self.plans = PlanCache(path=str(plan_path))
        self.plans.load()
        self.ctx = GemmContext(hw=hw, matmul_backend=serving["matmul_backend"],
                               quant_mode=quantize, plan_cache=self.plans)
        self.request_cls = Request
        self._ctx_cm = None
        with self:
            self.engine = ServeEngine(
                self.model_cfg, make_local_mesh(), params,
                num_slots=mix["clients"], max_len=max_prompt + max_output,
                prompt_pad=serving["prefill_chunk"], param_axes=axes,
                kv_block_size=serving["kv_block_size"],
                num_kv_blocks=serving.get("num_kv_blocks"),
                kv_quantize=serving["kv_dtype"],
                prefill_chunk=serving["prefill_chunk"],
                sched_policy=serving["sched_policy"],
                prefix_cache=serving["prefix_cache"])

    def __enter__(self):
        from repro.core.context import use_context

        self._ctx_cm = use_context(self.ctx)
        self._ctx_cm.__enter__()
        return self

    def __exit__(self, *exc):
        cm, self._ctx_cm = self._ctx_cm, None
        return cm.__exit__(*exc)

    def plan_warmup(self) -> dict:
        """Solve (or load) every GEMM plan the engine's programs use, and
        keep them for the next run."""
        out = self.engine.plan_warmup()
        self.plans.save()
        return out

    def lazy_solves(self) -> int:
        return self.plans.stats.lazy_solves + self.plans.stats.misses

    def warm_programs(self) -> None:
        """Run each prefill bucket and the decode step once, on lane 0
        writing only the null block, so every program the window uses is
        compiled; then give the engine fresh state."""
        import numpy as np

        eng = self.engine
        art, n = eng.art, eng.num_slots
        blocks = jnp.zeros((art.max_blocks,), jnp.int32)
        zero = jnp.int32(0)
        for bucket in eng.chunk_buckets:
            _, eng.state = art.prefill_fn(
                eng.params, eng.state, jnp.zeros((1, bucket), jnp.int32),
                zero, zero, jnp.int32(bucket), blocks)
        logits, eng.state = art.decode_fn(
            eng.params, eng.state, jnp.zeros((n, 1), jnp.int32),
            jnp.asarray(np.zeros((n,), np.int32)))
        jax.block_until_ready(logits)
        eng.reset()

    def release(self) -> None:
        """Free the engine's device state (the KV pool) and programs."""
        self.engine.state = None
        self.engine = None
