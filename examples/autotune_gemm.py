"""The paper's optimization methodology, end to end, on one GEMM.

Walks through: (1) the §4.5.1 compute-optimal IP, (2) the §4.5.2 balanced
iteration with its per-step log (the paper's <5-iteration convergence),
(3) the measured-feedback autotuner (wall clock of the kernel as the
measurement oracle: the Pallas kernel on a TPU, interpret mode elsewhere).

  PYTHONPATH=src python examples/autotune_gemm.py
"""
import jax
import jax.numpy as jnp

from repro.core import autotune, balance, perfmodel as pm

M, K, N = 2048, 2048, 2048

print(f"GEMM {M}x{K}x{N} bf16 on modeled {pm.TPU_V5E.name}\n")

# -- paper iteration with the analytical model as the measurement
res = balance.solve_balanced(M, K, N, in_dtype=jnp.bfloat16)
print("§4.5.2 balanced-point iteration (model-measured):")
for i, s in enumerate(res.steps):
    marker = " <-- balanced" if s.plan == res.plan else ""
    print(f"  iter {i}: bk={s.plan.bk:5d} bm={s.plan.bm:5d} bn={s.plan.bn:5d}"
          f"  T_comp={s.t_comp*1e3:6.3f}ms T_mem={s.t_mem*1e3:6.3f}ms"
          f"  {s.tops:6.1f} TOPS{marker}")

# -- beyond-paper: exhaustive sweep
ex = balance.solve_exhaustive(M, K, N, in_dtype=jnp.bfloat16)
print(f"\nexhaustive sweep: {ex.plan.bm}x{ex.plan.bk}x{ex.plan.bn} "
      f"{ex.tops:.1f} TOPS ({ex.tops/res.tops:.2f}x vs paper walk)")

# -- measured-feedback hillclimb, wall-clock oracle: the Pallas kernel on a
# TPU, the kernel in interpret mode elsewhere (a CPU time, not a chip time)
print("\nmeasured hillclimb (wall-clock oracle, small problem):")
measure = autotune.wallclock_measure_fn(
    512, 512, 512, in_dtype=jnp.float32, repeats=2,
    backend="pallas" if jax.default_backend() == "tpu" else "interpret")
tuned = autotune.autotune(
    512, 512, 512, in_dtype=jnp.float32, measure_fn=measure,
    hillclimb_rounds=1)
print(f"  tuned plan {tuned.plan.bm}x{tuned.plan.bk}x{tuned.plan.bn} "
      f"({tuned.seconds*1e6:.0f} us measured, "
      f"{len(tuned.history)} probes)")
