"""End-to-end training driver with the production fault-tolerance loop.

  restore-or-init -> [step -> straggler check -> periodic async checkpoint]*
  on 'checkpoint_and_rebalance': synchronous snapshot + (simulated) re-mesh
  via ft.elastic.resume_on_mesh.

Runs unchanged on CPU (smoke configs, local mesh) and on TPU slices (full
configs, production mesh; set --matmul-backend pallas to engage the balanced
Pallas kernels).

  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-4b --smoke \
      --steps 50 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs as C
from repro.core.context import use_context
from repro.data.synthetic import batch_for
from repro.ft import checkpoint as ckpt_lib
from repro.ft.elastic import resume_on_mesh
from repro.ft.straggler import StragglerMonitor
from repro.launch.args import (add_context_args, context_from_args,
                               use_compile_cache)
from repro.launch.mesh import make_local_mesh, make_production_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-4b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--log-every", type=int, default=5)
    add_context_args(ap, include_quant=False)
    args = ap.parse_args()

    use_compile_cache()
    with use_context(context_from_args(args)):
        return _run(args)


def _run(args):
    cfg = C.get_config(args.arch)
    if args.smoke:
        cfg = C.smoke(cfg)
    mesh = (make_production_mesh() if args.production_mesh
            else make_local_mesh())
    ckpt_dir = args.ckpt_dir or os.path.join(
        "checkpoints", cfg.name.replace("/", "_"))

    art, state, start = resume_on_mesh(cfg, mesh, ckpt_dir)
    print(f"[train] arch={cfg.name} mesh={dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"start_step={start} params≈{sum(x.size for x in jax.tree.leaves(state['params']))/1e6:.1f}M")

    ckpt = ckpt_lib.AsyncCheckpointer(ckpt_dir)
    monitor = StragglerMonitor()
    losses = []
    with mesh:
        for step in range(start, args.steps):
            b = batch_for(cfg, args.seq, args.batch, step)
            b = {k: jax.device_put(jnp.asarray(v), s) for (k, v), s in zip(
                b.items(), [art.batch_shardings.get(k) for k in b])}
            t0 = time.perf_counter()
            state, metrics = art.step_fn(state, b)
            jax.block_until_ready(metrics["loss"])
            dt = time.perf_counter() - t0
            verdict = monitor.record(step, dt)
            loss = float(metrics["loss"])
            losses.append(loss)
            if step % args.log_every == 0 or step == args.steps - 1:
                print(f"  step {step:5d} loss {loss:8.4f} "
                      f"gnorm {float(metrics['grad_norm']):7.3f} "
                      f"{dt*1e3:7.1f} ms [{verdict}]")
            if verdict == "checkpoint_and_rebalance":
                print(f"  [ft] straggler mitigation at step {step}: "
                      "sync snapshot + re-mesh")
                ckpt.wait()
                ckpt_lib.save(ckpt_dir, state, step + 1)
                art, state, _ = resume_on_mesh(cfg, mesh, ckpt_dir)
            elif (step + 1) % args.ckpt_every == 0:
                ckpt.save(state, step + 1)
        ckpt.wait()
        ckpt_lib.save(ckpt_dir, state, args.steps)
    print(f"[train] done: loss {losses[0]:.4f} -> {losses[-1]:.4f} "
          f"(ckpt at {ckpt_dir})")
    return losses


if __name__ == "__main__":
    main()
