"""Fault-tolerant training driver — the end-to-end loop a pod would run.

Composition of every substrate: data pipeline (deterministic skip-ahead) →
offload TargetRegion(train_step) → perf counters → async checkpointing →
watchdog restart. Designed for 1000+-node operation, degraded gracefully to
this container:

  * checkpoint/restore: atomic manifests; restore picks the newest valid
    step; the data pipeline resumes from the manifest's step (no data state);
  * node-failure handling: the step loop runs under a watchdog — a step
    exceeding ``--step-timeout`` (straggler/hang) or raising (failure) rolls
    back to the last checkpoint and re-dispatches; ``--inject-failure N``
    simulates a crash at step N to exercise the path (tests/test_driver.py);
  * elastic scaling: on restart the mesh is rebuilt from the CURRENTLY
    visible devices and parameters are re-device_put under the new sharding
    (checkpoint stores host arrays — mesh-shape-agnostic).

Usage (CPU container, reduced config):
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --smoke \
      --steps 50 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import os
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.checkpoint.manager import CheckpointManager
from repro.core.offload import TargetRegion
from repro.data import pipeline as dp
from repro.launch.compile_cache import enable_compile_cache
from repro.models import blocks, transformer
from repro.optim import adamw
from repro.parallel import compression
from repro.parallel import sharding as shlib
from repro.train import step as steps


class SimulatedFailure(RuntimeError):
    pass


def build_state(cfg, mesh, seed: int):
    with shlib.use_mesh(mesh):
        p_sds, p_axes = None, None
        params_t = transformer.init_model(jax.random.PRNGKey(seed), cfg)
        params, axes = blocks.split_params(params_t)
        sh = shlib.tree_shardings(axes, jax.tree_util.tree_map(
            lambda x: tuple(x.shape), params), mesh)
        params = jax.tree_util.tree_map(jax.device_put, params, sh)
        state = steps.TrainState(params=params, opt=adamw.init(params),
                                 step=jnp.zeros((), jnp.int32))
    return state, axes


def train(arch: str, smoke: bool, steps_total: int, ckpt_dir: str,
          batch: int, seq: int, lr: float, ckpt_every: int = 25,
          step_timeout: float = 600.0, inject_failure: Optional[int] = None,
          grad_accum: int = 1, compress: str = "none", seed: int = 0,
          log_every: int = 10):
    cfg = (configs.get_smoke_config(arch) if smoke else configs.get_config(arch))
    n_dev = jax.device_count()
    mesh = shlib.auto_mesh((n_dev, 1), ("data", "model"))
    mgr = CheckpointManager(ckpt_dir)
    dcfg = dp.DataConfig(vocab=cfg.vocab, seq_len=seq, global_batch=batch,
                         seed=seed, mtp=cfg.mtp)

    state, axes = build_state(cfg, mesh, seed)
    start_step = 0
    if mgr.latest_step() is not None:
        restored, extra = mgr.restore(state)   # elastic: re-put under mesh
        state = jax.tree_util.tree_map(jnp.asarray, restored)
        start_step = int(extra.get("data_step", mgr.latest_step()))
        print(f"[train] restored step {start_step} from {ckpt_dir}")

    comp = compression.Compressor(mode=compress) if compress != "none" else None
    ts_fn = steps.make_train_step(
        cfg, adamw.Config(lr=lr, total_steps=max(steps_total, 1)),
        grad_accum=grad_accum, compressor=comp)
    region = TargetRegion(ts_fn, mesh=mesh, name=f"train_{cfg.name}",
                          donate_argnums=(0,))

    step = start_step
    t_start = time.time()
    losses = []
    while step < steps_total:
        try:
            t0 = time.time()
            b = dp.make_batch(dcfg, step)
            with shlib.use_mesh(mesh):
                state, metrics = region(state, {k: jnp.asarray(v)
                                                for k, v in b.items()})
                if inject_failure is not None and step == inject_failure:
                    inject_failure = None  # fire once
                    raise SimulatedFailure(f"injected at step {step}")
                loss = float(metrics["loss"])  # blocks → completes the step
            dt = time.time() - t0
            if dt > step_timeout:
                raise TimeoutError(f"straggler: step took {dt:.1f}s")
            losses.append(loss)
            step += 1
            if step % log_every == 0:
                tok_s = b["tokens"].size / dt
                print(f"[train] step {step} loss {loss:.4f} "
                      f"({dt:.2f}s, {tok_s:,.0f} tok/s)", flush=True)
            if step % ckpt_every == 0:
                mgr.save(step, jax.tree_util.tree_map(np.asarray, state),
                         extra={"data_step": step}, blocking=False)
        except (SimulatedFailure, TimeoutError, jax.errors.JaxRuntimeError) as e:
            print(f"[train] FAILURE at step {step}: {e} — rolling back")
            mgr.wait()
            if mgr.latest_step() is not None:
                restored, extra = mgr.restore(state)
                state = jax.tree_util.tree_map(jnp.asarray, restored)
                step = int(extra.get("data_step", mgr.latest_step()))
            else:
                state, _ = build_state(cfg, mesh, seed)
                step = 0
            print(f"[train] resumed at step {step}")
    mgr.wait()
    mgr.save(step, jax.tree_util.tree_map(np.asarray, state),
             extra={"data_step": step})
    wall = time.time() - t_start
    print(f"[train] done: {step} steps in {wall:.1f}s; "
          f"loss {losses[0]:.3f} → {losses[-1]:.3f}")
    return losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--inject-failure", type=int, default=None)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--compress", default="none",
                    choices=["none", "bf16", "int8"])
    args = ap.parse_args()
    enable_compile_cache()
    train(args.arch, args.smoke, args.steps, args.ckpt_dir, args.batch,
          args.seq, args.lr, ckpt_every=args.ckpt_every,
          inject_failure=args.inject_failure, grad_accum=args.grad_accum,
          compress=args.compress)


if __name__ == "__main__":
    main()
