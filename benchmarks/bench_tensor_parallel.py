"""Tensor-parallel paged serving: decode throughput at tp ∈ {1, 2, 4}.

HEROv2 scales its accelerator by instantiating multiple RISC-V clusters
behind one offload interface; the serving analogue is the executor's tp
mesh (serve/executor.py): KV pages and the paged-attention head walk shard
over ``tp`` devices while the scheduler, page tables, and allocator stay
host-side and replicated. This bench drives the same ragged request mix
through the chunked engine at tp=1/2/4 on **forced host-platform CPU
devices** and records decode throughput per level.

Two claims are asserted, not just measured:

* greedy streams at tp=2 and tp=4 are **bit-identical** to tp=1 (sharding
  only concatenates per-head partial outputs — never a cross-shard
  reduction), and
* every level drains the full workload (no scheduling interaction with the
  mesh).

Wall-clock throughput on forced host devices measures *dispatch overhead*,
not speedup — four virtual devices share the same silicon, and the Pallas
kernels run in interpret mode. The numbers exist as the cross-PR perf
trajectory for the tp path, the correctness assertions are the gate.

Usage:  PYTHONPATH=src python benchmarks/bench_tensor_parallel.py [--smoke]

When the current process already initialised jax with fewer than 4 devices
(e.g. under benchmarks/run.py), the bench re-execs itself in a subprocess
with ``XLA_FLAGS=--xla_force_host_platform_device_count=4``. It runs on the
CPU backend only: elsewhere it fails, since this process then holds the
accelerator and the subprocess could never reach it. Appends the
``tensor_parallel`` section to BENCH_serve.json and writes
benchmarks/results/tensor_parallel.json.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

_FORCE = "--xla_force_host_platform_device_count=4"
if "jax" not in sys.modules and _FORCE.split("=")[0] not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " " + _FORCE).strip()

import jax
import numpy as np

from benchmarks.common import rerun_on_host_devices, save_bench, save_json

TP_LEVELS = (1, 2, 4)


def _mix(cfg, rng):
    from repro.serve.engine import Request
    reqs = []
    for i, (L, new) in enumerate([(4, 20), (4, 20), (24, 12), (9, 6),
                                  (6, 2), (6, 2), (14, 8), (3, 16)]):
        reqs.append((max(0, i - 2),
                     Request(seq_id=i,
                             prompt=rng.integers(0, cfg.vocab, L)
                             .astype(np.int32), max_new=new)))
    return reqs


def _drive(eng, schedule, max_iters=5000):
    pending = sorted(schedule, key=lambda t: t[0])
    done, it = [], 0
    while True:
        while pending and pending[0][0] <= it:
            assert eng.submit(pending[0][1])
            pending.pop(0)
        if not pending and eng.idle:
            return done
        done.extend(eng.step())
        it += 1
        if it > max_iters:
            raise RuntimeError("tp bench workload did not drain")


def run(smoke: bool = True, arch: str = "qwen2-0.5b", token_budget: int = 14,
        page_tokens: int = 8, n_slots: int = 4):
    if rerun_on_host_devices(__file__, max(TP_LEVELS), smoke, arch):
        return None
    from repro import configs
    from repro.models import blocks, transformer
    from repro.serve.cache import CacheConfig
    from repro.serve.engine import Engine, EngineConfig

    # kv heads must divide every tp level: run the qwen2 smoke family at
    # n_kv=4 (MHA at its 4 query heads) so tp=4 gives one kv head per shard
    cfg = configs.get_smoke_config(arch, n_kv=4)
    params_t = transformer.init_model(jax.random.PRNGKey(0), cfg)
    params, _ = blocks.split_params(params_t)
    max_seq, n_pages = 96, 24
    reps = 1 if smoke else 3

    levels, streams = {}, {}
    for tp in TP_LEVELS:
        econf = EngineConfig(
            n_slots=n_slots, max_seq=max_seq, chunked=True,
            token_budget=token_budget, tp=tp,
            cache=CacheConfig(page_tokens=page_tokens, n_pages=n_pages))
        # warmup engine shares the jit cache with the measured ones
        _drive(Engine(cfg, params, config=econf),
               _mix(cfg, np.random.default_rng(0)))
        walls = []
        for _ in range(reps):
            eng = Engine(cfg, params, config=econf)
            t0 = time.perf_counter()
            done = _drive(eng, _mix(cfg, np.random.default_rng(0)))
            walls.append(time.perf_counter() - t0)
        s = eng.stats_summary()
        streams[tp] = {r.seq_id: list(r.tokens_out) for r in done}
        assert len(streams[tp]) == 8, "every request must finish"
        wall = float(np.median(walls))
        levels[f"tp{tp}"] = {
            "devices": tp,
            "wall_s": wall,
            "tok_per_s": s["decode_tokens"] / wall,
            "decode_steps": s["decode_steps"],
            "decode_tokens": s["decode_tokens"],
        }
    for tp in TP_LEVELS[1:]:
        assert streams[tp] == streams[1], \
            f"tp={tp} greedy streams are not bit-identical to tp=1"

    payload = {
        "arch": arch, "n_kv": cfg.n_kv, "page_tokens": page_tokens,
        "n_pages": n_pages, "n_slots": n_slots, "token_budget": token_budget,
        "requests": 8, "identical_streams": 1, **levels,
    }
    save_json("tensor_parallel", payload)
    path = save_bench("serve", payload, section="tensor_parallel")
    for tp in TP_LEVELS:
        m = levels[f"tp{tp}"]
        print(f"tensor_parallel_tp{tp},{m['wall_s'] * 1e6:.1f},"
              f"tok_per_s={m['tok_per_s']:.1f}")
    print(f"# tensor parallel: streams bit-identical at tp=2/4 "
          f"(forced host devices); wrote {path}")
    return payload


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--smoke", action="store_true",
                    help="one rep per tp level, interpret-mode kernels")
    ap.add_argument("--token-budget", type=int, default=14)
    args = ap.parse_args()
    run(smoke=args.smoke, arch=args.arch, token_budget=args.token_budget)


if __name__ == "__main__":
    main()
