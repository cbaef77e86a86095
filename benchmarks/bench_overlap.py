"""Overlapped engine loop: hide swap DMA, COW copies, and scheduling under
the device step (PR 8).

Drives the tiered + tensor-parallel oversubscribed mix (the bench_trace
workload: tp=2, 4 hot pages, 12 requests needing ~6x the hot tier) twice:

* **sync** — ``overlap=False``: the PR-7 loop. Every host phase (admission,
  swap waits, chunk packing) runs while the device is idle, so the traced
  stall breakdown charges them as real stall (the PR-7 baseline measured
  ~64% ``schedule`` + ~2% ``fetch`` + ~0.4% ``dma`` on this mix).
* **overlap** — ``overlap=True`` (the new default): iteration k's device
  step is dispatched, then iteration k+1's scheduling, swap-in DMAs, and
  COW pre-forks run in its shadow; the loop blocks only at the commit-point
  token fetch. The tracer relabels host spans that ran entirely inside a
  device window to the ``shadowed`` bucket, so the non-compute stall share
  (``schedule + fetch + dma``) measures what the host still serializes.

Asserts:

* **bit-identical streams** — the overlapped loop changes *when* tokens
  commit (one-iteration lag), never *which* tokens a greedy request
  streams;
* **≥2x non-compute stall reduction** — overlap's
  ``schedule + fetch + dma`` percentage is at most half of sync's (the
  tentpole acceptance: the PR-7 baseline's ~66% non-compute share must
  drop to the commit fetch + post-commit packing residue).

Usage:  PYTHONPATH=src python benchmarks/bench_overlap.py [--smoke]

Re-execs itself with ``XLA_FLAGS=--xla_force_host_platform_device_count=4``
when the process initialised jax with fewer than 2 devices, and fails on a
non-CPU backend (same contract as bench_trace). Appends the ``overlap``
section to BENCH_serve.json and writes benchmarks/results/overlap.json.
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

TP = 2
NONCOMPUTE = ("schedule", "fetch", "dma")   # what the host still serializes
MIN_STALL_REDUCTION = 2.0                   # overlap must at least halve it


def _mix(n_req):
    return [(6, 6)] * n_req


def _submit_all(eng, cfg, mix):
    from repro.serve.engine import Request
    rng = np.random.default_rng(0)
    for i, (L, new) in enumerate(mix):
        assert eng.submit(Request(
            seq_id=i, prompt=rng.integers(0, cfg.vocab, L).astype(np.int32),
            max_new=new))


def _engine(cfg, params, *, n_slots, max_seq, page_tokens, hot_pages,
            host_budget_bytes, token_budget, overlap, trace=False):
    from repro.serve.cache import CacheConfig
    from repro.serve.engine import Engine, EngineConfig
    return Engine(cfg, params, config=EngineConfig(
        n_slots=n_slots, max_seq=max_seq, chunked=True,
        token_budget=token_budget, preempt_quantum=1, tp=TP,
        overlap=overlap, trace=trace,
        cache=CacheConfig(paged=True, tiered=True, page_tokens=page_tokens,
                          n_pages=hot_pages,
                          host_budget_bytes=host_budget_bytes)))


def _drain(eng, mix, cfg, max_steps=200000):
    _submit_all(eng, cfg, mix)
    t0 = time.perf_counter()
    done = eng.run(max_steps=max_steps)
    wall = time.perf_counter() - t0
    return done, wall


def _noncompute_pct(summary) -> float:
    return float(sum(summary[f"stall_pct_{b}"] for b in NONCOMPUTE))


def _side(eng, done, wall, summary, tstats):
    out = {
        "completed": len(done),
        "tokens": sum(len(r.tokens_out) for r in done),
        "wall_s": wall, "iterations": tstats["iterations"],
        "noncompute_pct": _noncompute_pct(summary),
        "swap_out_count": eng.pool.swap_out_count,
        "swap_in_count": eng.pool.swap_in_count,
    }
    for b in ("schedule", "fetch", "dma", "shadowed", "other"):
        out[f"stall_pct_{b}"] = summary[f"stall_pct_{b}"]
    return out


def run(smoke: bool = True, arch: str = "qwen2-0.5b", n_slots: int = 2,
        max_seq: int = 64, page_tokens: int = 8, hot_pages: int = 4,
        token_budget: int = 10):
    if rerun_on_host_devices(__file__, TP, smoke, arch):
        return None
    from repro import configs
    from repro.models import blocks, transformer
    from repro.serve.kvcache import token_bytes

    cfg = configs.get_smoke_config(arch, n_kv=4)
    params_t = transformer.init_model(jax.random.PRNGKey(0), cfg)
    params, _ = blocks.split_params(params_t)

    n_req = 3 * hot_pages                   # 12: needs ~6x the hot tier
    mix = _mix(n_req)
    host_budget = 16 * n_req * 2 * token_bytes(cfg) * page_tokens
    kw = dict(n_slots=n_slots, max_seq=max_seq, page_tokens=page_tokens,
              hot_pages=hot_pages, host_budget_bytes=host_budget,
              token_budget=token_budget)

    # warmup: both loops share the jit'd step regions
    _drain(_engine(cfg, params, overlap=True, **kw), mix, cfg)

    # sync: the PR-7 loop, traced — the stall baseline AND the stream ref
    eng_s = _engine(cfg, params, overlap=False, trace=True, **kw)
    done_s, wall_s = _drain(eng_s, mix, cfg)
    streams_s = {r.seq_id: list(r.tokens_out) for r in done_s}
    sum_s = eng_s.trace_summary()

    # overlap: the same workload under the overlapped loop
    eng_o = _engine(cfg, params, overlap=True, trace=True, **kw)
    done_o, wall_o = _drain(eng_o, mix, cfg)
    streams_o = {r.seq_id: list(r.tokens_out) for r in done_o}
    sum_o = eng_o.trace_summary()

    assert streams_o == streams_s and len(streams_o) == n_req, \
        "overlapped greedy streams must be bit-identical to the sync loop"
    assert eng_o.pool.swap_out_count > 0, \
        "the oversubscribed mix must exercise the shadow-phase swap path"

    nc_s, nc_o = _noncompute_pct(sum_s), _noncompute_pct(sum_o)
    ratio = nc_s / max(nc_o, 1e-9)
    for name, s in (("sync", sum_s), ("overlap", sum_o)):
        print(f"# {name} stall% sched/fetch/dma/shadowed/other = "
              f"{s['stall_pct_schedule']:.2f}/{s['stall_pct_fetch']:.2f}/"
              f"{s['stall_pct_dma']:.2f}/{s['stall_pct_shadowed']:.2f}/"
              f"{s['stall_pct_other']:.2f}")
    assert ratio >= MIN_STALL_REDUCTION, (
        f"overlap must cut the non-compute stall share "
        f"(schedule+fetch+dma) at least {MIN_STALL_REDUCTION}x: "
        f"sync {nc_s:.2f}% vs overlap {nc_o:.2f}% (ratio {ratio:.2f})")

    payload = {
        "arch": arch, "hot_pages": hot_pages, "page_tokens": page_tokens,
        "n_slots": n_slots, "requests": n_req, "tp": TP,
        "token_budget": token_budget,
        "identical_streams": 1,             # overlap == sync, bit-for-bit
        "noncompute_stall_reduction": ratio,
        "sync": _side(eng_s, done_s, wall_s, sum_s, eng_s.tracer.stats()),
        "overlap": _side(eng_o, done_o, wall_o, sum_o, eng_o.tracer.stats()),
    }
    save_json("overlap", payload)
    path = save_bench("serve", payload, section="overlap")
    for name, side in (("sync", payload["sync"]),
                       ("overlap", payload["overlap"])):
        print(f"overlap_{name},{side['wall_s'] * 1e6:.1f},"
              f"completed={side['completed']} "
              f"noncompute%={side['noncompute_pct']:.1f} "
              f"shadowed%={side['stall_pct_shadowed']:.1f}")
    print(f"# non-compute stall {nc_s:.1f}% -> {nc_o:.1f}% "
          f"({ratio:.1f}x reduction, floor {MIN_STALL_REDUCTION}x); "
          f"streams bit-identical; wrote {path}")
    return payload


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny config, interpret-mode kernels (CI job)")
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--page-tokens", type=int, default=8)
    ap.add_argument("--hot-pages", type=int, default=4)
    ap.add_argument("--token-budget", type=int, default=10)
    args = ap.parse_args()
    run(smoke=args.smoke, arch=args.arch, n_slots=args.slots,
        max_seq=args.max_seq, page_tokens=args.page_tokens,
        hot_pages=args.hot_pages, token_budget=args.token_budget)


if __name__ == "__main__":
    main()
