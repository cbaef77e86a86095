"""Execution tracing + stall-time attribution on the tiered + tensor-
parallel oversubscribed mix.

Drives the chunked engine (tp=2, host-DRAM swap tier at 4 hot pages, 12
requests needing ~6x that) three ways:

* **plain** — tracing off, wall clock: the reference streams.
* **traced** — tracing on: same workload; asserts the observe-only
  contract (greedy streams bit-identical to plain), records the stall
  breakdown (``stall_pct_{schedule,fetch,dma,other}``), and asserts
  **closure**: each iteration's exclusive buckets sum to its wall time
  within 5% (they are exact by construction — the tolerance absorbs float
  accumulation only). The event ring is exported as Chrome trace-event
  JSON next to BENCH_serve.json (``BENCH_serve.trace.json``, uploaded as
  a CI artifact) — open it in Perfetto to see the swap DMA windows
  overlapping the admission pass.
* **fake-clock twins** — two fresh engines, tracing OFF, each on its own
  deterministic FakeClock: their ``metrics_snapshot()`` JSON must be
  **bit-identical**. This is the time-determinism gate for the unified
  clock path: if any serve-side code still read ``time.perf_counter()``
  directly (instead of the tracer's injected clock), wall time would leak
  into the snapshots and the twins would diverge.

Usage:  PYTHONPATH=src python benchmarks/bench_trace.py [--smoke]

When the current process already initialised jax with fewer than 2 devices
(e.g. under benchmarks/run.py), the bench re-execs itself in a subprocess
with ``XLA_FLAGS=--xla_force_host_platform_device_count=4``. It runs on the
CPU backend only: elsewhere it fails, since this process then holds the
accelerator and the subprocess could never reach it. Appends the
``trace`` section to BENCH_serve.json and writes
benchmarks/results/trace.json.
"""
from __future__ import annotations

import argparse
import json
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

from benchmarks.common import (REPO_ROOT, rerun_on_host_devices,
                               save_bench, save_json)

TP = 2
CLOSURE_TOL_PCT = 5.0       # per-iteration |sum(buckets) - dur| / dur bound


class FakeClock:
    """Deterministic monotonic clock: each read advances a fixed step."""

    def __init__(self, step: float = 1e-3):
        self.t = 0.0
        self.step = step
        self.reads = 0

    def __call__(self) -> float:
        self.t += self.step
        self.reads += 1
        return self.t


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
            host_budget_bytes, token_budget, trace=False, clock=None):
    from repro.serve.cache import CacheConfig
    from repro.serve.engine import Engine, EngineConfig
    return Engine(cfg, params, config=EngineConfig(
        n_slots=n_slots, max_seq=max_seq, chunked=True,
        token_budget=token_budget, preempt_quantum=1, tp=TP,
        trace=trace, clock=clock,
        cache=CacheConfig(paged=True, tiered=True, page_tokens=page_tokens,
                          n_pages=hot_pages,
                          host_budget_bytes=host_budget_bytes)))


def _drain(eng, mix, cfg, max_steps=200000):
    _submit_all(eng, cfg, mix)
    t0 = time.perf_counter()
    done = eng.run(max_steps=max_steps)
    wall = time.perf_counter() - t0
    return done, wall


def _closure_worst_err_pct(stall_log) -> float:
    """Largest per-iteration |sum(buckets) - dur| as a percent of dur."""
    worst = 0.0
    for e in stall_log:
        if e["dur"] <= 0.0:
            continue
        err = abs(sum(e["buckets"].values()) - e["dur"]) / e["dur"] * 100.0
        worst = max(worst, err)
    return worst


def run(smoke: bool = True, arch: str = "qwen2-0.5b", n_slots: int = 2,
        max_seq: int = 64, page_tokens: int = 8, hot_pages: int = 4,
        token_budget: int = 10):
    if rerun_on_host_devices(__file__, TP, smoke, arch):
        return None
    from repro import configs
    from repro.models import blocks, transformer
    from repro.serve.kvcache import token_bytes

    # kv heads must divide tp (and the mesh shards the kv axis): same
    # n_kv=4 smoke family as bench_tensor_parallel
    cfg = configs.get_smoke_config(arch, n_kv=4)
    params_t = transformer.init_model(jax.random.PRNGKey(0), cfg)
    params, _ = blocks.split_params(params_t)

    n_req = 3 * hot_pages                   # 12: needs ~6x the hot tier
    mix = _mix(n_req)
    host_budget = 16 * n_req * 2 * token_bytes(cfg) * page_tokens
    kw = dict(n_slots=n_slots, max_seq=max_seq, page_tokens=page_tokens,
              hot_pages=hot_pages, host_budget_bytes=host_budget,
              token_budget=token_budget)

    # warmup: every engine below shares the jit'd step regions
    _drain(_engine(cfg, params, **kw), mix, cfg)

    # plain: tracing off, wall clock — the reference streams
    eng_p = _engine(cfg, params, **kw)
    done_p, wall_p = _drain(eng_p, mix, cfg)
    streams_p = {r.seq_id: list(r.tokens_out) for r in done_p}

    # traced: same workload, tracing on
    eng_t = _engine(cfg, params, trace=True, **kw)
    done_t, wall_t = _drain(eng_t, mix, cfg)
    streams_t = {r.seq_id: list(r.tokens_out) for r in done_t}
    assert streams_t == streams_p and len(streams_t) == n_req, \
        "tracing must not change greedy streams (observe-only contract)"

    summary = eng_t.trace_summary()
    tstats = eng_t.tracer.stats()
    worst_err = _closure_worst_err_pct(eng_t.tracer.stall_log())
    assert worst_err <= CLOSURE_TOL_PCT, (
        f"stall buckets must close each iteration's wall time within "
        f"{CLOSURE_TOL_PCT}% (worst {worst_err:.3f}%)")
    from repro.serve import trace as _trace
    total_pct = sum(summary[f"stall_pct_{b}"] for b in _trace.BUCKETS)
    assert abs(total_pct - 100.0) <= CLOSURE_TOL_PCT, \
        f"aggregate stall percentages must sum to ~100 (got {total_pct:.2f})"
    events = eng_t.tracer.chrome_trace()["traceEvents"]
    dma_windows = sum(1 for e in events
                     if e.get("ph") == "b" and e["name"].endswith("_dma"))
    assert dma_windows > 0, "oversubscribed tiered run must record swap DMA"
    trace_path = eng_t.trace_export(
        os.path.join(REPO_ROOT, "BENCH_serve.trace.json"))

    # fake-clock twins: tracing OFF, deterministic clock — snapshots must be
    # bit-identical (any stray time.perf_counter() call would leak wall time)
    snaps = []
    for _ in range(2):
        eng_f = _engine(cfg, params, clock=FakeClock(), **kw)
        done_f, _ = _drain(eng_f, mix, cfg)
        assert {r.seq_id: list(r.tokens_out)
                for r in done_f} == streams_p, "fake-clock streams diverged"
        snaps.append(json.dumps(eng_f.metrics_snapshot(), sort_keys=True))
    assert snaps[0] == snaps[1], (
        "metrics_snapshot() must be bit-identical across fake-clock twins "
        "(a direct perf_counter call is leaking wall time)")

    traced = {
        "completed": len(done_t), "tokens": sum(
            len(r.tokens_out) for r in done_t),
        "wall_s": wall_t, "iterations": tstats["iterations"],
        "events": tstats["events"], "dropped": tstats["dropped"],
        "stall_pct_schedule": summary["stall_pct_schedule"],
        "stall_pct_fetch": summary["stall_pct_fetch"],
        "stall_pct_dma": summary["stall_pct_dma"],
        "stall_pct_shadowed": summary["stall_pct_shadowed"],
        "stall_pct_other": summary["stall_pct_other"],
        "dma_windows": dma_windows,
    }
    payload = {
        "arch": arch, "hot_pages": hot_pages, "page_tokens": page_tokens,
        "n_slots": n_slots, "requests": n_req, "tp": TP,
        "token_budget": token_budget,
        "plain_wall_s": wall_p,
        "identical_streams": 1,             # traced + fake-clock == plain
        "deterministic_snapshot": 1,        # fake-clock twins bit-identical
        "closure_worst_err_pct": worst_err,
        "trace_json": os.path.basename(trace_path),
        "traced": traced,
    }
    save_json("trace", payload)
    path = save_bench("serve", payload, section="trace")
    print(f"trace_plain,{wall_p * 1e6:.1f},completed={len(done_p)}")
    print(f"trace_traced,{wall_t * 1e6:.1f},"
          f"iterations={traced['iterations']} events={traced['events']} "
          f"stall%={summary['stall_pct_schedule']:.1f}/"
          f"{summary['stall_pct_fetch']:.1f}/{summary['stall_pct_dma']:.1f}/"
          f"{summary['stall_pct_shadowed']:.1f}/"
          f"{summary['stall_pct_other']:.1f} (sched/fetch/dma/shadowed/other)")
    print(f"# closure worst err {worst_err:.4f}% (tol {CLOSURE_TOL_PCT}%); "
          f"{dma_windows} dma windows; "
          f"streams bit-identical traced/untraced/fake-clock; "
          f"exported {os.path.basename(trace_path)}; wrote {path}")
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
