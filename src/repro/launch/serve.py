"""Serving driver: mailbox-batched continuous decoding.

The model is served at its published widths (``configs.get_config``);
``--smoke`` swaps in the reduced ``smoke_config()`` of the same family,
which is what a CPU run with interpret-mode kernels can afford. Usage on
a CPU host:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --smoke \
      --requests 8
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --smoke \
      --paged --page-tokens 16 --pages 32
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --smoke \
      --tiered --pages 8 --host-budget-mb 64 --requests 16
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --smoke \
      --chunked-prefill --token-budget 24 --requests 16

``--paged`` switches the engine to the page-table KV cache (vmm-backed pool +
paged flash-decode kernel); ``--pages`` caps the physical page pool — when
omitted it defaults to parity with the dense pool's HBM footprint.
``--tiered`` layers a host-DRAM swap tier under the paged pool: when the hot
tier is exhausted and requests wait, the LRU resident's pages swap out over
hero_memcpy DMA and the request resumes later (preemptive scheduling);
``--host-budget-mb`` bounds the cold tier (HeroMemory L3/DRAM level).
``--chunked-prefill`` fuses prefill and decode into one token-budgeted step
loop (continuous batching with chunked prefill; implies --paged, composes
with --tiered); ``--token-budget`` caps the tokens any iteration may process
— decode tokens are packed first, prompt chunks fill the remainder.
The chunked step loop runs **overlapped** by default (PR 8): iteration k's
device step is dispatched, then iteration k+1's scheduling, swap-in DMAs,
and COW pre-forks run in its shadow, blocking only at the commit-point
token fetch — greedy streams are bit-identical either way. ``--no-overlap``
restores the fully synchronous loop (each phase flushed before the next),
which is the right mode for latency-bisection debugging.
``--prefix-cache`` (implies --chunked-prefill) turns on shared-prefix KV
caching: completed prompts are indexed in a radix tree and later arrivals
adopt the ref-counted pages of their longest cached prefix instead of
re-prefilling it; ``--prefix-cache-pages`` caps how many hot pages the cache
may pin (LRU-evicted on demand).

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --smoke \
      --prefix-cache --shared-prefix-len 32 --requests 12

``--tp N`` shards the paged serving path over N devices (tensor parallelism:
KV pages and the paged-attention head walk shard along the kv-head axis;
page tables and the allocator stay host-side and replicated — see
serve/executor.py). On the CPU greedy streams are bit-identical to --tp 1;
on TPU v5e chips they differed (cause not yet traced), and both stay
within the bf16 tolerance of a float32 reference (``chip_smoke.py --tp
2``). On a CPU container, force host devices first:

  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --smoke \
      --chunked-prefill --tp 2 --requests 8

Observability + SLO policy (PR 6): ``--metrics-log N`` prints one
structured-JSON metrics-bus snapshot line every N engine iterations
(``[metrics] {...}`` — counters/gauges/windowed histograms; see
serve/metrics.py). ``--max-in-system``/``--max-queue`` attach the SLO
policy's admission gate and load shedding, ``--itl-target-ms`` its
decode-latency budget shaping, and ``--priorities`` cycles submitted
requests through that many priority classes (highest class first out of
the mailbox; see serve/policy.py). Shed requests are reported with their
typed verdicts at the end of the run.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --smoke \
      --tiered --pages 8 --requests 16 --max-in-system 4 --max-queue 4 \
      --priorities 2 --metrics-log 16

Execution tracing (PR 7): ``--trace out.json`` records a span timeline of
the whole run (engine iterations and their schedule/policy/dispatch/fetch
phases, per-request lifecycle tracks, and swap DMA transfers) and exports
it as Chrome trace-event JSON — open the file in
Perfetto (https://ui.perfetto.dev) or ``chrome://tracing``. The run also
prints a ``[serve:trace]`` stall-attribution line decomposing iteration
wall time into schedule/fetch/dma/other. ``--trace-buffer N`` bounds the
in-memory event ring (oldest events drop first).

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --smoke \
      --tiered --pages 8 --requests 16 --trace /tmp/serve.trace.json

Fleet serving (PR 9): ``--replicas N`` routes the request mix through a
:class:`~repro.serve.router.Fleet` of N engine replicas instead of one
engine — placement by longest prefix-fingerprint match with an occupancy
tie-break (``--router round_robin`` for the baseline policy), admission
backpressure when every replica's SLO gate refuses, per-replica namespaced
metrics. Greedy streams are bit-identical to a single engine regardless of
replica count or router.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --smoke \
      --prefix-cache --shared-prefix-len 32 --requests 12 --replicas 2
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro import configs
from repro.launch.compile_cache import enable_compile_cache
from repro.models import blocks, transformer
from repro.serve.cache import CacheConfig
from repro.serve.engine import Engine, EngineConfig, Request
from repro.serve.policy import PolicyConfig


def _serve_fleet(cfg, params, econf, args):
    """--replicas N path: route the request mix through a Fleet instead of
    a single Engine (prefix-aware placement by default; see
    serve/router.py), then print fleet-level stats."""
    from repro.serve.router import Fleet

    fleet = Fleet(cfg, params, econf, replicas=args.replicas,
                  router=args.router)
    rng = np.random.default_rng(0)
    shared = rng.integers(0, cfg.vocab, args.shared_prefix_len)
    t0 = time.time()
    for i in range(args.requests):
        suffix = rng.integers(0, cfg.vocab, args.prompt_len)
        fleet.submit(Request(
            seq_id=i,
            prompt=np.concatenate([shared, suffix]).astype(np.int32),
            max_new=args.max_new,
            priority=(i % args.priorities if args.priorities else 0)))
    if args.metrics_log > 0:
        done, it = [], 0
        while not fleet.idle and it < 10000:
            done.extend(fleet.step())
            it += 1
            if it % args.metrics_log == 0:
                print(f"[metrics] {json.dumps(fleet.metrics_snapshot())}",
                      flush=True)
        if it % args.metrics_log != 0:
            print(f"[metrics] {json.dumps(fleet.metrics_snapshot())}",
                  flush=True)
    else:
        done = fleet.run(max_steps=10000)
    wall = time.time() - t0
    total_new = sum(len(r.tokens_out) for r in done)
    ss = fleet.stats_summary()
    fs = ss["fleet"]
    print(f"[serve:fleet] {args.replicas} replicas ({args.router} router): "
          f"{len(done)} requests, {total_new} tokens in {wall:.2f}s "
          f"({total_new / wall:.1f} tok/s); routed {fs['routed']} "
          f"({fs['routed_prefix']} prefix-affine, "
          f"{fs['routed_prefix_tokens']} matched tok), backpressure waits "
          f"{fs['backpressure_waits']}, shed {fs['shed']}")
    for name, s in sorted(ss["per_replica"].items()):
        rinfo = fs["replicas"][name]
        print(f"[serve:fleet]   {name} ({rinfo['state']}, gen "
              f"{rinfo['generation']}): finished {rinfo['finished']}, "
              f"decode steps {s['decode_steps']}, prefill chunk tokens "
              f"{s.get('prefill_chunk_tokens', 0)}, prefix shared tokens "
              f"{s.get('prefix_shared_tokens', 0)}")


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--smoke", action="store_true",
                    help="serve the reduced smoke_config() instead of the "
                         "published widths (CPU runs)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache (page-table flash-decode kernel)")
    ap.add_argument("--page-tokens", type=int, default=16,
                    help="tokens per physical KV page")
    ap.add_argument("--pages", type=int, default=None,
                    help="physical page-pool size (default: dense parity)")
    ap.add_argument("--tiered", action="store_true",
                    help="host-DRAM swap tier under the paged pool "
                         "(preemptive scheduling; implies --paged)")
    ap.add_argument("--kv-dtype", choices=("compute", "int8"),
                    default="compute",
                    help="KV page storage format: 'compute' keeps pages at "
                         "the model compute dtype; 'int8' quantizes pages "
                         "with per-(page, kv-head) scales (~4x resident "
                         "sequences per HBM byte, ~4x fewer swap bytes; "
                         "implies --paged)")
    ap.add_argument("--host-budget-mb", type=int, default=None,
                    help="cold-tier budget in MiB (HeroMemory L3/DRAM)")
    ap.add_argument("--preempt-quantum", type=int, default=1,
                    help="decode steps a resident is exempt from eviction")
    ap.add_argument("--chunked-prefill", action="store_true",
                    help="continuous batching with chunked prefill: fuse "
                         "prefill and decode into one token-budgeted step "
                         "loop (implies --paged)")
    ap.add_argument("--token-budget", type=int, default=None,
                    help="tokens per engine iteration (decode first, prompt "
                         "chunks fill the remainder; default "
                         "slots + 4×page-tokens)")
    ap.add_argument("--no-overlap", action="store_true",
                    help="disable the overlapped step loop: run scheduling, "
                         "swap DMAs, and COW copies synchronously instead "
                         "of in the device step's shadow (streams are "
                         "bit-identical either way)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="shared-prefix KV caching: radix prompt index + "
                         "ref-counted copy-on-write pages (implies "
                         "--chunked-prefill)")
    ap.add_argument("--prefix-cache-pages", type=int, default=None,
                    help="max hot pages the prefix cache may pin "
                         "(default: half the page pool)")
    ap.add_argument("--shared-prefix-len", type=int, default=0,
                    help="prepend a shared system-prompt prefix of this many "
                         "tokens to every request (demonstrates prefix reuse)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree: shard KV pages + paged "
                         "attention over this many devices (kv-head axis; "
                         "implies --paged)")
    ap.add_argument("--metrics-log", type=int, default=0, metavar="N",
                    help="print a [metrics] JSON snapshot line every N "
                         "engine iterations (0 = off)")
    ap.add_argument("--max-in-system", type=int, default=None,
                    help="SLO policy: cap concurrently-resident requests "
                         "(admission gate; see serve/policy.py)")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="SLO policy: cap the waiting line; the lowest-"
                         "priority tail beyond it is shed with a typed "
                         "verdict")
    ap.add_argument("--itl-target-ms", type=float, default=None,
                    help="SLO policy: decode inter-token-latency p99 target; "
                         "prefill's budget share is squeezed while over it")
    ap.add_argument("--priorities", type=int, default=0,
                    help="cycle submitted requests through this many "
                         "priority classes (0 = all default class)")
    ap.add_argument("--trace", type=str, default=None, metavar="PATH",
                    help="record an execution trace and export it as "
                         "Chrome trace-event JSON (open in Perfetto)")
    ap.add_argument("--trace-buffer", type=int, default=None, metavar="N",
                    help="tracer event-ring capacity (oldest events drop "
                         "first; default 65536)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serve through a Fleet of this many engine "
                         "replicas with prefix-aware routing (see "
                         "serve/router.py); 1 = single engine")
    ap.add_argument("--router", choices=("prefix", "round_robin"),
                    default="prefix",
                    help="fleet placement policy: longest prefix-"
                         "fingerprint match (occupancy tie-break) or plain "
                         "round-robin (--replicas > 1 only)")
    return ap


def load_model(args, seed: int = 0):
    """The model ``args`` (from :func:`build_parser`) names, with random
    weights from ``seed``: published widths, or ``smoke_config()`` under
    ``--smoke``. Returns (cfg, params)."""
    cfg = (configs.get_smoke_config(args.arch) if args.smoke
           else configs.get_config(args.arch))
    params_t = transformer.init_model(jax.random.PRNGKey(seed), cfg)
    params, _ = blocks.split_params(params_t)
    return cfg, params


def engine_config(args) -> EngineConfig:
    """The engine configuration ``args`` describe: what the CLI serves, and
    what ``chip_smoke.py`` builds its engines from."""
    policy = None
    if (args.max_in_system is not None or args.max_queue is not None
            or args.itl_target_ms is not None):
        policy = PolicyConfig(
            max_in_system=args.max_in_system, max_queue=args.max_queue,
            itl_target_s=(args.itl_target_ms / 1000.0
                          if args.itl_target_ms is not None else None))
    # the driver builds the declarative config directly (the Engine flag
    # kwargs still work but are the deprecated path)
    trace_kw = {}
    if args.trace_buffer is not None:
        trace_kw["trace_buffer"] = args.trace_buffer
    return EngineConfig(
        n_slots=args.slots, max_seq=args.max_seq,
        chunked=args.chunked_prefill, token_budget=args.token_budget,
        preempt_quantum=args.preempt_quantum, overlap=not args.no_overlap,
        tp=args.tp, policy=policy,
        trace=args.trace is not None, **trace_kw,
        cache=CacheConfig(
            paged=args.paged or args.tp > 1 or args.kv_dtype != "compute",
            page_tokens=args.page_tokens,
            n_pages=args.pages, tiered=args.tiered,
            host_budget_bytes=(args.host_budget_mb * 1024 * 1024
                               if args.host_budget_mb else None),
            prefix=args.prefix_cache,
            prefix_pages=args.prefix_cache_pages,
            kv_dtype=args.kv_dtype))


def main():
    args = build_parser().parse_args()
    enable_compile_cache()
    cfg, params = load_model(args)
    econf = engine_config(args)
    if args.replicas > 1:
        _serve_fleet(cfg, params, econf, args)
        return
    eng = Engine(cfg, params, config=econf)

    rng = np.random.default_rng(0)
    shared = rng.integers(0, cfg.vocab, args.shared_prefix_len)
    t0 = time.time()
    for i in range(args.requests):
        suffix = rng.integers(0, cfg.vocab, args.prompt_len)
        eng.submit(Request(
            seq_id=i,
            prompt=np.concatenate([shared, suffix]).astype(np.int32),
            max_new=args.max_new,
            priority=(i % args.priorities if args.priorities else 0)))
    if args.metrics_log > 0:
        # manual step loop so the snapshot cadence tracks engine iterations
        done, it = [], 0
        while not eng.idle and it < 10000:
            done.extend(eng.step())
            it += 1
            if it % args.metrics_log == 0:
                print(f"[metrics] {json.dumps(eng.metrics_snapshot())}",
                      flush=True)
        if it % args.metrics_log != 0:
            # final partial window: the drain's tail iterations would
            # otherwise never appear in the log
            print(f"[metrics] {json.dumps(eng.metrics_snapshot())}",
                  flush=True)
    else:
        done = eng.run(max_steps=10000)
    wall = time.time() - t0
    total_new = sum(len(r.tokens_out) for r in done)
    occ = np.mean(eng.stats["batch_occupancy"]) if eng.stats["batch_occupancy"] else 0
    chunked = args.chunked_prefill or args.prefix_cache
    paged = args.paged or args.tp > 1 or args.kv_dtype != "compute"
    mode = "tiered" if args.tiered else ("paged" if paged else "dense")
    if chunked:
        mode = "chunked+" + mode if args.tiered else "chunked"
    if args.prefix_cache:
        mode = "prefix+" + mode
    if args.tp > 1:
        mode = f"tp{args.tp}+" + mode
    if args.kv_dtype != "compute":
        mode = f"{args.kv_dtype}+" + mode
    print(f"[serve:{mode}] {len(done)} requests, {total_new} tokens in "
          f"{wall:.2f}s ({total_new / wall:.1f} tok/s), "
          f"decode steps {eng.stats['decode_steps']}, "
          f"mean batch occupancy {occ:.2f}")
    if paged or args.tiered or chunked:
        a = eng.pool.alloc
        print(f"[serve:{mode}] pool {a.n_pages} pages × {a.page_tokens} tok "
              f"({eng.pool.footprint_bytes()} B), free {a.free_pages}, "
              f"admission refusals {eng.stats['admission_refusals']}")
    if args.prefix_cache:
        s = eng.stats_summary()
        print(f"[serve:{mode}] prefix hits {s['prefix_hits']} "
              f"({s['prefix_full_hits']} full), shared tokens "
              f"{s['prefix_shared_tokens']}, cached pages "
              f"{s['prefix_held_pages']}, cow forks {s['cow_forks']}, "
              f"evicted pages {s['prefix_evicted_pages']}")
    if chunked:
        s = eng.stats_summary()
        print(f"[serve:{mode}] token budget {s['token_budget']} "
              f"(max iter {s['max_iter_tokens']}), prefill chunks "
              f"{s['prefill_chunks']} ({s['prefill_chunk_tokens']} tok), "
              f"decode tokens {s['decode_tokens']}, ttft p50/p99 "
              f"{s['ttft_p50_s']:.3f}/{s['ttft_p99_s']:.3f} s")
    if econf.policy is not None:
        s = eng.stats_summary()
        by_code = {}
        for r in eng.shed:
            by_code[r.verdict.code] = by_code.get(r.verdict.code, 0) + 1
        codes = ", ".join(f"{c}: {n}" for c, n in sorted(by_code.items()))
        print(f"[serve:slo] shed {s['shed']} ({codes or 'none'}), "
              f"itl p50/p99 "
              f"{s['itl_p50_s'] * 1e3:.1f}/{s['itl_p99_s'] * 1e3:.1f} ms")
    if args.trace is not None:
        path = eng.trace_export(args.trace)
        ts = eng.trace_summary()
        st = eng.tracer.stats()
        print(f"[serve:trace] {st['iterations']} iterations, "
              f"{st['events']} events ({st['dropped']} dropped) -> {path}; "
              f"stall% schedule/fetch/dma/shadowed/other "
              f"{ts['stall_pct_schedule']:.1f}/{ts['stall_pct_fetch']:.1f}/"
              f"{ts['stall_pct_dma']:.1f}/{ts['stall_pct_shadowed']:.1f}/"
              f"{ts['stall_pct_other']:.1f}")
    if args.tiered:
        s = eng.stats_summary()
        print(f"[serve:tiered] preemptions {s['preemptions']}, swap out "
              f"{s['swap_out_count']}×/{s['swap_out_bytes']} B, swap in "
              f"{s['swap_in_count']}×/{s['swap_in_bytes']} B, peak host "
              f"{s['peak_host_bytes']} B, peak in-system "
              f"{s['peak_in_system']} seqs, queue p50/p90/p99 "
              f"{s['queue_lat_p50_s']:.3f}/{s['queue_lat_p90_s']:.3f}/"
              f"{s['queue_lat_p99_s']:.3f} s")


if __name__ == "__main__":
    main()
