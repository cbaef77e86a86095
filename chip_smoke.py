#!/usr/bin/env python3
"""Smoke run of the serving path on one TPU chip. Not a benchmark.

Builds qwen2-0.5b at its published widths (24 layers, d_model 896, 14/2
heads, d_ff 4864, vocab 151,936) with random weights from ``--seed``,
through the serve CLI's own construction (``repro.launch.serve``), and
serves 8 requests (prompts of 128-512 tokens, 32 new tokens each) on the
engine's default path: chunked prefill, paged bf16 KV, 16-token pages.
Then it checks that

  * every request finished with all its tokens;
  * the decode region's compiled text holds ``tpu_custom_call``: the Pallas
    attention kernels were compiled by Mosaic, not interpreted;
  * every generated token agrees with a float32 reference: each prompt and
    its output are teacher-forced through ``transformer.forward`` under
    ``default_matmul_precision("highest")``, and the reference logit of each
    generated token lies within ``TOL_SIGMA`` of the reference maximum;
  * a short pass over int8 KV pages completes (its token match against the
    bf16 streams is printed, not gated: int8 is a different result).

``--tp 2`` runs only the tensor-parallel path, on a four-chip host: the same
requests through a tp=2 engine and the tp=1 engine it is compared with. The
tp mesh must hold distinct chips, and the streams must be bit-identical or
else both pass the reference check.

``--smoke`` runs the reduced ``smoke_config()`` with a small request mix on
whatever backend JAX has (the CPU rehearsal, kernels in interpret mode).
Without it the script exits non-zero, printing no result, unless JAX finds
a TPU. Any failed check exits non-zero. The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The times printed on the way are of this smoke run, compiles included.

Usage:  python chip_smoke.py [--seed N] [--tp 2] [--smoke]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))
from repro.launch import serve  # noqa: E402  (after the path insert)
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import transformer  # noqa: E402
from repro.serve.engine import Engine, Request  # noqa: E402

ARCH = "qwen2-0.5b"
PAGE_TOKENS = 16
# Tolerance of the reference check, in standard deviations of the float32
# reference logit row. The served path computes in bf16 (unit roundoff 2^-8)
# through every layer and rounds the logits to bf16, so a generated token may
# miss the reference argmax where the top logits lie closer than that error.
# At published widths cut to 12 layers (CPU), the bf16 logit error had a
# standard deviation of 0.016 sigma, so a bf16 miss stays near 0.1 sigma; a
# path computing in fp8 (16x the roundoff) or a wrong kernel lands far beyond.
TOL_SIGMA = 0.25


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"[smoke] FAILED: {what}")


class CompileCounter:
    """Counts backend compiles (persistent-cache hits included) and their
    seconds, from JAX's monitoring events."""

    def __init__(self):
        self.n = self.hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def engine_args(smoke: bool, n_slots: int, max_seq: int, tp: int = 1,
                kv_dtype: str = "compute"):
    argv = ["--arch", ARCH, "--chunked-prefill",
            "--page-tokens", str(PAGE_TOKENS), "--slots", str(n_slots),
            "--max-seq", str(max_seq), "--tp", str(tp),
            "--kv-dtype", kv_dtype]
    return serve.build_parser().parse_args(argv + (["--smoke"] if smoke
                                                   else []))


def serve_requests(cfg, params, econf, prompts, max_new):
    """Serve ``prompts`` through one Engine; returns (engine, streams,
    wall seconds). Every request must finish with all its tokens."""
    eng = Engine(cfg, params, config=econf)
    t0 = time.perf_counter()
    for i, p in enumerate(prompts):
        require(eng.submit(Request(seq_id=i, prompt=p, max_new=max_new)),
                f"request {i} refused at submit")
    done = eng.run(max_steps=100_000)
    wall = time.perf_counter() - t0
    streams = {r.seq_id: list(r.tokens_out) for r in done}
    require(sorted(streams) == list(range(len(prompts))),
            f"{len(streams)} of {len(prompts)} requests finished")
    short = {i: len(s) for i, s in streams.items() if len(s) != max_new}
    require(not short, f"requests ended short of {max_new} tokens: {short}")
    return eng, streams, wall


def reference_check(cfg, params, prompts, streams, label: str) -> None:
    """Teacher-force every prompt and its output through the float32
    reference forward; each generated token's reference logit must lie
    within TOL_SIGMA row standard deviations of the row's maximum."""
    cfg32 = dataclasses.replace(cfg, compute_dtype=jnp.float32)
    n_new = len(streams[0])
    L = max(len(p) for p in prompts) + n_new - 1

    @jax.jit
    def margins(params, toks, start, out):
        logits, _, _ = transformer.forward(params, toks, cfg32)
        rows = jax.lax.dynamic_slice_in_dim(logits[0], start, n_new, 0)
        chosen = jnp.take_along_axis(rows, out[:, None], axis=1)[:, 0]
        return (jnp.max(rows, axis=1) - chosen) / jnp.std(rows, axis=1)

    worst, n_match, n_tok = 0.0, 0, 0
    with jax.default_matmul_precision("highest"):
        for i, p in enumerate(prompts):
            out = np.asarray(streams[i], np.int32)
            seq = np.zeros((1, L), np.int32)      # causal: the tail pad is
            seq[0, :len(p)] = p                   # never seen by the rows
            seq[0, len(p):len(p) + n_new - 1] = out[:-1]
            m = np.asarray(margins(params, seq, len(p) - 1, out))
            worst = max(worst, float(m.max()))
            n_match += int((m == 0).sum())
            n_tok += n_new
    print(f"[smoke] {label} vs float32 reference: {n_tok} tokens, argmax "
          f"match share {n_match / n_tok:.4f}, worst margin {worst:.4f} "
          f"sigma (tolerance {TOL_SIGMA})", flush=True)
    require(worst <= TOL_SIGMA, f"{label}: a generated token lies "
            f"{worst:.4f} sigma below the reference maximum")


def token_match(a, b) -> float:
    pairs = [(x, y) for i in a for x, y in zip(a[i], b[i])]
    return sum(x == y for x, y in pairs) / len(pairs)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    ap.add_argument("--tp", type=int, default=1,
                    help="2: run only the tensor-parallel comparison")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config and request mix, any backend")
    args = ap.parse_args()

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"[smoke] device {device}, jax {jax.__version__}", flush=True)
    if device["platform"] != "tpu" and not args.smoke:
        raise SystemExit(f"[smoke] no TPU found (JAX platform "
                         f"{device['platform']!r}); this run needs a TPU")

    print(f"[smoke] compile cache {enable_compile_cache()}", flush=True)
    compiles = CompileCounter()
    t_start = time.perf_counter()

    rng = np.random.default_rng(args.seed)
    n_req, (lo, hi), max_new = ((3, (12, 40), 4) if args.smoke
                                else (8, (128, 512), 32))
    max_seq = -(-(hi + max_new + 2) // PAGE_TOKENS) * PAGE_TOKENS
    base = engine_args(args.smoke, n_req, max_seq)
    cfg, params = serve.load_model(base, seed=args.seed)
    print(f"[smoke] {cfg.name}: {cfg.n_layers()} layers, d_model "
          f"{cfg.d_model}, heads {cfg.n_heads}/{cfg.n_kv}, d_ff {cfg.d_ff}, "
          f"vocab {cfg.vocab}", flush=True)
    prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32)
               for n in rng.integers(lo, hi + 1, n_req)]
    print(f"[smoke] {n_req} requests, prompt lengths "
          f"{[len(p) for p in prompts]}, {max_new} new tokens each",
          flush=True)

    if args.tp > 1:
        _, s1, _ = serve_requests(cfg, params, serve.engine_config(base),
                                  prompts, max_new)
        tp_conf = serve.engine_config(engine_args(args.smoke, n_req, max_seq,
                                                  tp=args.tp))
        eng2, s2, _ = serve_requests(cfg, params, tp_conf, prompts, max_new)
        mesh_devs = list(eng2.executor.mesh.devices.flat)
        print(f"[smoke] tp={args.tp} mesh on devices "
              f"{[(d.id, d.platform) for d in mesh_devs]}", flush=True)
        require(len({d.id for d in mesh_devs}) == args.tp,
                "the tp mesh does not hold distinct devices")
        require(args.smoke or all(d.platform == "tpu" for d in mesh_devs),
                "the tp mesh is not on TPU chips")
        share = token_match(s1, s2)
        print(f"[smoke] tp={args.tp} vs tp=1 token match {share:.4f} "
              f"({'bit-identical' if s1 == s2 else 'streams differ'})",
              flush=True)
        if s1 != s2:
            reference_check(cfg, params, prompts, s1, "tp=1")
            reference_check(cfg, params, prompts, s2, f"tp={args.tp}")
    else:
        eng, streams, wall = serve_requests(
            cfg, params, serve.engine_config(base), prompts, max_new)
        s = eng.stats_summary()
        print(f"[smoke] bf16 KV served {n_req} requests in {wall:.3f} s "
              f"(compiles included): {s['prefill_chunks']} prefill chunks, "
              f"{s['decode_steps']} decode steps", flush=True)
        text = eng.executor.compile_decode(eng.pool).as_text()
        if device["platform"] == "tpu":
            require("tpu_custom_call" in text,
                    "no tpu_custom_call in the compiled decode region")
        else:
            require(eng.executor.interpret,
                    "off TPU the kernels must run in interpret mode")
        print(f"[smoke] decode region: interpret={eng.executor.interpret}, "
              f"tpu_custom_call x{text.count('tpu_custom_call')}",
              flush=True)
        reference_check(cfg, params, prompts, streams, "bf16 KV")

        n_q, q_new = max(1, n_req // 2), min(8, max_new)
        q_conf = serve.engine_config(engine_args(args.smoke, n_req, max_seq,
                                                 kv_dtype="int8"))
        _, q_streams, q_wall = serve_requests(cfg, params, q_conf,
                                              prompts[:n_q], q_new)
        ref = {i: streams[i][:q_new] for i in range(n_q)}
        print(f"[smoke] int8 KV served {n_q} requests x {q_new} tokens in "
              f"{q_wall:.3f} s; token match vs bf16 "
              f"{token_match(ref, q_streams):.4f}", flush=True)

    stats = devices[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use", "not reported")
    print(f"[smoke] smoke run, not a benchmark: wall "
          f"{time.perf_counter() - t_start:.3f} s, {compiles.n} compiles "
          f"({compiles.hits} persistent-cache hits) taking "
          f"{compiles.seconds:.3f} s, peak_bytes_in_use {peak}", flush=True)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
