"""Quantized KV pages (int8 + per-page scales) vs f32 compute-dtype pages.

Three measurements, one section:

* **Residency at equal HBM** — size an f32 page pool and an int8 page pool
  from the SAME byte budget (``page_nbytes()`` is the real per-page cost,
  payload + scale rows) and admit identical requests until refusal: the
  quantized pool must hold ≥ 2x the resident sequences.
* **Swap traffic** — the same oversubscribed workload through two tiered
  engines with equally many *hot pages*: the quantized stack's swap-out +
  swap-in bytes must be ≥ 2x smaller (pages travel quantized, scales ride
  along).
* **Stream ablation** — the accuracy cost: twin engines (f32 vs int8 pages,
  identical schedule) report the greedy-token match rate, and a direct
  paged-prefill → decode-step comparison on the real model reports the max
  absolute logit error the int8 pages introduce.

Usage:  PYTHONPATH=src python benchmarks/bench_kv_quant.py [--smoke]
Writes BENCH_serve.json at the repo root (section ``kv_quant``) and
benchmarks/results/kv_quant.json (full detail).
"""
from __future__ import annotations

import argparse
import sys
import time
import os

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import save_bench, save_json
from repro import configs
from repro.models import blocks, transformer
from repro.serve import kvcache, paged_step
from repro.serve.engine import Engine, Request


def _residency(cfg, page_tokens: int, budget_pages_f32: int):
    """Admit identical (prompt=8, max_new=8) requests into an f32 pool and an
    int8 pool sized from the SAME HBM byte budget; count admissions."""
    probe_f32 = kvcache.PagedCachePool(cfg, max_batch=1, max_seq=64,
                                       n_pages=1, page_tokens=page_tokens)
    probe_int8 = kvcache.PagedCachePool(cfg, max_batch=1, max_seq=64,
                                        n_pages=1, page_tokens=page_tokens,
                                        kv_dtype="int8")
    budget = budget_pages_f32 * probe_f32.page_nbytes()
    out = {"hbm_budget_bytes": budget,
           "page_nbytes_f32": probe_f32.page_nbytes(),
           "page_nbytes_int8": probe_int8.page_nbytes()}
    for key, kvd in (("resident_seqs_f32", "compute"),
                     ("resident_seqs_int8", "int8")):
        n_pages = max(1, budget // (probe_f32.page_nbytes()
                                    if kvd == "compute"
                                    else probe_int8.page_nbytes()))
        pool = kvcache.PagedCachePool(
            cfg, max_batch=4 * n_pages, max_seq=64, n_pages=n_pages,
            page_tokens=page_tokens, kv_dtype=kvd)
        n = 0
        while pool.can_admit(page_tokens, page_tokens):    # 2 pages each
            pool.admit(n, page_tokens, page_tokens)
            n += 1
        out[key] = n
    out["residency_gain"] = out["resident_seqs_int8"] / \
        max(1, out["resident_seqs_f32"])
    return out


def _run_engine(cfg, params, mix, *, kv_dtype, n_slots, max_seq, page_tokens,
                n_pages, tiered, host_budget_bytes=None, max_steps=200000):
    from repro.serve.cache import CacheConfig
    from repro.serve.engine import EngineConfig
    eng = Engine(cfg, params, config=EngineConfig(
        n_slots=n_slots, max_seq=max_seq,
        cache=CacheConfig(paged=True, tiered=tiered, page_tokens=page_tokens,
                          n_pages=n_pages,
                          host_budget_bytes=host_budget_bytes,
                          kv_dtype=kv_dtype)))
    rng = np.random.default_rng(0)
    for i, (L, new) in enumerate(mix):
        assert eng.submit(Request(
            seq_id=i, prompt=rng.integers(0, cfg.vocab, L).astype(np.int32),
            max_new=new))
    t0 = time.perf_counter()
    done = eng.run(max_steps=max_steps)
    wall = time.perf_counter() - t0
    toks = sum(len(r.tokens_out) for r in done)
    out = {"completed": len(done), "tokens": toks, "wall_s": wall,
           "tok_per_s": toks / wall,
           "streams": {r.seq_id: list(r.tokens_out) for r in done}}
    out.update(eng.stats_summary())
    return out


def _logit_ablation(cfg, params, page_tokens: int, prompt_len: int):
    """Prefill a real prompt through the paged chunk step, decode one token,
    on f32 pages and on int8 pages — max |Δlogit| is the quantization cost
    in the model's own units (and the two argmax tokens usually agree)."""
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, cfg.vocab, prompt_len).astype(np.int32)
    logits = {}
    interpret = jax.default_backend() == "cpu"
    for kvd in ("compute", "int8"):
        pool = kvcache.PagedCachePool(cfg, max_batch=1, max_seq=64,
                                      n_pages=8, page_tokens=page_tokens,
                                      kv_dtype=kvd)
        slot = pool.admit_prefill(0, prompt_len)
        chunk = paged_step.make_paged_prefill_chunk_step(
            cfg, page_tokens, interpret=interpret)
        tbl = jnp.asarray(pool.page_table_row(slot), jnp.int32)
        lg, pages = chunk(params, jnp.asarray(prompt)[None], pool.pages,
                          tbl, jnp.asarray(0, jnp.int32))
        pool.pages = pages
        pool.lengths[slot] = prompt_len
        pool.ensure(slot, prompt_len + 1)
        tok = int(jnp.argmax(lg[0]))
        dstep = paged_step.make_paged_decode_step(
            cfg, page_tokens, interpret=interpret)
        lg2, _ = dstep(params, jnp.asarray([[tok]], jnp.int32), pool.pages,
                       jnp.asarray(pool.device_page_tables()),
                       jnp.asarray([prompt_len], jnp.int32),
                       jnp.asarray([True]))
        logits[kvd] = np.asarray(lg2[0], np.float32)
    return float(np.max(np.abs(logits["compute"] - logits["int8"])))


def _match_rate(a_streams, b_streams):
    total = matched = 0
    for sid in a_streams:
        a, b = a_streams[sid], b_streams.get(sid, [])
        total += max(len(a), len(b))
        matched += sum(1 for x, y in zip(a, b) if x == y)
    return matched / max(1, total)


def run(smoke: bool = True, arch: str = "qwen2-0.5b", n_slots: int = 2,
        max_seq: int = 64, page_tokens: int = 8, hot_pages: int = 4):
    # f32 compute dtype maximizes the contrast the int8 pages deliver (~4x);
    # bf16 compute would still halve pages but the claim is dtype-relative
    cfg = configs.get_smoke_config(arch, compute_dtype=jnp.float32)
    params_t = transformer.init_model(jax.random.PRNGKey(0), cfg)
    params, _ = blocks.split_params(params_t)

    # -- residency at equal HBM budget ------------------------------------
    res = _residency(cfg, page_tokens, budget_pages_f32=8 if smoke else 32)

    # -- tiered swap traffic at equal hot-page counts ----------------------
    per_req = (6, 6) if smoke else (8, 8)
    n_req = (3 if smoke else 6) * hot_pages
    mix = [per_req] * n_req
    host_budget = 64 * n_req * res["page_nbytes_f32"]
    kw = dict(n_slots=n_slots, max_seq=max_seq, page_tokens=page_tokens,
              n_pages=hot_pages, tiered=True, host_budget_bytes=host_budget)
    f32 = _run_engine(cfg, params, mix, kv_dtype="compute", **kw)
    int8 = _run_engine(cfg, params, mix, kv_dtype="int8", **kw)
    swap_f32 = f32["swap_out_bytes"] + f32["swap_in_bytes"]
    swap_int8 = int8["swap_out_bytes"] + int8["swap_in_bytes"]

    # -- ablation: greedy streams + direct logit error ---------------------
    match = _match_rate(f32.pop("streams"), int8.pop("streams"))
    logit_err = _logit_ablation(cfg, params, page_tokens,
                                prompt_len=2 * page_tokens + 3)

    assert f32["completed"] == int8["completed"] == n_req, \
        "both stacks must finish the workload"
    assert res["residency_gain"] >= 2.0, \
        f"int8 pages must hold >=2x sequences at equal HBM, " \
        f"got {res['residency_gain']:.2f}x"
    assert f32["swap_out_count"] == int8["swap_out_count"] and swap_int8, \
        "same schedule must drive the same swap events on both stacks"
    swap_reduction = swap_f32 / swap_int8
    assert swap_reduction >= 2.0, \
        f"int8 pages must swap >=2x fewer bytes, got {swap_reduction:.2f}x"
    assert match >= 0.5, f"greedy streams diverged too far ({match:.2f})"
    assert np.isfinite(logit_err)

    payload = {
        "arch": arch, "page_tokens": page_tokens, "hot_pages": hot_pages,
        "n_slots": n_slots, "requests": n_req,
        **res,
        "swap_bytes_f32": swap_f32, "swap_bytes_int8": swap_int8,
        "swap_byte_reduction": swap_reduction,
        "token_match_rate": match,
        "max_abs_logit_err": logit_err,
        "f32": f32, "int8": int8,
    }
    save_json("kv_quant", payload)
    path = save_bench("serve", payload, section="kv_quant")
    print(f"# equal HBM budget {res['hbm_budget_bytes']} B: "
          f"f32 {res['resident_seqs_f32']} seqs, "
          f"int8 {res['resident_seqs_int8']} seqs "
          f"({res['residency_gain']:.2f}x)")
    print(f"kv_quant_swap,f32={swap_f32},int8={swap_int8},"
          f"reduction={swap_reduction:.2f}x")
    print(f"kv_quant_ablation,token_match={match:.3f},"
          f"max_abs_logit_err={logit_err:.4f}")
    print(f"# wrote {path}")
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
    args = ap.parse_args()
    run(smoke=args.smoke, arch=args.arch, n_slots=args.slots,
        max_seq=args.max_seq, page_tokens=args.page_tokens,
        hot_pages=args.hot_pages)


if __name__ == "__main__":
    main()
