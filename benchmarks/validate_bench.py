"""Schema validator for the BENCH_serve.json perf-trajectory artifact.

CI runs this after the smoke benches so a malformed bench write (missing
section, non-numeric field, NaN, truncated JSON) fails the workflow instead
of silently uploading a broken artifact that the cross-PR trajectory diff
would then choke on.

Usage:  python benchmarks/validate_bench.py BENCH_serve.json \
            [--require tiering chunked_prefill]

The schema is deliberately shallow — required keys and numeric-ness, not
values: perf numbers move across PRs by design; shape regressions are bugs.
"""
from __future__ import annotations

import argparse
import json
import math
import sys

# section -> {key: type-check}; "num" = int/float, finite, >= 0
_NUM = "num"
SCHEMAS = {
    "tiering": {
        "arch": str, "hot_pages": _NUM, "page_tokens": _NUM, "n_slots": _NUM,
        "requests": _NUM, "concurrent_pages_needed": _NUM,
        "throughput_tok_per_s": _NUM, "peak_hbm_bytes": _NUM,
        "admitted_seq_count": _NUM, "swap_overhead_ratio": _NUM,
        "reference_untiered_large": dict, "untiered_hot_only": dict,
        "tiered": dict,
    },
    "chunked_prefill": {
        "arch": str, "token_budget": _NUM, "n_slots": _NUM,
        "page_tokens": _NUM, "n_pages": _NUM, "requests": _NUM,
        "late_arrivals": _NUM, "ttft_speedup": _NUM, "stall_p99_ratio": _NUM,
        "monolithic": dict, "chunked": dict,
    },
    "prefix_cache": {
        "arch": str, "token_budget": _NUM, "n_slots": _NUM,
        "page_tokens": _NUM, "n_pages": _NUM, "requests": _NUM,
        "prefix_len": _NUM, "prefill_token_reduction": _NUM,
        "ttft_speedup": _NUM, "baseline": dict, "prefix": dict,
    },
    "tensor_parallel": {
        "arch": str, "n_kv": _NUM, "page_tokens": _NUM, "n_pages": _NUM,
        "n_slots": _NUM, "token_budget": _NUM, "requests": _NUM,
        "identical_streams": _NUM,           # 1 = tp=2/4 streams == tp=1
        "tp1": dict, "tp2": dict, "tp4": dict,
    },
    "slo": {
        "arch": str, "hot_pages": _NUM, "page_tokens": _NUM, "n_slots": _NUM,
        "requests": _NUM, "interactive_requests": _NUM,
        "itl_target_s": _NUM, "itl_uncontended_p50_s": _NUM,
        "baseline_refusals": _NUM, "slo_refusals": _NUM,
        "shed_total": _NUM, "shed_overload": _NUM, "shed_deadline": _NUM,
        "baseline_itl_p99_s": _NUM, "slo_itl_p99_s": _NUM,
        "identical_streams": _NUM,           # 1 = admitted streams == ref
        "reference": dict, "baseline": dict, "slo": dict,
    },
    "trace": {
        "arch": str, "hot_pages": _NUM, "page_tokens": _NUM, "n_slots": _NUM,
        "requests": _NUM, "tp": _NUM, "token_budget": _NUM,
        "plain_wall_s": _NUM,
        "identical_streams": _NUM,           # 1 = traced/fake-clock == plain
        "deterministic_snapshot": _NUM,      # 1 = fake-clock twins identical
        "closure_worst_err_pct": _NUM,       # buckets vs iteration wall
        "trace_json": str,                   # exported Perfetto artifact
        "traced": dict,
    },
    "overlap": {
        "arch": str, "hot_pages": _NUM, "page_tokens": _NUM, "n_slots": _NUM,
        "requests": _NUM, "tp": _NUM, "token_budget": _NUM,
        "identical_streams": _NUM,           # 1 = overlap streams == sync
        "noncompute_stall_reduction": _NUM,  # sync/(overlap) schedule+fetch+dma
        "sync": dict, "overlap": dict,
    },
    "fleet": {
        "arch": str, "token_budget": _NUM, "n_slots": _NUM,
        "page_tokens": _NUM, "n_pages": _NUM, "replicas": _NUM,
        "tenants": _NUM, "requests": _NUM, "prefix_len": _NUM,
        "prefill_token_reduction": _NUM,     # round_robin / prefix tokens
        "ttft_speedup": _NUM,
        "single": dict, "round_robin": dict, "prefix": dict,
    },
    "kv_quant": {
        "arch": str, "page_tokens": _NUM, "hot_pages": _NUM,
        "n_slots": _NUM, "requests": _NUM, "hbm_budget_bytes": _NUM,
        "page_nbytes_f32": _NUM, "page_nbytes_int8": _NUM,
        "resident_seqs_f32": _NUM, "resident_seqs_int8": _NUM,
        "residency_gain": _NUM,              # >= 2 asserted by the bench
        "swap_bytes_f32": _NUM, "swap_bytes_int8": _NUM,
        "swap_byte_reduction": _NUM,         # >= 2 asserted by the bench
        "token_match_rate": _NUM,            # greedy-stream agreement
        "max_abs_logit_err": _NUM,           # direct decode-step comparison
        "f32": dict, "int8": dict,
    },
}
# keys every per-engine sub-dict must carry with numeric values
ENGINE_NUM_KEYS = {
    "tiering": ("completed", "tokens", "wall_s", "tok_per_s", "decode_steps",
                "prefills", "admission_refusals", "preemptions",
                "swap_out_bytes", "swap_in_bytes", "peak_in_system"),
    "chunked_prefill": ("ttft_mean_s", "ttft_p99_s", "decode_stall_p99_s",
                        "prefills", "decode_tokens"),
    "prefix_cache": ("ttft_mean_s", "ttft_p99_s", "prefills",
                     "prefill_chunk_tokens", "decode_tokens"),
    "tensor_parallel": ("devices", "wall_s", "tok_per_s", "decode_steps",
                        "decode_tokens"),
    "slo": ("completed", "tokens", "wall_s", "tok_per_s", "decode_steps",
            "admission_refusals", "shed", "itl_p50_s", "itl_p99_s"),
    "trace": ("completed", "tokens", "wall_s", "iterations", "events",
              "dropped", "stall_pct_schedule", "stall_pct_fetch",
              "stall_pct_dma", "stall_pct_shadowed", "stall_pct_other",
              "dma_windows"),
    "overlap": ("completed", "tokens", "wall_s", "iterations",
                "noncompute_pct", "stall_pct_schedule", "stall_pct_fetch",
                "stall_pct_dma", "stall_pct_shadowed", "stall_pct_other",
                "swap_out_count", "swap_in_count"),
    "fleet": ("ttft_mean_s", "prefill_chunk_tokens"),
    "kv_quant": ("completed", "tokens", "wall_s", "tok_per_s",
                 "decode_steps", "preemptions", "swap_out_count",
                 "swap_in_count", "swap_out_bytes", "swap_in_bytes"),
}


def _is_num(v) -> bool:
    return (isinstance(v, (int, float)) and not isinstance(v, bool)
            and math.isfinite(v) and v >= 0)


def _check(errors, path, obj, schema):
    for key, want in schema.items():
        if key not in obj:
            errors.append(f"{path}: missing key {key!r}")
            continue
        v = obj[key]
        if want is _NUM:
            if not _is_num(v):
                errors.append(f"{path}.{key}: expected finite number >= 0, "
                              f"got {v!r}")
        elif not isinstance(v, want):
            errors.append(f"{path}.{key}: expected {want.__name__}, "
                          f"got {type(v).__name__}")


def validate(path: str, require=("tiering", "chunked_prefill",
                                 "prefix_cache", "tensor_parallel", "slo",
                                 "trace", "overlap", "fleet", "kv_quant")):
    """Returns a list of error strings (empty = valid)."""
    errors = []
    try:
        with open(path) as f:
            obj = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        return [f"{path}: unreadable ({e})"]
    if not isinstance(obj, dict):
        return [f"{path}: top level must be an object of sections"]
    for section in require:
        if section not in obj:
            errors.append(f"{path}: missing section {section!r}")
            continue
        sec = obj[section]
        if not isinstance(sec, dict):
            errors.append(f"{path}.{section}: not an object")
            continue
        _check(errors, section, sec, SCHEMAS[section])
        for key, sub in sec.items():
            if isinstance(sub, dict):
                for nk in ENGINE_NUM_KEYS.get(section, ()):
                    if nk not in sub:
                        errors.append(f"{section}.{key}: missing {nk!r}")
                    elif not _is_num(sub[nk]):
                        errors.append(f"{section}.{key}.{nk}: expected "
                                      f"finite number >= 0, got {sub[nk]!r}")
    return errors


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("path", nargs="?", default="BENCH_serve.json")
    ap.add_argument("--require", nargs="+",
                    default=["tiering", "chunked_prefill", "prefix_cache",
                             "tensor_parallel", "slo", "trace", "overlap",
                             "fleet", "kv_quant"])
    args = ap.parse_args()
    errors = validate(args.path, require=tuple(args.require))
    if errors:
        for e in errors:
            print(f"BENCH-SCHEMA-ERROR: {e}", file=sys.stderr)
        raise SystemExit(1)
    print(f"{args.path}: schema OK "
          f"({', '.join(args.require)} sections validated)")


if __name__ == "__main__":
    main()
