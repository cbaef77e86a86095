#!/usr/bin/env python3
"""Readings a cell's fixed numbers are set from; not part of a run.

    python3 bench/calibrate.py sweep   --workload <cell> --rates 0.2,0.4 --seeds 1,2 --seconds 20
    python3 bench/calibrate.py control --workload <cell> --seeds 1,2,3 --seconds 10

``sweep`` offers an open-loop cell's sessions at each rate in turn, on
each of the traffic seeds, with one set-up for all, and prints per rate and
seed the end-to-end metrics and how many requests due in the window still
waited for a first token at its close: the knee is the highest rate at
which that backlog stays near zero on every seed.

``control`` runs, per seed, a short window at the cell's own load and
sizes, then reads on the same sample of served requests the widest gap of
the program's tokens below the float32 reference (what a run compares) and
the widest gap of the tokens that the reference computed in fp8 puts first
(the control, which has to read above the limit). One JSON line per seed.

Both need the chip the cell asks for, as ``run.py`` does.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def _sweep(h, spec, args, say):
    counter = h.CompileCounter()
    cfg, params, econf = h.setup(spec, args.seed, say, T_PROCESS)
    say(f"set-up done at {time.perf_counter() - T_PROCESS:.3f} s")
    seeds = [int(s) for s in args.seeds.split(",")] if args.seeds else \
        [args.seed]
    for rate, seed in [(float(r), s) for r in args.rates.split(",")
                       for s in seeds]:
        spec.cell["load"]["session_rate_per_s"] = rate
        _, win, failed, _ = h.measure(spec, cfg, params, econf, seed,
                                      args.seconds, counter, T_PROCESS)
        due = win.due_in_window()
        waiting = sum(1 for r in due
                      if not 0.0 < r.req.t_first <= win.t1)
        row = {"rate_per_s": rate, "seed": seed, "due": len(due),
               "waiting_at_close":
               waiting, "failed": failed,
               "programs_in_window": win.window_programs,
               "decode_batch_mean": h._reader(
                   "layer_metrics", "decode_batch_mean")(win),
               "prefill_tokens": win.delta("prefill_chunk_tokens"),
               "prefill_chunks": win.delta("prefill_chunks"),
               "decode_steps": win.delta("decode_steps")}
        for kind, name in (("layer_metrics", "ttft_p50"),
                           ("layer_metrics", "ttft_p95"),
                           ("metrics", "itl_p95"),
                           ("metrics", "output_tok_s")):
            row[name] = h._reader(kind, name)(win)
        print(json.dumps(row), flush=True)


def _control(h, spec, args, say):
    from reference import check
    counter = h.CompileCounter()
    dims = spec.ref.Dims.of(spec.conf)
    length = spec.cell["engine"]["max_seq"]
    for seed in [int(s) for s in args.seeds.split(",")]:
        cfg, params, econf = h.setup(spec, seed, say, T_PROCESS)
        _, win, failed, _ = h.measure(spec, cfg, params, econf, seed,
                                      args.seconds, counter, T_PROCESS)
        pick = h.sample(win.records, seed, spec.cell["check"]["requests"])
        samples = [{"prompt": r.req.prompt.tolist(),
                    "served": list(r.req.tokens_out)} for r in pick]
        prog = check.served_gaps(params, spec.ref, dims, length, samples)
        ctrl = check.served_gaps(params, spec.ref, dims, length, samples,
                                 quant="fp8")
        flat = lambda gs: [float(x) for g in gs for x in g]
        p, c = flat(prog), flat(ctrl)
        print(json.dumps({"seed": seed, "tokens": len(p),
                          "program_worst": max(p), "control_worst": max(c),
                          "control_median": sorted(c)[len(c) // 2],
                          "failed": failed}), flush=True)
        del params, win
        gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("mode", choices=("sweep", "control"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seeds", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(ROOT / ".bench_cache" / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    import harness
    spec = harness.spec(args.workload)
    if jax.devices()[0].platform != "tpu":
        print("[calibrate] needs a TPU", file=sys.stderr)
        return 3
    say = lambda msg: print(f"[calibrate] {msg}", file=sys.stderr,
                            flush=True)
    (_sweep if args.mode == "sweep" else _control)(harness, spec, args, say)
    return 0


if __name__ == "__main__":
    sys.exit(main())
