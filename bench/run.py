#!/usr/bin/env python3
"""Run one cell of the serving benchmark on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. The cells, metrics and bounds are in
``BENCHMARK.json``; each cell's files are under ``bench/`` (see
``bench/harness.py``). With ``--trace 0`` the result holds the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics, read from a
profiler trace of the window's last seconds and from the engine's counters.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, ``breakdown`` (traced
runs), and last ``compared``, each number the check compared beside its
limit (also printed as the last lines of standard error).

Without a TPU, or with fewer chips than the cell asks for, it exits with
code 3 and prints no result. JAX's compilation cache and the trace are kept
at fixed paths under ``.bench_cache/`` in the checkout.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = ROOT / ".bench_cache"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]

    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    import harness

    spec = harness.spec(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < spec.chips:
        print(f"[bench] {args.workload} needs {spec.chips} TPU chip(s); JAX "
              f"finds {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 3
    trace_dir = CACHE / "trace" / args.workload
    shutil.rmtree(trace_dir, ignore_errors=True)
    trace_dir.mkdir(parents=True)
    result = harness.run(spec, args.seed, args.seconds, bool(args.trace),
                         T_PROCESS, trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
