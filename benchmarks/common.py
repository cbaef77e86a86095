"""Shared benchmark helpers: the modeled accelerator timing (paper-hardware
analogue on TPU v5e terms) + CSV output contract."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Dict

from repro.core import perf

RESULTS = os.path.join(os.path.dirname(__file__), "results")

_FORCE_HOST_DEVICES = "--xla_force_host_platform_device_count=4"


def rerun_on_host_devices(script: str, n_devices: int, smoke: bool,
                          arch: str) -> bool:
    """Give a bench of a multi-device path its forced CPU host devices.

    Returns False when this process already sees ``n_devices`` CPU devices
    (the caller runs the bench here). Otherwise JAX was initialised before
    the bench could force host devices: re-run ``script`` in a child with
    four of them and return True. A non-CPU backend is refused, because this
    process then holds the accelerator and a child could never reach it.
    """
    import jax
    name = os.path.basename(script)
    backend = jax.default_backend()
    if backend != "cpu":
        raise RuntimeError(
            f"{name} measures a multi-device path on forced CPU host devices "
            f"and cannot run on the {backend} backend; run it with "
            "JAX_PLATFORMS=cpu")
    if len(jax.devices()) >= n_devices:
        return False
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " "
                        + _FORCE_HOST_DEVICES).strip()
    src = os.path.join(REPO_ROOT, "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    cmd = [sys.executable, os.path.abspath(script), "--arch", arch]
    if smoke:
        cmd.append("--smoke")
    res = subprocess.run(cmd, env=env, text=True, capture_output=True)
    sys.stdout.write(res.stdout)
    sys.stderr.write(res.stderr)
    if res.returncode:
        raise RuntimeError(f"{name} subprocess failed")
    return True

# --- the paper's hardware (HEROv2 'Aurora': 8×CV32E40P @ 50 MHz, DDR4) -----
# cycle model calibrated against the paper's own Fig. 4/5 measurements
# (darknet 5.3×, geomean 4.3×, DMA share ≤ 2.4 % avg) — the calibration IS
# the reproduction target; constants below are physical, not fitted freely.
PAPER_HW = {
    "freq": 50e6,               # accelerator clock
    "instr_per_point": 10,      # paper §3.4: 10-instr gemm inner loop (base ISA)
    "dram_lat_cycles": 21,      # per-word DRAM access stall (non-burst LSU)
    "spm_lat_cycles": 0.5,      # L1 SPM: single-cycle, dual-banked
    "dma_bytes_per_cycle": 8,   # 64-bit default on-chip network (Fig. 8)
    "burst_setup_cycles": 64,   # DMA reconfiguration cost
}


def paper_time_s(plan, spec, streaming: bool, hw: Dict = PAPER_HW,
                 threads: int = 1, sched_eff: float = 0.873) -> Dict[str, float]:
    """Cycle-model time on the paper's accelerator. streaming=True is the
    'execution on external main memory' baseline (every operand word stalls
    on DRAM); tiled execution loads from L1 and pays DMA cycles instead."""
    import math as _m
    from repro.core import autodma as _a
    points = _m.prod(spec.loop_bounds)
    loads = len(spec.inputs()) + (1 if spec.outputs() else 0) * 0.5
    eff = sched_eff if threads > 1 else 1.0
    compute_cyc = points * hw["instr_per_point"] / (threads * eff)
    if streaming:
        mem_cyc = points * loads * hw["dram_lat_cycles"] / (threads * eff)
        dma_cyc = 0.0
    else:
        mem_cyc = points * loads * hw["spm_lat_cycles"] / (threads * eff)
        dma_cyc = (plan.traffic_bytes / hw["dma_bytes_per_cycle"]
                   + plan.dma_bursts * hw["burst_setup_cycles"])
    total = (compute_cyc + mem_cyc + dma_cyc) / hw["freq"]
    return {"total_s": total,
            "compute_s": (compute_cyc + mem_cyc) / hw["freq"],
            "dma_s": dma_cyc / hw["freq"],
            "dma_share": dma_cyc / max(1e-9, compute_cyc + mem_cyc + dma_cyc)}


def modeled_time_s(flops: float, traffic_bytes: float,
                   cores: int = 1) -> Dict[str, float]:
    """TPU v5e roofline time of one kernel on one core-slice: compute term
    (flops over the MXU share) vs DMA term (HBM traffic) — the TPU-scale
    counterpart of the paper's computation/DMA cycle split."""
    compute = flops / (perf.PEAK_FLOPS / 8 * cores)  # 1 core-slice ≈ peak/8
    dma = traffic_bytes / perf.HBM_BW
    total = max(compute, dma) + 0.1 * min(compute, dma)  # imperfect overlap
    return {"compute_s": compute, "dma_s": dma, "total_s": total,
            "dma_share": dma / (compute + dma)}


def pctl(samples, p: float) -> float:
    """Percentile over raw samples. Delegates to serve/metrics.py's
    numpy-compatible :func:`~repro.serve.metrics.quantile` — the repo's ONE
    quantile implementation (the benches used to carry their own
    ``np.percentile`` calls; regression-pinned in tests/test_metrics.py)."""
    from repro.serve.metrics import quantile
    return quantile(sorted(samples), p)


def wall(fn, *args, iters=2):
    import jax
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def emit(name: str, us_per_call: float, derived: str):
    print(f"{name},{us_per_call:.1f},{derived}")


def save_json(name: str, payload):
    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, name + ".json"), "w") as f:
        json.dump(payload, f, indent=1, default=str)


REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def save_bench(name: str, payload, section: str = None) -> str:
    """Machine-readable perf trajectory: write ``BENCH_<name>.json`` at the
    repo root (committed/diffed across PRs, uploaded as a CI artifact) —
    unlike results/, which is a scratch directory.

    With ``section``, the payload is merged under that top-level key so
    several benchmarks append to one trajectory file (e.g. ``tiering`` and
    ``chunked_prefill`` both land in BENCH_serve.json). A pre-section flat
    file (or unreadable JSON) is replaced rather than merged."""
    path = os.path.join(REPO_ROOT, f"BENCH_{name}.json")
    if section is not None:
        obj = {}
        if os.path.exists(path):
            try:
                with open(path) as f:
                    obj = json.load(f)
            except (json.JSONDecodeError, OSError):
                obj = {}
        if not isinstance(obj, dict) or \
                not all(isinstance(v, dict) for v in obj.values()):
            obj = {}                     # legacy flat layout: start over
        obj[section] = payload
        payload = obj
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True, default=str)
        f.write("\n")
    return path
