"""Cost of the serving loop's profiler spans on this host.

Every phase of the engine loop enters a ``jax.profiler.TraceAnnotation``
through ``repro.serve.trace`` whether the tracer is enabled or not. This
script times one span through a disabled tracer (what a serving run with
tracing off pays), and the same spans while a profiler session records
them: a plain phase span, one with metadata (``prefill_chunk``), a dispatch
span and the iteration's step annotation. Each figure is the best of five
loops, in microseconds.

Usage:  PYTHONPATH=src python benchmarks/span_cost.py

Prints one JSON object: ``off_us`` and ``session_us``, each by case.
"""
from __future__ import annotations

import json
import shutil
import tempfile
import time

import jax

from repro.serve import trace


def _cases(tr):
    return {
        "span": lambda: tr.span("schedule"),
        "span_meta": lambda: tr.span("prefill_chunk", seq_id=3, start=0,
                                     size=80),
        "dispatch": lambda: tr.dispatch("decode"),
        "iteration": lambda: tr.iteration(7),
    }


def _best_us(make, loops: int) -> float:
    best = float("inf")
    for _ in range(5):
        t = time.perf_counter()
        for _ in range(loops):
            with make():
                pass
        best = min(best, (time.perf_counter() - t) / loops)
    return best * 1e6


def measure(loops: int = 100_000) -> dict:
    """Microseconds a span, by case, off and under a profiler session."""
    cases = _cases(trace.null_tracer())
    off = {k: _best_us(m, loops) for k, m in cases.items()}
    d = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(d)
        try:
            on = {k: _best_us(m, max(1, loops // 10))
                  for k, m in cases.items()}
        finally:
            jax.profiler.stop_trace()
    finally:
        shutil.rmtree(d, ignore_errors=True)
    return {"backend": jax.default_backend(), "off_us": off,
            "session_us": on}


if __name__ == "__main__":
    print(json.dumps(measure()))
