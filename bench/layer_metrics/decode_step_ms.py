"""Device time, ms, of one execution of the decode program, averaged over
the traced window (profiler trace; see trace_reduce.py for how the decode
program is told from the prefill programs)."""


def read(w):
    d = w.trace.decode_steps_s if w.trace else []
    return 1000.0 * sum(d) / len(d) if d else None
