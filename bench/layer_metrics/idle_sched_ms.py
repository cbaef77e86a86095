"""Device idle, ms an iteration, while the host was in the scheduler's own
``serve.*`` spans: the iteration's self time, schedule, policy, submit,
commit, decode inputs, the prefill chunk's self time, COW copies and swap
waits (profiler trace; serve_spans.py)."""
import serve_spans


def read(w):
    s = serve_spans.of(w)
    return s.idle_ms("sched") if s else None
