"""Device idle, ms an iteration, while the host was dispatching a compiled
step, under ``serve.dispatch.*`` (profiler trace; serve_spans.py)."""
import serve_spans


def read(w):
    s = serve_spans.of(w)
    return s.idle_ms("dispatch") if s else None
