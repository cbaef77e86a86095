"""Device idle, ms an iteration, while the host was in no ``serve.*`` span:
the load generator and the harness between engine calls (profiler trace;
serve_spans.py)."""
import serve_spans


def read(w):
    s = serve_spans.of(w)
    return s.idle_ms("outside") if s else None
