"""Device idle, ms an iteration, while the host was in the blocking token
fetch, ``serve.fetch_tokens`` (profiler trace; serve_spans.py)."""
import serve_spans


def read(w):
    s = serve_spans.of(w)
    return s.idle_ms("fetch") if s else None
