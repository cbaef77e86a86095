"""The host's own time, ms, in one engine iteration: the mean over the
``serve.iteration`` spans begun in the traced window of their length less
their ``serve.fetch_tokens`` children, whether the device hid it or not
(profiler trace; serve_spans.py)."""
import serve_spans


def read(w):
    s = serve_spans.of(w)
    return s.host_step_ms() if s else None
