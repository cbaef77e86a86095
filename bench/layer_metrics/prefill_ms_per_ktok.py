"""Device time, ms, of the prefill-chunk programs in the traced window per
1,000 prompt tokens those steps computed."""


def read(w):
    if not w.trace or not w.trace.prefill_programs_s:
        return None
    tokens = w.work(w.trace_t0, w.trace_t1).prefill_tokens
    return 1e6 * w.trace.prefill_programs_s / tokens if tokens else None
