"""Whole-step share, %, of the chip's bf16 peak: the model FLOPs the tokens
served in the traced window needed (costs.py), over the window's length
times the peak of the device kind (peaks.json)."""


def read(w):
    if not w.trace or not w.trace.window_s:
        return None
    work = w.work(w.trace_t0, w.trace_t1)
    peak = w.peak()["bf16_flops_per_s"]
    return 100.0 * work.model_flops / (w.trace.window_s * peak)
