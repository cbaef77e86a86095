"""Share, %, of the chip's bf16 peak reached by the prefill-chunk programs
in the traced window: the model FLOPs of the prompt positions they computed
(projections, MLP and causal attention, costs.py; the output head is counted
with the emitted tokens in ``mfu``) over their summed device time."""


def read(w):
    if not w.trace or not w.trace.prefill_programs_s:
        return None
    flops = w.work(w.trace_t0, w.trace_t1).prefill_model_flops
    peak = w.peak()["bf16_flops_per_s"]
    return 100.0 * flops / (w.trace.prefill_programs_s * peak) if flops \
        else None
