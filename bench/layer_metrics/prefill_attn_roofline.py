"""Share, %, of the roofline reached by the paged prefill kernel in the
traced window: least time of the causal attention work of the prompt
positions computed (costs.py) over the kernel's summed device time."""
import costs


def read(w):
    t = w.trace.kernel_s.get("paged_prefill_attention") if w.trace else None
    if not t:
        return None
    work = w.work(w.trace_t0, w.trace_t1)
    least, _ = costs.least_time(work.prefill_attn_flops,
                                work.prefill_attn_bytes, w.peak())
    return 100.0 * least / t
