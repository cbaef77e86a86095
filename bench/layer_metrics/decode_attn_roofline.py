"""Share, %, of the roofline reached by the paged decode kernel in the
traced window: the least time its work needs (the larger of its FLOPs over
the bf16 peak and its live K/V, q and out bytes over HBM bandwidth, from
costs.py) over its summed device time in the trace."""
import costs


def read(w):
    t = w.trace.kernel_s.get("paged_decode_attention") if w.trace else None
    if not t:
        return None
    work = w.work(w.trace_t0, w.trace_t1)
    least, _ = costs.least_time(work.decode_attn_flops,
                                work.decode_attn_bytes, w.peak())
    return 100.0 * least / t
