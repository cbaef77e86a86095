"""Mean decode batch in the window: decode tokens over decode steps, from
the scheduler's counters."""


def read(w):
    steps = w.delta("decode_steps")
    return w.delta("decode_tokens") / steps if steps else None
