"""Share, %, of the traced window in which no operation ran on the device:
1 - busy union / window, from the profiler trace."""


def read(w):
    if not w.trace or not w.trace.window_s:
        return None
    return 100.0 * (1.0 - w.trace.busy_s / w.trace.window_s)
