"""Every output token that landed in the window, over the window's length."""


def read(w):
    return w.emitted(w.t0, w.t1) / (w.t1 - w.t0)
