"""Programs set-up needed, from process start to the window: every program
JAX obtained, compiled or loaded from the persistent cache (its monitoring
events, ``harness.CompileCounter``)."""


def read(w):
    return float(w.setup_programs)
