"""95th percentile, ms, of every inter-token gap whose later token landed
in the window, over all requests."""
from harness import quantile


def read(w):
    v = quantile(w.gaps(), 95)
    return None if v is None else 1000.0 * v
