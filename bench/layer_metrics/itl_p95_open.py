"""95th percentile, ms, of every inter-token gap whose later token landed
in the window, over all requests, in an open-loop cell. There a burst of
arrivals stretches the steps for seconds, so this tail swings from run to
run far beyond any bound, and it is read as a layer's number, not held as an
end-to-end one (``itl_p95`` is, in the closed-loop cells)."""
from harness import quantile


def read(w):
    v = quantile(w.gaps(), 95)
    return None if v is None else 1000.0 * v
