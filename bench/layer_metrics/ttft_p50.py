"""Median time to first token, ms, of every request due in the window,
timed from its due time; one still waiting at the close counts its wait."""
from harness import quantile


def read(w):
    v = quantile(w.ttfts(), 50)
    return None if v is None else 1000.0 * v
