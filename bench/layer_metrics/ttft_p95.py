"""95th percentile of the same times to first token as ``ttft_p50``, ms."""
from harness import quantile


def read(w):
    v = quantile(w.ttfts(), 95)
    return None if v is None else 1000.0 * v
