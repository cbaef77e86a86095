"""95th percentile, ms, of the scheduler's queue latency (submit to first
admission, ``stats["queue_lat_s"]``) of the admissions made in the window."""
from harness import quantile


def read(w):
    v = quantile(w.admit_lat, 95)
    return None if v is None else 1000.0 * v
