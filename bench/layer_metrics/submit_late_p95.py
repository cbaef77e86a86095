"""95th percentile, ms, of how late the load generator submitted the
requests due in the window: ``Engine.submit`` time minus due time. The
generator and the engine share one host thread, so a request due during a
step waits for that step to end; TTFT counts this wait, and this reading
tells it apart from the wait in the scheduler's queue."""
from harness import quantile


def read(w):
    v = quantile([r.t_submit - r.due for r in w.due_in_window()
                  if r.t_submit > 0.0], 95)
    return None if v is None else 1000.0 * v
