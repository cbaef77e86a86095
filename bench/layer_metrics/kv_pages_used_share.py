"""Mean share, %, of the KV page pool in use (the metrics bus's
``used_pages`` gauge over the pool's pages), sampled after each step."""


def read(w):
    s = w.page_share
    return 100.0 * sum(s) / len(s) if s else None
