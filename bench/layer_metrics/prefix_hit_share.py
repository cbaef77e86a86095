"""Share, %, of the prompt tokens admitted in the window that the prefix
cache served: prefix-shared tokens over shared plus prefilled tokens."""


def read(w):
    shared = w.delta("prefix_shared_tokens")
    total = shared + w.delta("prefill_chunk_tokens")
    return 100.0 * shared / total if total else None
