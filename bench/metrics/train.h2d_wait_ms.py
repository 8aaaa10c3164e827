"""Milliseconds per step that the step waited for its batch from the
engine's prefetcher (``Prefetcher.stats["h2d_wait_ms"]``, the counter
``Engine.last_fit_stats`` reports), over the window."""


def read(rec):
    t = rec["train"]
    return t["h2d_wait_ms"] / t["steps"] if t["steps"] else None
