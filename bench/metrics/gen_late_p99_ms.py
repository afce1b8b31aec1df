"""How late the load generator released requests: the 99th percentile of
release time minus due time, in ms (harness clock)."""
from jzb.harness import percentile


def read(w):
    return percentile((w.release - w.due) * 1e3, 0.99)
