"""99th percentile latency of every request due in the window, in ms,
from its due time (harness clock); an errored or unanswered request counts
as the time to the end of the drain. The whole path's tail: the queue
before the one model-stage worker, and every host stall in the window."""
from jzb.harness import percentile


def read(w):
    return percentile(w.latency_s * 1e3, 0.99)
