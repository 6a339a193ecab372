"""Median over every stream due in the window of its time from its
scheduled due time to the end of the engine step that set its result."""

from harness import latency


def read(v):
    return latency.percentile(latency.stream_latencies(v.outcome), 50)
