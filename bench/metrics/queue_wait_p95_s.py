"""Admission layer (serve/queue.py): 95th percentile over the streams due in
the window of the time from the due time to the start of the engine step
that admitted the stream to a slot."""

import math

from harness import latency


def read(v):
    return latency.percentile(
        [s.admitted - s.due if s.admitted is not None else math.inf
         for s in v.outcome.due], 95)
