"""Percentiles of per-stream times over every stream the window owed."""

from __future__ import annotations

import math

import numpy as np


def percentile(values: list[float], q: float) -> float | None:
    """The q-th percentile, interpolated linearly between order statistics
    (numpy's default); an infinite value (an answer that never came) makes
    every percentile that reaches it infinite."""
    if not values:
        return None
    a = np.sort(np.asarray(values, np.float64))
    pos = q / 100.0 * (a.size - 1)
    lo, frac = int(math.floor(pos)), pos - math.floor(pos)
    if frac == 0.0:
        return float(a[lo])
    if math.isinf(a[lo + 1]):
        return math.inf
    return float(a[lo] + (a[lo + 1] - a[lo]) * frac)


def stream_latencies(outcome) -> list[float]:
    """Due time to result, per owed stream (infinite where none came)."""
    return [s.done - s.due if s.done is not None else math.inf
            for s in outcome.due]
