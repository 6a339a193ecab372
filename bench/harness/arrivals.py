"""Seeded traffic shapes shared by the drivers.

Both come in blocks, each block the same multiset in its own seeded order,
so that a seed changes which work comes when and never how much work there
is: every block of stream lengths holds each length once, and every block of
inter-arrival gaps spans the same time.
"""

from __future__ import annotations

from typing import Iterator

import numpy as np

GAP_BLOCK = 64


def lengths(rng: np.random.Generator, lo: int, hi: int) -> Iterator[int]:
    """Stream lengths lo..hi, each once per block, blocks in seeded order."""
    cycle = np.arange(lo, hi + 1)
    while True:
        yield from (int(r) for r in rng.permutation(cycle))


def poisson_gaps(rng: np.random.Generator, rate: float) -> Iterator[float]:
    """Exponential gaps of mean ``1/rate`` seconds: per block of
    ``GAP_BLOCK``, the stratified quantiles of the distribution."""
    u = (np.arange(GAP_BLOCK) + 0.5) / GAP_BLOCK
    block = -np.log1p(-u) / rate
    while True:
        yield from (float(g) for g in rng.permutation(block))
