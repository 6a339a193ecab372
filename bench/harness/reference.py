"""Float64 host reference of one stream's answer.

The semantics the engine promises for a stream of R rounds (each (n, p)):
the exponentially forgotten sample covariance, per-round weight
``beta^(R-1-r)``, mean-centred and truncated to the band ``|i - j| <= h``
(the local covariance hypothesis), and the variance its top-q subspace
holds.  This module computes those from the stream's input alone, in NumPy
float64; it imports nothing of the program.  ``banded_covariance`` is
copied from ``chip_smoke.py`` (``_reference``).
"""

from __future__ import annotations

import numpy as np
import scipy.linalg


def banded_covariance(rounds: np.ndarray, forgetting: float,
                      h: int) -> np.ndarray:
    """(p, p) float64 banded covariance with per-round forgetting."""
    r, n, p = rounds.shape
    w = forgetting ** np.arange(r - 1, -1, -1, dtype=np.float64)
    x = rounds.reshape(r * n, p).astype(np.float64)
    wr = np.repeat(w, n)
    t = wr.sum()
    mean = wr @ x / t
    xs = x * np.sqrt(wr)[:, None]
    c = xs.T @ xs / t - np.outer(mean, mean)
    i = np.arange(p)
    c[np.abs(i[:, None] - i[None, :]) > h] = 0.0
    return c


def top_eigvals(c: np.ndarray, q: int) -> np.ndarray:
    """The q largest eigenvalues of the symmetric ``c``, descending."""
    p = c.shape[0]
    return scipy.linalg.eigh(c, eigvals_only=True,
                             subset_by_index=(p - q, p - 1))[::-1]
