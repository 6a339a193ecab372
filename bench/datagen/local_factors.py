"""Sensor data of a wide region: spatially local latent factors plus noise.

Copied from ``chip_smoke.py`` (``_loadings``, ``_stream``).  RANK Gaussian
bumps, narrower than the covariance band, give a banded population
covariance whose top-q subspace is the factors'.  Within each round the
factor series are centred and mutually orthogonal, so every round's sample
covariance of the signal is exactly the banded population covariance (the
comparison then measures the engine, not the estimator's sampling noise).

A seeded pool of ``pool_streams`` streams of ``pool_rounds`` rounds is
built once; a stream of R rounds is a slice of R consecutive rounds of a
pool entry (a view: no copy).
"""

from __future__ import annotations

import numpy as np


def _loadings(p: int, rank: int, h: int, width: float) -> np.ndarray:
    centers = (np.arange(rank) + 0.5) * (p / rank)
    d = np.arange(p)[:, None] - centers[None, :]
    L = np.exp(-0.5 * (d / width) ** 2) * (np.abs(d) <= h // 2)
    L /= np.linalg.norm(L, axis=0, keepdims=True)
    return L.astype(np.float32)


def _stream(rng: np.random.Generator, L: np.ndarray, rounds: int, n: int,
            noise: float) -> np.ndarray:
    p, rank = L.shape
    var = rng.uniform(1.0, 4.0, size=rank)
    a = rng.standard_normal((rounds, n, rank + 1))
    a[:, :, 0] = 1.0
    f = np.linalg.qr(a)[0][:, :, 1:] * np.sqrt(n * var)
    eps = rng.standard_normal((rounds, n, p), dtype=np.float32)
    return (f @ L.T).astype(np.float32) + noise * eps


class Source:
    def __init__(self, config: dict, seed: int):
        data = config["data"]
        s = config["stream"]
        self.n = config["epochs_per_round"]
        rng = np.random.default_rng(seed)
        L = _loadings(s["p"], data["rank"], s["halfwidth"], data["width"])
        self.pool = [_stream(rng, L, data["pool_rounds"], self.n,
                             data["noise"])
                     for _ in range(data["pool_streams"])]

    def take(self, rng: np.random.Generator, rounds: int) -> np.ndarray:
        j = int(rng.integers(len(self.pool)))
        entry = self.pool[j]
        start = int(rng.integers(entry.shape[0] - rounds + 1))
        return entry[start:start + rounds]


def build(config: dict, seed: int) -> Source:
    return Source(config, seed)
