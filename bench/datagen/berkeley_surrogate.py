"""Sensor data of the paper's lab network: the Intel-Berkeley surrogate.

Copied from ``src/repro/sensors/dataset.py::berkeley_surrogate`` and
``src/repro/core/topology.py`` (``berkeley_like_layout``,
``bandwidth_reduce``), with the temporal smoothing loop run by
``scipy.signal.lfilter`` (the same recursion): p sensors on the lab's
40 m x 30 m floor (the layout of seed 7), 30 s epochs, a shared diurnal
cycle (the dominant component), a spatially correlated slow residual,
localized AC plateaus and i.i.d. noise, clipped to 12-38 C.  Sensors are
numbered in the reverse Cuthill-McKee order of the neighbour graph at
``radio_range_m``, the order in which the configuration's band half-width
holds every neighbour pair.

``traces`` surrogate traces of ``trace_epochs`` epochs each are built once
from the seed.  A stream comes from network k, drawn uniformly from
``networks``, which reads trace ``k % traces``: R * n consecutive epochs
from a uniformly drawn start (a view: no copy).
"""

from __future__ import annotations

from collections import deque

import numpy as np
import scipy.signal

LAB_LAYOUT_SEED = 7


def _layout(p: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    n_ring = p // 2
    t = np.linspace(0, 1, n_ring, endpoint=False)
    ring = np.stack([20 + 19 * np.cos(2 * np.pi * t),
                     15 + 13 * np.sin(2 * np.pi * t)], axis=1)
    n_rows = p - n_ring
    xs = rng.uniform(4, 36, size=n_rows)
    ys = np.tile(np.array([7.5, 15.0, 22.5]), n_rows // 3 + 1)[:n_rows]
    out = np.concatenate([ring, np.stack([xs, ys], axis=1)], axis=0)[:p]
    return out + rng.uniform(-0.8, 0.8, size=out.shape)


def _rcm(adjacency: np.ndarray) -> np.ndarray:
    """Reverse Cuthill-McKee ordering of a neighbour graph."""
    p = adjacency.shape[0]
    degrees = adjacency.sum(axis=1)
    visited = np.zeros(p, dtype=bool)
    order: list[int] = []
    while len(order) < p:
        seed = int(np.argmin(np.where(visited, p + 1, degrees)))
        visited[seed] = True
        dq = deque([seed])
        order.append(seed)
        while dq:
            u = dq.popleft()
            nbrs = np.nonzero(adjacency[u] & ~visited)[0]
            nbrs = nbrs[np.argsort(degrees[nbrs], kind="stable")]
            for v in nbrs:
                visited[v] = True
                order.append(int(v))
                dq.append(int(v))
    return np.array(order[::-1], dtype=np.int64)


def band_order(positions: np.ndarray, radio_range: float) -> np.ndarray:
    d2 = ((positions[:, None, :] - positions[None, :, :]) ** 2).sum(-1)
    adj = d2 <= radio_range ** 2
    np.fill_diagonal(adj, False)
    return _rcm(adj)


def surrogate(positions: np.ndarray, n_epochs: int, seed: int,
              noise_std: float = 0.25) -> np.ndarray:
    """(n_epochs, p) float64 degrees C at the sensors ``positions``."""
    rng = np.random.default_rng(seed)
    p = positions.shape[0]
    t = np.arange(n_epochs) * 30.0 / 86_400.0
    diurnal = 24.0 + 6.5 * np.sin(2 * np.pi * (t - 0.3))
    diurnal = diurnal + 1.2 * np.sin(4 * np.pi * (t - 0.1))
    gain = 0.75 + 0.5 * rng.beta(2.0, 2.0, size=p)
    offset = rng.normal(0.0, 1.0, size=p)
    d = np.linalg.norm(positions[:, None, :] - positions[None, :, :], axis=-1)
    K = np.exp(-(d / 18.0) ** 2) + 1e-6 * np.eye(p)
    Lk = np.linalg.cholesky(K)
    z = rng.normal(size=(n_epochs, p))
    alpha = 0.015
    c = np.sqrt(alpha * (2 - alpha))
    # z[i] = (1 - alpha) z[i-1] + c z[i] for i >= 1, z[0] unchanged
    z[0] /= c
    z = scipy.signal.lfilter([c], [1.0, -(1 - alpha)], z, axis=0)
    spatial = 1.6 * (z @ Lk.T)
    events = np.zeros((n_epochs, p))
    for _ in range(10):
        site = rng.integers(0, p)
        start = rng.integers(0, n_epochs - 1_200)
        dur = rng.integers(400, 1_200)
        amp = rng.uniform(-3.0, -1.0)
        foot = np.exp(-(d[site] / 6.0) ** 2)
        window = np.zeros(n_epochs)
        window[start:start + dur] = 1.0
        window = np.convolve(window, np.ones(11) / 11.0, mode="same")
        events += amp * window[:, None] * foot[None, :]
    x = (offset[None, :] + gain[None, :] * diurnal[:, None] + spatial
         + events + rng.normal(0.0, noise_std, size=(n_epochs, p)))
    return np.clip(x, 12.0, 38.0)


class Source:
    def __init__(self, config: dict, seed: int):
        data = config["data"]
        self.p = config["stream"]["p"]
        self.n = config["epochs_per_round"]
        self.networks = data["networks"]
        positions = _layout(self.p, LAB_LAYOUT_SEED)
        order = band_order(positions, data["radio_range_m"])
        seeds = np.random.SeedSequence(seed).generate_state(data["traces"])
        self.traces = [np.ascontiguousarray(
            surrogate(positions, data["trace_epochs"], int(s))[:, order],
            dtype=np.float32) for s in seeds]

    def take(self, rng: np.random.Generator, rounds: int) -> np.ndarray:
        k = int(rng.integers(self.networks))
        trace = self.traces[k % len(self.traces)]
        epochs = rounds * self.n
        span = trace.shape[0] - epochs + 1
        start = int(rng.integers(span))
        return trace[start:start + epochs].reshape(rounds, self.n, self.p)


def build(config: dict, seed: int) -> Source:
    return Source(config, seed)
