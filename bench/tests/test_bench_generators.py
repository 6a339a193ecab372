"""The seeded generators: data and arrivals are the same for the same seed,
differ for another, and the copies match what they were copied from."""

import importlib.util
import itertools

import numpy as np
import pytest

from bench_tiny import BENCH, CELLS, spec, tiny_cell
from harness import arrivals


def _take(name, seed):
    cell = tiny_cell(name)
    gen = spec.load_module("datagen", cell.config["data"]["kind"])
    source = gen.build(cell.config, seed)
    rng = np.random.default_rng(seed)
    return [source.take(rng, r).copy() for r in (4, 7, 9)]


@pytest.mark.parametrize("name", CELLS)
def test_datagen_is_seeded(name):
    a, b, c = (_take(name, s) for s in (3, 3, 4))
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not all(np.array_equal(x, y) for x, y in zip(a, c))
    cfg = tiny_cell(name).config
    assert a[1].shape == (7, cfg["epochs_per_round"], cfg["stream"]["p"])
    assert a[1].dtype == np.float32


def test_lengths_blocks_hold_each_length_once():
    seq = list(itertools.islice(
        arrivals.lengths(np.random.default_rng(5), 4, 16), 13 * 3))
    for k in range(3):
        assert sorted(seq[13 * k:13 * (k + 1)]) == list(range(4, 17))
    again = list(itertools.islice(
        arrivals.lengths(np.random.default_rng(5), 4, 16), 13 * 3))
    other = list(itertools.islice(
        arrivals.lengths(np.random.default_rng(6), 4, 16), 13 * 3))
    assert seq == again and seq != other


def test_gap_blocks_span_the_same_time():
    n = arrivals.GAP_BLOCK
    g1 = list(itertools.islice(
        arrivals.poisson_gaps(np.random.default_rng(1), 50.0), 2 * n))
    g2 = list(itertools.islice(
        arrivals.poisson_gaps(np.random.default_rng(2), 50.0), 2 * n))
    assert g1 != g2
    assert sum(g1[:n]) == pytest.approx(sum(g2[n:]), rel=1e-12)
    assert np.mean(g1) == pytest.approx(1 / 50.0, rel=0.05)


def test_surrogate_copy_matches_the_original():
    from repro.sensors.dataset import berkeley_surrogate
    gen = spec.load_module("datagen", "berkeley_surrogate")
    positions = gen._layout(52, gen.LAB_LAYOUT_SEED)
    mine = gen.surrogate(positions, 3000, seed=0)
    theirs = berkeley_surrogate(p=52, n_epochs=3000, seed=0).measurements
    np.testing.assert_allclose(mine, theirs, rtol=0, atol=1e-9)


def test_factor_copy_matches_chip_smoke():
    spec_ = importlib.util.spec_from_file_location(
        "chip_smoke_for_test", BENCH.parent / "chip_smoke.py")
    smoke = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(smoke)
    gen = spec.load_module("datagen", "local_factors")
    L = gen._loadings(smoke.P, smoke.RANK, smoke.H, 16.0)
    np.testing.assert_array_equal(L, smoke._loadings(None))
    a = gen._stream(np.random.default_rng(9), L, 2, 64, smoke.NOISE)
    b = smoke._stream(np.random.default_rng(9), L, 2, 64)
    np.testing.assert_array_equal(a, b)
