"""The float64 reference and the numbers ``correct`` is decided by."""

import types

import numpy as np
import pytest

from bench_tiny import BENCH  # noqa: F401  (puts bench/ on the path)
from harness import check, reference, runner

SCFG = {"p": 12, "q": 2, "halfwidth": 3, "forgetting": 0.9,
        "warmup_rounds": 4, "compression": {"epsilon": 0.5}}
CONFIG = {"stream": SCFG, "engine": {"chunk": 4}}


def test_banded_covariance_by_loops():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(3, 5, 6)) + 2.0
    got = reference.banded_covariance(x, 0.5, 2)
    w = np.repeat(0.5 ** np.array([2.0, 1.0, 0.0]), 5)
    rows = x.reshape(15, 6)
    mean = (w[:, None] * rows).sum(0) / w.sum()
    for i in range(6):
        for j in range(6):
            want = 0.0
            if abs(i - j) <= 2:
                want = (w * rows[:, i] * rows[:, j]).sum() / w.sum() \
                    - mean[i] * mean[j]
            assert got[i, j] == pytest.approx(want, abs=1e-12)


def test_decisions_and_books():
    # chunk 4, warmup 4: decisions at round indices 3, 7, 8 for R = 9
    assert check._decisions_past_warmup(4, 4, 4) == 0
    assert check._decisions_past_warmup(5, 4, 4) == 1
    assert check._decisions_past_warmup(9, 4, 4) == 2
    ok = types.SimpleNamespace(reason="completed", rounds=9, refreshes=2)
    assert check.books_ok(9, ok, 4, 4)
    assert not check.books_ok(9, types.SimpleNamespace(
        reason="completed", rounds=9, refreshes=3), 4, 4)
    assert not check.books_ok(9, types.SimpleNamespace(
        reason="completed", rounds=8, refreshes=1), 4, 4)
    assert not check.books_ok(4, types.SimpleNamespace(
        reason="completed", rounds=4, refreshes=1), 4, 4)


def test_refresh_round_where_the_schedule_places_it():
    # chunk 4, warmup 4: decisions past warmup at 7, 11, 13 for R = 14
    assert check.refresh_round(14, 1, 4, 4) == 7
    assert check.refresh_round(14, 3, 4, 4) == 13
    assert check.refresh_round(14, 2, 4, 4) is None
    assert check.refresh_round(4, 0, 4, 4) is None
    assert check.refresh_round(6, 1, 4, 4) == 5


def _answer(x, refreshes=1, scale=1.0):
    """A StreamResult-like answer computed from the reference itself."""
    c = reference.banded_covariance(x, SCFG["forgetting"], SCFG["halfwidth"])
    w = np.linalg.eigh(c)[1][:, ::-1][:, :SCFG["q"]]
    energies = np.sum(w * (c @ w), axis=0)
    tr = np.trace(c)
    return types.SimpleNamespace(
        components=w, energies=energies * scale, total_variance=tr * scale,
        retained=energies.sum() / tr, refreshes=refreshes, rounds=x.shape[0],
        reason="completed", compression_max_err=0.25)


def _streams(answers):
    out = []
    for x, ans in answers:
        req = types.SimpleNamespace(done=True, result=ans, rounds=x)
        out.append(runner.Stream(req=req, rounds=x.shape[0], due=0.0))
    return out


def test_exact_answers_read_zero_and_altered_ones_do_not():
    rng = np.random.default_rng(1)
    xs = [rng.normal(size=(8, 6, 12)) for _ in range(3)]
    params = {"sample": 8}
    nums = check.evaluate(_streams([(x, _answer(x, refreshes=1))
                                    for x in xs]), CONFIG, params,
                          np.random.default_rng(0))
    assert nums["missing"] == 0 and nums["books"] == 0
    for k in ("fold_trace_rel", "fold_energy_rel", "retained_abs",
              "refresh_shortfall"):
        assert nums[k] == pytest.approx(0.0, abs=1e-9), k
    assert nums["eps_excess"] == pytest.approx(0.25 - np.float32(0.5))
    bad = check.evaluate(_streams([(x, _answer(x, refreshes=1, scale=1.01))
                                   for x in xs]), CONFIG, params,
                         np.random.default_rng(0))
    assert bad["fold_trace_rel"] == pytest.approx(0.01)
    ok, table = check.verdict(bad, {"fold_trace_rel": 1e-3, "books": 0})
    assert not ok and table["books"]["value"] == 0


def test_a_missing_answer_and_a_nan_fail():
    rng = np.random.default_rng(2)
    x = rng.normal(size=(8, 6, 12))
    streams = _streams([(x, _answer(x, refreshes=1))])
    streams.append(runner.Stream(
        req=types.SimpleNamespace(done=False, result=None), rounds=8,
        due=0.0))
    nan = _answer(x, refreshes=1)
    nan.total_variance = float("nan")
    streams += _streams([(x, nan)])
    nums = check.evaluate(streams, CONFIG, {"sample": 8},
                          np.random.default_rng(0))
    assert nums["missing"] == 1
    assert nums["fold_trace_rel"] == float("inf")
    assert not check.verdict(nums, {"missing": 0})[0]


def test_refresh_shortfall_reads_the_basis_at_its_refresh():
    """The single refresh of a 12-round stream is at round 7: the top-q
    basis of the first 8 rounds reads 0 there, and a basis that missed the
    refresh (the top-q of other data) does not."""
    rng = np.random.default_rng(3)
    x = rng.normal(size=(12, 6, 12)) * np.linspace(0.2, 3.0, 12)
    first = _answer(x[:8], refreshes=1)
    first.rounds = 12
    nums = check.stream_numbers(x, first, SCFG, chunk=4)
    assert nums["refresh_shortfall"] == pytest.approx(0.0, abs=1e-9)
    stale = _answer(rng.normal(size=(8, 6, 12)), refreshes=1)
    nums = check.stream_numbers(x, stale, SCFG, chunk=4)
    assert nums["refresh_shortfall"] > 0.05
    # a refresh at both decisions: the last set the basis, at round 11
    nums = check.stream_numbers(x, _answer(x, refreshes=2), SCFG, chunk=4)
    assert nums["refresh_shortfall"] == pytest.approx(0.0, abs=1e-9)
    # two refreshes of three decisions: the schedule does not place it
    x14 = rng.normal(size=(14, 6, 12))
    nums = check.stream_numbers(x14, _answer(x14, refreshes=2), SCFG,
                                chunk=4)
    assert "refresh_shortfall" not in nums
