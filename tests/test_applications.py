"""Application-layer behaviour: supervised compression (+/- eps guarantee),
low-variance event detection, and the refresh, deflation and transform
steps on a banded problem."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import covariance as cov
from repro.core.compression import SupervisedCompressor, scores
from repro.core.events import LowVarianceDetector
from repro.core.pca import DistributedPCA
from repro.core.power_iteration import deflated_power_iteration
from repro.sensors.dataset import berkeley_surrogate, kfold_blocks
from repro.streaming.scheduler import ortho_refresh_evals


@pytest.fixture(scope="module")
def fitted():
    data = berkeley_surrogate(p=52, n_epochs=3600, seed=0)
    tr, te = kfold_blocks(data.n_epochs, k=5)[0]
    train, test = data.measurements[tr], data.measurements[te]
    res = DistributedPCA(q=5, method="eigh").fit(train)
    return res, train, test


class TestSupervisedCompression:
    def test_epsilon_guarantee_holds(self, fitted):
        """Sec. 2.4.1: every sink value within +/- eps of the truth."""
        res, train, test = fitted
        comp = SupervisedCompressor(res.components, res.mean, epsilon=0.5)
        out = comp.run(test[:500])
        assert np.abs(out.x_hat - test[:500]).max() <= 0.5 + 1e-12

    def test_notification_rate_decreases_with_epsilon(self, fitted):
        res, train, test = fitted
        rates = []
        for eps in (0.1, 0.5, 2.0):
            comp = SupervisedCompressor(res.components, res.mean, epsilon=eps)
            rates.append(comp.run(test[:500]).flagged.mean())
        assert rates[0] >= rates[1] >= rates[2]
        assert rates[2] < 0.25   # 2 C tolerance: few notifications

    def test_flagged_entries_are_exact(self, fitted):
        res, train, test = fitted
        comp = SupervisedCompressor(res.components, res.mean, epsilon=0.3)
        out = comp.run(test[:200])
        np.testing.assert_array_equal(out.x_hat[out.flagged],
                                      test[:200][out.flagged])


class TestEventDetection:
    def test_detects_injected_low_variance_event(self):
        data = berkeley_surrogate(p=52, n_epochs=7200, seed=0)
        X = data.measurements
        train, cal, test = X[:3600], X[3600:4800], X[4800:].copy()
        res = DistributedPCA(q=52, method="eigh").fit(train)
        W_low = res.components[:, 10:30]
        det = LowVarianceDetector(W_low, res.eigenvalues[10:30], res.mean,
                                  alpha=1e-3)
        det.calibrate(cal)
        pattern = W_low[:, 3] + 0.5 * W_low[:, 7]
        pattern = pattern / np.abs(pattern).max() * 1.2
        test[1000:1040] += pattern[None, :]
        out = det.detect(test)
        win = np.zeros(len(test), bool)
        win[1000:1040] = True
        assert out.events[win].mean() > 0.8
        assert out.events[~win].mean() < 0.05

    def test_calibration_reduces_false_alarms(self):
        data = berkeley_surrogate(p=52, n_epochs=3600, seed=1)
        X = data.measurements
        res = DistributedPCA(q=52, method="eigh").fit(X[:1800])
        det = LowVarianceDetector(res.components[:, 10:30],
                                  res.eigenvalues[10:30], res.mean,
                                  alpha=1e-3)
        fpr_chi2 = det.detect(X[1800:]).events.mean()
        det.calibrate(X[1800:2400])
        fpr_cal = det.detect(X[2400:]).events.mean()
        assert fpr_cal <= fpr_chi2 + 1e-9


class TestProductionSteps:
    """The refresh, deflation and transform steps of the served path,
    validated on a small banded problem."""

    def _banded_problem(self, p=256, h=8, seed=0):
        rng = np.random.default_rng(seed)
        # SPD banded matrix: A^T A of a banded A stays banded (2h)
        a = rng.normal(size=(p, p)) * cov.mask_from_band(p, h // 2)
        c = a @ a.T + 0.1 * np.eye(p)
        c = np.where(cov.mask_from_band(p, h), c, 0.0)
        band = cov.dense_to_band(jnp.asarray(c, jnp.float32), h)
        return band, c

    def test_pim_block_step_converges(self):
        """The scheduler's refresh (blocked orthogonal iteration with the
        Cholesky ``inv(L)^T`` orthonormalization) converges to the top-4
        eigenvalues."""
        band, c = self._banded_problem()
        evals = np.linalg.eigvalsh(c)
        v = jax.random.normal(jax.random.PRNGKey(0), (256, 4), jnp.float32)
        _, rayleigh = ortho_refresh_evals(band, v, 101)
        got = np.sort(np.asarray(rayleigh))[::-1]
        want = evals[::-1][:4]
        np.testing.assert_allclose(got, want, rtol=2e-2)

    def test_pim_block_orthonormal(self):
        band, _ = self._banded_problem()
        v = jax.random.normal(jax.random.PRNGKey(1), (256, 4), jnp.float32)
        v, _ = ortho_refresh_evals(band, v, 1)
        np.testing.assert_allclose(np.asarray(v.T @ v), np.eye(4), atol=1e-4)

    def test_pim_deflated_step_matches_matvec(self):
        """Algorithm 2 over the banded matvec finds the top eigenvalue."""
        band, c = self._banded_problem()
        res = deflated_power_iteration(
            lambda v: cov.banded_matvec_ref(band, v), 256, 1,
            jax.random.PRNGKey(2), t_max=200, delta=0.0)
        evals = np.linalg.eigvalsh(c)
        lam = float(res.eigenvalues[0])
        assert abs(lam - evals[-1]) < 1e-2 * evals[-1]

    def test_transform_step_centered_scores(self):
        rng = np.random.default_rng(3)
        w = np.linalg.qr(rng.normal(size=(256, 4)))[0].astype(np.float32)
        mean = rng.normal(size=256).astype(np.float32)
        x = rng.normal(size=(16, 256)).astype(np.float32)
        z = scores(w, x, mean)
        expected = (x - mean) @ w
        np.testing.assert_allclose(z, expected, atol=1e-4)
