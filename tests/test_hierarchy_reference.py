"""The two-level run against a plain float64 reference (DESIGN.md Sec. 13).

The reference below is NumPy in float64 and imports nothing of the
program.  From each region's own rounds it computes the exponentially
forgotten, mean-centred covariance truncated to the band ``|i - j| <= h``;
from the basis ``W`` the run returned for that region, the energies
``diag(W' C W)``; the fleet's total variance, the sum of the regions'
traces; and the top-``q_fleet`` selection over every region's energies:
the owning region and column of each selected component, its energy, and
the retained fraction ``rho``.  ``hierarchical_stream_run`` with
compression and detection on (the fused chunk path) is compared with it on
a 4-device ``region`` mesh (4 forced host devices, tests/multihost.py)
and on one device.

Tolerances, each against float64, set between what the program gives
(float32) and what its bf16 tile path gives, on this test's data:

* energies, within ``ENERGY_TOL`` = 1e-5 of the region's trace: float32
  folding and evaluation over the 128 epochs each region folds here leaves
  1.7e-7; the bf16 path reads each epoch with an 8-bit mantissa and
  leaves 4.9e-4.
* the fleet's total variance, within ``TRACE_TOL`` = 1e-6 of itself: the
  same rounding of the band's centre row, 4.3e-8 against 2.4e-5.
* ``rho``, within ``RHO_TOL`` = 1e-6: the selected energies over the
  total, 9.1e-9 against 8.3e-6.
* the selection (region and column of every component) exactly: the data
  keep every selected energy ≥ 15 % above the first one left out, far
  above either rounding.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import multihost
from repro.launch.mesh import make_fleet_mesh
from repro.streaming import (StreamConfig, hierarchical_stream_init,
                             hierarchical_stream_run)
from repro.streaming.compressor import CompressionConfig
from repro.streaming.detector import DetectionConfig
from repro.streaming.driver import _uses_fused

P, Q, H = 16, 2, 3
N_REGIONS, ROUNDS, EPOCHS, CHUNK = 8, 8, 16, 4
Q_FLEET = 5
FORGETTING = 0.99
ENERGY_TOL = 1e-5
TRACE_TOL = 1e-6
RHO_TOL = 1e-6


# ---------------------------------------------------------------------------
# the float64 reference
# ---------------------------------------------------------------------------
def banded_covariance(rounds, forgetting, h):
    """(p, p) forgotten, mean-centred sample covariance of (R, n, p)
    rounds, round r weighted ``forgetting ** (R - 1 - r)``, zero outside
    the band ``|i - j| <= h``."""
    r, n, p = rounds.shape
    w = np.repeat(forgetting ** np.arange(r - 1, -1, -1, dtype=np.float64),
                  n)
    x = rounds.reshape(r * n, p).astype(np.float64)
    mean = w @ x / w.sum()
    xc = x - mean
    c = (xc * w[:, None]).T @ xc / w.sum()
    i = np.arange(p)
    c[np.abs(i[:, None] - i[None, :]) > h] = 0.0
    return c


def reference_merge(xs, W, q_fleet, forgetting, h):
    """The two-level answer for regions' rounds ``xs`` (regions, R, n, p)
    and the bases ``W`` (regions, p, q) the run returned: the energy
    table, the total variance and the top-``q_fleet`` selection."""
    covs = [banded_covariance(x, forgetting, h) for x in xs]
    W = np.asarray(W, np.float64)
    table = np.stack([np.sum(w * (c @ w), axis=0) for c, w in zip(covs, W)])
    traces = np.array([np.trace(c) for c in covs])
    q_local = table.shape[1]
    order = np.argsort(-table.ravel(), kind="stable")[:q_fleet]
    lam = table.ravel()[order]
    return dict(table=table, traces=traces, total=traces.sum(),
                region=order // q_local, col=order % q_local, lam=lam,
                rho=lam.sum() / traces.sum())


# ---------------------------------------------------------------------------
# the run under test
# ---------------------------------------------------------------------------
def _cfg(precision="fp32"):
    return StreamConfig(p=P, q=Q, halfwidth=H, forgetting=FORGETTING,
                        warmup_rounds=2, refresh_iters=16, precision=precision,
                        compression=CompressionConfig(
                            epsilon=0.5, emit_reconstruction=False),
                        detection=DetectionConfig(calib_rounds=3,
                                                  emit_statistics=False))


def _data(seed=0):
    """Per-region rounds from Q local factors of the region's own scale:
    region r's factor variances are (9, 2.25) * 1.69**r, which keeps the
    top-q_fleet selection clear of the next component (checked below), and
    a mean of its own."""
    rng = np.random.default_rng(seed)
    i = np.arange(P)
    centres = (np.arange(Q) + 0.5) * P / Q
    L = np.exp(-0.5 * ((i[:, None] - centres[None, :]) / 1.5) ** 2)
    L[np.abs(i[:, None] - centres[None, :]) > H] = 0.0
    L /= np.linalg.norm(L, axis=0)
    xs = np.empty((N_REGIONS, ROUNDS, EPOCHS, P), np.float32)
    for r in range(N_REGIONS):
        gains = 1.3 ** r * np.array([3.0, 1.5])
        f = rng.standard_normal((ROUNDS, EPOCHS, Q)) * gains
        noise = 0.05 * rng.standard_normal((ROUNDS, EPOCHS, P))
        xs[r] = f @ L.T + noise + rng.normal(size=P)
    return xs


def _run(cfg, xs, n_devices):
    mesh = make_fleet_mesh(region=n_devices)
    states = hierarchical_stream_init(cfg, jax.random.PRNGKey(3),
                                      xs.shape[0])
    fin, _, fleet = hierarchical_stream_run(cfg, mesh, states,
                                            jnp.asarray(xs), chunk=CHUNK,
                                            q_fleet=Q_FLEET)
    return np.asarray(fin.sched.W), jax.device_get(fleet.basis)


def _gaps(cfg, n_devices):
    """Each compared number's gap to the reference, and whether the
    selection agrees."""
    xs = _data()
    W, basis = _run(cfg, xs, n_devices)
    ref = reference_merge(xs, W, Q_FLEET, FORGETTING, H)
    table = np.asarray(basis.lam_table, np.float64)
    return {
        "energy": float(np.max(np.abs(table - ref["table"])
                               / ref["traces"][:, None])),
        "trace": float(abs(float(basis.total_variance) - ref["total"])
                       / ref["total"]),
        "rho": float(abs(float(basis.rho) - ref["rho"])),
        "selected_energy": float(np.max(np.abs(
            np.asarray(basis.lam, np.float64) - ref["lam"]))) / ref["total"],
        "selection": bool(
            np.array_equal(np.asarray(basis.region), ref["region"])
            and np.array_equal(np.asarray(basis.col), ref["col"])),
    }


def _within(g):
    return (g["energy"] <= ENERGY_TOL and g["trace"] <= TRACE_TOL
            and g["rho"] <= RHO_TOL and g["selected_energy"] <= ENERGY_TOL
            and g["selection"])


def test_the_run_takes_the_fused_path():
    assert _uses_fused(_cfg(), None)


def test_reference_selection_is_unambiguous():
    """The data's energies give the top-q_fleet selection a clear margin
    over the next component, so a float32 run cannot flip it."""
    xs = _data()
    covs = [banded_covariance(x, FORGETTING, H) for x in xs]
    top = np.sort(np.concatenate([np.linalg.eigvalsh(c)[-Q:]
                                  for c in covs]))[::-1]
    assert top[Q_FLEET - 1] > 1.15 * top[Q_FLEET]


def test_one_device_matches_reference():
    g = _gaps(_cfg(), 1)
    assert _within(g), g


def test_one_device_bf16_is_outside_the_tolerances():
    g = _gaps(_cfg("bf16"), 1)
    assert not _within(g), g


# ---------------------------------------------------------------------------
# 4 regions' shards on 4 forced host devices: the merge's collectives cross
# devices
# ---------------------------------------------------------------------------
@pytest.mark.skipif(multihost.in_worker(),
                    reason="outer launcher — already inside the worker")
def test_four_device_suite():
    proc = multihost.relaunch_in_worker(__file__, n_devices=4,
                                        select="mh_ref_")
    assert proc.returncode == 0, \
        proc.stdout[-2000:] + "\n" + proc.stderr[-2000:]


needs_worker = pytest.mark.skipif(
    not multihost.in_worker(),
    reason="needs 4 forced devices (run via test_four_device_suite)")


@needs_worker
def test_mh_ref_four_devices_match_reference():
    assert jax.device_count() >= 4
    g = _gaps(_cfg(), 4)
    assert _within(g), g


@needs_worker
def test_mh_ref_four_devices_bf16_is_outside_the_tolerances():
    assert jax.device_count() >= 4
    g = _gaps(_cfg("bf16"), 4)
    assert not _within(g), g
