"""The batch drivers' chunk path refreshes only the networks that fired
(DESIGN.md Sec. 8.2).

``batched_stream_run(..., chunk=K)`` is a ``lax.scan`` of the fleet body
(``_fleet_chunk_step``: the engine's step without donation), whose refresh
runs in buckets over the networks whose trigger fired.  The form it
replaced, ``vmap(chunked_stream_run)``, lowers the scheduler's cond to a
select that refreshes every network.  On the CPU the two agree bit for
bit, states and metrics: checked here at 16 networks (buckets of 8) for
0, some and all networks firing, with and without liveness masks, on a
stream that fills its decisions and on one whose tail is padded.  The
sharded and two-level drivers, which run the same path per shard, are
checked against the batched driver on 4 forced host devices.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import multihost
from repro.launch.mesh import make_fleet_mesh
from repro.streaming import (hierarchical_stream_run, merge_fleet,
                             sharded_stream_run)
from repro.streaming.compressor import CompressionConfig
from repro.streaming.detector import DetectionConfig
from repro.streaming.driver import (StreamConfig, batched_stream_init,
                                    batched_stream_run, chunked_stream_run)
from repro.streaming.hierarchy import region_energies

S, K, N, P, Q, H = 16, 4, 4, 16, 2, 2
WARMUP = 4
DEAD_ROUND = 5          # masked cases: the round a fired network loses a sensor


def _cfg() -> StreamConfig:
    # drift never triggers: which networks fire is set by each case (the
    # unconditional first refresh, or churn on the masked path)
    return StreamConfig(p=P, q=Q, halfwidth=H, forgetting=0.9,
                        drift_threshold=10.0, warmup_rounds=WARMUP,
                        interpret=True,
                        compression=CompressionConfig(epsilon=0.5),
                        detection=DetectionConfig(alpha=1e-3,
                                                  calib_rounds=3))


@functools.lru_cache(maxsize=None)
def _select_form(cfg: StreamConfig, masked: bool):
    """The form the chunk path replaced: every network through
    ``chunked_stream_run`` under one vmap."""
    if masked:
        return jax.jit(jax.vmap(lambda s, x, m: chunked_stream_run(
            cfg, s, x, m, chunk=K)))
    return jax.jit(jax.vmap(lambda s, x: chunked_stream_run(
        cfg, s, x, chunk=K)))


def _assert_trees_equal(a, b):
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _case(n_fired: int, masked: bool, rounds: int):
    """States, rounds and masks of a fleet in which exactly the networks
    of the returned set fire, once, at the first decision past warm-up."""
    cfg = _cfg()
    fired = np.zeros(S, bool)
    fired[np.random.default_rng(n_fired).permutation(S)[:n_fired]] = True
    states = batched_stream_init(cfg, jax.random.PRNGKey(n_fired), S)
    rng = np.random.default_rng(rounds)
    scale = np.linspace(3.0, 1.0, P, dtype=np.float32)
    xs = jnp.asarray(rng.normal(size=(S, rounds, N, P)).astype(np.float32)
                     * scale)
    if masked:
        # every network has refreshed once; a sensor lost in a fired
        # network's sixth round raises its churn trigger
        states = states._replace(sched=states.sched._replace(
            refreshes=jnp.ones((S,), jnp.int32)))
        masks = np.ones((S, rounds, P), np.float32)
        masks[fired, DEAD_ROUND:, 0] = 0.0
        return cfg, states, xs, jnp.asarray(masks), fired
    # a network that has refreshed once does not fire again (drift is off)
    states = states._replace(sched=states.sched._replace(
        refreshes=jnp.asarray(np.where(fired, 0, 1), jnp.int32)))
    return cfg, states, xs, None, fired


@pytest.mark.parametrize("rounds", [8, 10], ids=["whole", "padded-tail"])
@pytest.mark.parametrize("masked", [False, True],
                         ids=["unmasked", "masked"])
@pytest.mark.parametrize("n_fired", [0, 5, S])
def test_chunk_path_equals_the_select_form(n_fired, masked, rounds):
    cfg, states, xs, masks, fired = _case(n_fired, masked, rounds)
    args = (states, xs) if masks is None else (states, xs, masks)
    want = _select_form(cfg, masked)(*args)
    got = batched_stream_run(cfg, *args, chunk=K)
    # the case fires what it says: the chosen networks, at the decision
    # that closes round 8
    did = np.asarray(got[1].did_refresh)
    assert did.shape == (S, -(-rounds // K))
    np.testing.assert_array_equal(did[:, 1], fired)
    assert not did[:, 0].any() and not did[:, 2:].any()
    assert got[1].refresh_slots is None
    _assert_trees_equal(got, want)


def test_networks_that_did_not_fire_keep_their_scheduler_state():
    cfg, states, xs, _, fired = _case(5, False, 8)
    fin, _ = batched_stream_run(cfg, states, xs, chunk=K)
    before = jax.tree.map(np.asarray, states.sched)
    after = jax.tree.map(np.asarray, fin.sched)
    for name in ("W", "lam", "rho_ref", "refreshes"):
        np.testing.assert_array_equal(getattr(after, name)[~fired],
                                      getattr(before, name)[~fired],
                                      err_msg=name)
    np.testing.assert_array_equal(after.refreshes[fired], 1)


def test_probe_every_keeps_its_decision_cadence():
    """``probe_every`` below ``chunk`` decides every ``probe_every``
    rounds on both forms."""
    cfg, states, xs, _, _ = _case(S, False, 10)
    want = jax.jit(jax.vmap(lambda s, x: chunked_stream_run(
        cfg, s, x, chunk=K, probe_every=2)))(states, xs)
    got = batched_stream_run(cfg, states, xs, chunk=K, probe_every=2)
    assert np.asarray(got[1].rho).shape == (S, 5)
    _assert_trees_equal(got, want)


def _sharded_masked_equals_batched(mesh, chunk):
    """``sharded_stream_run`` with per-network liveness masks against
    ``batched_stream_run`` with the same masks: bit for bit, and the churn
    fires exactly the networks that lost a sensor."""
    cfg, states, xs, masks, fired = _case(5, True, 8)
    want = batched_stream_run(cfg, states, xs, masks, chunk=chunk)
    np.testing.assert_array_equal(
        np.asarray(want[1].did_refresh).any(axis=1), fired)
    got = sharded_stream_run(cfg, mesh, states, xs, masks, chunk=chunk)
    _assert_trees_equal(got, want)


@pytest.mark.parametrize("chunk", [1, K])
def test_sharded_run_takes_liveness_masks(chunk):
    _sharded_masked_equals_batched(
        jax.make_mesh((1, 1), ("data", "model")), chunk)


# ---------------------------------------------------------------------------
# The sharded and two-level drivers on 4 forced host devices
# ---------------------------------------------------------------------------
@pytest.mark.skipif(multihost.in_worker(),
                    reason="outer launcher — already inside the worker")
def test_four_device_suite():
    proc = multihost.relaunch_in_worker(__file__, n_devices=4, select="mh_")
    assert proc.returncode == 0, \
        proc.stdout[-2000:] + "\n" + proc.stderr[-2000:]


needs_worker = pytest.mark.skipif(
    not multihost.in_worker(),
    reason="needs 4 forced devices (run via test_four_device_suite)")


def _drifting_fleet():
    """A fleet whose networks fire on their own schedules: drift on, a
    second factor that grows over the stream."""
    cfg = StreamConfig(p=P, q=Q, halfwidth=H, forgetting=0.8,
                       drift_threshold=0.02, warmup_rounds=WARMUP,
                       interpret=True,
                       compression=CompressionConfig(epsilon=0.5),
                       detection=DetectionConfig(alpha=1e-3, calib_rounds=3))
    rng = np.random.default_rng(7)
    rounds = 14
    scale = np.linspace(3.0, 1.0, P, dtype=np.float32)
    ramp = np.linspace(0.0, 4.0, rounds, dtype=np.float32)
    xs = rng.normal(size=(S, rounds, N, P)).astype(np.float32) * scale
    xs[..., -1] += ramp[None, :, None] * rng.normal(
        size=(S, rounds, N)).astype(np.float32) * rng.uniform(
        0.0, 1.0, size=(S, 1, 1)).astype(np.float32)
    states = batched_stream_init(cfg, jax.random.PRNGKey(3), S)
    return cfg, states, jnp.asarray(xs)


@needs_worker
def test_mh_sharded_run_takes_liveness_masks():
    assert jax.device_count() >= 4
    _sharded_masked_equals_batched(jax.make_mesh((4, 1), ("data", "model")),
                                   1)


@needs_worker
def test_mh_sharded_chunk_path_equals_batched():
    assert jax.device_count() >= 4
    cfg, states, xs = _drifting_fleet()
    want = batched_stream_run(cfg, states, xs, chunk=K)
    fired = np.asarray(want[1].did_refresh)
    assert 0 < fired[:, 2:].sum() < fired[:, 2:].size
    mesh = jax.make_mesh((4, 1), ("data", "model"))
    got = sharded_stream_run(cfg, mesh, states, xs, chunk=K)
    _assert_trees_equal(got, want)


@needs_worker
def test_mh_hierarchical_chunk_path_equals_batched():
    assert jax.device_count() >= 4
    cfg, states, xs = _drifting_fleet()
    fin_b, m_b = batched_stream_run(cfg, states, xs, chunk=K)
    mesh = make_fleet_mesh(region=4)
    fin, metrics, fleet = hierarchical_stream_run(cfg, mesh, states, xs,
                                                  chunk=K)
    _assert_trees_equal((fin, metrics), (fin_b, m_b))
    lam, den = jax.vmap(region_energies)(fin_b)
    basis = merge_fleet(lam, jnp.sum(den), cfg.q)
    np.testing.assert_array_equal(np.asarray(fleet.basis.region),
                                  np.asarray(basis.region))
    np.testing.assert_array_equal(np.asarray(fleet.basis.col),
                                  np.asarray(basis.col))
    np.testing.assert_allclose(np.asarray(fleet.basis.lam_table),
                               np.asarray(lam), rtol=1e-6, atol=0)
