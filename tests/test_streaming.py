"""Streaming subsystem: online covariance, scheduler, batched driver, engine.

The three acceptance properties from the subsystem spec:
1. the online covariance with forgetting=1 matches the batch estimator on a
   static stream (the decayed sums reduce to the plain Eq. 9-10 sums),
2. the recompute scheduler stays quiet on a stationary stream and fires on an
   injected distribution shift,
3. the vmap-batched fleet driver (and the shard_map-sharded runner) agree
   with the per-network python loop.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import costs
from repro.core import covariance as cov
from repro.kernels import ops
from repro.streaming import (
    RecomputeScheduler, StreamConfig, batched_stream_run, online_estimate,
    online_init, online_update, retained_fraction, sharded_stream_run,
    stream_covariance, stream_init, stream_run,
)
from repro.streaming.driver import batched_stream_init
from repro.streaming.online_cov import online_total_variance

P, H, Q = 32, 4, 3


def _rounds(key, n_rounds, n, p=P, scales=None):
    """Rounds of sensor measurements with a per-sensor variance profile."""
    x = jax.random.normal(key, (n_rounds, n, p))
    if scales is not None:
        x = x * jnp.asarray(scales)[None, None, :]
    return x


def _shifted_profile():
    """Two variance profiles concentrating energy at opposite ends.

    Strictly decreasing scales keep the top-q eigenvalues simple (no ties),
    so the tracked subspace is well defined and the retained fraction is
    stable on a stationary stream.
    """
    a = np.linspace(4.0, 1.0, P).astype(np.float32)
    b = a[::-1].copy()
    return a, b


class TestOnlineCovariance:
    def test_static_stream_matches_batch(self):
        """forgetting=1.0: streaming fold == one-shot batch statistics."""
        xs = _rounds(jax.random.PRNGKey(0), 6, 16)
        state, _ = stream_covariance(online_init(P, H), xs, forgetting=1.0,
                                     interpret=True)
        flat = xs.reshape(-1, P)
        batch = cov.banded_update(cov.banded_init(P, H), flat)
        np.testing.assert_allclose(np.asarray(state.band),
                                   np.asarray(batch.band), rtol=1e-4,
                                   atol=1e-3)
        np.testing.assert_allclose(np.asarray(online_estimate(state)),
                                   np.asarray(cov.banded_estimate(batch)),
                                   rtol=1e-4, atol=1e-4)

    def test_forgetting_discounts_history(self):
        """With beta<1 the estimate tracks the recent distribution."""
        key = jax.random.PRNGKey(1)
        a, b = _shifted_profile()
        old = _rounds(key, 30, 16, scales=a)
        new = _rounds(jax.random.PRNGKey(2), 30, 16, scales=b)
        st = online_init(P, H)
        st, _ = stream_covariance(st, old, forgetting=0.7, interpret=True)
        st, _ = stream_covariance(st, new, forgetting=0.7, interpret=True)
        est = np.asarray(online_estimate(st))
        variances = est[H]                  # center diagonal
        # energy must now sit on the second half of the sensors
        assert variances[P // 2:].mean() > 3 * variances[: P // 2].mean()

    def test_total_variance_matches_estimate_trace(self):
        xs = _rounds(jax.random.PRNGKey(3), 4, 16)
        st, _ = stream_covariance(online_init(P, H), xs, interpret=True)
        tr = float(online_total_variance(st))
        assert tr == pytest.approx(float(np.trace(
            np.asarray(cov.band_to_dense(online_estimate(st))))), rel=1e-5)

    def test_all_ones_mask_bit_identical_to_unmasked(self):
        """The masked-statistics fix must keep the all-alive path exact:
        every state leaf (including the new per-sensor counts) and the
        estimate are bit-identical between mask=None and an all-ones mask."""
        x = np.asarray(_rounds(jax.random.PRNGKey(4), 1, 16))[0]
        st0 = online_init(P, H)
        for masked in (np.ones(P, np.float32), np.ones((16, P), np.float32)):
            a = online_update(st0, jnp.asarray(x), forgetting=0.9,
                              interpret=True)
            b = online_update(st0, jnp.asarray(x), forgetting=0.9,
                              mask=jnp.asarray(masked), interpret=True)
            for leaf_a, leaf_b in zip(a, b):
                np.testing.assert_array_equal(np.asarray(leaf_a),
                                              np.asarray(leaf_b))
            np.testing.assert_array_equal(
                np.asarray(online_estimate(a)), np.asarray(online_estimate(b)))

    def test_dropout_mean_and_variance_unbiased(self):
        """The pre-fix path normalized every sensor by the ROUND count, so a
        sensor present in half the rows had its mean halved and its variance
        inflated by the phantom zero rows.  Per-sensor counts repair both:
        a constant present reading must estimate (mean=c, var=0)."""
        rng = np.random.default_rng(0)
        n, c = 64, 5.0
        x = rng.normal(size=(n, P)).astype(np.float32)
        x[:, 0] = c                                 # sensor 0: constant 5.0
        mask = np.ones((n, P), np.float32)
        mask[::2, 0] = 0.0                          # ... present in half rows
        st = online_update(online_init(P, H), jnp.asarray(x),
                           mask=jnp.asarray(mask), interpret=True)
        t_i = np.asarray(st.t_i)
        assert t_i[0] == n / 2 and t_i[1] == n      # per-sensor counts
        mean0 = float(st.s[0] / t_i[0])
        assert mean0 == pytest.approx(c, rel=1e-6)  # old path: c/2
        est = np.asarray(online_estimate(st))
        assert abs(est[H, 0]) < 1e-3                # old path: ~c^2/4
        # untouched sensors keep the plain sample statistics
        v1 = x[:, 1].var()
        assert est[H, 1] == pytest.approx(v1, rel=1e-3)

    def test_non_nested_dropout_cross_covariance_unbiased(self):
        """Two perfectly correlated sensors with OVERLAPPING but non-nested
        dropout: the cross-covariance must be normalized by the pairwise
        present count (the t_band fix), not the round count or
        min(t_i, t_j) — both of which shrink it toward zero."""
        rng = np.random.default_rng(7)
        n = 128
        x = rng.normal(size=(n, P)).astype(np.float32)
        x[:, 1] = x[:, 0]                       # corr(0, 1) = 1
        mask = np.ones((n, P), np.float32)
        mask[: n // 2, 0] = 0.0                 # sensor 0 absent first half
        mask[n // 4: 3 * n // 4, 1] = 0.0       # sensor 1 absent mid half
        both = (mask[:, 0] > 0) & (mask[:, 1] > 0)   # last quarter only
        st = online_update(online_init(P, H), jnp.asarray(x),
                           mask=jnp.asarray(mask), interpret=True)
        assert float(st.t_band[H + 1, 0]) == both.sum() == n // 4
        est = np.asarray(online_estimate(st))
        # the oracle: second-moment over the common rows minus the product
        # of each sensor's own-window mean
        m0 = x[mask[:, 0] > 0, 0].mean()
        m1 = x[mask[:, 1] > 0, 1].mean()
        want = (x[both, 0] * x[both, 1]).mean() - m0 * m1
        assert est[H + 1, 0] == pytest.approx(want, rel=1e-4)

    def test_death_wave_pairwise_counts_match_batch_oracle(self):
        """After a death wave, the covariance among the SURVIVORS must equal
        the batch estimate over all rounds, and entries pairing a survivor
        with a dead sensor must equal the batch estimate over the rounds
        both were alive (the pairwise t_band window)."""
        xs = np.asarray(_rounds(jax.random.PRNGKey(5), 8, 16))
        dead = [0, 1]
        st = online_init(P, H)
        for r in range(8):
            mask = np.ones(P, np.float32)
            if r >= 4:
                mask[dead] = 0.0                    # die at round 4, stay dead
            st = online_update(st, jnp.asarray(xs[r]),
                               mask=jnp.asarray(mask), interpret=True)
        est = np.asarray(online_estimate(st))
        flat = xs.reshape(-1, P)
        batch_all = np.asarray(cov.banded_estimate(
            cov.banded_update(cov.banded_init(P, H), jnp.asarray(flat))))
        # survivors-only entries: normalized over every round
        assert est[H, 4] == pytest.approx(batch_all[H, 4], rel=1e-3)
        # dead sensor's own variance: over its alive rounds only
        flat_alive = xs[:4].reshape(-1, P)
        batch_alive = np.asarray(cov.banded_estimate(
            cov.banded_update(cov.banded_init(P, H), jnp.asarray(flat_alive))))
        assert est[H, 0] == pytest.approx(batch_alive[H, 0], rel=1e-3)
        # cross entry survivor x dead: pairwise window = the alive rounds
        # (the survivor's mean is taken over its full history, so the mean
        # product differs from the alive-window oracle by O(sampling noise))
        assert est[H + 2, 0] == pytest.approx(batch_alive[H + 2, 0],
                                              abs=0.08)


class TestBatchedKernelWrapper:
    def test_matches_per_network_kernel(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (5, 16, P))
        out = ops.cov_band_update_batched(x, H, interpret=True)
        for i in range(5):
            np.testing.assert_allclose(
                np.asarray(out[i]),
                np.asarray(ops.cov_band_update(x[i], H, interpret=True)),
                rtol=1e-5, atol=1e-5)

    def test_rejects_unbatched_input(self):
        with pytest.raises(ValueError):
            ops.cov_band_update_batched(jnp.zeros((16, P)), H)


class TestScheduler:
    def _stream(self, cfg, xs):
        state = stream_init(cfg, jax.random.PRNGKey(7))
        return stream_run(cfg, state, xs)

    def test_stationary_stream_single_refresh(self):
        """Only the warmup refresh fires when the distribution is static."""
        cfg = StreamConfig(p=P, q=Q, halfwidth=H, forgetting=0.95,
                           drift_threshold=0.1, warmup_rounds=8,
                           interpret=True)
        a, _ = _shifted_profile()
        xs = _rounds(jax.random.PRNGKey(0), 60, 16, scales=a)
        final, metrics = self._stream(cfg, xs)
        assert int(final.sched.refreshes) == 1
        assert bool(metrics.did_refresh[cfg.warmup_rounds])

    def test_injected_shift_triggers_refresh(self):
        """A variance shift to new sensors must fire a second refresh."""
        cfg = StreamConfig(p=P, q=Q, halfwidth=H, forgetting=0.9,
                           drift_threshold=0.1, warmup_rounds=8,
                           interpret=True)
        a, b = _shifted_profile()
        xs = jnp.concatenate([
            _rounds(jax.random.PRNGKey(0), 30, 16, scales=a),
            _rounds(jax.random.PRNGKey(1), 30, 16, scales=b),
        ])
        final, metrics = self._stream(cfg, xs)
        fired = np.asarray(metrics.did_refresh)
        assert int(final.sched.refreshes) >= 2
        # the post-shift refresh happens after the shift round, not before
        assert fired[30:].any() and not fired[cfg.warmup_rounds + 1:30].any()
        # the post-shift refresh recovers retained variance: rho (measured
        # pre-refresh) jumps between the trigger round and the following
        # round.  Later drift-triggered refreshes under forgetting 0.9 sit
        # at the threshold, where fp32 rounding decides whether they fire,
        # so the check pins the refresh the shift caused
        rho = np.asarray(metrics.rho)
        post = 30 + int(np.argmax(fired[30:]))
        assert rho[post + 1] > rho[post]

    def test_refresh_books_table1_cost(self):
        cfg = StreamConfig(p=P, q=Q, halfwidth=H, warmup_rounds=2,
                           interpret=True)
        xs = _rounds(jax.random.PRNGKey(0), 6, 16)
        final, metrics = self._stream(cfg, xs)
        sched = cfg.scheduler()
        expected = (6 * sched.round_cost()
                    + int(final.sched.refreshes) * sched.refresh_cost(P))
        assert float(final.sched.comm_packets) == pytest.approx(expected)

    def test_refresh_recovers_eigh_subspace(self):
        """ortho_refresh from a stale basis lands on the eigh subspace."""
        from repro.streaming.scheduler import ortho_refresh
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(512, P)).astype(np.float32)
                        * np.linspace(3.0, 0.5, P)[None, :])
        st = online_update(online_init(P, H), x, interpret=True)
        band = online_estimate(st)
        W0 = jnp.linalg.qr(jax.random.normal(jax.random.PRNGKey(0), (P, Q)))[0]
        W = ortho_refresh(band, W0, iters=50)
        dense = np.asarray(cov.band_to_dense(band))
        evals, evecs = np.linalg.eigh(dense)
        top = evecs[:, np.argsort(-evals)[:Q]]
        # principal angles ~ 0: |top^T W| has singular values ~ 1
        sv = np.linalg.svd(top.T @ np.asarray(W), compute_uv=False)
        assert sv.min() > 0.99


class TestBatchedDriver:
    def _cfg(self):
        return StreamConfig(p=P, q=Q, halfwidth=H, forgetting=0.9,
                            drift_threshold=0.05, warmup_rounds=5,
                            interpret=True)

    def test_batched_agrees_with_per_network_loop(self):
        cfg = self._cfg()
        B = 4
        key = jax.random.PRNGKey(0)
        states = batched_stream_init(cfg, key, B)
        xsb = jax.random.normal(jax.random.PRNGKey(1), (B, 15, 8, P))
        finb, mb = batched_stream_run(cfg, states, xsb)
        for i in range(B):
            st_i = jax.tree.map(lambda a: a[i], states)
            fin_i, m_i = stream_run(cfg, st_i, xsb[i])
            np.testing.assert_allclose(np.asarray(fin_i.sched.W),
                                       np.asarray(finb.sched.W[i]),
                                       rtol=1e-4, atol=1e-4)
            assert int(fin_i.sched.refreshes) == int(finb.sched.refreshes[i])
            np.testing.assert_allclose(np.asarray(m_i.rho),
                                       np.asarray(mb.rho[i]),
                                       rtol=1e-4, atol=1e-4)

    def test_sharded_agrees_with_batched(self):
        cfg = self._cfg()
        B = 4
        states = batched_stream_init(cfg, jax.random.PRNGKey(0), B)
        xsb = jax.random.normal(jax.random.PRNGKey(1), (B, 12, 8, P))
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        fin_v, m_v = batched_stream_run(cfg, states, xsb)
        fin_s, m_s = sharded_stream_run(cfg, mesh, states, xsb)
        np.testing.assert_allclose(np.asarray(fin_v.sched.W),
                                   np.asarray(fin_s.sched.W),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(m_v.comm_packets),
                                   np.asarray(m_s.comm_packets))

    def test_network_axis_spec_rejects_unknown_axis(self):
        from repro.distributed.sharding import network_axis_spec
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        with pytest.raises(ValueError):
            network_axis_spec(mesh, "nonexistent")


class TestServeEngine:
    def test_continuous_batching_retires_all_streams(self):
        from repro.serve.engine import StreamingPCAEngine, StreamRequest
        cfg = StreamConfig(p=P, q=Q, halfwidth=H, forgetting=0.95,
                           drift_threshold=0.05, warmup_rounds=4,
                           interpret=True)
        eng = StreamingPCAEngine(cfg, slots=3, seed=0)
        rng = np.random.default_rng(0)
        reqs = [StreamRequest(rounds=rng.normal(
            size=(10 + 2 * i, 8, P)).astype(np.float32)) for i in range(5)]
        for r in reqs:
            eng.submit(r)
        eng.run_until_done()
        assert all(r.done for r in reqs)
        for r in reqs:
            assert r.result is not None
            assert r.result.refreshes >= 1          # warmup refresh at least
            assert r.result.comm_packets > 0
            assert r.result.components.shape == (P, Q)
            assert r.result.rounds == r.rounds.shape[0]

    def test_rejects_mismatched_network_size(self):
        from repro.serve.engine import StreamingPCAEngine, StreamRequest
        cfg = StreamConfig(p=P, q=Q, halfwidth=H, interpret=True)
        eng = StreamingPCAEngine(cfg, slots=2)
        with pytest.raises(ValueError):
            eng.submit(StreamRequest(rounds=np.zeros((4, 8, P + 1),
                                                     np.float32)))

    def test_rejects_heterogeneous_round_shape_and_empty_stream(self):
        """The device batch is shape-homogeneous: n is fixed by the first
        stream, and empty streams never enter a slot."""
        from repro.serve.engine import StreamingPCAEngine, StreamRequest
        cfg = StreamConfig(p=P, q=Q, halfwidth=H, interpret=True)
        eng = StreamingPCAEngine(cfg, slots=2)
        eng.submit(StreamRequest(rounds=np.zeros((3, 8, P), np.float32)))
        with pytest.raises(ValueError):
            eng.submit(StreamRequest(rounds=np.zeros((3, 16, P), np.float32)))
        with pytest.raises(ValueError):
            eng.submit(StreamRequest(rounds=np.zeros((0, 8, P), np.float32)))


class TestEngineFaultDeterminism:
    """Two engine runs with the same seed and fault schedule are identical:
    retirement order, bases (bitwise), and cost bills."""

    def _fault_requests(self):
        from repro.serve.engine import StreamRequest
        reqs = []
        for i in range(5):
            R = 18
            rng = np.random.default_rng(200 + i)
            live = np.ones((R, P), np.float32)
            if i == 1:       # total blackout at round 6, revival at round 12
                live[6:12, :] = 0.0
            if i == 3:       # permanent partial wave (stays above threshold)
                live[9:, :10] = 0.0
            if i == 4:       # dies for good at round 10
                live[10:, :] = 0.0
            rounds = (rng.normal(size=(R, 8, P)).astype(np.float32)
                      * np.linspace(4, 1, P, dtype=np.float32))
            reqs.append(StreamRequest(rounds=rounds, liveness=live))
        return reqs

    def _run(self):
        from repro.serve.engine import StreamingPCAEngine
        cfg = StreamConfig(p=P, q=Q, halfwidth=H, forgetting=0.9,
                           drift_threshold=0.1, warmup_rounds=4,
                           link_loss=0.1, interpret=True)
        eng = StreamingPCAEngine(cfg, slots=2, seed=0)
        reqs = self._fault_requests()
        for r in reqs:
            eng.submit(r)
        eng.run_until_done()
        return eng, reqs

    def test_two_runs_identical(self):
        eng1, reqs1 = self._run()
        eng2, reqs2 = self._run()
        order1 = [(reqs1.index(q), why) for q, why in eng1.retired_log]
        order2 = [(reqs2.index(q), why) for q, why in eng2.retired_log]
        assert order1 == order2
        assert eng1.plan_history == eng2.plan_history
        for a, b in zip(reqs1, reqs2):
            assert a.done and b.done
            assert a.result.reason == b.result.reason
            # bitwise: same jitted programs folded in the same order
            np.testing.assert_array_equal(a.result.components,
                                          b.result.components)
            assert a.result.comm_packets == b.result.comm_packets
            assert a.result.rounds == b.result.rounds
            assert len(a.retirements) == len(b.retirements)
            for ra, rb in zip(a.retirements, b.retirements):
                np.testing.assert_array_equal(ra.components, rb.components)
                assert ra.comm_packets == rb.comm_packets

    def test_fault_lifecycle(self):
        """The schedule above exercises every retirement path."""
        eng, reqs = self._run()
        assert reqs[0].result.reason == "completed" and not reqs[0].retirements
        # blackout + revival: one dead retirement, then completed
        assert len(reqs[1].retirements) == 1
        assert reqs[1].retirements[0].reason == "dead"
        assert reqs[1].result.reason == "completed"
        # partial wave above min_alive_fraction: survives to completion
        assert reqs[3].result.reason == "completed" and not reqs[3].retirements
        # permanent death: retired dead, never re-admitted; the partial IS
        # the final result (not duplicated into retirements)
        assert reqs[4].result.reason == "dead"
        assert reqs[4].result.rounds < reqs[4].rounds.shape[0]
        assert not reqs[4].retirements
        # the elastic planner saw the fleet drain below full occupancy and
        # re-planned down to the single-network mesh at the tail
        assert eng.plan_history[0].n_devices == 2
        assert eng.plan_history[-1].n_devices == 1
        assert len(eng.plan_history) >= 2


class TestStreamingCosts:
    def test_round_cost_positive_and_scales_with_q(self):
        c1 = costs.streaming_round_cost(8, 1, 4)
        c5 = costs.streaming_round_cost(8, 5, 4)
        assert 0 < c1.communication < c5.communication

    def test_refresh_dominates_round(self):
        """The design premise: a refresh costs >> one round (else scheduling
        would be pointless)."""
        round_c = costs.streaming_round_cost(8, 5, 4).communication
        refresh_c = costs.streaming_refresh_cost(52, 5, 8, 4, 8).communication
        assert refresh_c > 20 * round_c
