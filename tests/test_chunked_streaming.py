"""Chunked streaming path: fused multi-round cov kernel, chunk driver, engine.

The contract under test (ISSUE 5 / DESIGN.md Sec. 12):
1. the chunk kernel matches the weighted-sum oracle (ref.py) on divisible,
   non-divisible and masked shapes, and at K=1/w=1 is BIT-identical to the
   per-round kernel,
2. ``chunked_stream_run(..., probe_every=1)`` is bit-identical to
   ``stream_run`` — states and metrics, masked and unmasked, with
   forgetting < 1 and with compression/detection stages attached,
3. chunk mode keeps the per-epoch cost booking exact (booked == counted)
   including K∤R tail chunks,
4. the chunk body is structurally one cov launch + one refresh select per
   chunk (the amortization claim, verified on the jaxpr),
5. the chunked engine retires streams exactly like the per-round engine.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.streaming import (
    CompressionConfig, DetectionConfig, StreamConfig, batched_stream_run,
    chunked_stream_run, online_init, online_update, online_update_chunk,
    sharded_stream_run, stream_init, stream_run,
)
from repro.streaming.driver import batched_stream_init, chunk_stream_step

P, H, Q = 32, 4, 3


def _rounds(key, n_rounds, n, p=P):
    return jax.random.normal(key, (n_rounds, n, p)) \
        * jnp.linspace(4.0, 1.0, p)[None, None, :]


def _assert_trees_equal(a, b, msg=""):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=msg)


class TestChunkKernel:
    def test_matches_weighted_oracle(self):
        rng = np.random.default_rng(0)
        xs = jnp.asarray(rng.normal(size=(5, 8, P)).astype(np.float32))
        w = jnp.asarray((0.9 ** np.arange(4, -1, -1)).astype(np.float32))
        out = ops.cov_band_update_chunk(xs, w, H, interpret=True)
        want = ref.cov_band_update_chunk(xs, w, H)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_k1_w1_bit_identical_to_per_round(self):
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.normal(size=(1, 16, P)).astype(np.float32))
        one = ops.cov_band_update_chunk(x, jnp.ones(1), H, interpret=True)
        per = ops.cov_band_update(x[0], H, interpret=True)
        np.testing.assert_array_equal(np.asarray(one), np.asarray(per))
        # masked variant against the masked per-round kernel
        m = jnp.asarray((rng.random((1, P)) > 0.3).astype(np.float32))
        onem = ops.cov_band_update_chunk(x, jnp.ones(1), H, mask=m,
                                         interpret=True)
        perm = ops.cov_band_update_masked(x[0], m[0], H, interpret=True)
        np.testing.assert_array_equal(np.asarray(onem), np.asarray(perm))

    def test_masked_and_nondivisible_shapes(self):
        """Prime p and odd n take the pad-to-block path (zero-weight pad
        rows, sliced feature pad) and still match the oracle; liveness
        (K, p) and dropout (K, n, p) masks both work."""
        rng = np.random.default_rng(2)
        for (k, n, p, h) in ((3, 5, 29, 3), (4, 8, 32, 4), (2, 7, 16, 2)):
            xs = jnp.asarray(rng.normal(size=(k, n, p)).astype(np.float32))
            w = jnp.asarray((0.8 ** np.arange(k - 1, -1, -1))
                            .astype(np.float32))
            out = ops.cov_band_update_chunk(xs, w, h, interpret=True)
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(ref.cov_band_update_chunk(
                    xs, w, h)), rtol=1e-4, atol=1e-4)
            for mshape in ((k, p), (k, n, p)):
                m = jnp.asarray((rng.random(mshape) > 0.25)
                                .astype(np.float32))
                got = ops.cov_band_update_chunk(xs, w, h, mask=m,
                                                interpret=True)
                np.testing.assert_allclose(
                    np.asarray(got),
                    np.asarray(ref.cov_band_update_chunk_masked(xs, m, w, h)),
                    rtol=1e-4, atol=1e-4)

    def test_zero_weight_rounds_contribute_nothing(self):
        rng = np.random.default_rng(3)
        xs = jnp.asarray(rng.normal(size=(4, 8, P)).astype(np.float32))
        w = jnp.asarray([1.0, 0.0, 0.5, 0.0], jnp.float32)
        out = ops.cov_band_update_chunk(xs, w, H, interpret=True)
        want = ref.cov_band_update_chunk(xs[:3:2], w[:3:2], H)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_batched_matches_per_network(self):
        rng = np.random.default_rng(4)
        xb = jnp.asarray(rng.normal(size=(3, 4, 8, P)).astype(np.float32))
        w = jnp.asarray((0.9 ** np.arange(3, -1, -1)).astype(np.float32))
        ob = ops.cov_band_update_chunk_batched(xb, w, H, interpret=True)
        for i in range(3):
            np.testing.assert_allclose(
                np.asarray(ob[i]),
                np.asarray(ops.cov_band_update_chunk(xb[i], w, H,
                                                     interpret=True)),
                rtol=1e-6, atol=1e-6)

    def test_rejects_bad_shapes(self):
        with pytest.raises(ValueError):
            ops.cov_band_update_chunk(jnp.zeros((8, P)), jnp.ones(8), H)
        with pytest.raises(ValueError):
            ops.cov_band_update_chunk(jnp.zeros((2, 8, P)), jnp.ones(3), H)
        with pytest.raises(ValueError):
            ops.cov_band_update_chunk(jnp.zeros((2, 8, P)), jnp.ones(2), H,
                                      mask=jnp.ones((3, P)))


class TestChunkedOnlineCov:
    def test_chunk_fold_equals_sequential_fold(self):
        """One fused chunk == K sequential per-round updates (allclose:
        the decay powers are folded differently) for every mask flavor."""
        rng = np.random.default_rng(5)
        xs = jnp.asarray(rng.normal(size=(6, 8, P)).astype(np.float32))
        masks_l = jnp.asarray((rng.random((6, P)) > 0.2).astype(np.float32))
        masks_d = jnp.asarray((rng.random((6, 8, P)) > 0.2)
                              .astype(np.float32))
        for masks in (None, masks_l, masks_d):
            seq = online_init(P, H)
            for t in range(6):
                m = None if masks is None else masks[t]
                seq = online_update(seq, xs[t], forgetting=0.9, mask=m,
                                    interpret=True)
            chk = online_update_chunk(online_init(P, H), xs, forgetting=0.9,
                                      masks=masks, interpret=True)
            for a, b in zip(seq, chk):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-4, atol=1e-4)

    def test_round_valid_tail_equals_short_chunk(self):
        """Pad rounds flagged invalid are absent: fold(xs[:4] padded to 6,
        rv=[1,1,1,1,0,0]) == fold(xs[:4])."""
        rng = np.random.default_rng(6)
        xs = jnp.asarray(rng.normal(size=(4, 8, P)).astype(np.float32))
        padded = jnp.concatenate([xs, jnp.zeros((2, 8, P))], axis=0)
        rv = jnp.asarray([1, 1, 1, 1, 0, 0], jnp.float32)
        a = online_update_chunk(online_init(P, H), xs, forgetting=0.9,
                                interpret=True)
        b = online_update_chunk(online_init(P, H), padded, forgetting=0.9,
                                round_valid=rv, interpret=True)
        for x, y in zip(a, b):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=1e-6, atol=1e-6)


class TestProbeEveryOneDifferential:
    """The acceptance pin: chunked_stream_run(K, probe_every=1) must be
    BIT-identical to stream_run — every state leaf and every metric leaf."""

    def _cfg(self, **kw):
        base = dict(p=P, q=Q, halfwidth=H, forgetting=0.9,
                    drift_threshold=0.05, warmup_rounds=5, interpret=True)
        base.update(kw)
        return StreamConfig(**base)

    @pytest.mark.parametrize("chunk", [2, 4, 5])
    def test_plain(self, chunk):
        cfg = self._cfg()
        xs = _rounds(jax.random.PRNGKey(0), 14, 8)
        st = stream_init(cfg, jax.random.PRNGKey(7))
        _assert_trees_equal(stream_run(cfg, st, xs),
                            chunked_stream_run(cfg, st, xs, chunk=chunk,
                                               probe_every=1),
                            f"chunk={chunk}")

    def test_masked_and_forgetting(self):
        cfg = self._cfg(forgetting=0.8)
        xs = _rounds(jax.random.PRNGKey(1), 13, 8)
        masks = (jax.random.uniform(jax.random.PRNGKey(2), (13, P)) > 0.2) \
            .astype(jnp.float32)
        st = stream_init(cfg, jax.random.PRNGKey(8))
        _assert_trees_equal(
            stream_run(cfg, st, xs, masks),
            chunked_stream_run(cfg, st, xs, masks, chunk=4, probe_every=1),
            "masked")

    def test_with_compression_and_detection(self):
        cfg = self._cfg(
            compression=CompressionConfig(epsilon=0.5, score_bits=4),
            detection=DetectionConfig(alpha=1e-3, calib_rounds=4),
            link_loss=0.1)
        xs = _rounds(jax.random.PRNGKey(3), 12, 8)
        st = stream_init(cfg, jax.random.PRNGKey(9))
        _assert_trees_equal(
            stream_run(cfg, st, xs),
            chunked_stream_run(cfg, st, xs, chunk=3, probe_every=1),
            "stages")

    def test_batched_and_sharded_threading(self):
        cfg = self._cfg()
        B = 4
        states = batched_stream_init(cfg, jax.random.PRNGKey(0), B)
        xsb = jax.random.normal(jax.random.PRNGKey(1), (B, 12, 8, P))
        # the per-round select form the fleet body replaced, kept here as
        # the reference
        _assert_trees_equal(
            jax.vmap(lambda s, x: stream_run(cfg, s, x))(states, xsb),
            batched_stream_run(cfg, states, xsb),
            "batched")
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        fin_b, m_b = batched_stream_run(cfg, states, xsb, chunk=4)
        fin_s, m_s = sharded_stream_run(cfg, mesh, states, xsb, chunk=4)
        _assert_trees_equal((fin_b, m_b), (fin_s, m_s), "sharded")


class TestChunkModeSemantics:
    def _cfg(self, **kw):
        base = dict(p=P, q=Q, halfwidth=H, forgetting=0.9,
                    drift_threshold=0.05, warmup_rounds=5, interpret=True)
        base.update(kw)
        return StreamConfig(**base)

    def test_tail_chunk_booked_equals_counted(self):
        """K∤R: the tail chunk folds and books only its real rounds —
        total bill is exactly R round records + refreshes refresh floods."""
        cfg = self._cfg()
        R = 14                                       # 3 full chunks + tail 2
        xs = _rounds(jax.random.PRNGKey(4), R, 8)
        st = stream_init(cfg, jax.random.PRNGKey(10))
        fin, metrics = chunked_stream_run(cfg, st, xs, chunk=4)
        assert int(fin.rounds) == R
        assert metrics.rho.shape == (4,)             # one row per decision
        sched = cfg.scheduler()
        expected = (R * sched.round_cost()
                    + int(fin.sched.refreshes) * sched.refresh_cost(P))
        assert float(fin.sched.comm_packets) == pytest.approx(expected,
                                                              rel=1e-6)

    def test_tail_with_stages_booked_equals_counted(self):
        comp = CompressionConfig(epsilon=0.4, score_bits=4,
                                 emit_reconstruction=False)
        det = DetectionConfig(alpha=1e-3, calib_rounds=3,
                              emit_statistics=False)
        cfg = self._cfg(compression=comp, detection=det)
        R = 11                                       # 2 full chunks + tail 3
        xs = _rounds(jax.random.PRNGKey(5), R, 8)
        st = stream_init(cfg, jax.random.PRNGKey(11))
        fin, metrics = chunked_stream_run(cfg, st, xs, chunk=4)
        from repro.streaming.compressor import compression_round_cost
        from repro.streaming.detector import detection_packet_split
        sched = cfg.scheduler()
        flagfree_c = compression_round_cost(Q, cfg.c_max, comp)
        flagfree_d, per_alarm = detection_packet_split(Q, cfg.c_max)
        extras = float(np.asarray(metrics.compression.extra_packets).sum())
        alarms = float(np.asarray(metrics.detection.alarms).sum())
        expected = (R * (sched.round_cost() + flagfree_c + flagfree_d)
                    + int(fin.sched.refreshes) * sched.refresh_cost(P)
                    + extras + alarms * per_alarm)
        assert float(fin.sched.comm_packets) == pytest.approx(expected,
                                                              rel=1e-5)

    def test_chunk_compression_metrics_scale_per_epoch(self):
        """The fixed A/F record (and its bits) is per EPOCH: a chunk's
        metrics row must carry live×(A+F), not one record per dispatch —
        summed over the run, booked bits == R fixed floods + the run's own
        flagged extras."""
        from repro.streaming.compressor import epoch_packet_split
        comp = CompressionConfig(epsilon=0.4, score_bits=4,
                                 emit_reconstruction=False)
        cfg = self._cfg(compression=comp)
        R = 11                                       # K∤R tail included
        xs = _rounds(jax.random.PRNGKey(8), R, 8)
        st = stream_init(cfg, jax.random.PRNGKey(14))
        _, metrics = chunked_stream_run(cfg, st, xs, chunk=4)
        a_pk, f_pk = epoch_packet_split(Q, cfg.c_max, comp)
        extras = float(np.asarray(metrics.compression.extra_packets).sum())
        want_bits = (a_pk + f_pk) * comp.word_bits * R \
            + extras * comp.word_bits
        got_bits = float(np.asarray(metrics.compression.bits_on_air).sum())
        assert got_bits == pytest.approx(want_bits, rel=1e-6)
        assert float(np.asarray(
            metrics.compression.score_packets).sum()) \
            == pytest.approx(a_pk * R, rel=1e-6)

    def test_chunk_cov_state_matches_per_round_fold(self):
        """Decisions are amortized but the covariance is not: after R
        rounds the chunked covariance equals the per-round fold."""
        cfg = self._cfg()
        xs = _rounds(jax.random.PRNGKey(6), 14, 8)
        st = stream_init(cfg, jax.random.PRNGKey(12))
        fin_c, _ = chunked_stream_run(cfg, st, xs, chunk=4)
        fin_r, _ = stream_run(cfg, st, xs)
        for a, b in zip(fin_r.cov, fin_c.cov):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)

    def test_probe_every_validation(self):
        cfg = self._cfg()
        xs = _rounds(jax.random.PRNGKey(0), 8, 8)
        st = stream_init(cfg, jax.random.PRNGKey(0))
        with pytest.raises(ValueError):
            chunked_stream_run(cfg, st, xs, chunk=4, probe_every=3)
        with pytest.raises(ValueError):
            chunked_stream_run(cfg, st, xs, chunk=0)

    def test_churn_triggers_at_chunk_boundary(self):
        """A mid-chunk death wave must still raise the churn trigger at
        the next boundary decision."""
        cfg = self._cfg(drift_threshold=10.0)        # drift never triggers
        R = 16
        xs = _rounds(jax.random.PRNGKey(7), R, 8)
        masks = np.ones((R, P), np.float32)
        masks[10:, :8] = 0.0                         # death inside chunk 2
        st = stream_init(cfg, jax.random.PRNGKey(13))
        fin, metrics = chunked_stream_run(cfg, st, xs,
                                          jnp.asarray(masks), chunk=4)
        fired = np.asarray(metrics.did_refresh)
        assert bool(fired[2])                        # boundary after round 10
        assert int(fin.sched.refreshes) >= 2         # warmup + churn


class TestLaunchCounts:
    """The structural amortization claim: ONE cov pallas launch and at most
    one refresh select (eigh) per chunk body, independent of K."""

    @staticmethod
    def _count(jaxpr, names):
        # shared recursive walker (repro.analysis) — descends into cond
        # branches, scan/while bodies, pjit calls and shard_map sub-jaxprs
        from repro.analysis.jaxpr_lint import count_primitives
        return count_primitives(jaxpr, names)

    def test_one_launch_one_select_per_chunk(self):
        cfg = StreamConfig(p=P, q=Q, halfwidth=H, forgetting=0.9,
                           warmup_rounds=4, interpret=True)
        st = stream_init(cfg, jax.random.PRNGKey(0))
        for K in (1, 4, 8):
            jx = jax.make_jaxpr(
                lambda s, x: chunk_stream_step(cfg, s, x))(
                st, jnp.zeros((K, 8, P)))
            counts = self._count(jx.jaxpr, {"pallas_call", "eigh"})
            assert counts.get("pallas_call", 0) == 1, (K, counts)
            assert counts.get("eigh", 0) <= 1, (K, counts)


class TestChunkedEngine:
    def _cfg(self):
        return StreamConfig(p=P, q=Q, halfwidth=H, forgetting=0.95,
                            drift_threshold=0.05, warmup_rounds=4,
                            interpret=True)

    def test_chunked_engine_retires_all_streams_exact_rounds(self):
        from repro.serve.engine import StreamingPCAEngine, StreamRequest
        eng = StreamingPCAEngine(self._cfg(), slots=3, seed=0, chunk=4)
        rng = np.random.default_rng(0)
        reqs = [StreamRequest(rounds=rng.normal(
            size=(9 + 3 * i, 8, P)).astype(np.float32)) for i in range(5)]
        for r in reqs:
            eng.submit(r)
        eng.run_until_done()
        assert all(r.done for r in reqs)
        for r in reqs:
            # tails shorter than the chunk fold only their real rounds
            assert r.result.rounds == r.rounds.shape[0]
            assert r.result.refreshes >= 1
            assert r.result.comm_packets > 0

    def test_chunk1_engine_bitwise_matches_per_round_driver(self):
        """chunk=1 keeps the engine on the per-round trajectory: a
        single-slot engine reproduces stream_run's books exactly and its
        basis to fp32 rounding."""
        from repro.serve.engine import StreamingPCAEngine, StreamRequest
        cfg = self._cfg()
        eng = StreamingPCAEngine(cfg, slots=1, seed=0, chunk=1)
        rng = np.random.default_rng(1)
        req = StreamRequest(rounds=rng.normal(
            size=(12, 8, P)).astype(np.float32))
        eng.submit(req)
        eng.run_until_done()
        st = stream_init(cfg, jax.random.split(jax.random.PRNGKey(0), 1)[0])
        fin, _ = stream_run(cfg, st, jnp.asarray(req.rounds))
        # the engine's vmapped cond→select refresh batches eigh/cholesky,
        # which rounds differently than the driver's unbatched cond branch
        np.testing.assert_allclose(req.result.components,
                                   np.asarray(fin.sched.W),
                                   rtol=1e-5, atol=1e-5)
        assert req.result.comm_packets == float(fin.sched.comm_packets)
        assert req.result.refreshes == int(fin.sched.refreshes)

    def test_chunked_engine_books_match_chunked_driver(self):
        """A single-slot chunked engine == chunked_stream_run with the
        same chunk (the engine is the driver plus slot management)."""
        from repro.serve.engine import StreamingPCAEngine, StreamRequest
        cfg = self._cfg()
        K = 4
        eng = StreamingPCAEngine(cfg, slots=1, seed=0, chunk=K)
        rng = np.random.default_rng(2)
        req = StreamRequest(rounds=rng.normal(
            size=(14, 8, P)).astype(np.float32))     # K∤R tail
        eng.submit(req)
        eng.run_until_done()
        st = stream_init(cfg, jax.random.split(jax.random.PRNGKey(0), 1)[0])
        fin, _ = chunked_stream_run(cfg, st, jnp.asarray(req.rounds),
                                    chunk=K)
        # books and counters are exact; the basis is allclose only — the
        # engine's vmapped cond→select refresh batches eigh/cholesky, which
        # rounds differently than the driver's unbatched cond branch
        np.testing.assert_allclose(req.result.components,
                                   np.asarray(fin.sched.W),
                                   rtol=1e-5, atol=1e-5)
        assert req.result.comm_packets == float(fin.sched.comm_packets)
        assert req.result.rounds == int(fin.rounds)
        assert req.result.refreshes == int(fin.sched.refreshes)

    def test_chunked_engine_deterministic_with_faults(self):
        from repro.serve.engine import StreamingPCAEngine, StreamRequest

        def run():
            cfg = StreamConfig(p=P, q=Q, halfwidth=H, forgetting=0.9,
                               drift_threshold=0.1, warmup_rounds=4,
                               link_loss=0.1, interpret=True)
            eng = StreamingPCAEngine(cfg, slots=2, seed=0, chunk=3)
            reqs = []
            for i in range(4):
                rng = np.random.default_rng(300 + i)
                live = np.ones((17, P), np.float32)
                if i == 1:
                    live[6:12, :] = 0.0              # blackout + revival
                if i == 3:
                    live[9:, :] = 0.0                # dies for good
                reqs.append(StreamRequest(
                    rounds=rng.normal(size=(17, 8, P)).astype(np.float32),
                    liveness=live))
            for r in reqs:
                eng.submit(r)
            eng.run_until_done()
            return reqs

        r1, r2 = run(), run()
        for a, b in zip(r1, r2):
            assert a.done and b.done
            assert a.result.reason == b.result.reason
            np.testing.assert_array_equal(a.result.components,
                                          b.result.components)
            assert a.result.comm_packets == b.result.comm_packets
