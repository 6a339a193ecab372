"""The engine's bucketed fleet refresh (DESIGN.md Sec. 8.2).

The engine's chunk step refreshes only the slots whose trigger fired, B at
a time (``bucket_size``), where the vmapped ``chunk_stream_step`` lowers
the scheduler's cond to a select that refreshes every slot.  Per slot the
two must agree: the books and counters exactly, the basis to the rounding
of batched ``eigh``/``cholesky`` at another batch size, and a slot that did
not fire keeps its scheduler state bit for bit.  Checked here at S=16
slots (B=8) for fired counts of 0, fewer than B, B, more than B and S.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serve.engine import StreamingPCAEngine, StreamRequest
from repro.streaming.compressor import CompressionConfig
from repro.streaming.detector import DetectionConfig
from repro.streaming.driver import (StreamConfig, chunk_stream_step,
                                    engine_chunk_step_fn, stream_init)
from repro.streaming.scheduler import bucket_size

S, K, N, P, Q, H = 16, 2, 4, 16, 2, 2
B = 8
WARMUP = 4
FIRED_COUNTS = (0, 3, B, 11, S)


def _cfg() -> StreamConfig:
    # drift never triggers: which slots fire is set by each test (the
    # unconditional first refresh, or churn on the masked step)
    return StreamConfig(p=P, q=Q, halfwidth=H, forgetting=0.9,
                        drift_threshold=10.0, warmup_rounds=WARMUP,
                        interpret=True,
                        compression=CompressionConfig(epsilon=0.5),
                        detection=DetectionConfig(alpha=1e-3,
                                                  calib_rounds=3))


@pytest.fixture(scope="module")
def warm():
    """A fleet that has folded WARMUP rounds and never refreshed."""
    cfg = _cfg()
    keys = jax.random.split(jax.random.PRNGKey(0), S)
    states = jax.vmap(lambda k: stream_init(cfg, k))(keys)
    rng = np.random.default_rng(0)
    scale = np.linspace(3.0, 1.0, P, dtype=np.float32)
    xs = [jnp.asarray(rng.normal(size=(S, K, N, P)).astype(np.float32)
                      * scale) for _ in range(WARMUP // K + 1)]
    rv = jnp.ones((S, K), jnp.float32)
    ref = jax.jit(jax.vmap(
        lambda s, x, r: chunk_stream_step(cfg, s, x, round_valid=r)))
    for x in xs[:-1]:
        states, metrics = ref(states, x, rv)
        assert not bool(jnp.any(metrics.did_refresh))
    return cfg, states, xs[-1], rv


def _firing(n_fired: int) -> np.ndarray:
    fired = np.zeros(S, bool)
    fired[np.random.default_rng(n_fired).permutation(S)[:n_fired]] = True
    return fired


def _compare(states, fired, ref_out, out):
    (r_new, r_m), (f_new, f_m) = ref_out, out
    np.testing.assert_array_equal(np.asarray(f_m.did_refresh), fired)
    np.testing.assert_array_equal(np.asarray(r_m.did_refresh), fired)
    for name in ("refreshes", "comm_packets"):
        np.testing.assert_array_equal(
            np.asarray(getattr(f_new.sched, name)),
            np.asarray(getattr(r_new.sched, name)), err_msg=name)
    np.testing.assert_array_equal(np.asarray(f_new.rounds),
                                  np.asarray(r_new.rounds))
    for name in ("W", "lam", "rho_ref"):
        np.testing.assert_allclose(
            np.asarray(getattr(f_new.sched, name)),
            np.asarray(getattr(r_new.sched, name)),
            rtol=1e-5, atol=1e-5, err_msg=name)
    keep = ~fired
    for name in ("W", "lam", "rho_ref", "refreshes"):
        np.testing.assert_array_equal(
            np.asarray(getattr(f_new.sched, name))[keep],
            np.asarray(getattr(states.sched, name))[keep], err_msg=name)
    n = int(fired.sum())
    assert int(f_m.refresh_slots) == -(-n // B) * B


@pytest.mark.parametrize("n_fired", FIRED_COUNTS)
def test_unmasked_step_refreshes_only_the_fired(warm, n_fired):
    cfg, states, x, rv = warm
    fired = _firing(n_fired)
    # a slot that already refreshed once does not fire again (drift is off)
    states = states._replace(sched=states.sched._replace(
        refreshes=jnp.asarray(np.where(fired, 0, 1), jnp.int32)))
    ref = jax.jit(jax.vmap(
        lambda s, xc, r: chunk_stream_step(cfg, s, xc, round_valid=r)))
    ref_out = ref(states, x, rv)
    out = engine_chunk_step_fn(cfg)(jax.tree.map(jnp.copy, states), x, rv)
    _compare(states, fired, ref_out, out)


@pytest.mark.parametrize("n_fired", FIRED_COUNTS)
def test_masked_step_refreshes_only_the_fired(warm, n_fired):
    cfg, states, x, rv = warm
    fired = _firing(n_fired)
    # every slot has refreshed once; churn (a sensor lost in the chunk's
    # second round) fires the chosen ones
    states = states._replace(sched=states.sched._replace(
        refreshes=jnp.ones((S,), jnp.int32)))
    masks = np.ones((S, K, P), np.float32)
    masks[fired, 1, 0] = 0.0
    masks = jnp.asarray(masks)
    ref = jax.jit(jax.vmap(
        lambda s, xc, m, r: chunk_stream_step(cfg, s, xc, m, r)))
    ref_out = ref(states, x, masks, rv)
    out = engine_chunk_step_fn(cfg, masked=True)(
        jax.tree.map(jnp.copy, states), x, masks, rv)
    _compare(states, fired, ref_out, out)


def test_refresh_fired_leaves_the_others_bit_for_bit(warm):
    cfg, states, _, _ = warm
    fired = _firing(11)
    new, computed = cfg.scheduler().refresh_fired(
        states.sched, states.cov, jnp.asarray(fired))
    for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(states.sched)):
        np.testing.assert_array_equal(np.asarray(a)[~fired],
                                      np.asarray(b)[~fired])
    np.testing.assert_array_equal(np.asarray(new.refreshes)[fired], 1)
    assert int(computed) == 2 * B


@pytest.mark.parametrize("n_fired", FIRED_COUNTS)
def test_refresh_fired_computes_whole_buckets_of_the_fired(warm, n_fired):
    """No slot fired: no pass and no slot computed; f fired: ceil(f/B)
    passes of B slots."""
    cfg, states, _, _ = warm
    fired = _firing(n_fired)
    new, computed = cfg.scheduler().refresh_fired(
        states.sched, states.cov, jnp.asarray(fired))
    assert int(computed) == -(-n_fired // B) * B
    np.testing.assert_array_equal(np.asarray(new.refreshes),
                                  fired.astype(np.int32))


@pytest.mark.parametrize("slots, want", [(1, 1), (2, 2), (8, 8), (12, 8),
                                         (16, 8), (200, 16), (256, 16),
                                         (1000, 64), (1024, 64)])
def test_bucket_size_follows_the_slot_count(slots, want):
    assert bucket_size(slots) == want


def test_engine_counts_fired_and_computed_slots():
    cfg = _cfg()
    eng = StreamingPCAEngine(cfg, slots=S, seed=0, chunk=K, telemetry=True)
    rng = np.random.default_rng(3)
    reqs = [StreamRequest(rounds=rng.normal(
        size=(int(r), N, P)).astype(np.float32))
        for r in rng.integers(WARMUP + 1, 4 * WARMUP, size=S + 4)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_done()
    fired = sum(r.result.refreshes for r in reqs)
    assert fired >= len(reqs)               # every stream refreshed once
    counts = eng.refresh_slots
    assert counts["fired"] == fired
    assert counts["computed"] % B == 0
    assert fired <= counts["computed"] < fired + B * eng._clock
    summary = eng.telemetry.summary()
    assert summary["refresh_fired_slots"] == fired
    assert summary["refresh_computed_slots"] == counts["computed"]
    assert summary["refresh_bucket_fill"] == fired / counts["computed"]
