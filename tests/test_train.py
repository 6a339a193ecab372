"""Training substrate: optimizer, data pipeline, checkpoint/resume (bitwise),
fault-injection restart, elastic planning, health monitoring."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.data.tokens import TokenPipeline
from repro.runtime.elastic import plan_mesh
from repro.runtime.health import HealthMonitor, StragglerPolicy
from repro.train import checkpoint as CKPT
from repro.train.optimizer import (AdamWConfig, adamw_init, adamw_update,
                                   warmup_cosine)
from repro.train.trainer import TrainConfig, Trainer

SMOKE = configs.get("llama3.2-1b").smoke()


def _tiny_pipeline(**kw):
    return TokenPipeline(vocab_size=SMOKE.vocab_size, seq_len=16,
                         global_batch=4, seed=1, **kw)


class TestOptimizer:
    def test_adamw_reduces_quadratic(self):
        w = {"w": jnp.asarray(np.random.default_rng(0)
                              .normal(size=(8, 8)).astype(np.float32))}
        cfg = AdamWConfig(lr=0.05, weight_decay=0.0)
        st = adamw_init(w, cfg)

        def loss(p):
            return jnp.sum(p["w"] ** 2)

        start = float(loss(w))
        for _ in range(80):
            g = jax.grad(loss)(w)
            w, st, _ = adamw_update(w, g, st, cfg, cfg.lr)
        assert float(loss(w)) < 1e-2 * start

    def test_warmup_cosine_shape(self):
        lrs = [float(warmup_cosine(jnp.asarray(s), peak_lr=1.0, warmup=10,
                                   total=100)) for s in range(100)]
        assert lrs[0] == 0.0
        assert abs(lrs[10] - 1.0) < 0.11
        assert lrs[99] < 0.2
        assert max(lrs) <= 1.0 + 1e-6

    def test_grad_clip(self):
        from repro.train.optimizer import clip_by_global_norm, global_norm
        g = {"a": jnp.ones((100,)) * 10}
        clipped, norm = clip_by_global_norm(g, 1.0)
        assert float(norm) == pytest.approx(100.0)
        assert float(global_norm(clipped)) == pytest.approx(1.0, rel=1e-5)


class TestDataPipeline:
    def test_deterministic(self):
        p1, p2 = _tiny_pipeline(), _tiny_pipeline()
        np.testing.assert_array_equal(next(p1), next(p2))

    def test_resume_cursor(self):
        p1 = _tiny_pipeline()
        next(p1); next(p1)
        state = p1.state_dict()
        p2 = _tiny_pipeline()
        p2.load_state_dict(state)
        np.testing.assert_array_equal(next(p1), next(p2))

    def test_host_sharding_partitions_batch(self):
        full = _tiny_pipeline().batch_at(0)
        h0 = TokenPipeline(vocab_size=SMOKE.vocab_size, seq_len=16,
                           global_batch=4, seed=1, host_id=0, n_hosts=2)
        h1 = TokenPipeline(vocab_size=SMOKE.vocab_size, seq_len=16,
                           global_batch=4, seed=1, host_id=1, n_hosts=2)
        np.testing.assert_array_equal(
            np.concatenate([h0.batch_at(0), h1.batch_at(0)]), full)

    def test_has_learnable_structure(self):
        """Bigram structure: next-token entropy < unigram entropy."""
        p = TokenPipeline(vocab_size=64, seq_len=512, global_batch=8, seed=0)
        toks = p.batch_at(0).ravel()
        # a simple predictor: most common successor of previous token
        from collections import Counter, defaultdict
        succ = defaultdict(Counter)
        for a, b in zip(toks[:-1], toks[1:]):
            succ[a][b] += 1
        correct = sum(c.most_common(1)[0][1] for c in succ.values())
        acc = correct / (len(toks) - 1)
        assert acc > 0.2   # far above 1/64 chance


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        tree = {"a": jnp.arange(10.0), "b": {"c": jnp.ones((3, 4))}}
        CKPT.save(str(tmp_path), 5, tree, extra={"step": 5})
        out, extra = CKPT.restore(str(tmp_path), tree)
        assert extra["step"] == 5
        np.testing.assert_array_equal(np.asarray(out["a"]),
                                      np.asarray(tree["a"]))

    def test_roundtrip_bfloat16(self, tmp_path):
        """Regression: np.savez stores bfloat16 as raw |V2 void bytes, and
        restore used to die with 'No cast function available' — breaking
        EVERY resume of a bf16 training run (examples/train_lm.py).  The
        manifest's dtype record now reinterprets the bytes."""
        tree = {"w": jnp.arange(12.0, dtype=jnp.bfloat16).reshape(3, 4),
                "b": jnp.ones((2,), jnp.float32)}
        CKPT.save(str(tmp_path), 1, tree)
        out, _ = CKPT.restore(str(tmp_path), tree)
        assert out["w"].dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(out["w"], np.float32), np.asarray(tree["w"],
                                                         np.float32))

    def test_atomic_no_partial_visible(self, tmp_path):
        tree = {"a": jnp.ones((4,))}
        CKPT.save(str(tmp_path), 1, tree)
        # simulate a crash leaving a tmp dir behind
        os.makedirs(tmp_path / "step_00000002.tmp")
        assert CKPT.latest_step(str(tmp_path)) == 1

    def test_retention_gc(self, tmp_path):
        tree = {"a": jnp.ones((2,))}
        for s in range(6):
            CKPT.save(str(tmp_path), s, tree, keep=3)
        steps = sorted(int(n[5:]) for n in os.listdir(tmp_path))
        assert steps == [3, 4, 5]

    def test_shape_mismatch_raises(self, tmp_path):
        CKPT.save(str(tmp_path), 1, {"a": jnp.ones((4,))})
        with pytest.raises(CKPT.CheckpointError, match="shape mismatch"):
            CKPT.restore(str(tmp_path), {"a": jnp.ones((5,))})

    def test_elastic_reshard_placement(self, tmp_path):
        """Restore under a different sharding than the save used."""
        from jax.sharding import NamedSharding, PartitionSpec
        tree = {"w": jnp.arange(16.0).reshape(4, 4)}
        CKPT.save(str(tmp_path), 1, tree)
        mesh = jax.make_mesh((1,), ("data",))
        sh = {"w": NamedSharding(mesh, PartitionSpec("data", None))}
        out, _ = CKPT.restore(str(tmp_path), tree, shardings=sh)
        assert out["w"].sharding == sh["w"]
        np.testing.assert_array_equal(np.asarray(out["w"]),
                                      np.asarray(tree["w"]))


class TestTrainerEndToEnd:
    def _make(self, tmp_path, **tkw):
        tcfg = TrainConfig(optimizer=AdamWConfig(lr=1e-3),
                           warmup_steps=2, total_steps=50,
                           checkpoint_dir=str(tmp_path), checkpoint_every=5,
                           remat=False, **tkw)
        return Trainer(SMOKE, tcfg, _tiny_pipeline(),
                       key=jax.random.PRNGKey(0))

    def test_loss_decreases(self, tmp_path):
        tr = self._make(tmp_path)
        hist = tr.run(20, log_every=0)
        first = np.mean([h["loss"] for h in hist[:4]])
        last = np.mean([h["loss"] for h in hist[-4:]])
        assert last < first

    def test_bitwise_resume_after_crash(self, tmp_path):
        """Train 10, 'crash', resume from step 10, continue to 15 — losses
        must match an uninterrupted 15-step run exactly."""
        tr1 = self._make(tmp_path / "a")
        tr1.run(15, log_every=0)
        losses_full = [h["loss"] for h in tr1.history]

        tr2 = self._make(tmp_path / "b")
        tr2.run(10, log_every=0)
        tr2.save(async_=False)
        # crash: rebuild everything from scratch and resume
        tr3 = self._make(tmp_path / "b")
        assert tr3.try_resume()
        assert tr3.state.step == 10
        tr3.run(5, log_every=0)
        losses_resumed = [h["loss"] for h in tr2.history] + \
            [h["loss"] for h in tr3.history]
        np.testing.assert_allclose(losses_full, losses_resumed,
                                   rtol=0, atol=0)

    def test_compressed_training_converges(self, tmp_path):
        tcfg = TrainConfig(optimizer=AdamWConfig(lr=1e-3), warmup_steps=2,
                           total_steps=50, compress_rank=4, remat=False)
        tr = Trainer(SMOKE, tcfg, _tiny_pipeline(), key=jax.random.PRNGKey(0))
        hist = tr.run(20, log_every=0)
        assert hist[-1]["loss"] < hist[0]["loss"]

    def test_microbatched_equals_full_batch_loss_scale(self, tmp_path):
        """Gradient accumulation: same data, same first-step loss."""
        tcfg1 = TrainConfig(optimizer=AdamWConfig(lr=0.0), warmup_steps=1,
                            total_steps=5, microbatches=1, remat=False)
        tcfg2 = TrainConfig(optimizer=AdamWConfig(lr=0.0), warmup_steps=1,
                            total_steps=5, microbatches=2, remat=False)
        t1 = Trainer(SMOKE, tcfg1, _tiny_pipeline(), key=jax.random.PRNGKey(0))
        t2 = Trainer(SMOKE, tcfg2, _tiny_pipeline(), key=jax.random.PRNGKey(0))
        h1 = t1.run(1, log_every=0)
        h2 = t2.run(1, log_every=0)
        assert h1[0]["loss"] == pytest.approx(h2[0]["loss"], rel=1e-4)


class TestRuntime:
    def test_straggler_detection(self):
        mon = HealthMonitor(StragglerPolicy(straggler_factor=2.0,
                                            min_samples=4))
        for s in range(8):
            mon.heartbeat(step=s, duration=1.0)
        mon.heartbeat(step=9, duration=5.0)
        assert mon.straggler_count() == 1

    def test_stall_detection(self):
        now = [0.0]
        mon = HealthMonitor(StragglerPolicy(stall_timeout=10.0),
                            clock=lambda: now[0])
        mon.heartbeat(step=1, duration=1.0)
        now[0] = 5.0
        assert not mon.stalled()
        now[0] = 20.0
        assert mon.stalled()

    def test_elastic_plan(self):
        plan = plan_mesh(192, prefer_model=16, global_batch=256)
        assert plan.n_devices == 192
        assert plan.model == 16
        assert plan.global_batch % plan.data == 0
        # odd device counts still yield a plan
        plan2 = plan_mesh(7, prefer_model=16, global_batch=256)
        assert plan2.n_devices == 7
