"""Distribution layer: sharding rules, gradient compression, halo exchange,
pipeline schedule (single-device semantics + multi-device via shard_map where
the 1-device mesh suffices)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec

from repro.distributed import compression as C
from repro.distributed.sharding import (act_rules, logical_to_pspec,
                                        param_rules)
from repro.models.params import P, param_pspecs


class TestShardingRules:
    def test_param_pspecs_divisibility(self):
        schema = {
            "wq": P((4096, 128, 128), ("embed", "heads", "head_dim")),
            "wk": P((4096, 4, 128), ("embed", "kv_heads", "head_dim")),
        }
        specs = param_pspecs(schema, param_rules(multi_pod=False),
                             mesh_axis_sizes={"data": 16, "model": 16})
        assert specs["wq"] == PartitionSpec("data", "model", None)
        # 4 kv heads cannot shard over 16-way model axis -> replicated
        assert specs["wk"] == PartitionSpec("data", None, None)

    def test_multi_pod_fsdp_axes(self):
        schema = {"w": P((8192, 8192), ("embed", "mlp"))}
        specs = param_pspecs(schema, param_rules(multi_pod=True),
                             mesh_axis_sizes={"pod": 2, "data": 16,
                                              "model": 16})
        assert specs["w"] == PartitionSpec(("pod", "data"), "model")

    def test_act_rules_seq_sharding(self):
        spec = logical_to_pspec(("batch", "seq", "act_embed"),
                                act_rules(multi_pod=False, seq_shard=True))
        assert spec == PartitionSpec("data", "data", None) or \
            spec == PartitionSpec(("data",), ("data",), None)


class TestGradCompression:
    def _fake_grads(self, key):
        k1, k2, k3 = jax.random.split(key, 3)
        return {
            "w1": jax.random.normal(k1, (64, 96)),
            "stacked": jax.random.normal(k2, (4, 48, 64)),
            "bias": jax.random.normal(k3, (96,)),
        }

    def test_rank_r_exact_on_rank_r_matrix(self):
        """A rank-r gradient is reproduced exactly after 1-2 iterations."""
        rng = np.random.default_rng(0)
        u = rng.normal(size=(64, 4)).astype(np.float32)
        v = rng.normal(size=(96, 4)).astype(np.float32)
        g = {"w": jnp.asarray(u @ v.T)}
        state = C.init_compressor(g, rank=4, key=jax.random.PRNGKey(0))
        for _ in range(3):
            out, state = C.compress_gradients(g, state)
        np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(g["w"]),
                                   rtol=1e-3, atol=1e-3)

    def test_error_feedback_accumulates_residual(self):
        g = self._fake_grads(jax.random.PRNGKey(1))
        state = C.init_compressor(g, rank=2, key=jax.random.PRNGKey(2))
        out, state2 = C.compress_gradients(g, state)
        # compressed + error == original (up to fp32 rounding)
        err = state2.error["w1"]
        np.testing.assert_allclose(np.asarray(out["w1"] + err),
                                   np.asarray(g["w1"]), rtol=1e-4, atol=1e-4)

    def test_small_leaves_pass_through(self):
        g = self._fake_grads(jax.random.PRNGKey(3))
        state = C.init_compressor(g, rank=2, key=jax.random.PRNGKey(4))
        out, _ = C.compress_gradients(g, state)
        np.testing.assert_array_equal(np.asarray(out["bias"]),
                                      np.asarray(g["bias"]))

    def test_stacked_leading_dims(self):
        g = self._fake_grads(jax.random.PRNGKey(5))
        state = C.init_compressor(g, rank=2, key=jax.random.PRNGKey(6))
        out, state2 = C.compress_gradients(g, state)
        assert out["stacked"].shape == (4, 48, 64)
        assert state2.q["stacked"].shape == (4, 64, 2)

    def test_error_feedback_sgd_converges(self):
        """Least squares by compressed-gradient SGD reaches the solution —
        the error-feedback guarantee that makes the scheme production-safe."""
        rng = np.random.default_rng(7)
        A = rng.normal(size=(128, 32)).astype(np.float32) / np.sqrt(128)
        w_true = rng.normal(size=(32, 16)).astype(np.float32)
        Y = A @ w_true
        w = {"w": jnp.zeros((32, 16))}
        state = C.init_compressor(w, rank=2, key=jax.random.PRNGKey(8))
        lr = 0.3
        for _ in range(600):
            grad = {"w": jnp.asarray(A.T @ (A @ np.asarray(w["w"]) - Y))}
            cg, state = C.compress_gradients(grad, state)
            w = {"w": w["w"] - lr * cg["w"]}
        rel = np.linalg.norm(np.asarray(w["w"]) - w_true) / np.linalg.norm(w_true)
        assert rel < 0.05, rel

    def test_compression_ratio(self):
        g = {"w": jnp.zeros((1024, 1024)), "b": jnp.zeros((1024,))}
        ratio = C.compression_ratio(g, rank=4)
        # 1024*1024 -> 4*2048 (+1024 exact) ~ 0.0088
        assert ratio < 0.02


class TestHaloExchange:
    def test_single_device_ring(self):
        """halo_exchange on a 1-element axis: no neighbors -> zeros."""
        from repro.core.aggregation import halo_exchange
        mesh = jax.make_mesh((1,), ("p",))

        def f(x):
            l, r = halo_exchange(x, 2, "p")
            return l, r

        x = jnp.arange(8.0).reshape(1, 8)
        fm = jax.shard_map(f, mesh=mesh,
                           in_specs=PartitionSpec("p", None),
                           out_specs=(PartitionSpec("p", None),
                                      PartitionSpec("p", None)))
        l, r = fm(x)
        np.testing.assert_array_equal(np.asarray(l), np.zeros((1, 2)))
        np.testing.assert_array_equal(np.asarray(r), np.zeros((1, 2)))


class TestPipeline:
    def test_bubble_fraction(self):
        from repro.distributed.pipeline import bubble_fraction
        assert bubble_fraction(4, 12) == pytest.approx(3 / 15)
        assert bubble_fraction(1, 8) == 0.0

    def test_single_stage_identity(self):
        """With one stage the pipeline is just layer_fn over microbatches."""
        from repro.distributed.pipeline import pipeline_apply
        mesh = jax.make_mesh((1,), ("pipe",))
        w = jnp.asarray(np.random.default_rng(0).normal(size=(8, 8))
                        .astype(np.float32))
        x = jnp.asarray(np.random.default_rng(1).normal(size=(4, 8))
                        .astype(np.float32))

        def layer(p, h):
            return jnp.tanh(h @ p)

        def run(p, h):
            return pipeline_apply(layer, p, h, n_microbatches=2,
                                  axis_name="pipe")

        fm = jax.shard_map(run, mesh=mesh,
                           in_specs=(PartitionSpec(), PartitionSpec()),
                           out_specs=PartitionSpec(), check_vma=False)
        out = fm(w, x)
        np.testing.assert_allclose(np.asarray(out),
                                   np.tanh(np.asarray(x) @ np.asarray(w)),
                                   rtol=1e-5, atol=1e-5)
