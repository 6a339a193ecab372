"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracle.

Sweeps shapes/dtypes per the deliverable spec and asserts allclose against
ref.py.  interpret=True executes the kernel bodies on CPU; the BlockSpec
tilings are the ones used on real TPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:        # optional dev dependency (requirements-dev)
    # no-op stand-ins so the module still imports; the property tests
    # themselves are skipped by the importorskip fixture below
    def given(*args, **kwargs):
        return lambda f: f

    def settings(*args, **kwargs):
        return lambda f: f

    class _StubStrategies:
        def integers(self, *args, **kwargs):
            return None

    st = _StubStrategies()

from repro.kernels import ops, ref

def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


def _check(a, b, dtype, scale: float = 1.0):
    """Kernels accumulate in fp32; for bf16 inputs the oracle is evaluated in
    fp32 too, and tolerance covers bf16 *input representation* error (~2^-8
    relative per operand) scaled by the reduction length."""
    if dtype == jnp.bfloat16:
        atol, rtol = 0.02 * max(scale, 1.0) ** 0.5, 2e-2
    else:
        atol, rtol = 1e-5 * max(scale, 1.0) ** 0.5, 1e-5
    np.testing.assert_allclose(
        np.asarray(a, np.float32), np.asarray(b, np.float32),
        atol=atol, rtol=rtol)


class TestBackendSelection:
    """Kernels compile on a TPU, run interpreted on the CPU, and refuse any
    other backend instead of silently interpreting there."""

    @pytest.mark.parametrize("backend,want", [("tpu", False), ("cpu", True)])
    def test_auto_interpret(self, monkeypatch, backend, want):
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        assert ops._auto_interpret(None) is want
        assert ops._auto_interpret(not want) is (not want)   # explicit wins

    def test_unknown_backend_raises(self, monkeypatch):
        monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
        with pytest.raises(ValueError, match="gpu"):
            ops._auto_interpret(None)


class TestBandedMatvec:
    @pytest.mark.parametrize("p,h,block_p", [
        (128, 1, 64), (256, 4, 128), (512, 8, 128), (384, 16, 128),
        (1024, 2, 512), (128, 0, 128),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_shapes_dtypes(self, p, h, block_p, dtype):
        k1, k2 = jax.random.split(jax.random.PRNGKey(p + h))
        band = _rand(k1, (2 * h + 1, p), dtype)
        v = _rand(k2, (p,), dtype)
        out = ops.banded_matvec(band, v, block_p=block_p, interpret=True)
        oracle = ref.banded_matvec(band.astype(jnp.float32),
                                   v.astype(jnp.float32))
        _check(out, oracle, dtype, scale=2 * h + 1)

    def test_matches_dense_matvec(self):
        from repro.core import covariance as cov
        rng = np.random.default_rng(0)
        p, h = 256, 4
        c = rng.normal(size=(p, p)).astype(np.float32)
        c = np.where(cov.mask_from_band(p, h), c, 0.0)
        band = cov.dense_to_band(jnp.asarray(c), h)
        v = jnp.asarray(rng.normal(size=p).astype(np.float32))
        out = ops.banded_matvec(band, v, interpret=True)
        np.testing.assert_allclose(np.asarray(out), c @ np.asarray(v),
                                   rtol=2e-4, atol=2e-4)


class TestBandedMatmul:
    @pytest.mark.parametrize("p,q,h,block_p", [
        (128, 4, 2, 64), (256, 16, 8, 128), (512, 32, 4, 256), (384, 8, 12, 128),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_shapes_dtypes(self, p, q, h, block_p, dtype):
        k1, k2 = jax.random.split(jax.random.PRNGKey(p * q + h))
        band = _rand(k1, (2 * h + 1, p), dtype)
        V = _rand(k2, (p, q), dtype)
        out = ops.banded_matmul(band, V, block_p=block_p, interpret=True)
        oracle = ref.banded_matmul(band.astype(jnp.float32),
                                   V.astype(jnp.float32))
        _check(out, oracle, dtype, scale=2 * h + 1)


class TestCovUpdate:
    @pytest.mark.parametrize("n,p,h,bp,bn", [
        (64, 128, 2, 64, 32), (128, 256, 8, 128, 64), (32, 512, 4, 256, 32),
        (96, 384, 1, 128, 32),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_shapes_dtypes(self, n, p, h, bp, bn, dtype):
        x = _rand(jax.random.PRNGKey(n + p), (n, p), dtype)
        out = ops.cov_band_update(x, h, block_p=bp, block_n=bn, interpret=True)
        expected = ref.cov_band_update(x.astype(jnp.float32), h)
        # fp32 accumulation in the kernel: compare fp32-cast input oracle
        tol = 1e-4 if dtype == jnp.float32 else 0.15
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                                   rtol=tol, atol=tol * 8 * n ** 0.5)

    def test_accumulation_over_batch_blocks(self):
        """Grid revisiting must equal a single-pass reduction."""
        x = _rand(jax.random.PRNGKey(3), (128, 128), jnp.float32)
        out1 = ops.cov_band_update(x, 3, block_p=64, block_n=128, interpret=True)
        out2 = ops.cov_band_update(x, 3, block_p=64, block_n=16, interpret=True)
        np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                                   rtol=1e-5, atol=1e-3)

    def test_matches_core_banded_update(self):
        from repro.core import covariance as cov
        x = _rand(jax.random.PRNGKey(4), (64, 256), jnp.float32)
        h = 5
        st_ = cov.banded_update(cov.banded_init(256, h), x)
        out = ops.cov_band_update(x, h, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(st_.band),
                                   rtol=1e-4, atol=1e-3)

    @pytest.mark.parametrize("B,n,p,h", [
        (3, 7, 37, 2),       # both prime: _pick_block falls back to 1
        (2, 10, 53, 3),      # n divisible by 2 only, p prime
        (4, 16, 48, 1),      # p divisible by 16 but not 128-aligned
    ])
    def test_batched_matches_per_network_loop_nondivisible(self, B, n, p, h):
        """Regression for _pick_block's fallback path: shapes where neither
        axis divides the preferred tile sizes must still agree with a
        per-network Python loop over the single-network kernel (and the
        oracle).  Pins the fallback-to-1 behavior for prime p."""
        from repro.kernels.ops import _pick_block
        if p in (37, 53):
            assert _pick_block(p) == 1           # the path under test
        x = _rand(jax.random.PRNGKey(B * n + p), (B, n, p), jnp.float32)
        out = ops.cov_band_update_batched(x, h, interpret=True)
        assert out.shape == (B, 2 * h + 1, p)
        for i in range(B):
            single = ops.cov_band_update(x[i], h, interpret=True)
            np.testing.assert_allclose(np.asarray(out[i]), np.asarray(single),
                                       rtol=1e-5, atol=1e-5)
            oracle = ref.cov_band_update(x[i], h)
            np.testing.assert_allclose(np.asarray(out[i]), np.asarray(oracle),
                                       rtol=1e-4, atol=1e-4)


class TestPcaProject:
    @pytest.mark.parametrize("n,p,q,bn,bk", [
        (128, 256, 8, 64, 128), (64, 512, 32, 32, 256), (256, 128, 4, 128, 64),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_project(self, n, p, q, bn, bk, dtype):
        k1, k2 = jax.random.split(jax.random.PRNGKey(n + p + q))
        x = _rand(k1, (n, p), dtype)
        w = _rand(k2, (p, q), dtype)
        out = ops.pca_project(x, w, block_n=bn, block_k=bk, interpret=True)
        expected = ref.pca_project(x.astype(jnp.float32), w.astype(jnp.float32))
        tol = 1e-4 if dtype == jnp.float32 else 0.1
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                                   rtol=tol, atol=tol * p ** 0.5)

    @pytest.mark.parametrize("n,p,q,bn,bp", [
        (128, 256, 8, 64, 128), (64, 512, 16, 32, 256),
    ])
    def test_reconstruct(self, n, p, q, bn, bp):
        k1, k2 = jax.random.split(jax.random.PRNGKey(1))
        z = _rand(k1, (n, q), jnp.float32)
        w = _rand(k2, (p, q), jnp.float32)
        out = ops.pca_reconstruct(z, w, block_n=bn, block_p=bp, interpret=True)
        _check(out, ref.pca_reconstruct(z, w), jnp.float32)

    @pytest.mark.parametrize("n,p,q", [
        (100, 97, 5),        # p prime: the old auto-pick tiled by 1
        (37, 53, 3),         # both prime
        (100, 48, 4),        # n awkward, p fine
    ])
    def test_project_reconstruct_nondivisible(self, n, p, q):
        """Regression: awkward (prime/odd) shapes must work through the
        padded wrappers and be BIT-IDENTICAL to the zero-padded kernel
        (the padded oracle): padded feature columns multiply zero basis
        rows, so every fp32 partial sum they add is exactly 0.0."""
        from repro.kernels.ops import _pad_dim, _pick_block_padded
        from repro.kernels.pca_project import (pca_project_pallas,
                                               pca_reconstruct_pallas)
        k1, k2 = jax.random.split(jax.random.PRNGKey(n * p + q))
        x = _rand(k1, (n, p), jnp.float32)
        w = _rand(k2, (p, q), jnp.float32)

        z = ops.pca_project(x, w, interpret=True)
        assert z.shape == (n, q)
        _check(z, ref.pca_project(x, w), jnp.float32, scale=p)
        bn = _pick_block_padded(n, 128)
        bk = _pick_block_padded(p, 512)
        xp = jnp.pad(x, ((0, _pad_dim(n, bn) - n), (0, _pad_dim(p, bk) - p)))
        wp = jnp.pad(w, ((0, _pad_dim(p, bk) - p), (0, 0)))
        oracle = pca_project_pallas(xp, wp, block_n=bn, block_k=bk,
                                    interpret=True)[:n]
        np.testing.assert_array_equal(np.asarray(z), np.asarray(oracle))

        xh = ops.pca_reconstruct(z, w, interpret=True)
        assert xh.shape == (n, p)
        _check(xh, ref.pca_reconstruct(z, w), jnp.float32, scale=q)
        bp = _pick_block_padded(p, 512)
        zp = jnp.pad(z, ((0, _pad_dim(n, bn) - n), (0, 0)))
        oracle_r = pca_reconstruct_pallas(zp, wp, block_n=bn, block_p=bp,
                                          interpret=True)[:n, :p]
        np.testing.assert_array_equal(np.asarray(xh), np.asarray(oracle_r))

    def test_explicit_nondividing_block_pads_instead_of_crashing(self):
        """An explicit block that does not divide the axis used to trip the
        kernel asserts; the wrappers now pad-to-block and slice."""
        k1, k2 = jax.random.split(jax.random.PRNGKey(7))
        x = _rand(k1, (100, 97), jnp.float32)
        w = _rand(k2, (97, 4), jnp.float32)
        z = ops.pca_project(x, w, block_n=32, block_k=64, interpret=True)
        _check(z, ref.pca_project(x, w), jnp.float32, scale=97)
        xh = ops.pca_reconstruct(z, w, block_n=32, block_p=64, interpret=True)
        _check(xh, ref.pca_reconstruct(z, w), jnp.float32, scale=4)

    def test_divisible_shapes_bit_identical_to_unpadded_kernel(self):
        """The padding path must be invisible on divisible shapes: the
        wrapper output equals the direct kernel call bit-for-bit."""
        from repro.kernels.pca_project import pca_project_pallas
        k1, k2 = jax.random.split(jax.random.PRNGKey(9))
        x = _rand(k1, (128, 256), jnp.float32)
        w = _rand(k2, (256, 8), jnp.float32)
        out = ops.pca_project(x, w, block_n=64, block_k=128, interpret=True)
        direct = pca_project_pallas(x, w, block_n=64, block_k=128,
                                    interpret=True)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(direct))

    def test_project_reconstruct_roundtrip_orthonormal(self):
        """W orthonormal + X in span(W)  =>  reconstruct(project(X)) == X."""
        rng = np.random.default_rng(0)
        p, q, n = 256, 16, 64
        W = np.linalg.qr(rng.normal(size=(p, q)))[0].astype(np.float32)
        Z0 = rng.normal(size=(n, q)).astype(np.float32)
        X = Z0 @ W.T
        z = ops.pca_project(jnp.asarray(X), jnp.asarray(W), interpret=True)
        xh = ops.pca_reconstruct(z, jnp.asarray(W), interpret=True)
        np.testing.assert_allclose(np.asarray(xh), X, rtol=1e-4, atol=1e-4)


class TestKernelProperties:
    """Hypothesis sweeps over irregular (but block-divisible) shapes."""

    @pytest.fixture(autouse=True)
    def _require_hypothesis(self):
        pytest.importorskip("hypothesis")

    @settings(max_examples=20, deadline=None)
    @given(pb=st.integers(1, 8), h=st.integers(0, 6), seed=st.integers(0, 2**16))
    def test_matvec_property(self, pb, h, seed):
        p = 64 * pb
        key = jax.random.PRNGKey(seed)
        k1, k2 = jax.random.split(key)
        band = _rand(k1, (2 * h + 1, p), jnp.float32)
        v = _rand(k2, (p,), jnp.float32)
        out = ops.banded_matvec(band, v, block_p=64, interpret=True)
        _check(out, ref.banded_matvec(band, v), jnp.float32)

    @settings(max_examples=15, deadline=None)
    @given(nb=st.integers(1, 4), pb=st.integers(1, 4), q=st.integers(1, 24),
           seed=st.integers(0, 2**16))
    def test_project_property(self, nb, pb, q, seed):
        n, p = 32 * nb, 64 * pb
        k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
        x = _rand(k1, (n, p), jnp.float32)
        w = _rand(k2, (p, q), jnp.float32)
        out = ops.pca_project(x, w, block_n=32, block_k=64, interpret=True)
        _check(out, ref.pca_project(x, w), jnp.float32)

    @settings(max_examples=10, deadline=None)
    @given(h=st.integers(0, 5), seed=st.integers(0, 2**16))
    def test_cov_update_symmetry(self, h, seed):
        """band[h+k, i] == band[h-k, i+k] (S_ij == S_ji)."""
        x = _rand(jax.random.PRNGKey(seed), (32, 128), jnp.float32)
        band = np.asarray(ops.cov_band_update(x, h, interpret=True))
        p = 128
        for k in range(1, h + 1):
            lhs = band[h + k, : p - k]     # S_{i, i+k}
            rhs = band[h - k, k:]          # S_{i+k, i}
            np.testing.assert_allclose(lhs, rhs, rtol=1e-4, atol=1e-3)
