"""One-pass fused streaming mega-kernel: differential + structural suite.

The contract under test (ISSUE 7 / DESIGN.md Sec. 14):
1. the fused kernel's outputs agree at fp32 with the three split kernels
   it replaces (cov_band_update_chunk + supervised_compress + pca_monitor)
   to fp32 rounding (:data:`FP32_TOL`; the flags exactly) — divisible,
   non-divisible/prime-p, masked, zero-weight tail shapes — and are
   tolerance-bounded against the jnp oracle,
2. the pure-jnp stage twin (the driver's post-refresh fix-up) agrees with
   the kernel's stage outputs to fp32 rounding, fp32 and bf16 tiles, at
   multi-block shapes,
3. the fused driver path is bit-identical to the split path — states and
   metrics, per-round and chunked, masked and unmasked, through refresh
   rounds — and ``probe_every=1`` reproduces ``stream_run`` exactly,
4. the chunked step with compression AND detection traces to exactly ONE
   ``pallas_call`` per chunk body (cond branches included) — down from 3,
5. bf16 tile mode runs the same program within tolerance of fp32,
6. satellite regressions: the per-round cov wrappers pad prime/odd p to
   the target block (no silent block_p=1 tiling), kernel wrappers honour
   an explicit out_dtype, the bf16 checkpoint round-trip holds through
   the fused path, and the roofline tile targets are backend-aware.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.launch.tiling import block_targets
from repro.streaming import (
    CompressionConfig, DetectionConfig, StreamConfig, batched_stream_run,
    chunked_stream_run, stream_init, stream_run,
)
from repro.streaming.driver import batched_stream_init, chunk_stream_step
from repro.train import checkpoint


# two different programs computing the same fp32 arithmetic (fused vs split
# kernels, kernel vs jnp twin) agree to a few ulps of the largest entry
FP32_TOL = dict(rtol=1e-5, atol=1e-5)


def _assert_trees_equal(a, b, msg=""):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=msg)


def _operands(rows, p, q, seed=0, masked=False, zero_tail=False):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(rows, p)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.2, 1.0, size=(rows,)), jnp.float32)
    if zero_tail:
        w = w.at[-max(rows // 4, 1):].set(0.0)
    basis, _ = jnp.linalg.qr(
        jnp.asarray(rng.normal(size=(p, q)), jnp.float32))
    mean = jnp.asarray(rng.normal(size=(p,)), jnp.float32)
    il = jnp.asarray(rng.uniform(0.5, 2.0, size=(q,)), jnp.float32)
    mask = jnp.asarray(rng.random((rows, p)) > 0.2, jnp.float32) \
        if masked else None
    return x, w, basis, mean, il, mask


# jit with operands as ARGUMENTS (the wrappers' real calling structure):
# closure-constant jits compile different programs and void the bit claims
def _run_fused(x, w, basis, mean, il, mask, *, h, eps, precision="fp32"):
    f = jax.jit(functools.partial(
        ops.fused_stream_update, halfwidth=h, epsilon=eps,
        with_compress=True, with_monitor=True, precision=precision))
    if mask is None:
        return f(x, w, basis, mean, il)
    return f(x, w, basis, mean, il, mask=mask)


def _run_split(x, w, basis, mean, il, mask, *, h, eps):
    n_rows, p = x.shape

    def split(x, w, basis, mean, il, *m):
        mk = m[0] if m else None
        band = ops.cov_band_update_chunk(
            x[:, None, :], w, h,
            mask=mk[:, None, :] if mk is not None else None)
        z, xh, fl = ops.supervised_compress(x, basis, mean, epsilon=eps,
                                            mask=mk)
        _, t2, spe = ops.pca_monitor(x, basis, mean, il, mask=mk)
        return band, z, xh, fl, t2, spe

    f = jax.jit(split)
    if mask is None:
        return f(x, w, basis, mean, il)
    return f(x, w, basis, mean, il, mask)


SHAPES = [
    (32, 24, 4, False, False),   # divisible everything
    (32, 24, 4, True, False),    # masked
    (15, 17, 3, False, False),   # non-divisible rows, prime p
    (15, 17, 3, True, True),     # prime p, masked, zero-weight tail
    (8, 8, 2, False, True),      # K=1-sized, zero-weight tail
    (1, 8, 2, False, False),     # single row (probe_every=1 shape)
    (40, 12, 3, True, False),    # multi-row-block, masked
]


class TestFusedKernelDifferential:
    @pytest.mark.parametrize("rows,p,q,masked,zt", SHAPES)
    def test_fp32_bitwise_vs_split_kernels(self, rows, p, q, masked, zt):
        x, w, basis, mean, il, mask = _operands(rows, p, q, seed=rows + p,
                                                masked=masked, zero_tail=zt)
        got = _run_fused(x, w, basis, mean, il, mask, h=2, eps=0.4)
        want = _run_split(x, w, basis, mean, il, mask, h=2, eps=0.4)
        for name, a, b in zip(("band", "z", "x_hat", "flags", "t2", "spe"),
                              got, want):
            check = (np.testing.assert_array_equal if name == "flags"
                     else functools.partial(np.testing.assert_allclose,
                                            **FP32_TOL))
            check(np.asarray(a), np.asarray(b),
                  err_msg=f"{name} rows={rows} p={p} masked={masked}")

    @pytest.mark.parametrize("rows,p,q,masked,zt", SHAPES[:4])
    def test_matches_jnp_oracle(self, rows, p, q, masked, zt):
        x, w, basis, mean, il, mask = _operands(rows, p, q, seed=3,
                                                masked=masked, zero_tail=zt)
        band, z, xh, fl, t2, spe = _run_fused(x, w, basis, mean, il, mask,
                                              h=2, eps=0.4)
        oband, oz, oxh, ofl, ot2, ospe = ref.fused_stream(
            x, w, basis, mean, il, 2, 0.4, mask=mask)
        np.testing.assert_allclose(band, oband, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(z, oz, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(xh, oxh, rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(fl), np.asarray(ofl))
        np.testing.assert_allclose(t2, ot2, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(spe, ospe, rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("precision", ["fp32", "bf16"])
    @pytest.mark.parametrize("rows,p,q,masked,zt",
                             [(32, 24, 4, False, False),
                              (14, 32, 3, True, False),
                              (40, 8, 2, False, True)])
    def test_twin_bitwise_matches_kernel_stages(self, precision, rows, p, q,
                                                masked, zt):
        x, w, basis, mean, il, mask = _operands(rows, p, q, seed=7,
                                                masked=masked, zero_tail=zt)
        _, z, xh, fl, t2, spe = _run_fused(x, w, basis, mean, il, mask,
                                           h=2, eps=0.4, precision=precision)
        twin = jax.jit(functools.partial(
            ops.fused_stream_stages_blocked, epsilon=0.4,
            with_compress=True, with_monitor=True, precision=precision))
        if mask is None:
            tz, txh, tfl, tt2, tspe = twin(x, basis, mean, il)
        else:
            tz, txh, tfl, tt2, tspe = twin(x, basis, mean, il, mask=mask)
        for name, a, b in zip(("z", "x_hat", "flags", "t2", "spe"),
                              (z, xh, fl, t2, spe),
                              (tz, txh, tfl, tt2, tspe)):
            check = (np.testing.assert_array_equal if name == "flags"
                     else functools.partial(np.testing.assert_allclose,
                                            **FP32_TOL))
            check(np.asarray(a), np.asarray(b),
                  err_msg=f"twin {name} precision={precision}")

    def test_bf16_tolerance_vs_fp32(self):
        x, w, basis, mean, il, _ = _operands(32, 24, 4, seed=11)
        f32 = _run_fused(x, w, basis, mean, il, None, h=2, eps=0.4)
        b16 = _run_fused(x, w, basis, mean, il, None, h=2, eps=0.4,
                         precision="bf16")
        for a, b in zip(f32[:3], b16[:3]):      # band, z, x_hat
            assert b.dtype == jnp.float32       # fp32 accumulators out
            scale = float(jnp.max(jnp.abs(a))) + 1e-6
            assert float(jnp.max(jnp.abs(a - b))) / scale < 0.02

    def test_band_only_rejected(self):
        x, w, basis, mean, il, _ = _operands(8, 8, 2)
        with pytest.raises(AssertionError):
            ops.fused_stream_update(x, w, basis, mean, il, halfwidth=2,
                                    with_compress=False, with_monitor=False)


class TestFusedDriverDifferential:
    P, Q, H, N, R = 12, 3, 2, 4, 24

    def _cfg(self, comp=True, det=True, **kw):
        return StreamConfig(
            p=self.P, q=self.Q, halfwidth=self.H, forgetting=0.97,
            warmup_rounds=4, link_loss=0.05,
            compression=CompressionConfig(epsilon=0.5) if comp else None,
            detection=DetectionConfig(alpha=1e-3, calib_rounds=3)
            if det else None, **kw)

    def _stream(self, seed=1):
        rng = np.random.default_rng(seed)
        xs = jnp.asarray(rng.normal(size=(self.R, self.N, self.P)),
                         jnp.float32)
        masks = jnp.asarray(rng.random((self.R, self.P)) > 0.15,
                            jnp.float32)
        return xs, masks

    @pytest.mark.parametrize("comp,det", [(True, False), (False, True),
                                          (True, True)])
    @pytest.mark.parametrize("masked", [False, True])
    @pytest.mark.parametrize("chunk,probe", [(4, None), (4, 1), (5, None)])
    def test_fused_bitwise_matches_split(self, comp, det, masked, chunk,
                                         probe):
        cfg = self._cfg(comp, det)
        cfg_split = dataclasses.replace(cfg, fused=False)
        xs, masks = self._stream()
        m = masks if masked else None
        key = jax.random.PRNGKey(0)
        got = chunked_stream_run(cfg, stream_init(cfg, key), xs, m,
                                 chunk=chunk, probe_every=probe)
        want = chunked_stream_run(cfg_split, stream_init(cfg_split, key),
                                  xs, m, chunk=chunk, probe_every=probe)
        # the runs must actually exercise refreshes, or the cond'd twin
        # fix-up (the hard half of the parity claim) is never on trial
        assert bool(jnp.any(got[1].did_refresh))
        _assert_trees_equal(got, want,
                            f"fused vs split comp={comp} det={det} "
                            f"masked={masked} chunk={chunk} probe={probe}")

    @pytest.mark.parametrize("masked", [False, True])
    def test_probe_every_one_reproduces_stream_run(self, masked):
        cfg = self._cfg()
        xs, masks = self._stream()
        m = masks if masked else None
        key = jax.random.PRNGKey(0)
        want = stream_run(cfg, stream_init(cfg, key), xs, m)
        got = chunked_stream_run(cfg, stream_init(cfg, key), xs, m,
                                 chunk=4, probe_every=1)
        _assert_trees_equal(got, want, f"probe_every=1 masked={masked}")

    def test_batched_fused_bitwise_matches_split(self):
        cfg = self._cfg()
        cfg_split = dataclasses.replace(cfg, fused=False)
        rng = np.random.default_rng(5)
        B = 3
        xsb = jnp.asarray(rng.normal(size=(B, 16, self.N, self.P)),
                          jnp.float32)
        states = batched_stream_init(cfg, jax.random.PRNGKey(0), B)
        states_s = batched_stream_init(cfg_split, jax.random.PRNGKey(0), B)
        got = batched_stream_run(cfg, states, xsb, chunk=4)
        want = batched_stream_run(cfg_split, states_s, xsb, chunk=4)
        _assert_trees_equal(got, want, "batched fused vs split")

    def test_quantized_scores_keep_split_path(self):
        # score_bits > 0 needs whole-round scales between projection and
        # reconstruction: the config must route to the split path and stay
        # bit-identical whatever cfg.fused says
        cfg = self._cfg(comp=False, det=True)
        cfg = dataclasses.replace(
            cfg, compression=CompressionConfig(epsilon=0.5, score_bits=4))
        cfg_split = dataclasses.replace(cfg, fused=False)
        xs, _ = self._stream()
        key = jax.random.PRNGKey(0)
        got = chunked_stream_run(cfg, stream_init(cfg, key), xs, chunk=4)
        want = chunked_stream_run(cfg_split, stream_init(cfg_split, key),
                                  xs, chunk=4)
        _assert_trees_equal(got, want, "quantized config")

    def test_bf16_driver_tolerance(self):
        cfg = self._cfg()
        cfg_bf = dataclasses.replace(cfg, precision="bf16")
        xs, _ = self._stream()
        key = jax.random.PRNGKey(0)
        s32, _ = chunked_stream_run(cfg, stream_init(cfg, key), xs, chunk=4)
        s16, _ = chunked_stream_run(cfg_bf, stream_init(cfg_bf, key), xs,
                                    chunk=4)
        band32, band16 = s32.cov.band, s16.cov.band
        scale = float(jnp.max(jnp.abs(band32))) + 1e-6
        assert float(jnp.max(jnp.abs(band32 - band16))) / scale < 0.02

    def test_bad_precision_rejected(self):
        with pytest.raises(ValueError):
            self._cfg(precision="fp16")


# recursive jaxpr primitive counting now lives in the shared analysis walker
from repro.analysis.jaxpr_lint import count_primitive as _count_primitive


class TestFusedLaunchStructure:
    def _cfg(self, fused=True):
        return StreamConfig(
            p=12, q=3, halfwidth=2, warmup_rounds=4,
            compression=CompressionConfig(epsilon=0.5),
            detection=DetectionConfig(alpha=1e-3, calib_rounds=3),
            fused=fused)

    @pytest.mark.parametrize("K", [1, 4, 8])
    def test_one_pallas_call_per_chunk_body(self, K):
        cfg = self._cfg()
        st = stream_init(cfg, jax.random.PRNGKey(0))
        xc = jnp.zeros((K, 4, 12), jnp.float32)
        jx = jax.make_jaxpr(
            lambda s, x: chunk_stream_step(cfg, s, x))(st, xc)
        # recursive count: lax.cond branches (the twin fix-up) included
        assert _count_primitive(jx.jaxpr, "pallas_call") == 1

    def test_split_path_pays_three(self):
        cfg = self._cfg(fused=False)
        st = stream_init(cfg, jax.random.PRNGKey(0))
        xc = jnp.zeros((4, 4, 12), jnp.float32)
        jx = jax.make_jaxpr(
            lambda s, x: chunk_stream_step(cfg, s, x))(st, xc)
        assert _count_primitive(jx.jaxpr, "pallas_call") == 3


class TestPrimePBlockRegression:
    """Satellite 1: the per-round cov wrappers must pad prime/odd p to the
    block target instead of falling through the divisor ladder to
    block_p=1 (an up-to-512x tiling degradation the chunk path already
    avoided)."""

    @pytest.mark.parametrize("p", [17, 23, 51])
    def test_per_round_pads_prime_p(self, p):
        rng = np.random.default_rng(p)
        x = jnp.asarray(rng.normal(size=(16, p)), jnp.float32)
        got = ops.cov_band_update(x, 2)
        want = ref.cov_band_update(x, 2)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        # bit-exactness of the internal pad: identical to padding the
        # features externally to the picked block and slicing the band
        bp = ops.kernel_block_plan("cov", p=p)["block_p"]
        assert bp > 1, "prime p fell through to block_p=1 again"
        pad = (-p) % bp
        xp = jnp.pad(x, ((0, 0), (0, pad)))
        ext = ops.cov_band_update(xp, 2)[:, :p]
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ext))

    def test_masked_per_round_pads_prime_p(self):
        p = 17
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(16, p)), jnp.float32)
        mask = jnp.asarray(rng.random((16, p)) > 0.3, jnp.float32)
        got = ops.cov_band_update_masked(x, mask, 2)
        want = ref.cov_band_update_masked(x, mask, 2)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_chunk_k1_bitwise_matches_per_round_at_prime_p(self):
        p = 17
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.normal(size=(1, 16, p)), jnp.float32)
        chunk = ops.cov_band_update_chunk(x, jnp.ones(1), 2)
        per = ops.cov_band_update(x[0], 2)
        np.testing.assert_array_equal(np.asarray(chunk), np.asarray(per))


class TestDtypePolicy:
    """Satellite 2: wrapper output dtype is an explicit policy, not a
    hard-coded fp32 cast."""

    def test_cov_update_default_fp32_and_override(self):
        x = jnp.ones((8, 16), jnp.bfloat16)
        assert ops.cov_band_update(x, 2).dtype == jnp.float32
        assert ops.cov_band_update(
            x, 2, out_dtype=jnp.bfloat16).dtype == jnp.bfloat16

    def test_banded_matvec_follows_band_dtype(self):
        band = jnp.ones((5, 16), jnp.bfloat16)
        v = jnp.ones((16,), jnp.bfloat16)
        assert ops.banded_matvec(band, v).dtype == jnp.bfloat16
        assert ops.banded_matvec(
            band, v, out_dtype=jnp.float32).dtype == jnp.float32

    def test_bf16_checkpoint_roundtrip_through_fused_path(self, tmp_path):
        # the PR 4 restore fix (np.savez round-trips extension dtypes as
        # raw void bytes) pinned through the fused driver: a bf16-staged
        # engine state must survive save/restore bit-exactly AND resume
        # the fused stream on the same trajectory
        cfg = StreamConfig(p=12, q=3, halfwidth=2, warmup_rounds=4,
                           precision="bf16",
                           compression=CompressionConfig(epsilon=0.5),
                           detection=DetectionConfig(alpha=1e-3,
                                                     calib_rounds=3))
        rng = np.random.default_rng(2)
        xs = jnp.asarray(rng.normal(size=(16, 4, 12)), jnp.float32)
        key = jax.random.PRNGKey(0)
        mid, _ = chunked_stream_run(cfg, stream_init(cfg, key), xs[:8],
                                    chunk=4)
        # a bf16 engine stages chunk buffers and the flooded basis in bf16
        staged = {"state": mid,
                  "basis_bf16": mid.sched.W.astype(jnp.bfloat16),
                  "buffer_bf16": xs[8:].astype(jnp.bfloat16)}
        checkpoint.save(str(tmp_path), 1, staged)
        restored, _ = checkpoint.restore(str(tmp_path), staged)
        assert restored["basis_bf16"].dtype == jnp.bfloat16
        assert restored["buffer_bf16"].dtype == jnp.bfloat16
        _assert_trees_equal(restored, staged, "bf16 checkpoint roundtrip")
        want = chunked_stream_run(cfg, mid, xs[8:], chunk=4)
        got = chunked_stream_run(cfg, restored["state"],
                                 restored["buffer_bf16"]
                                 .astype(jnp.float32), chunk=4)
        # resumed fused-bf16 stream continues the same trajectory up to
        # the bf16 staging quantization of the buffered rounds
        np.testing.assert_allclose(
            np.asarray(got[0].cov.band), np.asarray(want[0].cov.band),
            rtol=0.02, atol=1e-3)


class TestTileTargets:
    """Roofline-informed block targets (launch/tiling.py)."""

    def test_non_tpu_keeps_historical(self):
        for kind in ("cov", "stage", "fused", "banded"):
            assert block_targets(kind, backend="cpu") == \
                {"rows": 128, "features": 512, "row_align": 1,
                 "feature_align": 1}
        with pytest.raises(ValueError):
            block_targets("cov", backend="gpu")     # no silent CPU tiling

    def test_tpu_targets_derived(self):
        t32 = block_targets("fused", "fp32", backend="tpu")
        t16 = block_targets("fused", "bf16", backend="tpu")
        assert t32["features"] == 512
        assert t16["features"] == 1024          # half the bytes per lane
        assert (t32["row_align"], t32["feature_align"]) == (8, 128)
        assert (t16["row_align"], t16["feature_align"]) == (16, 128)
        assert t32["rows"] >= 8 and t32["rows"] & (t32["rows"] - 1) == 0
        # the halo and mask slabs count: a wide padded row shrinks the
        # row block, and the double-buffered blocks fit half of VMEM
        wide = block_targets("fused", "fp32", backend="tpu", block_p=512,
                             p_pad=1024, halo=256)
        assert wide["rows"] < t32["rows"]
        row_bytes = 2 * (4 * (2 * 512 + 2 * 1280) + 4 * 2 * 1024)
        assert wide["rows"] * row_bytes <= 16 * 2**20 // 2

    def test_unknown_kind_rejected(self):
        with pytest.raises(ValueError):
            block_targets("attention")
