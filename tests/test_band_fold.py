"""The band fold (``kernels/cov_update.py::band_fold``) against a float64
oracle, in interpret mode at the tiles a TPU picks.

Every kernel that folds a band (the four ``cov_band_update_*`` variants
and the fused chunk kernel) runs the same fold.  The cases sweep the band
half-width (h = 2, 4, 7 and the wsn-1m region's 128) over a prime p, the
paper's 54-mote network and the region's 1,024 sensors, with the row
count off the block grid, so the wrappers pad it with zero-weight,
zero-mask rows.  The chunk variants also carry a round of weight 0.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.cov_update import halo_pad
from repro.launch.tiling import block_targets

K, N = 3, 7                                 # rounds × epochs: 21 rows
WEIGHTS = np.array([0.81, 0.9, 0.0])        # the last round weighs 0
VARIANTS = ["plain", "masked", "chunk", "chunk_masked", "fused"]


@pytest.fixture(autouse=True)
def _release_executables():
    """Drop the compiled interpret-mode programs after each case: each
    holds its own code mappings, and the sweep's sixty shapes would
    otherwise pile them up in one test process."""
    yield
    jax.clear_caches()


def _tpu_blocks(kind: str, p: int, h: int, rows: int):
    """(block_p, block_n) as the TPU branch of the plan picks them."""
    t = block_targets(kind, backend="tpu")
    bp = ops._pick_block_padded(p, t["features"], t["feature_align"])
    t = block_targets(kind, backend="tpu", block_p=bp,
                      p_pad=ops._pad_dim(p, bp), halo=2 * halo_pad(h))
    return bp, ops._pick_block_padded(rows, t["rows"], t["row_align"])


def _band64(x, h, w=None):
    """``band[k, i] = Σ_r w_r x[r, i] x[r, i + k - h]`` in float64."""
    x = np.asarray(x, np.float64)
    w = np.ones(x.shape[0]) if w is None else np.asarray(w, np.float64)
    xp = np.pad(x, ((0, 0), (h, h)))
    p = x.shape[1]
    return np.stack([(w[:, None] * x * xp[:, k:k + p]).sum(0)
                     for k in range(2 * h + 1)])


def _fold(variant, x, mask, h):
    """The variant's band and the float64 band it must equal."""
    rows, p = x.shape
    kind = "fused" if variant == "fused" else "cov"
    bp, bn = _tpu_blocks(kind, p, h, rows)
    w_rows = np.repeat(WEIGHTS, N)
    xm = x * mask
    if variant == "plain":
        return (ops.cov_band_update(x, h, block_p=bp, block_n=bn,
                                    interpret=True), _band64(x, h))
    if variant == "masked":
        return (ops.cov_band_update_masked(x, mask, h, block_p=bp,
                                           block_n=bn, interpret=True),
                _band64(xm, h))
    if variant in ("chunk", "chunk_masked"):
        m = mask.reshape(K, N, p) if variant == "chunk_masked" else None
        want = _band64(xm if m is not None else x, h, w_rows)
        return (ops.cov_band_update_chunk(x.reshape(K, N, p), WEIGHTS, h,
                                          mask=m, block_p=bp, block_n=bn,
                                          interpret=True), want)
    basis = np.linalg.qr(np.random.default_rng(p).normal(size=(p, 2)))[0]
    band, *_ = ops.fused_stream_update(
        x, w_rows, basis, halfwidth=h, epsilon=0.5, with_compress=True,
        with_monitor=True, mask=mask, block_p=bp, block_n=bn,
        interpret=True)
    return band, _band64(xm, h, w_rows)


@pytest.mark.parametrize("variant", VARIANTS)
@pytest.mark.parametrize("p", [17, 54, 1024])
@pytest.mark.parametrize("h", [2, 4, 7, 128])
def test_fold_matches_float64_oracle(h, p, variant):
    rng = np.random.default_rng(1000 * h + p)
    x = rng.normal(size=(K * N, p)).astype(np.float32)
    mask = (rng.random((K * N, p)) > 0.2).astype(np.float32)
    got, want = _fold(variant, jnp.asarray(x), jnp.asarray(mask), h)
    got = np.asarray(got, np.float64)
    assert got.shape == want.shape == (2 * h + 1, p)
    scale = np.abs(want).max()
    # fp32 products summed over 21 rows: ~1e-7 of the band's scale; a
    # diagonal out of place is O(1), a one-pass bf16 product ~1e-3
    np.testing.assert_array_less(np.abs(got - want), 2e-6 * scale)
    if variant == "plain" and h <= 7:       # ref.py's jnp oracle agrees
        np.testing.assert_allclose(
            got, np.asarray(ref.cov_band_update(jnp.asarray(x), h)),
            rtol=0, atol=2e-6 * scale)


@pytest.mark.parametrize("h", [2, 4, 7, 128])
def test_plan_names_the_fold_form(h):
    for kind in ("cov", "fused"):
        plan = ops.kernel_block_plan(kind, rows=21, p=54, halfwidth=h)
        assert plan["fold_form"] == "mxu"
    assert "fold_form" not in ops.kernel_block_plan("cov", rows=21, p=54)


def test_engine_summary_carries_fold_form():
    from repro.serve.engine import StreamingPCAEngine
    from repro.streaming.driver import StreamConfig
    cfg = StreamConfig(p=16, q=2, halfwidth=3, forgetting=0.9)
    eng = StreamingPCAEngine(cfg, slots=2, telemetry=True)
    want = ops.kernel_block_plan("cov", p=16, halfwidth=3)["fold_form"]
    assert eng.telemetry.summary()["fold_form"] == want
    eng.telemetry.reset()               # configuration, not a total
    assert eng.telemetry.summary()["fold_form"] == want
    assert StreamingPCAEngine(cfg, slots=2).telemetry is None
