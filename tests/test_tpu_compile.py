"""The served kernels compile for a TPU v5e (described, not attached).

Interpret mode on the CPU accepts any slice; the chip's compiler (Mosaic)
refuses a lane offset it cannot prove aligned and a block that is not a
whole register tile.  These tests compile the band-fold kernels of the
main path at real widths for one chip of a described ``v5e:2x2`` — the
wsn-1m region shape (p=1024, h=128, q=32, 256 rows) and the paper's
54-mote network (p=54, h=4) — with the tiles the TPU branch of
:func:`repro.launch.tiling.block_targets` picks.  Nothing runs: a compile
that passes is not a chip run.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops
from repro.kernels.cov_update import halo_pad
from repro.launch.tiling import block_targets

ROWS = 256
CASES = [(1024, 128, 32), (54, 4, 8)]       # (p, h, q)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _tpu_blocks(kind: str, p: int, h: int, dtype: str = "fp32"):
    """(block_p, block_n) the wrappers pick on a TPU backend."""
    t = block_targets(kind, dtype, backend="tpu")
    bp = ops._pick_block_padded(p, t["features"], t["feature_align"])
    t = block_targets(kind, dtype, backend="tpu", block_p=bp,
                      p_pad=ops._pad_dim(p, bp), halo=2 * halo_pad(h))
    bn = ops._pick_block_padded(ROWS, t["rows"], t["row_align"])
    assert bp % t["feature_align"] == 0 and bn % t["row_align"] == 0
    return bp, bn


def _compile(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("precision", ["fp32", "bf16"])
@pytest.mark.parametrize("p,h,q", CASES)
def test_fused_stream_update_compiles(one_chip, p, h, q, precision):
    bp, bn = _tpu_blocks("fused", p, h, precision)

    def step(x, w, basis, mean, inv_lam):
        return ops.fused_stream_update(
            x, w, basis, mean, inv_lam, halfwidth=h, epsilon=0.1,
            with_compress=True, with_monitor=True, precision=precision,
            block_p=bp, block_n=bn, interpret=False)

    compiled = _compile(step, (ROWS, p), (ROWS,), (p, q), (p,), (q,),
                        sharding=one_chip)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("p,h,q", CASES)
def test_cov_band_update_chunk_compiles(one_chip, p, h, q, masked):
    bp, bn = _tpu_blocks("cov", p, h)
    K = 4

    def fold(xs, w, *mask):
        return ops.cov_band_update_chunk(
            xs, w, h, mask=mask[0] if mask else None, block_p=bp,
            block_n=bn, interpret=False)

    shapes = [(K, ROWS // K, p), (K,)] + ([(K, p)] if masked else [])
    compiled = _compile(fold, *shapes, sharding=one_chip)
    assert "tpu_custom_call" in compiled.as_text()
