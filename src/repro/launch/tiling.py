"""Kernel tile targets (per backend, per dtype).

The kernel wrappers in :mod:`repro.kernels.ops` take their block-size
targets from here (through :func:`repro.kernels.ops.kernel_block_plan`), so
a backend with different constraints picks different tiles without touching
kernel code.

TPU branch (v5e):

* blocks are whole register tiles — (8, 128) sublanes × lanes at fp32,
  (16, 128) at bf16 (packed sublanes): the feature block is a multiple of
  128 and the row block a multiple of 8 (16), and the wrappers pad p and
  the row count up to them;
* the feature target is 512 lanes at fp32 and double at bf16 (half the
  bytes per lane).  The band fold is not bandwidth-bound at the wsn-1m
  width (h = 128): the lane-roll fold it replaced ran at 1.1 % of its
  roofline, bound by 2h+1 whole-window rotations on the XLU and the
  multiply-adds on the VPU.  The MXU fold
  (:func:`repro.kernels.cov_update.band_fold`) works per 128-column
  sub-tile, so a wider feature block adds sub-tiles, not work per column;
* the row target is the largest power of two whose double-buffered blocks
  fit half of :data:`VMEM_BYTES` (the other half holds the in-kernel
  values: the halo window, one sub-tile's MXU product — (128, 128 + 2·pad)
  fp32, 192 KiB at h = 128 — and its skewed copies, the stage temporaries
  and the band accumulator).  The blocks of one row are counted per kernel
  family: the x and mask tiles, the x and mask halo slabs — the whole
  padded row plus both halos, re-read for every feature block — and the
  fp32 x_hat and flag rows of the fused stages.

The CPU backend runs every kernel in interpret mode (tests) and keeps the
historical targets (rows 128, features 512) with no alignment, so
interpret-mode results do not move with this table.  Any other backend is
an error: there is no tiling for it.
"""

from __future__ import annotations

import jax

__all__ = ["VMEM_BYTES", "MIN_TILE", "block_targets"]

# the scoped VMEM a Mosaic kernel may use by default on v5e — the budget
# the double-buffered blocks plus the in-kernel values must fit
VMEM_BYTES = 16 * 2 ** 20

# native register tile (sublanes, lanes) per dtype byte-width
MIN_TILE = {4: (8, 128), 2: (16, 128)}

# the shapes the kernels were tuned at in interpret mode (CPU)
_HISTORICAL = {"rows": 128, "features": 512, "row_align": 1,
               "feature_align": 1}

# per block row: (tiles block_p wide, halo slabs p_pad + halo wide — both
# in the tile dtype; fp32 stage output rows p_pad wide)
_ROW_BLOCKS = {"cov": (2, 2, 0), "fused": (2, 2, 2), "stage": (2, 0, 2),
               "banded": (2, 0, 0)}


def _dtype_bytes(dtype: str) -> int:
    return {"fp32": 4, "float32": 4, "bf16": 2, "bfloat16": 2}[dtype]


def block_targets(kind: str, dtype: str = "fp32",
                  backend: str | None = None, *,
                  block_p: int | None = None, p_pad: int | None = None,
                  halo: int = 0) -> dict[str, int]:
    """Tile-size targets for a kernel family: ``{"rows", "features",
    "row_align", "feature_align"}``.

    ``kind`` names the wrapper family (``"cov"``, ``"stage"``,
    ``"fused"``, ``"banded"``); ``dtype`` the tile-load dtype
    (``"fp32"``/``"bf16"``); ``backend`` overrides the detected JAX
    backend (the described-TPU compile tests pass ``"tpu"``).  On TPU the
    row target counts the blocks of one row at the chosen ``block_p``
    (default: the feature target) and padded width ``p_pad`` (default:
    ``block_p``), with ``halo`` extra slab columns (both halos).

    The returned numbers are *targets*: the wrappers still prefer exact
    divisors and pad otherwise (:func:`repro.kernels.ops._pick_block_padded`),
    always to a multiple of the alignment.
    """
    if kind not in _ROW_BLOCKS:
        raise ValueError(f"unknown kernel family {kind!r}")
    be = backend or jax.default_backend()
    if be == "cpu":
        return dict(_HISTORICAL)
    if be != "tpu":
        raise ValueError(f"no kernel tiling for backend {be!r}")
    nbytes = _dtype_bytes(dtype)
    sub, lanes = MIN_TILE[nbytes]
    features = 512 * (4 // nbytes)
    bp = block_p or features
    width = p_pad or bp
    tiles, slabs, stage_rows = _ROW_BLOCKS[kind]
    row_bytes = 2 * (nbytes * (tiles * bp + slabs * (width + halo))
                     + 4 * stage_rows * width)          # double-buffered
    rows = sub
    while 2 * rows * row_bytes <= VMEM_BYTES // 2 and rows < 1024:
        rows *= 2
    return {"rows": rows, "features": features, "row_align": sub,
            "feature_align": lanes}
