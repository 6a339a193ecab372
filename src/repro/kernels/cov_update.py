"""Pallas TPU kernel: streaming banded-covariance update (Eq. 10).

``delta[k, i] = sum_t x[t, i] * x[t, i + k - h]`` — the per-epoch sufficient
statistic update of the paper's Sec. 3.3, batched over a measurement block.
This is a rank-n update restricted to the band.  Per 128-column sub-tile
of the feature block it is one MXU product of the sub-tile with its halo
window, contracted over the row block, whose diagonals are the band rows
(:func:`band_fold`).

Tiling: grid = (feature blocks, batch blocks); the batch axis is the inner
grid dimension so the output band tile is revisited consecutively and
accumulated in place (Pallas output-revisiting pattern).

Shifted reads (the Mosaic alignment rule): the batch is zero-padded by
:func:`halo_pad` (``h`` rounded up to whole 128-lane tiles) on both sides,
so every read of the halo slab starts on a lane-tile boundary.  Each grid
step loads ONE ``(block_n, block_p + 2·halo_pad)`` window at the aligned
offset ``i·block_p``; the centre tile is the window's columns from
``halo_pad`` on, and each sub-tile's window is the lane-aligned slice
``[s·128, s·128 + 128 + 2·halo_pad)`` — no load at an unaligned lane
offset, which the TPU compiler refuses.  The mask is applied once, to the
window, so the centre tile and every window slice carry it.  The band
output carries ``2h+1`` rounded up to a multiple of 8 rows, and the extra
rows are sliced off.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["LANES", "SUBLANES", "FOLD_FORM", "halo_pad", "band_rows_pad",
           "band_fold", "load_window",
           "cov_band_update_pallas", "cov_band_update_masked_pallas",
           "cov_band_update_chunk_pallas",
           "cov_band_update_chunk_masked_pallas"]

LANES = 128                  # lane width of a TPU vreg (the last dim)
SUBLANES = 8                 # fp32 sublanes of a vreg (the second-to-last)
# how band_fold forms the band (an MXU product per sub-tile, skewed into
# band rows); ops.kernel_block_plan reports it as ``fold_form``
FOLD_FORM = "mxu"


def halo_pad(halfwidth: int) -> int:
    """Zero columns padded on EACH side of the batch: ``h`` rounded up to
    whole lane tiles, so the slab's centre columns and every per-block
    window start on a lane-tile boundary."""
    return -(-halfwidth // LANES) * LANES


def band_rows_pad(halfwidth: int) -> int:
    """Rows of the band accumulator: ``2h+1`` rounded up to a sublane
    tile."""
    return -(-(2 * halfwidth + 1) // SUBLANES) * SUBLANES


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def band_fold(xw: jnp.ndarray, win: jnp.ndarray, band_ref, *,
              halfwidth: int) -> None:
    """``band_ref[k] += sum_r xw[r] * win[r, k + pad - h : ... + block_p]``.

    ``xw`` is the (block_n, block_p) centre tile (mask and row weight
    already applied) and ``win`` the fp32 (block_n, block_p + 2·pad)
    masked halo window that starts ``pad`` columns left of the tile.
    Rows past ``2h`` hold other diagonals and are sliced off by the
    callers.

    The band of one centre sub-tile of ``t`` columns (128, or the whole
    tile when ``block_p`` is not a multiple of 128) is the diagonals of one
    MXU product: ``P = xwᵀ · win_s`` over the row block, with ``win_s`` the
    sub-tile's lane-aligned (block_n, t + 2·pad) window slice, holds
    ``band[k, a] = P[a, a + k + pad − h]``.  Band row k is P's diagonal at
    offset ``k + pad − h``, so row ``a`` of P has to move left by ``a``
    (plus a constant) to line the diagonals up as columns.

    Mosaic's strided ``pltpu.roll`` rotates row ``a`` by ``shift + a·stride``
    lanes, and refuses both other ways to get a per-row shift of ``−a``: a
    sublane roll strided along lanes ("stride dimension is the minor
    most"), and a lane roll of stride ``W − 1`` (≡ −1; "the max shift in a
    vreg … is larger than the vreg's width").  So the skew is a stride
    +1 roll of the anti-diagonals: the centre columns are reversed first
    (``xw · J``, J the t×t anti-identity; exact, its entries are 0/1), which
    turns row ``a`` of P into row ``a′ = t−1−a``; a uniform left roll by
    ``t − 1 + pad − h``, then the stride +1 roll (row ``a′`` right by
    ``a′``), leaves column k holding band row k at column ``a′``.  The two
    rolls stay apart: a strided roll's largest shift inside one vreg, its
    shift mod 128 plus 127, must not pass the vreg's width.  The
    transpose, cut to the band's rows, times J restores the column order.

    Every dot runs at ``Precision.HIGHEST``: the fold is fp32, and the two
    products with J are exact.  The MXU computes all ``t + 2·pad``
    diagonals, ≈1.5× the band's 2h+1 at h = 128.
    """
    h = halfwidth
    pad = halo_pad(h)
    block_p = xw.shape[1]
    n_rows = band_ref.shape[0]
    t = LANES if block_p % LANES == 0 else block_p
    width = t + 2 * pad
    rr = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
    cc = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
    rev = jnp.where(rr + cc == t - 1, 1.0, 0.0).astype(jnp.float32)
    lead = (width - (t - 1 + pad - h)) % width      # left by t−1+pad−h
    for s in range(block_p // t):
        lo = s * t
        xr = _dot(xw[:, lo:lo + t], rev, ((1,), (0,)))      # (block_n, t)
        prod = _dot(xr, win[:, lo:lo + width], ((0,), (0,)))  # (t, width)
        # left by t−1+pad−h, then row a′ right by a′: column k holds band
        # row k
        prod = pltpu.roll(prod, lead, 1)
        prod = pltpu.roll(prod, 0, 1, stride=1, stride_axis=0)
        band = _dot(prod.T[:n_rows], rev, ((1,), (0,)))    # (n_rows, t)
        band_ref[:, lo:lo + t] += band.astype(band_ref.dtype)


def load_window(xpad_ref, mpad_ref, *, block_p: int, halfwidth: int
                ) -> jnp.ndarray:
    """Feature block ``program_id(0)``'s fp32 halo window, masked when a
    mask slab is given (a dead sensor or a pad row is an exact 0)."""
    pad = halo_pad(halfwidth)
    i = pl.program_id(0)
    cols = pl.ds(pl.multiple_of(i * block_p, block_p), block_p + 2 * pad)
    win = xpad_ref[:, cols]
    if mpad_ref is not None:
        win = win * mpad_ref[:, cols]
    return win.astype(jnp.float32)


def _kernel(*refs, masked: bool, weighted: bool, halfwidth: int,
            block_p: int):
    """One (feature block i, row block j) step of every band-update
    variant: the centre tile is the masked window's middle, times the
    per-row weight; the shifted operand is masked but unweighted, so each
    product carries its row's weight exactly once."""
    xpad_ref = refs[0]
    mpad_ref = refs[1] if masked else None
    w_ref = refs[1 + masked] if weighted else None
    out_ref = refs[-1]
    pad = halo_pad(halfwidth)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    win = load_window(xpad_ref, mpad_ref, block_p=block_p,
                      halfwidth=halfwidth)
    xw = win[:, pad:pad + block_p]
    if weighted:
        xw = xw * w_ref[...].astype(jnp.float32)
    band_fold(xw, win, out_ref, halfwidth=halfwidth)


def _band_call(x, mask, w, *, halfwidth: int, block_p: int, block_n: int,
               interpret: bool) -> jnp.ndarray:
    rows, p = x.shape
    h = halfwidth
    pad = halo_pad(h)
    nb = 2 * h + 1
    assert p % block_p == 0 and rows % block_n == 0, (rows, p, block_n,
                                                      block_p)
    grid = (p // block_p, rows // block_n)              # row axis innermost
    slab = pl.BlockSpec((block_n, p + 2 * pad), lambda i, j: (j, 0))
    operands = [jnp.pad(x, ((0, 0), (pad, pad)))]
    in_specs = [slab]
    if mask is not None:
        assert mask.shape == (rows, p), (mask.shape, rows, p)
        operands.append(jnp.pad(mask, ((0, 0), (pad, pad))))
        in_specs.append(slab)
    if w is not None:
        assert w.shape == (rows, 1), (w.shape, rows)
        operands.append(w)
        in_specs.append(pl.BlockSpec((block_n, 1), lambda i, j: (j, 0)))
    n_rows = band_rows_pad(h)
    band = pl.pallas_call(
        functools.partial(_kernel, masked=mask is not None,
                          weighted=w is not None, halfwidth=h,
                          block_p=block_p),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((n_rows, block_p), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((n_rows, p), jnp.float32),
        interpret=interpret,
    )(*operands)
    return band[:nb]


def cov_band_update_pallas(x: jnp.ndarray, *, halfwidth: int, block_p: int,
                           block_n: int,
                           interpret: bool = False) -> jnp.ndarray:
    """delta band (2h+1, p) from x (n, p)."""
    return _band_call(x, None, None, halfwidth=halfwidth, block_p=block_p,
                      block_n=block_n, interpret=interpret)


def cov_band_update_masked_pallas(x: jnp.ndarray, mask: jnp.ndarray, *,
                                  halfwidth: int, block_p: int, block_n: int,
                                  interpret: bool = False) -> jnp.ndarray:
    """Masked variant: delta[k, i] = sum_t m[t,i] x[t,i] m[t,i'] x[t,i'].

    ``mask`` is an (n, p) 0/1 validity matrix (sensor liveness broadcast over
    the batch, or per-reading measurement dropout); masked entries contribute
    nothing to any band product.  Same tiling and the same fold loop as the
    unmasked kernel — the mask only multiplies the loaded window, so with
    an all-ones mask the arithmetic (and hence the float bits) is the
    unmasked kernel's, which is what makes the differential test in
    tests/test_faults.py exact.
    """
    return _band_call(x, mask, None, halfwidth=halfwidth, block_p=block_p,
                      block_n=block_n, interpret=interpret)


def cov_band_update_chunk_pallas(x: jnp.ndarray, w: jnp.ndarray, *,
                                 halfwidth: int, block_p: int, block_n: int,
                                 interpret: bool = False) -> jnp.ndarray:
    """Multi-round fused band update: one launch folds a whole chunk.

    ``x`` is a chunk of rounds flattened on the row axis, (K*n, p);
    ``w`` (K*n, 1) carries each row's round weight (the exponential-
    forgetting factor of its round within the chunk; 0 for pad rows).
    delta[k, i] = sum_r w[r] * x[r, i] * x[r, i + k - h].

    Same tiling as :func:`cov_band_update_pallas` with the flattened row
    axis as the inner grid dimension: the band accumulator tile is
    revisited in VMEM across the WHOLE chunk and written back to HBM once
    per feature block — one band read-modify-write per chunk instead of one
    per round.
    """
    return _band_call(x, None, w, halfwidth=halfwidth, block_p=block_p,
                      block_n=block_n, interpret=interpret)


def cov_band_update_chunk_masked_pallas(x: jnp.ndarray, mask: jnp.ndarray,
                                        w: jnp.ndarray, *, halfwidth: int,
                                        block_p: int, block_n: int,
                                        interpret: bool = False
                                        ) -> jnp.ndarray:
    """Masked chunk variant: delta[k,i] = sum_r w_r m[r,i] x[r,i] m[r,i'] x[r,i'].

    Rows are the flattened (K*n) chunk; ``mask`` carries per-row validity
    (liveness broadcast over the round's epochs, or per-reading dropout)
    and ``w`` the per-row round weights, both fused into the window load.
    """
    return _band_call(x, mask, w, halfwidth=halfwidth, block_p=block_p,
                      block_n=block_n, interpret=interpret)
