"""Pallas TPU kernel: streaming banded-covariance update (Eq. 10).

``delta[k, i] = sum_t x[t, i] * x[t, i + k - h]`` — the per-epoch sufficient
statistic update of the paper's Sec. 3.3, batched over a measurement block.
This is a rank-n update restricted to the band: per feature tile it is an
elementwise product of the tile with a shifted view of the halo-padded batch,
reduced over the batch axis (VPU work with an 8-deep sublane reduction).

Tiling: grid = (feature blocks, batch blocks); the batch axis is the inner
grid dimension so the output band tile is revisited consecutively and
accumulated in place (Pallas output-revisiting pattern).

Shifted reads (the Mosaic alignment rule): the batch is zero-padded by
:func:`halo_pad` (``h`` rounded up to whole 128-lane tiles) on both sides,
so every read of the halo slab starts on a lane-tile boundary.  Each grid
step loads ONE ``(block_n, block_p + 2·halo_pad)`` window at the aligned
offset ``i·block_p``; the centre tile is the window's columns from
``halo_pad`` on, and the 2h+1 shifted views are lane rotations
(``pltpu.roll``) of the window followed by a slice at lane 0 — no load at
an unaligned lane offset, which the TPU compiler refuses.  The mask is
applied once, to the window, so the centre tile and every shifted view
carry it.  The band rows are produced eight at a time (one sublane tile)
by a ``fori_loop``, so the kernel body does not grow with ``h``; the band
output carries ``2h+1`` rounded up to a multiple of 8 rows, and the extra
rows are sliced off.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["LANES", "SUBLANES", "halo_pad", "band_rows_pad", "band_fold",
           "load_window",
           "cov_band_update_pallas", "cov_band_update_masked_pallas",
           "cov_band_update_chunk_pallas",
           "cov_band_update_chunk_masked_pallas"]

LANES = 128                  # lane width of a TPU vreg (the last dim)
SUBLANES = 8                 # fp32 sublanes of a vreg (the second-to-last)


def halo_pad(halfwidth: int) -> int:
    """Zero columns padded on EACH side of the batch: ``h`` rounded up to
    whole lane tiles, so the slab's centre columns and every per-block
    window start on a lane-tile boundary."""
    return -(-halfwidth // LANES) * LANES


def band_rows_pad(halfwidth: int) -> int:
    """Rows of the band accumulator: ``2h+1`` rounded up to a sublane
    tile, and at least two tiles, so the fold is a loop for every ``h``
    (the CPU interpreter inlines a one-trip loop into its neighbours, and
    the fused code then rounds differently per kernel variant)."""
    return max(2, -(-(2 * halfwidth + 1) // SUBLANES)) * SUBLANES


def band_fold(xw: jnp.ndarray, win: jnp.ndarray, band_ref, *,
              halfwidth: int) -> None:
    """``band_ref[k] += sum_r xw[r] * win[r, k + pad - h : ... + block_p]``.

    ``xw`` is the (block_n, block_p) centre tile (mask and row weight
    already applied) and ``win`` the fp32 (block_n, block_p + 2·pad)
    masked halo window that starts ``pad`` columns left of the tile.  The
    shifted view for diagonal k is ``win`` rotated left by ``k + pad - h``
    lanes, then its first ``block_p`` columns (the rotation never wraps
    into them: ``k + pad - h <= 2·pad``).  Rows past ``2h`` hold wrapped
    columns and are sliced off by the callers.
    """
    h = halfwidth
    pad = halo_pad(h)
    width = win.shape[1]
    block_p = xw.shape[1]
    n_rows = band_ref.shape[0]
    sub = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, block_p), 0)

    def group(g, carry):
        k0 = pl.multiple_of(g * SUBLANES, SUBLANES)
        tile = jnp.zeros((SUBLANES, block_p), jnp.float32)
        for r in range(SUBLANES):
            shift = jax.lax.rem(width - (k0 + r + pad - h), width)
            xs = pltpu.roll(win, shift, 1)[:, :block_p]
            row = jnp.sum(xw * xs, axis=0, keepdims=True)   # (1, block_p)
            tile = jnp.where(sub == r, row, tile)
        band_ref[pl.ds(k0, SUBLANES), :] += tile.astype(band_ref.dtype)
        return carry

    jax.lax.fori_loop(0, n_rows // SUBLANES, group, 0)


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
