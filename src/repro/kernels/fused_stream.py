"""Pallas TPU kernel: one-pass fused streaming chunk update (DESIGN.md
Sec. 14).

The chunked streaming hot loop used to pay up to three HBM passes over the
same flattened (K·n, p) chunk — the banded cov-update
(:mod:`repro.kernels.cov_update`), the ε-supervised compression pass and
the T²/SPE monitoring pass (:mod:`repro.kernels.pca_project`) are separate
``pallas_call``s reading identical tiles.  The paper's whole Sec.-2.4
argument is that ONE aggregation pass per epoch amortizes all per-round
work; Elgamal & Hefeeda (PAPERS.md) show the memory-traffic term dominating
distributed-PCA cost at scale.  This kernel loads each tile of the chunk
into VMEM once and produces, from the same tiles,

* the forgetting-weighted band accumulator
  ``delta[k, i] = Σ_r w_r m[r,i] x[r,i] m[r,i'] x[r,i']`` (the multi-round
  fold of :func:`repro.kernels.cov_update.cov_band_update_chunk_pallas`),
* the compression stage ``Z = ((X − mean)·m) W``, ``X_hat = Z Wᵀ + mean``,
  ``flags = (|X − X_hat| > ε) & m`` (when ``with_compress``),
* the monitoring stage ``T² = Σ_k z_k² inv_λ_k``,
  ``SPE = ‖((X − mean)·m − Z Wᵀ)·m‖²`` (when ``with_monitor``),

collapsing the chunk body from 3 kernel launches to 1.

Tiling: the grid is (feature blocks, row blocks) — the cov chunk
kernel's grid, with the same block specs and the same fold
(:func:`repro.kernels.cov_update.band_fold`: one lane-aligned halo window
per step; the band of each 128-column sub-tile is the diagonals of one
MXU product of the sub-tile with its window slice).  The band output
block has a j-constant index map and is revisited consecutively across
the row sweep of each feature block — the Pallas in-VMEM accumulation
pattern.

The stages run once per row block, on the FIRST feature step
(``pl.program_id(0) == 0``), reading the full rows back out of the halo
slab (which is resident anyway for the shifted band products) at the
lane-aligned offset ``halo_pad(h)`` and the padded width ``p_pad``: the
basis and mean are zero-padded to ``p_pad`` and pad columns carry mask 0,
so they add exact zeros, and the wrapper slices the outputs back to ``p``.
The stage outputs' index map advances with the row block on the first
feature step and then stays on the last row block for the rest of the
sweep, so a block the kernel did not write is never written back (with
more than one feature block a plain ``(j, 0)`` map would write stale VMEM
buffers over the stage outputs on the chip).

Precision: every tile is cast to fp32 on load and every accumulation runs
in fp32 (``preferred_element_type=jnp.float32``), whatever the operand
dtype, and the fold's dots run at ``Precision.HIGHEST`` (a one-pass bf16
MXU product would round the fp32 tiles) — so the optional bf16 mode (the
ops wrapper casts the large operands x/xpad/mask/W to bfloat16 before the
call) halves the HBM tile traffic while the band fold and the stage
reductions keep fp32 accumulators.  With fp32 operands the band is the
chunk kernel's fold and the stages are
``_supervised_kernel``/``_monitor_kernel``'s op order per row block; the
compiled programs differ, so the split path agrees to fp32 rounding, not
bit for bit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.cov_update import (band_fold, band_rows_pad, halo_pad,
                                      load_window)

__all__ = ["fused_stream_pallas"]


def _fused_kernel(xpad_ref, mpad_ref, w_ref, basis_ref, mean_ref,
                  invlam_ref, *out_refs, halfwidth: int, block_p: int,
                  eps: float, with_compress: bool, with_monitor: bool):
    i = pl.program_id(0)                    # feature block (band fold)
    j = pl.program_id(1)                    # row block
    pad = halo_pad(halfwidth)
    p_pad = basis_ref.shape[0]
    band_ref = out_refs[0]
    z_ref = out_refs[1]
    k_out = 2
    if with_compress:
        xh_ref, flag_ref = out_refs[k_out], out_refs[k_out + 1]
        k_out += 2
    if with_monitor:
        t2_ref, spe_ref = out_refs[k_out], out_refs[k_out + 1]

    @pl.when(j == 0)
    def _init():
        band_ref[...] = jnp.zeros_like(band_ref)

    # --- band fold: the masked chunk kernel's (masked window, centre tile
    # times the per-row round weight; shifted operand masked but
    # unweighted, so each product carries its weight once)
    win = load_window(xpad_ref, mpad_ref, block_p=block_p,
                      halfwidth=halfwidth)
    xw = win[:, pad:pad + block_p] * w_ref[...].astype(jnp.float32)
    band_fold(xw, win, band_ref, halfwidth=halfwidth)

    # --- stages, once per row block on the first feature step: identical
    # op order to _supervised_kernel/_monitor_kernel (the projection and
    # the VMEM-resident reconstruction are shared — the split kernels each
    # recomputed them from their own tile loads).  Rows come back out of
    # the halo slab at the lane-aligned offset ``pad``.
    @pl.when(i == 0)
    def _stages():
        x = xpad_ref[:, pad:pad + p_pad].astype(jnp.float32)
        m = mpad_ref[:, pad:pad + p_pad].astype(jnp.float32)
        w = basis_ref[...].astype(jnp.float32)          # (p_pad, q)
        mean = mean_ref[...].astype(jnp.float32)        # (1, p_pad)
        xc = (x - mean) * m
        z = jnp.dot(xc, w, preferred_element_type=jnp.float32)
        xh_r = jnp.dot(z, w.T, preferred_element_type=jnp.float32)
        z_ref[...] = z.astype(z_ref.dtype)
        if with_compress:
            xh = xh_r + mean
            err = jnp.abs(x - xh)
            flags = jnp.where((err > eps) & (m > 0.0), 1.0, 0.0)
            xh_ref[...] = xh.astype(xh_ref.dtype)
            flag_ref[...] = flags.astype(flag_ref.dtype)
        if with_monitor:
            il = invlam_ref[...].astype(jnp.float32)    # (1, q)
            resid = (xc - xh_r) * m
            t2_ref[...] = jnp.sum(z * z * il, axis=1,
                                  keepdims=True).astype(t2_ref.dtype)
            spe_ref[...] = jnp.sum(resid * resid, axis=1,
                                   keepdims=True).astype(spe_ref.dtype)


def fused_stream_pallas(x: jnp.ndarray, mask: jnp.ndarray,
                        w_rows: jnp.ndarray, basis: jnp.ndarray,
                        mean: jnp.ndarray, inv_lam: jnp.ndarray,
                        *, halfwidth: int, epsilon: float,
                        with_compress: bool, with_monitor: bool,
                        block_p: int, block_n: int, interpret: bool = False,
                        ) -> tuple[jnp.ndarray, ...]:
    """One fused chunk pass: band fold + compression + monitoring.

    ``x`` is the flattened chunk (rows, p_pad) (rows = K·n padded to
    ``block_n``, features padded to ``block_p``); ``mask`` the per-row 0/1
    validity (liveness × round validity — pad rows carry mask 0 AND
    weight 0, pad features mask 0); ``w_rows`` (rows, 1) the per-row
    forgetting weights; ``basis`` (p_pad, q), ``mean`` (1, p_pad) and
    ``inv_lam`` (1, q) the stage operands, zero-padded to ``p_pad`` and
    replicated to every grid step.

    Returns ``(band, z[, x_hat, flags][, t2, spe])`` — band (2h+1, p_pad)
    and per-row stage outputs (x_hat and flags p_pad wide), all fp32,
    gated by the static ``with_*`` flags (at least one must be set; a
    band-only chunk has no reason to pay the stage operand traffic — use
    the cov-update kernel).
    """
    rows, p_pad = x.shape
    h = halfwidth
    pad = halo_pad(h)
    q = basis.shape[1]
    assert with_compress or with_monitor, "band-only: use cov_band_update"
    assert rows % block_n == 0, (rows, block_n)
    assert p_pad % block_p == 0, (p_pad, block_p)
    assert mask.shape == (rows, p_pad)
    assert w_rows.shape == (rows, 1)
    assert basis.shape == (p_pad, q)
    assert mean.shape == (1, p_pad) and inv_lam.shape == (1, q)
    grid = (p_pad // block_p, rows // block_n)
    last = rows // block_n - 1

    def stage_rows(i, j):
        # advance on the first feature step, then park on the last row
        # block (written at i == 0, never rewritten)
        return (jnp.where(i == 0, j, last), 0)

    slab = pl.BlockSpec((block_n, p_pad + 2 * pad), lambda i, j: (j, 0))
    in_specs = [
        slab,                                                       # x
        slab,                                                       # mask
        pl.BlockSpec((block_n, 1), lambda i, j: (j, 0)),            # weights
        pl.BlockSpec((p_pad, q), lambda i, j: (0, 0)),              # basis
        pl.BlockSpec((1, p_pad), lambda i, j: (0, 0)),              # mean
        pl.BlockSpec((1, q), lambda i, j: (0, 0)),                  # inv_lam
    ]
    n_rows = band_rows_pad(h)
    out_specs = [
        pl.BlockSpec((n_rows, block_p), lambda i, j: (0, i)),       # band
        pl.BlockSpec((block_n, q), stage_rows),                     # z
    ]
    out_shape = [
        jax.ShapeDtypeStruct((n_rows, p_pad), jnp.float32),
        jax.ShapeDtypeStruct((rows, q), jnp.float32),
    ]
    if with_compress:
        out_specs += [pl.BlockSpec((block_n, p_pad), stage_rows),
                      pl.BlockSpec((block_n, p_pad), stage_rows)]
        out_shape += [jax.ShapeDtypeStruct((rows, p_pad), jnp.float32),
                      jax.ShapeDtypeStruct((rows, p_pad), jnp.float32)]
    if with_monitor:
        out_specs += [pl.BlockSpec((block_n, 1), stage_rows),
                      pl.BlockSpec((block_n, 1), stage_rows)]
        out_shape += [jax.ShapeDtypeStruct((rows, 1), jnp.float32),
                      jax.ShapeDtypeStruct((rows, 1), jnp.float32)]
    xpad = jnp.pad(x, ((0, 0), (pad, pad)))
    mpad = jnp.pad(mask, ((0, 0), (pad, pad)))
    out = pl.pallas_call(
        functools.partial(_fused_kernel, halfwidth=h, block_p=block_p,
                          eps=float(epsilon), with_compress=with_compress,
                          with_monitor=with_monitor),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(xpad, mpad, w_rows, basis, mean, inv_lam)
    return (out[0][:2 * h + 1],) + tuple(out[1:])
