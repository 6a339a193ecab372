"""Jitted public wrappers around the Pallas kernels.

Handles padding, block-size selection and dtype promotion, and picks how
the kernels run: compiled by Mosaic on a TPU, in the Pallas interpreter on
the CPU (the test backend).  There is no other backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.banded_matvec import banded_matvec_pallas, banded_matmul_pallas
from repro.kernels.cov_update import (cov_band_update_chunk_masked_pallas,
                                      cov_band_update_chunk_pallas,
                                      cov_band_update_pallas,
                                      cov_band_update_masked_pallas,
                                      halo_pad)
from repro.kernels.fused_stream import fused_stream_pallas
from repro.kernels.pca_project import (pca_monitor_pallas,
                                       pca_project_pallas,
                                       pca_reconstruct_pallas,
                                       supervised_compress_pallas)

__all__ = ["banded_matvec", "banded_matmul", "cov_band_update",
           "cov_band_update_masked", "cov_band_update_batched",
           "cov_band_update_chunk", "cov_band_update_chunk_batched",
           "pca_project", "pca_reconstruct",
           "supervised_compress", "supervised_compress_batched",
           "pca_monitor", "pca_monitor_batched",
           "fused_stream_update", "fused_stream_stages_blocked",
           "kernel_block_plan"]


def _auto_interpret(interpret: bool | None) -> bool:
    """``interpret=None`` means: compile on a TPU, interpret on the CPU.
    Any other backend raises — a kernel never silently runs interpreted
    where a device was expected."""
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise ValueError(f"no Pallas kernel path for backend {backend!r}")


def _pick_block(p: int, target: int = 512) -> int:
    """Largest divisor of p that is <= target (prefers multiples of 128)."""
    for cand in (target, 256, 128, 64, 32, 16, 8, 4, 2, 1):
        if cand <= target and p % cand == 0:
            return cand
    return 1


def _pick_block_padded(d: int, target: int, align: int = 1) -> int:
    """Block size for an axis the caller is allowed to zero-pad.

    Prefers the exact-divisor pick — no padding, so results stay
    bit-identical to the historical behavior on every shape a divisor
    covers — and only when the best divisor is degenerate (awkward ``d``,
    e.g. prime: the old path would tile by 1, a pathological grid) or not
    a multiple of ``align`` (the TPU register tile) switches to a padded
    tile: the next power of two on the CPU, ``d`` rounded up to ``align``
    on the TPU, both capped at ``target``.  The wrappers below pad the
    operand up to a multiple of the returned block and slice the result
    back.
    """
    b = _pick_block(d, target)
    if align > 1:
        return b if b % align == 0 else min(target, _pad_dim(d, align))
    if b > 1 or d <= 8:
        return b
    return min(target, 1 << (d - 1).bit_length())


def _pad_dim(d: int, block: int) -> int:
    return -(-d // block) * block


def kernel_block_plan(kind: str, *, rows: int | None = None,
                      p: int | None = None, dtype: str = "fp32",
                      halfwidth: int | None = None) -> dict:
    """The BlockSpec plan a wrapper will pick for the given logical shapes.

    The single source of tiling truth shared by the wrappers below (which
    call it to pick their blocks) and by the static resource certifier
    (:mod:`repro.analysis.resources`), which uses it as the *booked* side
    of the booked==traced VMEM/HBM bill — the plan and the traced
    ``pallas_call`` grid cannot drift apart without a rule failing.

    Returns ``block_n``/``rows_pad``/``row_blocks`` when ``rows`` is
    given, ``block_p``/``p_pad``/``feature_blocks`` when ``p`` is given,
    plus ``grid`` (feature-major, rows fastest — the kernel convention)
    when both are, and ``halo_width`` (the full-width padded slab a banded
    kernel re-fetches per feature block) when ``halfwidth`` is given too.
    On a TPU the row target counts that slab
    (:func:`repro.launch.tiling.block_targets`).
    """
    from repro.launch.tiling import block_targets
    t = block_targets(kind, dtype=dtype)
    plan: dict = {"feature_target": t["features"]}
    if p is not None:
        bp = _pick_block_padded(p, t["features"], t["feature_align"])
        p_pad = _pad_dim(p, bp)
        halo = 0 if halfwidth is None else 2 * halo_pad(halfwidth)
        plan.update(block_p=bp, p_pad=p_pad, feature_blocks=p_pad // bp)
        if halfwidth is not None:
            plan["halo_width"] = p_pad + halo
        # the row target counts the blocks of one row at this width
        t = block_targets(kind, dtype=dtype, block_p=bp, p_pad=p_pad,
                          halo=halo)
    plan["row_target"] = t["rows"]
    if rows is not None:
        bn = _pick_block_padded(rows, t["rows"], t["row_align"])
        plan.update(block_n=bn, rows_pad=_pad_dim(rows, bn),
                    row_blocks=_pad_dim(rows, bn) // bn)
    if rows is not None and p is not None:
        plan["grid"] = (plan["feature_blocks"], plan["row_blocks"])
    return plan


@functools.partial(jax.jit, static_argnames=("block_p", "interpret"))
def _banded_matvec(band, v, block_p, interpret):
    nb = band.shape[0]
    h = (nb - 1) // 2
    vpad = jnp.pad(v, (h, h)).reshape(1, -1)
    out = banded_matvec_pallas(band, vpad, block_p=block_p, interpret=interpret)
    return out[0]


def banded_matvec(band: jnp.ndarray, v: jnp.ndarray,
                  block_p: int | None = None,
                  interpret: bool | None = None,
                  out_dtype=None) -> jnp.ndarray:
    """y = C v with C banded (2h+1, p) diagonals; v (p,).

    Accumulates in fp32 inside the kernel whatever the operand dtype; the
    output is ``out_dtype`` (default: the band's dtype — a bf16 band stays
    bf16 instead of silently upcasting).  An awkward ``p`` (e.g. prime —
    the old divisor fallback tiled it by 1, a pathological grid) is
    zero-padded to the block and sliced back: pad columns hold zero band
    entries, so the surviving region is bit-identical.
    """
    nb, p = band.shape
    bp = block_p or kernel_block_plan("banded", p=p)["block_p"]
    p_pad = _pad_dim(p, bp)
    if p_pad != p:
        band = jnp.pad(band, ((0, 0), (0, p_pad - p)))
        v = jnp.pad(v, (0, p_pad - p))
    out = _banded_matvec(band, v, bp, _auto_interpret(interpret))[:p]
    return out if out_dtype is None else out.astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("block_p", "interpret"))
def _banded_matmul(band, V, block_p, interpret):
    nb = band.shape[0]
    h = (nb - 1) // 2
    vpad = jnp.pad(V, ((h, h), (0, 0)))
    return banded_matmul_pallas(band, vpad, block_p=block_p, interpret=interpret)


def banded_matmul(band: jnp.ndarray, V: jnp.ndarray,
                  block_p: int | None = None,
                  interpret: bool | None = None,
                  out_dtype=None) -> jnp.ndarray:
    """Y = C V with C banded; V (p, q).

    Same pad-to-block treatment and dtype policy as
    :func:`banded_matvec` (fp32 accumulate; output follows the band).
    """
    nb, p = band.shape
    bp = block_p or kernel_block_plan("banded", p=p)["block_p"]
    p_pad = _pad_dim(p, bp)
    if p_pad != p:
        band = jnp.pad(band, ((0, 0), (0, p_pad - p)))
        V = jnp.pad(V, ((0, p_pad - p), (0, 0)))
    out = _banded_matmul(band, V, bp, _auto_interpret(interpret))[:p]
    return out if out_dtype is None else out.astype(out_dtype)


@functools.partial(jax.jit,
                   static_argnames=("halfwidth", "block_p", "block_n",
                                    "interpret"))
def _cov_band_update(x, halfwidth, block_p, block_n, interpret):
    return cov_band_update_pallas(x, halfwidth=halfwidth, block_p=block_p,
                                  block_n=block_n, interpret=interpret)


def cov_band_update(x: jnp.ndarray, halfwidth: int,
                    block_p: int | None = None, block_n: int | None = None,
                    interpret: bool | None = None,
                    out_dtype=None) -> jnp.ndarray:
    """delta band (2h+1, p) = sum_t outer(x_t, x_t) restricted to the band.

    Accumulates in fp32 inside the kernel whatever ``x``'s dtype; the
    output is ``out_dtype`` (default fp32 — the historical contract; pass
    the state dtype to keep a bf16-configured engine's sufficient
    statistics in bf16 without a silent upcast).  Awkward shapes (e.g.
    prime ``p`` — the old divisor fallback degraded to ``block_p=1``, a
    silent up-to-512× tiling pessimization on the per-round path) are
    zero-padded to the block grid and sliced back: pad rows/columns are
    exact zero contributions, and every divisor-covered shape keeps its
    historical tiling bit-identically.
    """
    n, p = x.shape
    plan = kernel_block_plan("cov", rows=n, p=p, halfwidth=halfwidth)
    bp = block_p or plan["block_p"]
    bn = block_n or plan["block_n"]
    n_pad, p_pad = _pad_dim(n, bn), _pad_dim(p, bp)
    if (n_pad, p_pad) != (n, p):
        x = jnp.pad(x, ((0, n_pad - n), (0, p_pad - p)))
    out = _cov_band_update(x, halfwidth, bp, bn,
                           _auto_interpret(interpret))[:, :p]
    return out if out_dtype is None else out.astype(out_dtype)


@functools.partial(jax.jit,
                   static_argnames=("halfwidth", "block_p", "block_n",
                                    "interpret"))
def _cov_band_update_masked(x, mask, halfwidth, block_p, block_n, interpret):
    return cov_band_update_masked_pallas(x, mask, halfwidth=halfwidth,
                                         block_p=block_p, block_n=block_n,
                                         interpret=interpret)


def cov_band_update_masked(x: jnp.ndarray, mask: jnp.ndarray, halfwidth: int,
                           block_p: int | None = None,
                           block_n: int | None = None,
                           interpret: bool | None = None) -> jnp.ndarray:
    """Masked delta band: products where either entry is masked contribute 0.

    ``mask`` is a 0/1 validity array, either (p,) — a sensor-liveness mask
    broadcast over the batch (dead motes) — or (n, p) for per-reading
    measurement dropout.  The multiply is fused into the kernel's tile
    loads: no masked copy of ``x`` is materialized in HBM, though the mask
    itself streams alongside ``x`` (a (p,) mask is broadcast to the batch
    shape first, so the masked update reads roughly twice the input bytes
    of the unmasked kernel — acceptable for a VPU-bound kernel, and the
    ``mask=None`` fast path in callers keeps the fault-free fleet at
    unmasked cost).
    """
    n, p = x.shape
    mask = jnp.asarray(mask, dtype=x.dtype)
    if mask.ndim == 1:
        mask = jnp.broadcast_to(mask[None, :], (n, p))
    if mask.shape != (n, p):
        raise ValueError(f"mask shape {mask.shape} incompatible with {(n, p)}")
    plan = kernel_block_plan("cov", rows=n, p=p, halfwidth=halfwidth)
    bp = block_p or plan["block_p"]
    bn = block_n or plan["block_n"]
    n_pad, p_pad = _pad_dim(n, bn), _pad_dim(p, bp)
    if (n_pad, p_pad) != (n, p):
        x = jnp.pad(x, ((0, n_pad - n), (0, p_pad - p)))
        mask = jnp.pad(mask, ((0, n_pad - n), (0, p_pad - p)))
    out = _cov_band_update_masked(x, mask, halfwidth, bp, bn,
                                  _auto_interpret(interpret))
    return out[:, :p]


def cov_band_update_batched(x: jnp.ndarray, halfwidth: int,
                            block_p: int | None = None,
                            block_n: int | None = None,
                            interpret: bool | None = None) -> jnp.ndarray:
    """Per-network delta bands (B, 2h+1, p) from a fleet batch x (B, n, p).

    The networks axis is independent (no cross-network products), so the
    batched form is a ``vmap`` of the single-network kernel: Pallas turns the
    batch dimension into an extra outer grid axis, keeping the per-network
    tiling identical to :func:`cov_band_update`.  The streaming fleet driver
    reaches the same composition implicitly (``vmap`` over
    ``online_update``); this explicit wrapper is for callers that hold a
    (networks, n, p) block outside the driver — fleet-wide preprocessing,
    benchmarks, ad-hoc analysis.
    """
    if x.ndim != 3:
        raise ValueError(f"expected (networks, n, p), got {x.shape}")
    _, n, p = x.shape
    itp = _auto_interpret(interpret)
    return jax.vmap(
        lambda xi: cov_band_update(xi, halfwidth, block_p=block_p,
                                   block_n=block_n, interpret=itp))(x)


@functools.partial(jax.jit,
                   static_argnames=("halfwidth", "block_p", "block_n",
                                    "interpret"))
def _cov_band_update_chunk(x, w, halfwidth, block_p, block_n, interpret):
    return cov_band_update_chunk_pallas(x, w, halfwidth=halfwidth,
                                        block_p=block_p, block_n=block_n,
                                        interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("halfwidth", "block_p", "block_n",
                                    "interpret"))
def _cov_band_update_chunk_masked(x, mask, w, halfwidth, block_p, block_n,
                                  interpret):
    return cov_band_update_chunk_masked_pallas(
        x, mask, w, halfwidth=halfwidth, block_p=block_p, block_n=block_n,
        interpret=interpret)


def cov_band_update_chunk(xs: jnp.ndarray, weights: jnp.ndarray,
                          halfwidth: int, *,
                          mask: jnp.ndarray | None = None,
                          block_p: int | None = None,
                          block_n: int | None = None,
                          interpret: bool | None = None) -> jnp.ndarray:
    """Fold a (K, n, p) chunk of rounds into one delta band in ONE launch.

    ``weights`` (K,) scales each round's contribution —
    ``delta[k, i] = sum_t w[t] sum_r xs[t, r, i] * xs[t, r, i + k - h]`` —
    the per-round exponential-forgetting factors of the streaming fold
    (``gamma^(K-1-t)``), with 0 marking a padded round.  ``mask`` is an
    optional validity array, (K, p) per-round liveness or (K, n, p)
    per-reading dropout, fused into the tile loads like
    :func:`cov_band_update_masked`.

    Pad-to-block treatment: the flattened (K·n) row axis is zero-padded to
    the block grid with ZERO-WEIGHT rows (an exact no-op product), and an
    awkward feature axis (e.g. prime p) is zero-padded and the band sliced
    back, exactly like :func:`pca_project`; divisor-covered shapes keep the
    historical tiling, so at K=1 / w=1 the result is bit-identical to
    :func:`cov_band_update`.
    """
    if xs.ndim != 3:
        raise ValueError(f"expected (chunk, n, p), got {xs.shape}")
    K, n, p = xs.shape
    weights = jnp.asarray(weights, jnp.float32)
    if weights.shape != (K,):
        raise ValueError(f"weights shape {weights.shape} != {(K,)}")
    # the row tile covers the FLATTENED chunk: a K-round chunk becomes
    # ~K-fold fewer grid cells than K per-round launches (at K=1 the pick
    # degenerates to the per-round choice — bit-identity preserved)
    plan = kernel_block_plan("cov", rows=K * n, p=p, halfwidth=halfwidth)
    bp = block_p or plan["block_p"]
    bn = block_n or plan["block_n"]
    itp = _auto_interpret(interpret)
    x = xs.reshape(K * n, p)
    w = jnp.repeat(weights, n)[:, None]                 # (K*n, 1) row weights
    if mask is not None:
        mask = jnp.asarray(mask, xs.dtype)
        if mask.ndim == 2:
            if mask.shape != (K, p):
                raise ValueError(f"mask shape {mask.shape} != {(K, p)}")
            mask = jnp.broadcast_to(mask[:, None, :], (K, n, p))
        if mask.shape != (K, n, p):
            raise ValueError(f"mask shape {mask.shape} != {(K, n, p)}")
        mask = mask.reshape(K * n, p)
    rows_pad = _pad_dim(K * n, bn)
    p_pad = _pad_dim(p, bp)
    if (rows_pad, p_pad) != (K * n, p):
        x = jnp.pad(x, ((0, rows_pad - K * n), (0, p_pad - p)))
        w = jnp.pad(w, ((0, rows_pad - K * n), (0, 0)))
        if mask is not None:
            mask = jnp.pad(mask, ((0, rows_pad - K * n), (0, p_pad - p)))
    if mask is None:
        out = _cov_band_update_chunk(x, w, halfwidth, bp, bn, itp)
    else:
        out = _cov_band_update_chunk_masked(x, mask, w, halfwidth, bp, bn,
                                            itp)
    return out[:, :p]


def cov_band_update_chunk_batched(xs: jnp.ndarray, weights: jnp.ndarray,
                                  halfwidth: int, *,
                                  mask: jnp.ndarray | None = None,
                                  block_p: int | None = None,
                                  block_n: int | None = None,
                                  interpret: bool | None = None
                                  ) -> jnp.ndarray:
    """Fleet form of :func:`cov_band_update_chunk` over xs (B, K, n, p).

    ``weights`` is (B, K) per-network round weights (or (K,) shared),
    ``mask`` (B, K, p) / (B, K, n, p) / None.  A ``vmap`` of the fused
    chunk kernel: Pallas turns the networks axis into an extra outer grid
    axis, keeping the per-network tiling identical.
    """
    if xs.ndim != 4:
        raise ValueError(f"expected (networks, chunk, n, p), got {xs.shape}")
    B, K, n, p = xs.shape
    weights = jnp.asarray(weights, jnp.float32)
    if weights.ndim == 1:
        weights = jnp.broadcast_to(weights[None, :], (B, K))
    run = lambda xi, wi, mi: cov_band_update_chunk(
        xi, wi, halfwidth, mask=mi, block_p=block_p, block_n=block_n,
        interpret=interpret)
    if mask is None:
        return jax.vmap(lambda xi, wi: run(xi, wi, None))(xs, weights)
    return jax.vmap(run)(xs, weights, jnp.asarray(mask, xs.dtype))


@functools.partial(jax.jit,
                   static_argnames=("block_n", "block_k", "interpret"))
def _pca_project(x, w, block_n, block_k, interpret):
    return pca_project_pallas(x, w, block_n=block_n, block_k=block_k,
                              interpret=interpret)


def pca_project(x: jnp.ndarray, w: jnp.ndarray,
                block_n: int | None = None, block_k: int | None = None,
                interpret: bool | None = None) -> jnp.ndarray:
    """Z = X W (PCAg scores for a batch of rows); any (n, p) works.

    Non-divisible shapes (awkward n, prime p, or an explicit block that
    does not divide the axis) are zero-padded up to the block grid and the
    result sliced back: padded feature columns multiply zero basis rows, so
    every fp32 partial sum they contribute is exactly 0.0 and the sliced
    result is bit-identical to the unpadded kernel at the same block sizes.
    """
    n, p = x.shape
    plan = kernel_block_plan("stage", rows=n, p=p)
    bn = block_n or plan["block_n"]
    bk = block_k or plan["block_p"]
    n_pad, p_pad = _pad_dim(n, bn), _pad_dim(p, bk)
    if (n_pad, p_pad) != (n, p):
        x = jnp.pad(x, ((0, n_pad - n), (0, p_pad - p)))
        w = jnp.pad(w, ((0, p_pad - p), (0, 0)))
    out = _pca_project(x, w, bn, bk, _auto_interpret(interpret))
    return out[:n]


@functools.partial(jax.jit,
                   static_argnames=("block_n", "block_p", "interpret"))
def _pca_reconstruct(z, w, block_n, block_p, interpret):
    return pca_reconstruct_pallas(z, w, block_n=block_n, block_p=block_p,
                                  interpret=interpret)


def pca_reconstruct(z: jnp.ndarray, w: jnp.ndarray,
                    block_n: int | None = None, block_p: int | None = None,
                    interpret: bool | None = None) -> jnp.ndarray:
    """X_hat = Z W^T; any (n, p) works (padded + sliced like pca_project).

    Padded batch rows and padded basis rows produce extra output rows /
    columns that are sliced off; the surviving region is untouched (each
    output tile depends only on its own z rows and w rows).
    """
    n, q = z.shape
    p = w.shape[0]
    plan = kernel_block_plan("stage", rows=n, p=p)
    bn = block_n or plan["block_n"]
    bp = block_p or plan["block_p"]
    n_pad, p_pad = _pad_dim(n, bn), _pad_dim(p, bp)
    if (n_pad, p_pad) != (n, p):
        z = jnp.pad(z, ((0, n_pad - n), (0, 0)))
        w = jnp.pad(w, ((0, p_pad - p), (0, 0)))
    out = _pca_reconstruct(z, w, bn, bp, _auto_interpret(interpret))
    return out[:n, :p]


@functools.partial(jax.jit,
                   static_argnames=("epsilon", "block_n", "interpret"))
def _supervised_compress(x, w, mean2d, mask, epsilon, block_n, interpret):
    return supervised_compress_pallas(x, w, mean2d, mask, epsilon=epsilon,
                                      block_n=block_n, interpret=interpret)


def supervised_compress(x: jnp.ndarray, w: jnp.ndarray,
                        mean: jnp.ndarray | None = None,
                        *, epsilon: float,
                        mask: jnp.ndarray | None = None,
                        block_n: int | None = None,
                        interpret: bool | None = None,
                        ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One fused supervised-compression epoch (Sec. 2.4.1) on device.

    Returns ``(z, x_hat, flagged)``: scores (n, q) and reconstruction
    (n, p) in fp32, plus the bool notification mask ``|x - x_hat| > eps``
    (so every un-flagged entry is within the closed bound ``<= eps`` — the
    same convention as the NumPy oracle
    :class:`repro.core.compression.SupervisedCompressor`).  ``mask`` is an
    optional 0/1 liveness array, (p,) or (n, p); dead sensors contribute no
    score record and raise no notification.  ``epsilon`` is static (the
    kernel bakes it in); the batch axis is padded to the block like
    :func:`pca_project`, padded rows carry mask 0 so they project to
    nothing and never flag.
    """
    n, p = x.shape
    if mean is None:
        mean = jnp.zeros((p,), jnp.float32)
    mean2d = jnp.asarray(mean, jnp.float32).reshape(1, p)
    if mask is None:
        mask = jnp.ones((n, p), jnp.float32)
    else:
        mask = jnp.asarray(mask, jnp.float32)
        if mask.ndim == 1:
            mask = jnp.broadcast_to(mask[None, :], (n, p))
    bn = block_n or kernel_block_plan("stage", rows=n, p=p)["block_n"]
    n_pad = _pad_dim(n, bn)
    if n_pad != n:
        x = jnp.pad(x, ((0, n_pad - n), (0, 0)))
        mask = jnp.pad(mask, ((0, n_pad - n), (0, 0)))
    z, x_hat, flags = _supervised_compress(x, w, mean2d, mask,
                                           float(epsilon), bn,
                                           _auto_interpret(interpret))
    return z[:n], x_hat[:n], flags[:n] > 0.0


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def _pca_monitor(x, w, mean2d, invlam2d, mask, block_n, interpret):
    return pca_monitor_pallas(x, w, mean2d, invlam2d, mask,
                              block_n=block_n, interpret=interpret)


def pca_monitor(x: jnp.ndarray, w: jnp.ndarray,
                mean: jnp.ndarray | None = None,
                inv_lam: jnp.ndarray | None = None,
                *, mask: jnp.ndarray | None = None,
                block_n: int | None = None,
                interpret: bool | None = None,
                ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One fused monitoring epoch (Sec. 2.4.3) on device.

    Returns ``(z, t2, spe)``: scores (n, q) in fp32 plus the per-epoch
    statistics T² (n,) = Σ_k z_k²·inv_lam_k and SPE (n,) =
    ‖(x − mean)·mask − z Wᵀ‖² over live sensors — the same quantities the
    NumPy oracle (:class:`repro.core.events.LowVarianceDetector` /
    :func:`repro.kernels.ref.pca_monitor`) computes host-side.  ``inv_lam``
    defaults to all-ones (unnormalized T²); clamp the eigenvalue estimates
    *before* inverting.  ``mask`` is an optional 0/1 liveness array, (p,)
    or (n, p); dead sensors contribute no score record and no residual
    energy.  The batch axis is padded to the block like
    :func:`supervised_compress`; padded rows carry mask 0, so their scores
    and statistics are exactly zero and are sliced off.
    """
    n, p = x.shape
    q = w.shape[1]
    if mean is None:
        mean = jnp.zeros((p,), jnp.float32)
    mean2d = jnp.asarray(mean, jnp.float32).reshape(1, p)
    if inv_lam is None:
        inv_lam = jnp.ones((q,), jnp.float32)
    invlam2d = jnp.asarray(inv_lam, jnp.float32).reshape(1, q)
    if mask is None:
        mask = jnp.ones((n, p), jnp.float32)
    else:
        mask = jnp.asarray(mask, jnp.float32)
        if mask.ndim == 1:
            mask = jnp.broadcast_to(mask[None, :], (n, p))
    bn = block_n or kernel_block_plan("stage", rows=n, p=p)["block_n"]
    n_pad = _pad_dim(n, bn)
    if n_pad != n:
        x = jnp.pad(x, ((0, n_pad - n), (0, 0)))
        mask = jnp.pad(mask, ((0, n_pad - n), (0, 0)))
    z, t2, spe = _pca_monitor(x, w, mean2d, invlam2d, mask, bn,
                              _auto_interpret(interpret))
    return z[:n], t2[:n, 0], spe[:n, 0]


def pca_monitor_batched(x: jnp.ndarray, w: jnp.ndarray,
                        mean: jnp.ndarray | None = None,
                        inv_lam: jnp.ndarray | None = None,
                        *, mask: jnp.ndarray | None = None,
                        block_n: int | None = None,
                        interpret: bool | None = None,
                        ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fleet form of :func:`pca_monitor` over x (B, n, p).

    ``w`` is (B, p, q) per-network bases (or (p, q) shared), ``mean``
    (B, p) / (p,) / None, ``inv_lam`` (B, q) / (q,) / None, ``mask``
    (B, n, p) / (B, p) / None.  A ``vmap`` of the fused kernel, same
    composition as :func:`supervised_compress_batched`.
    """
    if x.ndim != 3:
        raise ValueError(f"expected (networks, n, p), got {x.shape}")
    B, n, p = x.shape
    if w.ndim == 2:
        w = jnp.broadcast_to(w[None], (B,) + w.shape)
    q = w.shape[2]
    if mean is None:
        mean = jnp.zeros((B, p), jnp.float32)
    else:
        mean = jnp.asarray(mean, jnp.float32)
        if mean.ndim == 1:
            mean = jnp.broadcast_to(mean[None, :], (B, p))
    if inv_lam is None:
        inv_lam = jnp.ones((B, q), jnp.float32)
    else:
        inv_lam = jnp.asarray(inv_lam, jnp.float32)
        if inv_lam.ndim == 1:
            inv_lam = jnp.broadcast_to(inv_lam[None, :], (B, q))
    if mask is None:
        mask = jnp.ones((B, n, p), jnp.float32)
    else:
        mask = jnp.asarray(mask, jnp.float32)
        if mask.ndim == 2:
            mask = jnp.broadcast_to(mask[:, None, :], (B, n, p))
    return jax.vmap(
        lambda xi, wi, mi, li, ki: pca_monitor(
            xi, wi, mi, li, mask=ki, block_n=block_n,
            interpret=interpret))(x, w, mean, inv_lam, mask)


def supervised_compress_batched(x: jnp.ndarray, w: jnp.ndarray,
                                mean: jnp.ndarray | None = None,
                                *, epsilon: float,
                                mask: jnp.ndarray | None = None,
                                block_n: int | None = None,
                                interpret: bool | None = None,
                                ) -> tuple[jnp.ndarray, jnp.ndarray,
                                           jnp.ndarray]:
    """Fleet form of :func:`supervised_compress` over x (B, n, p).

    ``w`` is (B, p, q) per-network bases (or (p, q) shared), ``mean``
    (B, p) / (p,) / None, ``mask`` (B, n, p) / (B, p) / None.  A ``vmap``
    of the fused kernel: Pallas turns the networks axis into an extra
    outer grid axis, keeping the per-network tiling identical.
    """
    if x.ndim != 3:
        raise ValueError(f"expected (networks, n, p), got {x.shape}")
    B, n, p = x.shape
    if w.ndim == 2:
        w = jnp.broadcast_to(w[None], (B,) + w.shape)
    if mean is None:
        mean = jnp.zeros((B, p), jnp.float32)
    else:
        mean = jnp.asarray(mean, jnp.float32)
        if mean.ndim == 1:
            mean = jnp.broadcast_to(mean[None, :], (B, p))
    if mask is None:
        mask = jnp.ones((B, n, p), jnp.float32)
    else:
        mask = jnp.asarray(mask, jnp.float32)
        if mask.ndim == 2:
            mask = jnp.broadcast_to(mask[:, None, :], (B, n, p))
    return jax.vmap(
        lambda xi, wi, mi, ki: supervised_compress(
            xi, wi, mi, epsilon=epsilon, mask=ki, block_n=block_n,
            interpret=interpret))(x, w, mean, mask)


@functools.partial(jax.jit,
                   static_argnames=("halfwidth", "epsilon", "with_compress",
                                    "with_monitor", "block_p", "block_n",
                                    "interpret"))
def _fused_stream(x, mask, w_rows, basis, mean2d, invlam2d, halfwidth,
                  epsilon, with_compress, with_monitor, block_p, block_n,
                  interpret):
    return fused_stream_pallas(
        x, mask, w_rows, basis, mean2d, invlam2d,
        halfwidth=halfwidth, epsilon=epsilon, with_compress=with_compress,
        with_monitor=with_monitor, block_p=block_p, block_n=block_n,
        interpret=interpret)


def _fused_prep(x, basis, mean, inv_lam, mask, precision):
    """Shared operand normalization of the fused wrapper and its blocked
    jnp twin: fp32 canonical forms, ones mask default, optional bf16
    downcast of the LARGE operands only (x/mask/basis — the tile traffic;
    mean, inv_lam and the row weights are replicated scalars/rows and stay
    fp32, as do every in-kernel accumulator and every output)."""
    rows, p = x.shape
    q = basis.shape[1]
    x = jnp.asarray(x, jnp.float32)
    mean2d = (jnp.zeros((1, p), jnp.float32) if mean is None
              else jnp.asarray(mean, jnp.float32).reshape(1, p))
    invlam2d = (jnp.ones((1, q), jnp.float32) if inv_lam is None
                else jnp.asarray(inv_lam, jnp.float32).reshape(1, q))
    if mask is None:
        mask = jnp.ones((rows, p), jnp.float32)
    else:
        mask = jnp.asarray(mask, jnp.float32)
        if mask.ndim == 1:
            mask = jnp.broadcast_to(mask[None, :], (rows, p))
    basis = jnp.asarray(basis, jnp.float32)
    if precision not in ("fp32", "bf16"):
        raise ValueError(f"precision must be 'fp32' or 'bf16', "
                         f"got {precision!r}")
    if precision == "bf16":
        x = x.astype(jnp.bfloat16)
        mask = mask.astype(jnp.bfloat16)     # 0/1: exact in bf16
        basis = basis.astype(jnp.bfloat16)
    return x, mask, basis, mean2d, invlam2d


def fused_stream_update(x: jnp.ndarray, weights: jnp.ndarray,
                        basis: jnp.ndarray,
                        mean: jnp.ndarray | None = None,
                        inv_lam: jnp.ndarray | None = None, *,
                        halfwidth: int, epsilon: float = 0.0,
                        with_compress: bool, with_monitor: bool,
                        mask: jnp.ndarray | None = None,
                        precision: str = "fp32",
                        block_p: int | None = None,
                        block_n: int | None = None,
                        interpret: bool | None = None,
                        ) -> tuple[jnp.ndarray, jnp.ndarray,
                                   jnp.ndarray | None, jnp.ndarray | None,
                                   jnp.ndarray | None, jnp.ndarray | None]:
    """ONE kernel pass over a flattened (rows, p) chunk: the forgetting-
    weighted band fold plus the configured per-row stages
    (:func:`repro.kernels.fused_stream.fused_stream_pallas`).

    ``weights`` (rows,) carries each row's round weight (γ^(live after)
    with 0 for pad/invalid rows); ``mask`` the per-row 0/1 validity
    ((rows, p), (p,) broadcast, or None = all live).  ``basis`` (p, q),
    ``mean`` (p,) and ``inv_lam`` (q,) are the stage operands.

    Returns ``(band, z, x_hat, flagged, t2, spe)`` — band (2h+1, p) fp32;
    z (rows, q); x_hat (rows, p) and bool ``flagged`` (compression, else
    None); t2/spe (rows,) (monitoring, else None).  With fp32 operands
    the band agrees with :func:`cov_band_update_chunk` and the stages with
    :func:`supervised_compress` / :func:`pca_monitor` to fp32 rounding;
    ``precision="bf16"`` downcasts the tile-load operands (x, mask, basis)
    to bfloat16 — halving the chunk's HBM traffic — while every
    accumulator and output stays fp32.

    The row axis is padded to the block with zero-weight zero-mask rows
    (exact no-ops everywhere), an awkward feature axis is zero-padded to
    the band's feature block exactly like :func:`cov_band_update_chunk`
    (basis and mean with zero rows, so the pad columns add exact zeros to
    the stage dots), and every output is sliced back.
    """
    rows, p = x.shape
    x, mask, basis, mean2d, invlam2d = _fused_prep(
        x, basis, mean, inv_lam, mask, precision)
    weights = jnp.asarray(weights, jnp.float32).reshape(rows, 1)
    plan = kernel_block_plan("fused", rows=rows, p=p, dtype=precision,
                             halfwidth=halfwidth)
    bp = block_p or plan["block_p"]
    bn = block_n or plan["block_n"]
    rows_pad = _pad_dim(rows, bn)
    p_pad = _pad_dim(p, bp)
    if (rows_pad, p_pad) != (rows, p):
        x = jnp.pad(x, ((0, rows_pad - rows), (0, p_pad - p)))
        mask = jnp.pad(mask, ((0, rows_pad - rows), (0, p_pad - p)))
        weights = jnp.pad(weights, ((0, rows_pad - rows), (0, 0)))
    if p_pad != p:
        basis = jnp.pad(basis, ((0, p_pad - p), (0, 0)))
        mean2d = jnp.pad(mean2d, ((0, 0), (0, p_pad - p)))
    out = _fused_stream(x, mask, weights, basis, mean2d, invlam2d,
                        halfwidth, float(epsilon), with_compress,
                        with_monitor, bp, bn, _auto_interpret(interpret))
    band, z = out[0][:, :p], out[1][:rows]
    i = 2
    x_hat = flagged = t2 = spe = None
    if with_compress:
        x_hat = out[i][:rows, :p]
        flagged = out[i + 1][:rows, :p] > 0.0
        i += 2
    if with_monitor:
        t2 = out[i][:rows, 0]
        spe = out[i + 1][:rows, 0]
    return band, z, x_hat, flagged, t2, spe


def fused_stream_stages_blocked(x: jnp.ndarray, basis: jnp.ndarray,
                                mean: jnp.ndarray | None = None,
                                inv_lam: jnp.ndarray | None = None, *,
                                epsilon: float = 0.0,
                                with_compress: bool, with_monitor: bool,
                                mask: jnp.ndarray | None = None,
                                precision: str = "fp32",
                                block_n: int | None = None,
                                ) -> tuple[jnp.ndarray,
                                           jnp.ndarray | None,
                                           jnp.ndarray | None,
                                           jnp.ndarray | None,
                                           jnp.ndarray | None]:
    """The fused kernel's STAGE arithmetic as a plain-jnp scan over row
    blocks — the kernel's row blocks, same op order, same fp32
    accumulation as the kernel body, so it agrees with the kernel's
    stages to fp32 rounding.  A ``lax.scan`` (not an unrolled python loop)
    mirrors the grid loop structurally.

    This is the post-refresh fix-up of the fused driver path
    (:func:`repro.streaming.driver.chunk_stream_step`): the kernel runs
    ONCE against the pre-decision basis; when the scheduler then rotates
    W, the stages must be re-evaluated against the post-decision basis —
    re-launching the kernel would double the chunk's HBM traffic on every
    refresh AND put a second ``pallas_call`` into the traced chunk body
    (the jaxpr launch-count guarantee counts both ``lax.cond`` branches).
    A pure-jnp twin recomputes only the MXU/VPU stage math (no band fold —
    the fold is basis-independent) with identical per-block shapes.

    Returns ``(z, x_hat, flagged, t2, spe)`` with None for disabled
    stages, like :func:`fused_stream_update` minus the band.
    """
    rows, p = x.shape
    x, mask, basis, mean2d, invlam2d = _fused_prep(
        x, basis, mean, inv_lam, mask, precision)
    bn = block_n or kernel_block_plan("fused", rows=rows, p=p,
                                      dtype=precision)["block_n"]
    rows_pad = _pad_dim(rows, bn)
    if rows_pad != rows:
        x = jnp.pad(x, ((0, rows_pad - rows), (0, 0)))
        mask = jnp.pad(mask, ((0, rows_pad - rows), (0, 0)))
    w = basis.astype(jnp.float32)
    nblk = rows_pad // bn

    def _block(_, xm):
        xb, mb = xm
        xb = xb.astype(jnp.float32)
        mb = mb.astype(jnp.float32)
        xc = (xb - mean2d) * mb
        z = jnp.dot(xc, w, preferred_element_type=jnp.float32)
        xh_r = jnp.dot(z, w.T, preferred_element_type=jnp.float32)
        if with_compress:
            xh = xh_r + mean2d
            fl = (jnp.abs(xb - xh) > epsilon) & (mb > 0.0)
        else:
            xh = fl = jnp.zeros((), jnp.float32)
        if with_monitor:
            resid = (xc - xh_r) * mb
            t2 = jnp.sum(z * z * invlam2d, axis=1)
            spe = jnp.sum(resid * resid, axis=1)
        else:
            t2 = spe = jnp.zeros((), jnp.float32)
        return None, (z, xh, fl, t2, spe)

    _, (z, xh, fl, t2, spe) = jax.lax.scan(
        _block, None, (x.reshape(nblk, bn, p), mask.reshape(nblk, bn, p)))
    flat = lambda a: a.reshape((rows_pad,) + a.shape[2:])[:rows]
    return (flat(z),
            flat(xh) if with_compress else None,
            flat(fl) if with_compress else None,
            flat(t2) if with_monitor else None,
            flat(spe) if with_monitor else None)
