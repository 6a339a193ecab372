"""Chip smoke test: the streaming PCA server end to end on a TPU.

    python chip_smoke.py              # one chip: the serving engine
    python chip_smoke.py --chips 4    # four chips: the two-level region fleet

One chip: a ``StreamingPCAEngine`` at the wsn-1m region shape (p=1024
sensors, q=32 components, band half-width 128, compression and detection
on, fp32) with 256 slots folds 256 epochs per slot per step.  More streams
than slots are submitted, each 2-4 chunks long, so admission, retirement
and re-admission all happen.  The run passes when every stream completes,
every compressed stream keeps its error bound, sampled slots agree with a
float64 NumPy reference, the compiled step holds Mosaic kernels, and a
pipelined repeat reproduces the synchronous results exactly.

Four chips: 64 regions of the same shape stream through
``hierarchical_stream_run`` on a 4-device ``region`` mesh (16 regions per
chip) and on a 1-device mesh; the shards, the merge collectives and the
merged fleet basis are checked.

Runs in one process and starts none.  It refuses to run without a TPU.  The
last line of standard output is one JSON object,
``{"ok": ..., "device": {"platform", "kind", "count"}}``; the exit code is
0 only when every check passed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

# the wsn-1m region shape (src/repro/configs/wsn_1m.py: region_p, q, h,
# batch_epochs)
P, Q, H = 1024, 32, 128
K, N = 4, 64                     # rounds per chunk, epochs per round:
#                                  K * N = 256 epochs per slot per step
SLOTS = 256                      # a quarter of wsn-1m's 1,024 regions
STREAMS = 320                    # more than SLOTS: re-admission happens
RANK = 32                        # latent factors of the generated data
NOISE = 0.01                     # isotropic sensor noise (std)
FORGETTING = 0.99
EPSILON = 0.05                   # compression error bound
SAMPLED = (0, 1, 255, 300)       # streams checked against the reference
PIPELINE_REPEAT = 64             # first streams re-run with pipeline=True

# tolerances of the reference comparison (fp32 device vs float64 host)
MAX_ANGLE = 0.05                 # largest principal angle (radians)
RETAINED_RTOL = 1e-3             # relative error of the retained fraction
# 4-chip fleet vs the same regions on one device
FLEET_ANGLE = 1e-3
FLEET_RTOL = 1e-4


def _loadings(rng: np.random.Generator) -> np.ndarray:
    """(P, RANK) spatially local factors: Gaussian bumps whose support is
    narrower than the band, so the population covariance is banded (the
    local covariance hypothesis) and its top-Q subspace is the factors'."""
    centers = (np.arange(RANK) + 0.5) * (P / RANK)
    idx = np.arange(P)[:, None]
    d = idx - centers[None, :]
    L = np.exp(-0.5 * (d / 16.0) ** 2) * (np.abs(d) <= H // 2)
    L /= np.linalg.norm(L, axis=0, keepdims=True)
    return L.astype(np.float32)


def _stream(rng: np.random.Generator, L: np.ndarray, rounds: int,
            n: int) -> np.ndarray:
    """(rounds, n, P) float32: RANK local factors plus small noise.

    Within each round the factor series are centred and mutually
    orthogonal, so every window's sample covariance of the signal is
    exactly the banded population covariance.  With independent factors
    the sample cross-covariances of distant factors, which the band
    truncates, rotate the estimated subspace by ~0.08 rad between 512 and
    1024 epochs — the comparison would measure that, not the engine.
    """
    var = rng.uniform(1.0, 4.0, size=RANK)
    a = rng.standard_normal((rounds, n, RANK + 1))
    a[:, :, 0] = 1.0
    f = np.linalg.qr(a)[0][:, :, 1:] * np.sqrt(n * var)
    noise = rng.standard_normal((rounds, n, P), dtype=np.float32)
    return (f @ L.T).astype(np.float32) + NOISE * noise


def _reference(rounds: np.ndarray, forgetting: float, h: int, q: int):
    """Float64 banded covariance with per-round forgetting, as the engine
    estimates it, and its top-q eigenvectors: (C_band, W_ref, rho_ref)."""
    r = rounds.shape[0]
    w = forgetting ** np.arange(r - 1, -1, -1, dtype=np.float64)
    x = rounds.astype(np.float64)
    t = w.sum() * rounds.shape[1]
    mean = np.einsum("r,rnp->p", w, x) / t
    prod = np.einsum("r,rnp,rnq->pq", w, x, x) / t
    c = prod - np.outer(mean, mean)
    i = np.arange(c.shape[0])
    c[np.abs(i[:, None] - i[None, :]) > h] = 0.0
    evals, evecs = np.linalg.eigh(c)
    w_ref = evecs[:, ::-1][:, :q]
    rho = np.trace(w_ref.T @ c @ w_ref) / np.trace(c)
    return c, w_ref, float(rho)


def _max_angle(a: np.ndarray, b: np.ndarray) -> float:
    """Largest principal angle between the column spans of a and b."""
    qa, _ = np.linalg.qr(np.asarray(a, np.float64))
    qb, _ = np.linalg.qr(np.asarray(b, np.float64))
    s = np.linalg.svd(qa.T @ qb, compute_uv=False)
    return float(np.arccos(np.clip(s.min(), -1.0, 1.0)))


def _same_result(a, b) -> bool:
    return (np.array_equal(a.components, b.components)
            and a.retained == b.retained and a.refreshes == b.refreshes
            and a.comm_packets == b.comm_packets and a.rounds == b.rounds
            and a.compression_max_err == b.compression_max_err
            and a.detection_events == b.detection_events)


def one_chip(checks: dict) -> None:
    import jax

    from repro.serve.engine import StreamingPCAEngine, StreamRequest
    from repro.streaming import StreamConfig
    from repro.streaming.compressor import CompressionConfig
    from repro.streaming.detector import DetectionConfig
    from repro.streaming.driver import engine_chunk_step_fn, stream_init

    cfg = StreamConfig(
        p=P, q=Q, halfwidth=H, forgetting=FORGETTING, warmup_rounds=K,
        compression=CompressionConfig(epsilon=EPSILON,
                                      emit_reconstruction=False),
        detection=DetectionConfig(emit_statistics=False))

    t0 = time.perf_counter()
    rng = np.random.default_rng(0)
    L = _loadings(rng)
    lengths = rng.integers(2 * K, 4 * K + 1, size=STREAMS)
    data = [_stream(rng, L, int(r), N) for r in lengths]
    print(f"data: {STREAMS} streams, {int(lengths.sum()) * N} epochs, "
          f"{time.perf_counter() - t0:.1f} s", flush=True)

    # the engine's compiled step: compile it once here (the engine's own
    # first call then loads it from the compilation cache) and look for
    # the Mosaic kernels in it
    states = jax.eval_shape(
        lambda k: jax.vmap(lambda s: stream_init(cfg, s))(
            jax.random.split(k, SLOTS)), jax.random.PRNGKey(0))
    batch = jax.ShapeDtypeStruct((SLOTS, K, N, P), np.float32)
    rv = jax.ShapeDtypeStruct((SLOTS, K), np.float32)
    t0 = time.perf_counter()
    text = engine_chunk_step_fn(cfg).lower(states, batch, rv).compile() \
        .as_text()
    print(f"engine step compile: {time.perf_counter() - t0:.1f} s",
          flush=True)
    checks["step_has_tpu_custom_call"] = "tpu_custom_call" in text

    def serve(n_streams: int, pipeline: bool):
        eng = StreamingPCAEngine(cfg, slots=SLOTS, seed=0, chunk=K,
                                 pipeline=pipeline)
        reqs = [StreamRequest(rounds=data[i]) for i in range(n_streams)]
        for r in reqs:
            if not eng.submit(r):
                raise RuntimeError("admission queue rejected a stream")
        t0 = time.perf_counter()
        eng.run_until_done()
        jax.block_until_ready(eng.states)
        return eng, reqs, time.perf_counter() - t0

    eng, reqs, wall = serve(STREAMS, pipeline=False)
    steps = eng._clock
    print(f"sync engine: {steps} steps, "
          f"{sum(r.result.rounds for r in reqs if r.done) * N} epochs "
          f"folded, {wall:.2f} s wall, {wall / max(steps, 1) * 1e3:.1f} ms "
          "per step (first step included)", flush=True)
    checks["every_stream_completed"] = all(
        r.done and r.result.reason == "completed"
        and r.result.rounds == r.rounds.shape[0] for r in reqs)
    checks["readmission_happened"] = len(eng.retired_log) == STREAMS \
        and STREAMS > SLOTS
    # the kernel flags |x - x_hat| > eps in fp32, so the bound is the fp32
    # value of eps (0.05 rounds up to 0.0500000007)
    bound = float(np.float32(EPSILON))
    worst = max(r.result.compression_max_err for r in reqs if r.done)
    print(f"worst compression error {worst!r} (bound {bound!r})")
    checks["compression_within_epsilon"] = worst <= bound
    del eng                      # free its fleet state before the repeat

    ok_ref = True
    for i in SAMPLED:
        res = reqs[i].result
        _, w_ref, rho_ref = _reference(data[i], FORGETTING, H, Q)
        angle = _max_angle(res.components, w_ref)
        rel = abs(res.retained - rho_ref) / rho_ref
        w = np.asarray(res.components, np.float64)
        ortho = np.abs(w.T @ w - np.eye(Q)).max()
        print(f"stream {i}: max principal angle {angle:.3g} rad "
              f"(<= {MAX_ANGLE}), retained {res.retained:.6f} vs "
              f"reference {rho_ref:.6f}, rel err {rel:.2e} "
              f"(<= {RETAINED_RTOL}), {res.refreshes} refreshes, "
              f"max|W'W - I| {ortho:.2e}")
        ok_ref &= angle <= MAX_ANGLE and rel <= RETAINED_RTOL
    checks["sampled_slots_match_reference"] = ok_ref

    pipe, pipe_reqs, wall = serve(PIPELINE_REPEAT, pipeline=True)
    print(f"pipelined repeat: {pipe._clock} steps, {wall:.2f} s wall",
          flush=True)
    checks["pipelined_matches_sync"] = all(
        p.done and _same_result(p.result, s.result)
        for p, s in zip(pipe_reqs, reqs))


def four_chips(checks: dict) -> None:
    import jax
    import jax.numpy as jnp

    from repro.distributed.sharding import shard_regions
    from repro.launch.mesh import make_fleet_mesh
    from repro.streaming import StreamConfig
    from repro.streaming.hierarchy import (fleet_basis_dense,
                                           hierarchical_stream_init,
                                           hierarchical_stream_run)

    regions, rounds = 64, 2 * K
    cfg = StreamConfig(p=P, q=Q, halfwidth=H, forgetting=FORGETTING,
                       warmup_rounds=K)
    rng = np.random.default_rng(1)
    L = _loadings(rng)
    gain = np.linspace(1.0, 2.0, regions, dtype=np.float32)
    xs = np.stack([_stream(rng, L, rounds, N) * g for g in gain])
    states = hierarchical_stream_init(cfg, jax.random.PRNGKey(0), regions)

    def run(n_dev: int):
        mesh = make_fleet_mesh(region=n_dev, data=1)
        st, x = shard_regions(mesh, (states, jnp.asarray(xs)))
        t0 = time.perf_counter()
        compiled = hierarchical_stream_run.lower(cfg, mesh, st, x,
                                                 chunk=K).compile()
        print(f"{n_dev}-device fleet compile: "
              f"{time.perf_counter() - t0:.1f} s", flush=True)
        t0 = time.perf_counter()
        out = compiled(st, x)
        jax.block_until_ready(out)
        print(f"{n_dev}-device fleet run: {time.perf_counter() - t0:.2f} s",
              flush=True)
        return compiled.as_text(), out

    text4, (fin4, _, fleet4) = run(4)
    _, (fin1, _, fleet1) = run(1)

    per_chip = regions // 4
    band = fin4.cov.band
    shards = sorted(band.addressable_shards, key=lambda s: s.index[0].start)
    checks["each_chip_holds_a_quarter"] = (
        len({s.device.id for s in shards}) == 4
        and [(s.index[0].start, s.index[0].stop) for s in shards]
        == [(d * per_chip, (d + 1) * per_chip) for d in range(4)]
        and all(s.data.shape[0] == per_chip for s in shards))
    checks["merge_collectives_compiled"] = ("all-gather" in text4
                                           and "all-reduce" in text4)
    b4, b1 = fleet4.basis, fleet1.basis
    angle = _max_angle(np.asarray(fleet_basis_dense(b4, fin4.sched.W)),
                       np.asarray(fleet_basis_dense(b1, fin1.sched.W)))
    rho4, rho1 = float(b4.rho), float(b1.rho)
    rel = abs(rho4 - rho1) / abs(rho1)
    print(f"fleet basis: max principal angle 4 vs 1 device {angle:.3g} rad "
          f"(<= {FLEET_ANGLE}); rho {rho4:.6f} vs {rho1:.6f}, rel err "
          f"{rel:.2e} (<= {FLEET_RTOL})")
    checks["fleet_matches_one_device"] = (
        np.array_equal(np.asarray(b4.region), np.asarray(b1.region))
        and angle <= FLEET_ANGLE and rel <= FLEET_RTOL)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the serving engine; 4: the region fleet")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {devices[0].platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              "device(s)", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    print(f"device: {devices[0].device_kind} x{len(devices)}; compile "
          f"cache {cache}", flush=True)

    checks: dict[str, bool] = {}
    t0 = time.perf_counter()
    (one_chip if args.chips == 1 else four_chips)(checks)
    for name, ok in checks.items():
        print(f"check {name}: {'PASS' if ok else 'FAIL'}")
    print(f"total {time.perf_counter() - t0:.1f} s", flush=True)
    ok = bool(checks) and all(checks.values())
    print(json.dumps({"ok": ok, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
