"""Streaming fleet benchmark: rounds/s, chunking, and the accuracy frontier.

Times the fleet driver (``batched_stream_run``: a scan of the fleet body,
one decision per round by default) at a few fleet sizes, sweeps the drift
threshold to chart the scheduler's communication-vs-retained-variance
tradeoff — the streaming analogue of the paper's Fig. 9/14 load curves —
and sweeps the chunk size K of the fleet body (DESIGN.md Sec. 12) against
K=1.  CSV derived column:

* ``stream/fleet{B}`` — network-rounds per second at fleet size B (K=1)
* ``stream/threshold{t}`` — "retained@end|refreshes|packets" per network
* ``stream/chunk1_fleet{B}`` — the chunk sweep's K=1 baseline
* ``stream/chunk{K}_fleet{B}`` — "rounds/s|speedup|launches/round|eigh/round"
  where launches/round counts the cov-update Pallas launches per streamed
  round and eigh/round the refresh's ``eigh`` sites, both read off the
  traced chunk body's jaxpr (1/K each — the structural amortization claim)
* ``stream/{split_fp32,fused_fp32,fused_bf16}_fleet{B}`` — the mega-kernel
  sweep (DESIGN.md Sec. 14): same data and chunk size with compression AND
  detection enabled, "rounds/s|speedup vs split|launches/chunk" (3 split →
  1 fused, read off the traced jaxpr); ``--fused`` runs only this sweep

Standalone: ``python benchmarks/streaming_bench.py --smoke --chunk 2,8
--json BENCH_streaming.json`` emits the same rows as a JSON artifact
(benchmarks/run.py --streaming-json does this inside the CI smoke run).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import row, timed
from repro.streaming import (CompressionConfig, DetectionConfig,
                             StreamConfig, batched_stream_run, stream_init)

P, Q, H = 32, 3, 4
N_PER_ROUND = 8


def _fleet(key, n_networks: int, n_rounds: int, shift_at: int) -> jnp.ndarray:
    base = jnp.linspace(4.0, 1.0, P)
    x = jax.random.normal(key, (n_networks, n_rounds, N_PER_ROUND, P))
    rounds = jnp.arange(n_rounds)[None, :, None, None]
    scale = jnp.where(rounds >= shift_at, base[::-1][None, None, None, :],
                      base[None, None, None, :])
    return x * scale


def _states(cfg, n_networks: int):
    keys = jax.random.split(jax.random.PRNGKey(1), n_networks)
    return jax.vmap(lambda k: stream_init(cfg, k))(keys)


def _count_prims(jaxpr, names, acc=None):
    """Recursively count primitive occurrences in a jaxpr (sub-jaxprs
    included) — the structural launch accounting of the chunk sweep."""
    acc = acc if acc is not None else {}
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in names:
            acc[eqn.primitive.name] = acc.get(eqn.primitive.name, 0) + 1
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                if hasattr(sub, "jaxpr"):
                    _count_prims(sub.jaxpr, names, acc)
    return acc


def _chunk_body_counts(cfg, chunk: int) -> tuple[float, float]:
    """(cov launches, refresh eigh sites) per ROUND of the chunk body."""
    from repro.streaming import stream_init as s_init
    from repro.streaming.driver import chunk_stream_step

    st = s_init(cfg, jax.random.PRNGKey(0))
    jx = jax.make_jaxpr(lambda s, x: chunk_stream_step(cfg, s, x))(
        st, jnp.zeros((chunk, N_PER_ROUND, P)))
    counts = _count_prims(jx.jaxpr, {"pallas_call", "eigh"})
    return (counts.get("pallas_call", 0) / chunk,
            counts.get("eigh", 0) / chunk)


def chunk_sweep(smoke: bool = False, chunks: tuple[int, ...] | None = None):
    """The fleet driver at K=1 and at a few chunk sizes K.

    Same data, same config: only the dispatch granularity changes.  The
    derived column records rounds/s, the speedup over K=1, and the
    structural cov-launch / refresh ``eigh`` counts per round (1/K — the
    per-chunk launch verified on the jaxpr).
    """
    out = []
    chunks = chunks or ((2, 8) if smoke else (2, 4, 8, 16))
    B = 4 if smoke else 16
    # the scan must be long enough that steady-state body cost dominates
    # scheduler-noise/dispatch jitter — 32 rounds keeps smoke in seconds
    # while making the best-of-5 ratio stable on a loaded CI box
    n_rounds = 32 if smoke else 64
    repeat = 5
    cfg = StreamConfig(p=P, q=Q, halfwidth=H, forgetting=0.9,
                       drift_threshold=0.1, warmup_rounds=5)
    xs = _fleet(jax.random.PRNGKey(0), B, n_rounds, shift_at=n_rounds // 2)
    states = _states(cfg, B)

    def _run(**kw):
        res = batched_stream_run(cfg, states, xs, **kw)
        jax.block_until_ready(res[1].rho)
        return res

    _run()                                           # compile outside timing
    _, us0 = timed(_run, repeat=repeat)
    rps0 = B * n_rounds / (us0 / 1e6)
    out.append(row(f"stream/chunk1_fleet{B}", us0, f"{rps0:.0f} rounds/s"))
    for k in chunks:
        _run(chunk=k)                                # compile outside timing
        _, us = timed(_run, chunk=k, repeat=repeat)
        rps = B * n_rounds / (us / 1e6)
        launches, eighs = _chunk_body_counts(cfg, k)
        out.append(row(
            f"stream/chunk{k}_fleet{B}", us,
            f"{rps:.0f} rounds/s|{us0 / us:.2f}x vs K=1|"
            f"{launches:.3f} launches/round|{eighs:.3f} eigh/round"))
    return out


def fused_sweep(smoke: bool = False):
    """Split vs fused chunk body, fp32 vs bf16 tiles (DESIGN.md Sec. 14).

    Same data, same chunk size, compression AND detection enabled (the
    configuration where the split body pays 3 stage launches per chunk):
    only the launch fusion and the tile-load dtype change.  The derived
    column records rounds/s, the speedup over the split body, and the
    structural pallas-launch count per chunk read off the traced jaxpr
    (3 split → 1 fused — the amortization claim of the mega-kernel).
    """
    out = []
    B = 4 if smoke else 16
    n_rounds = 32 if smoke else 64
    K = 8
    repeat = 5
    xs = _fleet(jax.random.PRNGKey(0), B, n_rounds, shift_at=n_rounds // 2)
    base = dict(p=P, q=Q, halfwidth=H, forgetting=0.9, drift_threshold=0.1,
                warmup_rounds=5,
                compression=CompressionConfig(epsilon=0.5,
                                              emit_reconstruction=False),
                detection=DetectionConfig(alpha=1e-3, calib_rounds=5))
    us_split = None
    for name, kw in (("split_fp32", dict(fused=False)),
                     ("fused_fp32", dict(fused=True)),
                     ("fused_bf16", dict(fused=True, precision="bf16"))):
        cfg = StreamConfig(**base, **kw)
        states = _states(cfg, B)

        def _run(c=cfg, s=states):
            res = batched_stream_run(c, s, xs, chunk=K)
            jax.block_until_ready(res[1].rho)
            return res

        _run()                                       # compile outside timing
        _, us = timed(_run, repeat=repeat)
        us_split = us_split or us
        rps = B * n_rounds / (us / 1e6)
        launches = _chunk_body_counts(cfg, K)[0] * K
        out.append(row(
            f"stream/{name}_fleet{B}", us,
            f"{rps:.0f} rounds/s|{us_split / us:.2f}x vs split|"
            f"{launches:.0f} launches/chunk"))
    return out


def run(smoke: bool = False, chunks: tuple[int, ...] | None = None):
    """``smoke`` shrinks the fleets and round counts to a seconds-scale
    pass over the same code paths (the CI entrypoint guard)."""
    out = []
    n_rounds = 10 if smoke else 40

    # -- throughput vs fleet size ------------------------------------------
    cfg = StreamConfig(p=P, q=Q, halfwidth=H, forgetting=0.9,
                       drift_threshold=0.1, warmup_rounds=5)
    for B in (4, 8) if smoke else (8, 32, 64):
        xs = _fleet(jax.random.PRNGKey(0), B, n_rounds, shift_at=n_rounds // 2)
        states = _states(cfg, B)
        batched_stream_run(cfg, states, xs)          # compile outside timing
        _, us = timed(
            lambda s=states, x=xs: jax.block_until_ready(
                batched_stream_run(cfg, s, x)[1].rho))
        rps = B * n_rounds / (us / 1e6)
        out.append(row(f"stream/fleet{B}", us, f"{rps:.0f} rounds/s"))

    # -- accuracy vs communication frontier --------------------------------
    B = 4 if smoke else 16
    xs = _fleet(jax.random.PRNGKey(0), B, n_rounds, shift_at=n_rounds // 2)
    def _run(c, s):
        res = batched_stream_run(c, s, xs)
        jax.block_until_ready(res[1].rho)
        return res

    for thr in ((0.1,) if smoke else (0.02, 0.1, 0.3)):
        cfg_t = StreamConfig(p=P, q=Q, halfwidth=H, forgetting=0.9,
                             drift_threshold=thr, warmup_rounds=5)
        states = _states(cfg_t, B)
        _run(cfg_t, states)                          # compile outside timing
        (final, m), us = timed(_run, cfg_t, states)
        rho_end = float(np.asarray(m.rho)[:, -1].mean())
        refreshes = float(np.asarray(final.sched.refreshes).mean())
        packets = float(np.asarray(final.sched.comm_packets).mean())
        out.append(row(
            f"stream/threshold{thr}", us,
            f"retained {rho_end:.3f}|{refreshes:.1f} refreshes|"
            f"{packets:.0f} packets"))

    # -- chunk-granular dispatch sweep -------------------------------------
    out.extend(chunk_sweep(smoke=smoke, chunks=chunks))

    # -- fused mega-kernel sweep (split vs fused x fp32 vs bf16) -----------
    out.extend(fused_sweep(smoke=smoke))
    return out


def main() -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale sweep (the CI setting)")
    ap.add_argument("--chunk",
                    help="comma-separated chunk sizes to sweep "
                         "(default: 2,8 smoke / 2,4,8,16 full)")
    ap.add_argument("--fused", action="store_true",
                    help="run only the fused-vs-split x fp32-vs-bf16 sweep")
    ap.add_argument("--json",
                    help="write the gathered rows to this path "
                         "(the BENCH_streaming.json artifact)")
    args = ap.parse_args()
    chunks = tuple(int(c) for c in args.chunk.split(",")) \
        if args.chunk else None
    rows = fused_sweep(smoke=args.smoke) if args.fused \
        else run(smoke=args.smoke, chunks=chunks)
    print("name,us_per_call,derived")
    for r in rows:
        print(f"{r['name']},{r['us_per_call']},{r['derived']}")
    if args.json:
        if not rows:
            print(f"ERROR: no rows gathered, refusing to write {args.json}")
            return 1
        with open(args.json, "w") as fh:
            json.dump(rows, fh, indent=2)
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
