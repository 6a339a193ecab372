"""Streaming distributed PCA over a fleet of sensor networks.

The online continuation of the paper (DESIGN.md Sec. 8): measurements arrive
round by round, each network folds them into its banded covariance with an
exponential forgetting factor (the Pallas cov-update kernel on the hot path),
and a recompute scheduler refreshes the principal-component basis only when
retained variance drifts — booking the paper-style communication cost of
every refresh (Table 1 / costs.py).

The fleet is batched: all networks stream in ONE jitted program, a scan of
the fleet body that refreshes only the networks whose trigger fired (the
"millions of users" serving shape; on a mesh the networks axis shards over
the data axis, see repro.streaming.driver.sharded_stream_run).  Halfway
through the stream, half of the fleet suffers a distribution shift — watch
the scheduler fire on exactly those networks.

Run:  PYTHONPATH=src python examples/streaming_pca.py
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import costs
from repro.streaming import StreamConfig, batched_stream_run, stream_init

N_NETWORKS = 64
N_ROUNDS = 120
N_PER_ROUND = 8          # measurement epochs per round
P = 32                   # sensors per network
Q = 3                    # principal components maintained
SHIFT_ROUND = 60         # distribution shift for the second half of the fleet


def fleet_streams(key) -> jnp.ndarray:
    """(networks, rounds, n, p) measurement stream.

    Every network observes sensors with a smoothly decaying variance profile
    (distinct eigenvalues, so the top-q subspace is well defined).  From
    SHIFT_ROUND on, networks 32..63 see the profile reversed — the energy
    moves to the other end of the network, the paper's 'air conditioning
    turns on' regime change.
    """
    k1, k2 = jax.random.split(key)
    base = jnp.linspace(4.0, 1.0, P)
    shifted = base[::-1]
    x = jax.random.normal(k1, (N_NETWORKS, N_ROUNDS, N_PER_ROUND, P))
    rounds = jnp.arange(N_ROUNDS)[None, :, None, None]
    nets = jnp.arange(N_NETWORKS)[:, None, None, None]
    use_shifted = (rounds >= SHIFT_ROUND) & (nets >= N_NETWORKS // 2)
    scale = jnp.where(use_shifted, shifted[None, None, None, :],
                      base[None, None, None, :])
    return x * scale


def main() -> None:
    print("=== Streaming distributed PCA: 64-network fleet ===\n")
    cfg = StreamConfig(p=P, q=Q, halfwidth=4, forgetting=0.9,
                       drift_threshold=0.1, refresh_iters=8,
                       warmup_rounds=8, n_max=8, c_max=4)
    print(f"fleet: {N_NETWORKS} networks x {N_ROUNDS} rounds x "
          f"{N_PER_ROUND} epochs/round, p={P} sensors, q={Q} components")
    print(f"policy: forgetting {cfg.forgetting}, refresh when retained "
          f"variance drops > {cfg.drift_threshold:.0%} since last refresh\n")

    key = jax.random.PRNGKey(0)
    xs = fleet_streams(key)
    states = jax.vmap(lambda k: stream_init(cfg, k))(
        jax.random.split(jax.random.PRNGKey(1), N_NETWORKS))

    t0 = time.perf_counter()
    final, metrics = batched_stream_run(cfg, states, xs)
    jax.block_until_ready(metrics.rho)
    dt = time.perf_counter() - t0

    rho = np.asarray(metrics.rho)                  # (networks, rounds)
    fired = np.asarray(metrics.did_refresh)
    refreshes = np.asarray(final.sched.refreshes)
    comm = np.asarray(final.sched.comm_packets)

    total_rounds = N_NETWORKS * N_ROUNDS
    print(f"streamed {total_rounds} network-rounds in {dt:.1f} s "
          f"({total_rounds / dt:.0f} rounds/s, one jitted scan of the "
          f"fleet body)")

    stable, shifted = slice(0, N_NETWORKS // 2), slice(N_NETWORKS // 2, None)
    print("\n-- scheduler activity ------------------------------------")
    print(f"refreshes/network: stable fleet half  "
          f"{refreshes[stable].mean():.2f} (warmup fit only is 1.0)")
    print(f"                   shifted fleet half {refreshes[shifted].mean():.2f}")
    counts = np.bincount(np.where(fired[shifted])[1], minlength=N_ROUNDS)
    first_post = int(np.nonzero(counts[SHIFT_ROUND:])[0][0]) + SHIFT_ROUND
    print(f"total refreshes: {int(refreshes.sum())} "
          f"(first post-shift trigger at round {first_post}; "
          f"shift injected at round {SHIFT_ROUND})")

    print("\n-- retained variance -------------------------------------")
    print(f"end of stream: stable half  {rho[stable, -1].mean():.3f}  "
          f"(pre-shift level {rho[stable, SHIFT_ROUND - 1].mean():.3f})")
    drifted_low = rho[shifted, SHIFT_ROUND:].min(axis=1).mean()
    print(f"               shifted half {rho[shifted, -1].mean():.3f}  "
          f"(drifted low point {drifted_low:.3f} before the refresh caught it)")

    print("\n-- communication bill (packets, highest-loaded node) -----")
    sched = cfg.scheduler()
    round_c, refresh_c = sched.round_cost(), sched.refresh_cost(P)
    print(f"per round (cov fold + drift probe): {round_c:.0f}")
    print(f"per refresh (ortho iteration + basis flood): {refresh_c:.0f}")
    print(f"accumulated/network: stable {comm[stable].mean():.0f}, "
          f"shifted {comm[shifted].mean():.0f}")
    every_round = round_c + refresh_c
    print(f"refresh-every-round baseline would pay "
          f"{N_ROUNDS * every_round:.0f}/network — the scheduler spends "
          f"{comm.mean() / (N_ROUNDS * every_round):.1%} of that")

    # the paper's Table-1 framing for one refresh at this scale
    rep = costs.streaming_refresh_cost(P, Q, cfg.n_max, cfg.c_max,
                                       cfg.refresh_iters)
    print(f"\nTable-1 view of one refresh: comm {rep.communication:.0f}, "
          f"compute O({rep.computation:.0f}), memory O({rep.memory:.0f})")

    assert int(refreshes.sum()) >= 1, "no refresh triggered"
    print("\nOK: fleet streamed, drift caught, refreshes scheduled.")


if __name__ == "__main__":
    main()
