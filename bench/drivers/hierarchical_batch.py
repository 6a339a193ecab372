"""Region fleet on a mesh: ``hierarchical_stream_run`` call after call.

The configuration's ``fleet`` group places ``regions`` regions on a
``region`` mesh of ``chips`` devices.  Each call folds ``rounds_per_call``
rounds per region (chunks of ``chunk``), then merges the regions' energy
records across the mesh (one all_gather, one psum); the fleet state is
carried from call to call.  The data is resident on the devices: ``blocks``
call batches are built from the seed and uploaded in set-up, and call c
folds block ``c % blocks``.  The window runs whole calls for ``--seconds``
and ends when the devices have finished the fleet state.

The answers owed are those of ``sample_regions`` regions drawn from the
seed: each region's final basis and summary against the reference over
every round it folded.  Two more numbers check the merge: ``merge_table``,
the largest gap between the gathered energy table and the regions' own
energies (over the largest energy), and ``merge_rho``, the relative gap
between the fleet's retained fraction and the top-q_fleet selection
computed on the host from those energies.

Parameters: ``blocks``, ``sample_regions``, ``warmup_calls``.
"""

from __future__ import annotations

import types

import numpy as np

from harness import runner, system


def _summaries(cfg):
    import jax

    from repro.streaming.hierarchy import region_energies
    from repro.streaming.online_cov import online_estimate
    from repro.streaming.scheduler import retained_fraction

    def one(st):
        lam, total = region_energies(st)
        rho = retained_fraction(online_estimate(st.cov), st.sched.W, total)
        return dict(W=st.sched.W, lam=lam, total=total, rho=rho,
                    refreshes=st.sched.refreshes, rounds=st.rounds)
    return jax.jit(jax.vmap(one))


def drive(run: runner.Run) -> runner.Outcome:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.launch.mesh import make_fleet_mesh
    from repro.streaming.hierarchy import (hierarchical_stream_init,
                                           hierarchical_stream_run)
    p = run.traffic
    f = run.config["fleet"]
    cfg = system.stream_config(run.config)
    regions, rounds, chunk = f["regions"], f["rounds_per_call"], f["chunk"]
    mesh = make_fleet_mesh(region=f["chips"], data=1)
    lead = NamedSharding(mesh, PartitionSpec("region"))
    blocks = [np.stack([run.source.take(run.rng, rounds)
                        for _ in range(regions)]) for _ in range(p["blocks"])]
    xs = [jax.device_put(b, lead) for b in blocks]
    init = jax.jit(lambda key: hierarchical_stream_init(cfg, key, regions),
                   out_shardings=lead)
    state = {"fin": init(jax.random.PRNGKey(runner.sub_seed(run.seed, 5))),
             "calls": 0, "max_err": jnp.zeros((regions,), jnp.float32)}

    def call():
        with run.annotate("bench.call"):
            fin, metrics, fleet = hierarchical_stream_run(
                cfg, mesh, state["fin"], xs[state["calls"] % len(xs)],
                chunk=chunk)
            if cfg.compression is not None:
                state["max_err"] = jnp.maximum(
                    state["max_err"], metrics.compression.max_err.max(axis=1))
        state.update(fin=fin, fleet=fleet, calls=state["calls"] + 1)

    run.warm_up(call, settled=lambda: state["calls"] >= p["warmup_calls"],
                tree=lambda: state["fin"])
    run.begin_trace()
    first = state["calls"]
    t0 = run.open_window()
    while run.now() < t0 + run.seconds:
        call()
    run.close_window(block=True, tree=state["fin"])
    run.end_trace()
    window_calls = state["calls"] - first

    pick = np.sort(run.rng.choice(regions, size=p["sample_regions"],
                                  replace=False))
    out = jax.device_get(_summaries(cfg)(state["fin"]))
    max_err = np.asarray(jax.device_get(state["max_err"]))
    owed = []
    for r in pick:
        history = np.concatenate([blocks[c % len(blocks)][r]
                                  for c in range(state["calls"])])
        result = types.SimpleNamespace(
            components=out["W"][r], energies=out["lam"][r],
            total_variance=float(out["total"][r]),
            retained=float(out["rho"][r]),
            refreshes=int(out["refreshes"][r]), rounds=int(out["rounds"][r]),
            reason="completed", compression_max_err=float(max_err[r]))
        req = types.SimpleNamespace(done=True, result=result, rounds=history)
        owed.append(runner.Stream(req=req, rounds=history.shape[0], due=t0,
                                  submitted=t0, admitted=t0, done=t0))
    fleet = jax.device_get(state["fleet"].basis)
    lam = np.asarray(out["lam"], np.float64)
    table_gap = float(np.max(np.abs(np.asarray(fleet.lam_table, np.float64)
                                    - lam)) / np.max(np.abs(lam)))
    top = np.sort(lam.ravel())[::-1][:fleet.lam.shape[0]].sum()
    rho_host = top / np.asarray(out["total"], np.float64).sum()
    rho_gap = abs(float(fleet.rho) - rho_host) / abs(rho_host)
    extra = {"merge_table": table_gap, "merge_rho": float(rho_gap)}
    return runner.Outcome(
        due=owed, lateness_s=[],
        epochs=window_calls * regions * rounds * run.n,
        notes={"calls": window_calls}, readings=extra)
