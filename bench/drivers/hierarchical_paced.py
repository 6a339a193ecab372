"""Region fleet on a mesh: ``hierarchical_stream_run`` call after call, one
call queued behind the one running.

The configuration's ``fleet`` group places ``regions`` regions on a
``region`` mesh of ``chips`` devices.  Each call folds ``rounds_per_call``
rounds per region (chunks of ``chunk``), then merges the regions' energy
records across the mesh (one all_gather, two psums); the fleet state is
carried from call to call.  The data is resident on the devices: ``blocks``
call batches are built from the seed and uploaded in set-up, and call c
folds block ``c % blocks``.

After dispatching call c the host waits for call c - 1 to finish, so the
device always has the next call queued and the host never runs further
ahead: the window runs whole calls for ``--seconds`` and ends when the
devices have finished the fleet state, one to two calls after it (the
loop looks at the clock when a call ends and the next is running).  (A
host that only dispatches queues as many calls as the runtime takes, and
the window overruns ``--seconds`` by all of them.)  A traced run's
window is ``trace_calls`` whole calls instead: its metrics are per call,
and a call is ~59,000 device events per chip at the wsn-1m shape, so a
10 s window on four chips (~2.8 million events) takes minutes to reduce.

The answers owed are those of ``sample_regions`` regions drawn from the
seed: each region's final basis and summary against the reference over
every round it folded.  Two more numbers check the merge: ``merge_table``,
the largest gap between the gathered energy table and the regions' own
energies (over the largest energy), and ``merge_rho``, the relative gap
between the fleet's retained fraction and the top-q_fleet selection
computed on the host from those energies.  Beside them, not compared,
what ``merge_rho`` would read at this run's size were the merge at fault:
``merge_rho_off_by_one`` (the selection takes the (q_fleet + 1)-th energy
for the q_fleet-th: the gap between the two over the selected sum) and
``merge_rho_lost_shard`` (the psum of the trace partials loses the last
chip's regions).

Parameters: ``blocks``, ``sample_regions``, ``warmup_calls``,
``trace_calls``.
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
        prev = state["fin"]
        with run.annotate("bench.call"):
            fin, metrics, fleet = hierarchical_stream_run(
                cfg, mesh, prev, xs[state["calls"] % len(xs)], chunk=chunk)
            if cfg.compression is not None:
                state["max_err"] = jnp.maximum(
                    state["max_err"], metrics.compression.max_err.max(axis=1))
        state.update(fin=fin, fleet=fleet, calls=state["calls"] + 1)
        with run.annotate("bench.wait"):
            run.block(prev)

    run.warm_up(call, settled=lambda: state["calls"] >= p["warmup_calls"],
                tree=lambda: state["fin"])
    run.begin_trace()
    first = state["calls"]
    t0 = run.open_window()
    if run.traced:
        for _ in range(p["trace_calls"]):
            call()
    else:
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
    total = np.asarray(out["total"], np.float64)
    table_gap = float(np.max(np.abs(np.asarray(fleet.lam_table, np.float64)
                                    - lam)) / np.max(np.abs(lam)))
    q_fleet = fleet.lam.shape[0]
    ranked = np.sort(lam.ravel())[::-1]
    top = ranked[:q_fleet].sum()
    rho_host = top / total.sum()
    rho_gap = abs(float(fleet.rho) - rho_host) / abs(rho_host)
    # what merge_rho reads where the merge is at fault (not compared): a
    # selection that takes the (q_fleet + 1)-th energy for the q_fleet-th,
    # or a psum of the trace partials that loses the last chip's regions
    last = total[-(regions // f["chips"]):].sum()
    extra = {"merge_table": table_gap, "merge_rho": float(rho_gap),
             "merge_rho_off_by_one": float(
                 (ranked[q_fleet - 1] - ranked[q_fleet]) / top),
             "merge_rho_lost_shard": float(last / (total.sum() - last))}
    return runner.Outcome(
        due=owed, lateness_s=[],
        epochs=window_calls * regions * rounds * run.n,
        notes={"calls": window_calls}, readings=extra)
