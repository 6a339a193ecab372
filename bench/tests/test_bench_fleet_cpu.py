"""The region fleet's cell, ``wsn1m-fleet4.batch``, rehearsed on the CPU.

A tiny version of the cell (p=16, q=2, h=2, 8 regions, 2 warm-up calls)
runs through the harness on 4 forced host devices, in a subprocess (the
device count is fixed when JAX starts): the sound program is correct and
its driver keeps one call queued; the control (the program's bf16 tile
path) is not correct, and neither is a run with a planted fault: a merge
that hands back a permuted energy table, a merge whose selection takes the
(q_fleet + 1)-th energy for the q_fleet-th, a psum of the trace partials
that loses the last chip's, or a refresh that keeps its basis
(``refresh_iters`` 0); the compiled fleet call carries every named scope
and the merge's collectives.  Then the fleet's scope
readers run on a small recorded trace of that program's shape
(``fixtures/fleet_scoped.hlo.txt``, ``fixtures/trace_fleet_scoped.json``).

    python3 bench/tests/test_bench_fleet_cpu.py   # the rehearsal alone
"""

from __future__ import annotations

import copy
import dataclasses
import json
import os
import subprocess
import sys
import types

import pytest

from bench_tiny import BENCH, run_tiny
from harness import fleet_scopes, runner, scopes, spec, trace

CELL = "wsn1m-fleet4.batch"
FIXTURES = BENCH / "tests" / "fixtures"
NS = 1e-9


def tiny_fleet_cell(**stream) -> spec.Cell:
    """The tiny cell, with ``stream`` (e.g. ``precision``) set in its
    configuration's ``stream`` group."""
    cell = spec.load_cell(CELL)
    cfg = copy.deepcopy(cell.config)
    cfg["stream"].update(p=16, q=2, halfwidth=2, **stream)
    cfg["fleet"].update(regions=8)
    cfg["epochs_per_round"] = 8
    cfg["data"].update(rank=2, width=2.0, pool_streams=4, pool_rounds=24)
    return dataclasses.replace(cell, config=cfg,
                               traffic=dict(cell.traffic, sample_regions=4),
                               check={"sample": 4})


def _permuted_table(real):
    """``hierarchical_stream_run`` whose merge hands back the energy table
    rolled by one region."""
    import jax.numpy as jnp

    def run(*args, **kwargs):
        fin, metrics, fleet = real(*args, **kwargs)
        basis = fleet.basis._replace(
            lam_table=jnp.roll(fleet.basis.lam_table, 1, axis=0))
        return fin, metrics, fleet._replace(basis=basis)
    return run


def _off_by_one(real):
    """``merge_fleet`` whose selection takes the (q_fleet + 1)-th energy
    for the q_fleet-th."""
    import jax.numpy as jnp

    def merge(lam_table, total_variance, q_fleet):
        b = real(lam_table, total_variance, q_fleet + 1)
        keep = jnp.r_[:q_fleet - 1, q_fleet]
        lam = b.lam[keep]
        return b._replace(region=b.region[keep], col=b.col[keep], lam=lam,
                          rho=jnp.sum(lam) / total_variance)
    return merge


def _lost_shard(real):
    """``jax.lax.psum`` whose last shard's operands are lost."""
    import jax
    import jax.numpy as jnp

    def psum(x, axis_name, **kwargs):
        last = jax.lax.axis_index(axis_name) == real(1, axis_name) - 1
        return real(jax.tree.map(lambda v: jnp.where(last, 0, v), x),
                    axis_name, **kwargs)
    return psum


def _planted(owner, name: str, fault) -> dict:
    """The tiny cell's traced run with ``owner.name`` replaced by
    ``fault(owner.name)`` (the fleet call traced anew, and again after)."""
    import jax
    real = getattr(owner, name)
    jax.clear_caches()
    setattr(owner, name, fault(real))
    try:
        return _summary(run_tiny(tiny_fleet_cell(), traced=True))
    finally:
        setattr(owner, name, real)
        jax.clear_caches()


def _summary(res: dict) -> dict:
    return {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"], "lines": res["lines"],
            "checks": {k: row["value"] for k, row in res["checks"].items()},
            "readings": {k: v for k, v in res["readings"].items()
                         if k not in res["checks"]}}


def _count_waits(run, held: dict, waits: list):
    """Keep the run, and count the driver's waits on the device."""
    block = run.block

    def counted(tree=None):
        waits.append(tree)
        return block(tree)
    run.block = counted
    held.update(run=run)


def _dispatches(real, waits: list, log: list):
    """``hierarchical_stream_run`` that logs, at each call, how many waits
    the driver made before it."""
    def run(*args, **kwargs):
        log.append(len(waits))
        return real(*args, **kwargs)
    return run


def rehearse() -> dict:
    """The three runs, how the sound run paced its calls, and the compiled
    call's scopes (4 devices).  Each run is traced, so its window is the
    mix's ``trace_calls`` calls and its readings do not depend on how fast
    this host is (float32 rounding grows with the rounds folded)."""
    from repro.streaming import hierarchy
    real = hierarchy.hierarchical_stream_run
    held, waits, log = {}, [], []
    hierarchy.hierarchical_stream_run = _dispatches(real, waits, log)
    try:
        out = {"sound": _summary(run_tiny(
            tiny_fleet_cell(), traced=True,
            patch=lambda run: _count_waits(run, held, waits)))}
    finally:
        hierarchy.hierarchical_stream_run = real
    out["waits_before_call"] = log
    names = fleet_scopes.call_op_names(types.SimpleNamespace(
        run=held["run"]))
    out["scopes"] = sorted({s for op in names.values()
                            for s in scopes.SCOPE_NAME.findall(op)})
    out["collectives"] = {
        opcode: [op for (_, code, _), op in names.items() if code == opcode]
        for opcode in ("all-gather", "all-reduce")}
    out["bf16"] = _summary(run_tiny(tiny_fleet_cell(precision="bf16"),
                                    traced=True))
    out["stale"] = _summary(run_tiny(tiny_fleet_cell(refresh_iters=0),
                                     traced=True))
    out["permuted"] = _planted(hierarchy, "hierarchical_stream_run",
                               _permuted_table)
    out["off_by_one"] = _planted(hierarchy, "merge_fleet", _off_by_one)
    import jax
    out["lost_shard"] = _planted(jax.lax, "psum", _lost_shard)
    return out


@pytest.fixture(scope="module")
def rehearsal():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, __file__], cwd=BENCH.parent,
                          env=env, capture_output=True, text=True,
                          timeout=900)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.splitlines()[-1])


def test_the_cell_is_listed_on_four_chips():
    cell = spec.load_cell(CELL)
    assert cell.chips == cell.config["fleet"]["chips"] == 4
    assert cell.traffic["driver"] == "hierarchical_paced"
    assert {m["name"] for m in cell.end_to_end} == {"setup_s",
                                                    "epochs_per_s"}
    assert {"merge_device_ms_per_call", "merge_collective_ms_per_call",
            "region_refresh_device_ms_per_call"} <= \
        {m["name"] for m in cell.per_layer}
    assert {"merge_table", "merge_rho"} <= set(cell.limits)
    # every sampled region is compared with the reference
    assert cell.check["sample"] == cell.traffic["sample_regions"]


def test_tiny_fleet_is_correct(rehearsal):
    sound = rehearsal["sound"]
    assert sound["correct"], sound["checks"]
    assert sound["attempted"] == 4 and sound["failed"] == 0
    assert set(sound["checks"]) == set(spec.load_cell(CELL).limits)
    assert "device: platform cpu device_kind 'cpu' count 4" in \
        sound["lines"][0]
    assert "compiles inside the window: 0" in sound["lines"]
    # a traced run measures the mix's trace_calls whole calls
    calls = spec.load_cell(CELL).traffic["trace_calls"]
    assert any(line.startswith("window ") and f"{{'calls': {calls}}}" in line
               for line in sound["lines"])


def test_the_host_keeps_one_call_queued(rehearsal):
    """Before dispatching call k the driver has waited k times (for calls
    0 .. k-1 in turn): at most one call is ever queued behind the one
    running, so the window ends one to two calls after its length."""
    log = rehearsal["waits_before_call"]
    assert len(log) > 3
    assert all(waited >= k for k, waited in enumerate(log))


def test_tiny_fleet_control_is_not_correct(rehearsal):
    assert not rehearsal["bf16"]["correct"], rehearsal["bf16"]["checks"]


def test_permuted_energy_table_is_not_correct(rehearsal):
    """Only the merge is broken: the table's check fails, the regions'
    own numbers still pass."""
    checks = rehearsal["permuted"]["checks"]
    limits = spec.load_cell(CELL).limits
    assert not rehearsal["permuted"]["correct"]
    assert checks["merge_table"] > limits["merge_table"]
    assert all(checks[k] <= limits[k] for k in limits if k != "merge_table")


@pytest.mark.parametrize("fault", ["off_by_one", "lost_shard"])
def test_merge_fault_is_not_correct(rehearsal, fault):
    """A wrong selection or a lost trace partial: only ``merge_rho``
    fails, and it reads what the driver says such a fault reads
    (``merge_rho_<fault>``, from the run's own table) up to float32
    rounding, which the sound runs read under the limit."""
    run = rehearsal[fault]
    checks = run["checks"]
    limits = spec.load_cell(CELL).limits
    assert not run["correct"]
    assert checks["merge_rho"] > limits["merge_rho"]
    assert all(checks[k] <= limits[k] for k in limits if k != "merge_rho")
    assert checks["merge_rho"] == pytest.approx(
        run["readings"][f"merge_rho_{fault}"], abs=limits["merge_rho"])


def test_stale_refresh_is_not_correct(rehearsal):
    """A refresh that keeps its basis passes the fold numbers and fails
    ``refresh_shortfall``."""
    checks = rehearsal["stale"]["checks"]
    limits = spec.load_cell(CELL).limits
    assert not rehearsal["stale"]["correct"]
    assert checks["refresh_shortfall"] > limits["refresh_shortfall"]
    for k in ("missing", "books", "fold_trace_rel", "fold_energy_rel"):
        assert checks[k] <= limits[k]


def test_the_fleet_call_carries_every_scope(rehearsal):
    assert {"pca.fold", "pca.probe", "pca.refresh", "pca.twin", "pca.books",
            "pca.merge"} <= set(rehearsal["scopes"])
    gathers = rehearsal["collectives"]["all-gather"]
    reduces = rehearsal["collectives"]["all-reduce"]
    # one all_gather, and the two psums (XLA may combine them into one)
    assert len(gathers) == 1 and 1 <= len(reduces) <= 2
    assert all("pca.merge" in op for op in gathers + reduces)


# -- the readers on a recorded trace ----------------------------------------
@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(trace.read_dump(FIXTURES / "trace_fleet_scoped.json"))


@pytest.fixture(scope="module")
def names():
    return fleet_scopes.op_names(
        (FIXTURES / "fleet_scoped.hlo.txt").read_text())


def _view(reduced, names, calls=2):
    v = runner.View(run=None, outcome=types.SimpleNamespace(
        notes={"calls": calls}), reduced=reduced, kind="TPU v5 lite")
    if names is not None:
        v._call_op_names = names
    return v


@pytest.mark.parametrize("scope,ns", [("pca.fold", 1000),
                                      ("pca.probe", 300),
                                      ("pca.refresh", 1200),
                                      ("pca.twin", 200),
                                      ("pca.books", 100),
                                      ("pca.merge", 750),
                                      ("pca.re", 0)])
def test_fleet_scope_seconds(reduced, names, scope, ns):
    # per chip, two calls: the scan's loop carries no scope, so each scope
    # reads the outermost of its own events (the refresh loop, not its
    # body's op); the second chip's all-gather takes 200 ns, the first's
    # 100; the merge's slice-done has no op_name and takes its operand's
    assert fleet_scopes.scope_seconds(reduced, names).get(scope, 0.0) == \
        pytest.approx(2 * ns * NS)


def test_unnamed_instructions_take_their_operands_scope(names):
    by_name = {key[0]: op for key, op in names.items()}
    assert "pca.merge" in by_name["%slice-start.1"]
    assert "pca.merge" in by_name["%slice-done.1"]
    # the scan's loop and the unscoped max keep no scope
    assert "pca." not in by_name["%while.5"]
    assert "pca." not in by_name["%fusion.33"]


def test_fleet_scope_line(reduced, names):
    line = fleet_scopes.line(reduced,
                             fleet_scopes.scope_seconds(reduced, names))
    assert line.startswith("fleet device seconds per scope: pca.refresh ")
    assert "pca.merge " in line and line.endswith(" s")


@pytest.mark.parametrize("metric,ms", [
    ("merge_device_ms_per_call", 750e-6),
    ("merge_collective_ms_per_call", 200e-6),
    ("region_refresh_device_ms_per_call", 1200e-6)])
def test_fleet_readers(reduced, names, metric, ms):
    read = spec.load_module("metrics", metric).read
    assert read(_view(reduced, names)) == pytest.approx(ms)
    # no trace, or no call in the window: nothing to read
    assert read(_view(None, names)) is None
    assert read(_view(reduced, names, calls=0)) is None


@pytest.mark.parametrize("metric", ["merge_device_ms_per_call",
                                    "region_refresh_device_ms_per_call"])
def test_fleet_scope_readers_without_the_scope(reduced, metric):
    """A program whose ops carry no scope (the merge before it was named)
    gives nothing to read."""
    bare = (FIXTURES / "fleet_scoped.hlo.txt").read_text() \
        .replace("pca.merge/", "").replace("pca.refresh/", "")
    read = spec.load_module("metrics", metric).read
    assert read(_view(reduced, fleet_scopes.op_names(bare))) is None


if __name__ == "__main__":
    print(json.dumps(rehearse()), flush=True)
