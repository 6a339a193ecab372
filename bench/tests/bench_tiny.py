"""Tiny versions of the benchmark's cells for the CPU tests.

Every width and count is cut so that a run takes seconds with the Pallas
kernels interpreted; the configuration's structure (stages, chunking,
pipelining, forgetting, warm-up, the data generator) is the cell's own, as
are the limits ``correct`` is judged by.
"""

from __future__ import annotations

import atexit
import copy
import dataclasses
import functools
import json
import pathlib
import shutil
import sys
import tempfile
import time

BENCH = pathlib.Path(__file__).resolve().parents[1]
for path in (str(BENCH.parent / "src"), str(BENCH)):
    if path not in sys.path:
        sys.path.insert(0, path)

from harness import runner, spec  # noqa: E402

CELLS = ("wsn1m-region.saturate", "intel-lab-fleet.open")
# cells built and rehearsed but not yet measured on the chip: their
# BENCHMARK.json entries wait in this file, and the tests read them from a
# checkout root whose BENCHMARK.json lists them too
DEFERRED = BENCH / "tests" / "fixtures" / "deferred_cells.json"


@functools.lru_cache(maxsize=None)
def _root_with_deferred() -> pathlib.Path:
    bench = spec.benchmark()
    for key, entries in json.loads(DEFERRED.read_text()).items():
        bench[key] = bench[key] + entries
    root = pathlib.Path(tempfile.mkdtemp(prefix="bench-root-"))
    atexit.register(shutil.rmtree, root, True)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "bench").symlink_to(BENCH)
    return root


def load_cell(name: str) -> spec.Cell:
    """The cell ``name``, listed in BENCHMARK.json or deferred."""
    if any(w["name"] == name for w in spec.benchmark()["workloads"]):
        return spec.load_cell(name)
    return spec.load_cell(name, root=_root_with_deferred())


def tiny_cell(name: str, precision: str | None = None) -> spec.Cell:
    cell = load_cell(name)
    cfg = copy.deepcopy(cell.config)
    traffic = dict(cell.traffic)
    if cfg["data"]["kind"] == "local_factors":
        cfg["stream"].update(p=16, q=2, halfwidth=2)
        cfg["engine"]["slots"] = 4
        cfg["epochs_per_round"] = 8
        cfg["data"].update(rank=2, width=2.0, pool_streams=4,
                           pool_rounds=24)
        traffic.update(backlog=4, rounds_min=4, rounds_max=12,
                       warmup_steps_min=2)
    else:
        cfg["engine"]["slots"] = 8
        cfg["data"].update(traces=2, trace_epochs=2000)
        traffic.update(rate_per_s=6.0, warmup_s=0.5, drain_s=30.0)
    if precision is not None:
        cfg["stream"]["precision"] = precision
    return dataclasses.replace(cell, config=cfg, traffic=traffic)


def run_tiny(cell: spec.Cell, seed: int = 2**31 + 11, seconds: float = 1.5,
             traced: bool = False, patch=None) -> dict:
    return runner.run_cell(cell, seed, seconds, traced, time.perf_counter(),
                           require_chip=False, patch=patch)
