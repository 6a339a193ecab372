"""Find a cell and everything it names, by name, in files of their own.

``BENCHMARK.json`` (at the checkout's root) lists the cells and metrics.
Each cell ``<cell>`` has ``bench/workloads/<cell>.json``; it names a
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``), and may override the mix's parameters.
A mix names its driver loop (``bench/drivers/<driver>.py``), and a
configuration its data generator (``bench/datagen/<kind>.py``).  Each
per-layer metric is read by ``bench/metrics/<metric>.py``.  Adding a cell,
a mix or a metric therefore means adding files and ``BENCHMARK.json``
entries, never editing a file that is already here.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import re

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


class SpecError(ValueError):
    """A cell, mix, configuration or metric that the files do not define."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict                 # bench/configs/<config>.json
    traffic: dict                # the mix's parameters, cell overrides applied
    check: dict                  # how many streams to compare, and how
    limits: dict                 # number compared -> limit (see check.py)
    end_to_end: tuple            # BENCHMARK.json end_to_end entries it reports
    per_layer: tuple             # BENCHMARK.json per_layer entries it reports


def _read_json(path: pathlib.Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SpecError(f"missing file {path.relative_to(ROOT)}") from None


def benchmark(root: pathlib.Path = ROOT) -> dict:
    return _read_json(root / "BENCHMARK.json")


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell ``name`` with its configuration, mix and metric lists."""
    bench = benchmark(root)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                        f"(known: {[w['name'] for w in bench['workloads']]})")
    raw = _read_json(root / "bench" / "workloads" / f"{name}.json")
    if (raw["config"], raw["traffic"]) != (entry["config"], entry["traffic"]):
        raise SpecError(f"{name}: cell file and BENCHMARK.json disagree on "
                        "its configuration or traffic")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == raw["config"])
    config = _read_json(root / cfg_entry["file"])
    traffic = dict(_read_json(root / "bench" / "traffic"
                              / f"{raw['traffic']}.json"))
    traffic.update(raw.get("traffic_params", {}))
    e2e = tuple(m for m in bench["end_to_end"] if _reports(m, name))
    per_layer = tuple(m for m in bench["per_layer"] if _reports(m, name))
    return Cell(name=name, chips=int(entry["chips"]), config=config,
                traffic=traffic, check=raw["check"],
                limits=dict(raw.get("limits", {})),
                end_to_end=e2e, per_layer=per_layer)


_MODULE_NAME = re.compile(r"[^A-Za-z0-9_]")


def load_module(kind: str, name: str, root: pathlib.Path = ROOT):
    """Import ``bench/<kind>/<name>.py`` (names may hold dots)."""
    path = root / "bench" / kind / f"{name}.py"
    if not path.exists():
        raise SpecError(f"missing file {path.relative_to(root)}")
    mod_name = f"bench_{kind}_{_MODULE_NAME.sub('_', name)}"
    spec = importlib.util.spec_from_file_location(mod_name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
