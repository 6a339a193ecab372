"""BENCHMARK.json and the files it names: lookup by name, and the contract
every entry keeps."""

import re

import pytest

from bench_tiny import CELLS, load_cell, spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return spec.benchmark()


@pytest.mark.parametrize("name", CELLS)
def test_cell_found_by_name(name):
    cell = load_cell(name)
    assert cell.name == name
    assert cell.chips == 1
    assert cell.limits, "a cell is judged by its limits"
    assert spec.load_module("drivers", cell.traffic["driver"]).drive
    assert spec.load_module("datagen", cell.config["data"]["kind"]).build
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer


def test_unknown_cell_is_refused():
    with pytest.raises(spec.SpecError):
        spec.load_cell("no-such-cell")


def test_names_units_and_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "bench/run.py"]
    assert bench["paths"] == ["bench"]
    assert 1 <= bench["run_seconds"] <= 51
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
        assert all(NAME.match(k) for k in c["reduced"])
        config = spec._read_json(spec.ROOT / c["file"])
        for key in c["reduced"]:
            assert key in config
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        for cell in m["workloads"]:
            moved = next(e for e in bench["end_to_end"]
                         if e["name"] == m["moves"])
            assert cell in moved.get("workloads", [cell])


def test_every_metric_has_its_reader(bench):
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] != "setup_s":
            assert callable(spec.load_module("metrics", m["name"]).read)
