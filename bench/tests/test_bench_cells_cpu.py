"""Each cell end to end at a tiny size on the CPU: the sound program is
correct, the control (the program's bf16 tile path) is not, and the
benchmark itself refuses to measure anything but a TPU."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench_tiny import BENCH, CELLS, run_tiny, tiny_cell


@pytest.mark.parametrize("name", CELLS)
def test_tiny_cell_is_correct(name):
    res = run_tiny(tiny_cell(name), traced=True)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    lines = "\n".join(res["lines"])
    assert "compiles inside the window: 0" in lines
    assert set(res["checks"]) == set(tiny_cell(name).limits)


@pytest.mark.parametrize("name", CELLS)
def test_tiny_control_is_not_correct(name):
    res = run_tiny(tiny_cell(name, precision="bf16"))
    assert not res["correct"], res["checks"]


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "wsn1m-region.saturate", "--seed", "0", "--seconds", "10",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def _has_result(stdout):
    for line in stdout.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return True
        except ValueError:
            pass
    return False


def test_no_tpu_no_result():
    proc = _run_py(BENCH.parent)
    assert proc.returncode != 0
    assert not _has_result(proc.stdout)
    assert "no TPU" in proc.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_py(tmp_path, {"PYTHONPATH": ""})
    assert proc.returncode != 0
    assert not _has_result(proc.stdout)
