"""Benchmark of the streaming PCA server on a TPU: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cells and metrics are listed in
``BENCHMARK.json``; ``bench/harness/runner.py`` says what a run does.
"""

import time

T_START = time.perf_counter()

import pathlib  # noqa: E402
import sys  # noqa: E402

_BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(_BENCH), str(_BENCH.parent / "src")]

from harness import runner  # noqa: E402

if __name__ == "__main__":
    sys.exit(runner.main(sys.argv[1:], T_START))
