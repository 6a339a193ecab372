"""Offered-rate sweep of an open-loop cell, in one process.

    python3 bench/tools/sweep.py --workload <cell> --rates 200,400,... \\
        [--seconds S] [--seed N] [--out PATH]

For each rate, one window of the cell's traffic at that rate: the streams
due, the answers in, latency p50/p95, and the admission queue's depth at
the window's open and close (a queue that grows through the window means
the rate is above what the engine sustains).  Needs the cell's chips.
"""

import dataclasses
import gc
import json
import pathlib
import sys
import time

_BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(_BENCH), str(_BENCH.parent / "src")]

from harness import runner, spec  # noqa: E402


def main() -> int:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--drain", type=float, default=20.0)
    ap.add_argument("--out")
    args = ap.parse_args()
    cell = spec.load_cell(args.workload)
    for rate in (float(r) for r in args.rates.split(",")):
        gc.collect()                 # free the last run's device buffers
        traffic = dict(cell.traffic, rate_per_s=rate, drain_s=args.drain)
        c = dataclasses.replace(cell, traffic=traffic)
        res = runner.run_cell(c, args.seed, args.seconds, False,
                              time.perf_counter())
        row = {"rate_per_s": rate, "attempted": res["attempted"],
               "failed": res["failed"], "correct": res["correct"],
               "metrics": {k: v["value"] for k, v in res["metrics"].items()
                           if k != "setup_s"},
               "lines": res["lines"][1:]}
        print(json.dumps(row), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
