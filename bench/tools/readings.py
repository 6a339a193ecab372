"""The readings that the limits of ``correct`` are set from, in one process.

    python3 bench/tools/readings.py --workload <cell> --seeds 1,2,... \\
        [--control-seeds 101,...] [--fault-seeds 201,...] [--seconds S] \\
        [--out PATH]

Runs the cell's window once per seed with the program as configured (the
sound runs), then once per control seed with the configuration's
``precision`` set to ``bf16`` (the program's own lower-precision path:
bf16 tiles in the fold kernel), then once per fault seed with
``refresh_iters`` set to 0 (a refresh that keeps the basis it had).
Prints, and appends to ``--out`` as JSON lines, every number ``check.py``
reads, with its side and seed.  Needs the cell's chips, like the
benchmark.
"""

import copy
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out")
    args = ap.parse_args()
    cell = spec.load_cell(args.workload)
    control = copy.deepcopy(cell.config)
    control["stream"]["precision"] = "bf16"
    stale = copy.deepcopy(cell.config)
    stale["stream"]["refresh_iters"] = 0
    plan = [("program", int(s), cell.config) for s in args.seeds.split(",")]
    plan += [("control", int(s), control)
             for s in args.control_seeds.split(",") if s]
    plan += [("refresh_stale", int(s), stale)
             for s in args.fault_seeds.split(",") if s]
    for side, seed, config in plan:
        gc.collect()                 # free the last run's device buffers
        t0 = time.perf_counter()
        try:
            res = runner.run_cell(cell, seed, args.seconds, False, t0,
                                  config=config)
        except Exception as e:           # a control that crashes has failed
            row = {"side": side, "seed": seed, "error": repr(e)}
        else:
            row = {"side": side, "seed": seed, "correct": res["correct"],
                   "attempted": res["attempted"],
                   "metrics": {k: v["value"]
                               for k, v in res["metrics"].items()},
                   "readings": res["readings"], "lines": res["lines"][1:]}
        print(json.dumps(row), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
