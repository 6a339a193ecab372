"""Set an open-loop cell's rate to 0.8 x the highest swept rate sustained.

    python3 bench/tools/pick_rate.py <sweep.jsonl> <cell>

Reads the JSON lines of ``bench/tools/sweep.py``.  A rate is sustained when
every answer came, the admission queue grew by at most 64 streams over the
window, and the owed streams drained within 5 s of its close.  Writes the
chosen rate into ``bench/workloads/<cell>.json``, with the swept rates and
their latencies beside it.
"""

import ast
import json
import pathlib
import sys

_ROOT = pathlib.Path(__file__).resolve().parents[2]


def sustained(row: dict) -> bool:
    line = next((x for x in row["lines"] if "answers owed; " in x), "")
    try:
        notes = ast.literal_eval(line.split("answers owed; ", 1)[1])
    except (IndexError, ValueError, SyntaxError):
        return False
    grew = notes.get("queue_at_close", 0) > notes.get("queue_at_open", 0) + 64
    return (row["failed"] == 0 and not grew
            and notes.get("drain_s", float("inf")) < 5.0)


def main() -> int:
    with open(sys.argv[1]) as f:
        rows = [json.loads(x) for x in f]
    ok = [r["rate_per_s"] for r in rows if sustained(r)]
    knee = max(ok) if ok else min(r["rate_per_s"] for r in rows)
    rate = round(0.8 * knee, 1)
    path = _ROOT / "bench" / "workloads" / f"{sys.argv[2]}.json"
    cell = json.loads(path.read_text())
    cell["traffic_params"]["rate_per_s"] = rate
    cell["sweep"] = [{"rate_per_s": r["rate_per_s"],
                      "sustained": r["rate_per_s"] in ok,
                      **r["metrics"]}
                     for r in rows]
    path.write_text(json.dumps(cell, indent=2) + "\n")
    print(f"sustained {ok}; knee {knee}; rate {rate}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
