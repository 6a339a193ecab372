"""Region merge (streaming/hierarchy.py): device milliseconds of the merge
collectives (all-gather, all-reduce) per fleet call, from the trace,
averaged over the chips."""

import re

COLLECTIVE = re.compile(r"all-gather|all-reduce|all_gather|all_reduce")


def read(v):
    calls = v.outcome.notes.get("calls")
    if v.reduced is None or not calls:
        return None
    seconds = v.reduced.op_seconds(COLLECTIVE)
    return 1e3 * seconds / calls if seconds > 0 else None
