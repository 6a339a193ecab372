"""Drift probe and refresh, with the stage twin and the books: device busy
milliseconds per engine step that folded anything, less the fold kernel's."""

from harness import fold


def read(v):
    seconds = fold.kernel_seconds(v)
    steps = fold.folded_steps(v)
    if seconds is None or not steps:
        return None
    return 1e3 * (v.reduced.busy_s - seconds) / len(steps)
