"""Fold kernel (kernels/fused_stream.py): device milliseconds of the fused
kernel per engine step that folded anything, from the trace."""

from harness import fold


def read(v):
    seconds = fold.kernel_seconds(v)
    steps = fold.folded_steps(v)
    if seconds is None or not steps:
        return None
    return 1e3 * seconds / len(steps)
