"""Fold kernel: its share of the roofline, in percent — the least time the
chip needs for the work the fold's semantics require
(bench/roofline/fused_stream.py, peaks from bench/harness/peaks.py) over
the kernel's device time in the trace."""

from harness import fold


def read(v):
    seconds = fold.kernel_seconds(v)
    if seconds is None:
        return None
    least, bound = fold.least_seconds(v)
    print(f"fold kernel roofline: least {least!r} s ({bound}-bound) over "
          f"{seconds!r} s of kernel", flush=True)
    return 100.0 * least / seconds
