"""The fold kernel's time and least time, for the fold-kernel readers."""

from __future__ import annotations

from harness import peaks, spec


def kernel_seconds(v) -> float | None:
    """Device seconds of the fused fold kernel in the traced window, or
    None (with a loud line) when no trace event carries its name."""
    if v.reduced is None:
        return None
    roof = spec.load_module("roofline", "fused_stream")
    seconds = v.reduced.op_seconds(roof.TRACE_NAME)
    if seconds <= 0.0:
        print(f"fold kernel: no trace event matches "
              f"{roof.TRACE_NAME.pattern!r}", flush=True)
        return None
    return seconds


def folded_steps(v) -> list:
    return [r for r in v.run.window_steps if r.live > 0]


def least_seconds(v) -> tuple[float, str]:
    """The least time the chip could fold the window's epochs in, and the
    bound (``flops`` or ``bytes``) that sets it, summed over steps."""
    roof = spec.load_module("roofline", "fused_stream")
    pk = peaks.peaks(v.kind)
    total, bounds = 0.0, set()
    for r in folded_steps(v):
        t, bound = roof.least_seconds(rows=r.rounds * v.run.n, slots=r.live,
                                      config=v.run.config, peaks=pk)
        total += t
        bounds.add(bound)
    return total, "+".join(sorted(bounds))
