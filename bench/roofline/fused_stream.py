"""Operations and bytes the fused streaming fold requires, from its shapes.

This counts the work the semantics ask for, whatever implements it, over
``rows`` folded epochs (K rounds x n epochs, summed over live slots) of p
sensors and ``slots`` live slots:

* band fold: ``2 * rows * p * (2h+1)`` (a multiply and an add per band
  entry per epoch);
* compression projection and reconstruction, or the monitor's residual:
  ``4 * rows * p * q``;
* bytes: x read once (its tile dtype), the band delta written once per
  slot ((2h+1) x p fp32), the scores z written (rows x q fp32), T^2 and
  SPE written per epoch with detection, and the sink view and flags
  (rows x p each) only where the configuration emits the reconstruction.
  No mask is read: the fleet runs with no liveness schedule.

``TRACE_NAME`` matches the kernel's events in a TPU profiler trace.
"""

from __future__ import annotations

import re

TRACE_NAME = re.compile(r"_fused_kernel|fused_stream")

_TILE_BYTES = {"fp32": 4, "bf16": 2}


def flops(rows: int, p: int, q: int, h: int, stages: bool) -> float:
    return 2.0 * rows * p * (2 * h + 1) + (4.0 * rows * p * q if stages
                                           else 0.0)


def bytes_moved(rows: int, slots: int, stream: dict) -> float:
    p, q, h = stream["p"], stream["q"], stream["halfwidth"]
    total = rows * p * _TILE_BYTES[stream.get("precision", "fp32")]
    total += slots * (2 * h + 1) * p * 4
    comp = stream.get("compression")
    det = stream.get("detection")
    if comp is not None or det is not None:
        total += rows * q * 4
    if det is not None:
        total += rows * 2 * 4
    if comp is not None and comp.get("emit_reconstruction", True):
        total += rows * p * 4 * 2
    return float(total)


def least_seconds(rows: int, slots: int, config: dict, peaks) -> tuple:
    """``(seconds, bound)``: the larger of operations over the peak rate
    and bytes over the peak bandwidth, and which of the two it was."""
    s = config["stream"]
    stages = s.get("compression") is not None \
        or s.get("detection") is not None
    t_flops = flops(rows, s["p"], s["q"], s["halfwidth"], stages) \
        / peaks.flops
    t_bytes = bytes_moved(rows, slots, s) / peaks.hbm_bw
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
