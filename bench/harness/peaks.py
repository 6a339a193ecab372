"""Published per-chip peaks, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s in bf16, 16 GB of HBM at 819 GB/s.  Copied from
``src/repro/launch/hlo_analysis.py::DEVICE_PEAKS`` so that a change to the
program cannot move the yardstick.  A device that is not in the table is an
error, never a default.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float                 # FLOP/s (bf16 matrix unit: the highest)
    hbm_bw: float                # HBM bytes/s
    hbm_bytes: float             # HBM capacity


PEAKS = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, hbm_bytes=16e9),
}


def peaks(kind: str) -> Peaks:
    try:
        return PEAKS[kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind {kind!r} "
                         f"(known: {sorted(PEAKS)})") from None
