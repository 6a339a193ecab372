"""Reduce a profiler trace to the numbers the per-layer metrics read.

A trace is first flattened to plain events ``(plane, line, name, start_ns,
dur_ns, detail)`` (``load``), so the reduction below runs the same on a
live trace and on the small recorded fixture the tests read.  Device events
are those of the ``XLA Ops`` line of each ``/device:TPU:<i>`` plane; their
``detail`` holds the head of each of their text stats (the HLO op and its
long name), where a kernel's name may show when the event's name does not.  The harness
marks its own host spans with ``jax.profiler.TraceAnnotation`` names that
start with ``bench.``; ``bench.window`` spans the measured window.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW = "bench.window"
DETAIL_CHARS = 200                  # of each text stat of a device event


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    detail: str = ""

    def matches(self, pattern: re.Pattern) -> bool:
        return bool(pattern.search(self.name) or pattern.search(self.detail))

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load(trace_dir: str) -> list[Event]:
    """Every device op and every harness span of the ``.xplane.pb`` under
    ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, "
                           f"found {len(paths)}")
    events = []
    for plane in ProfileData.from_file(paths[0]).planes:
        device = DEVICE_PLANE.match(plane.name) is not None
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for e in line.events:
                if device or e.name.startswith(SPAN_PREFIX):
                    events.append(Event(plane.name, line.name, e.name,
                                        float(e.start_ns),
                                        float(e.duration_ns),
                                        _detail(e) if device else ""))
    return events


def _detail(event) -> str:
    return " ".join(f"{k}={v[:DETAIL_CHARS]}" for k, v in event.stats
                    if isinstance(v, str))


def dump(events: list[Event], path: str) -> None:
    with open(path, "w") as f:
        json.dump([dataclasses.astuple(e) for e in events], f)


def read_dump(path: str) -> list[Event]:
    with open(path) as f:
        return [Event(*row) for row in json.load(f)]


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


@dataclasses.dataclass
class Reduced:
    """The trace inside the measured window."""

    window_s: float
    busy_s: float                       # union of device ops, mean per chip
    chips: int
    ops: list[Event]                    # device ops clipped to the window
    gaps: list[tuple[float, str]]       # (seconds, covering span) per gap

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_seconds(self, pattern: re.Pattern) -> float:
        """Device seconds of the ops whose name matches ``pattern``, summed
        over chips and divided by their number."""
        return sum(e.dur_ns for e in self.ops
                   if e.matches(pattern)) * 1e-9 / self.chips

    def top_ops(self, k: int = 10) -> list[list]:
        total: dict[str, float] = {}
        for e in self.ops:
            total[e.name] = total.get(e.name, 0.0) + e.dur_ns * 1e-9
        return [[n, s] for n, s in sorted(total.items(),
                                          key=lambda kv: -kv[1])[:k]]

    def top_gaps(self, k: int = 10) -> list[list]:
        return [[name, s] for s, name in sorted(self.gaps, reverse=True)[:k]]


def reduce(events: list[Event]) -> Reduced:
    windows = [e for e in events if e.name == WINDOW]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW} span, found "
                           f"{len(windows)}")
    w0, w1 = windows[0].start_ns, windows[0].end_ns
    ops = []
    for e in events:
        if DEVICE_PLANE.match(e.plane) is None:
            continue
        a, b = max(e.start_ns, w0), min(e.end_ns, w1)
        if b > a:
            ops.append(dataclasses.replace(e, start_ns=a, dur_ns=b - a))
    planes = sorted({e.plane for e in ops})
    chips = max(len(planes), 1)
    busy = 0.0
    for plane in planes:
        busy += sum(b - a for a, b in _union(
            [(e.start_ns, e.end_ns) for e in ops if e.plane == plane]))
    # idle gaps of the first chip, each named by the harness span (other
    # than the window; the harness's spans do not nest) that covers its
    # midpoint
    spans = sorted((e for e in events if e.name.startswith(SPAN_PREFIX)
                    and e.name != WINDOW), key=lambda e: e.start_ns)
    starts = [s.start_ns for s in spans]
    gaps = []
    if planes:
        cursor = w0
        for a, b in _union([(e.start_ns, e.end_ns) for e in ops
                            if e.plane == planes[0]]) + [(w1, w1)]:
            if a > cursor:
                mid = 0.5 * (a + cursor)
                i = bisect.bisect_right(starts, mid) - 1
                name = spans[i].name if i >= 0 and mid < spans[i].end_ns \
                    else "(no span)"
                gaps.append(((a - cursor) * 1e-9, name))
            cursor = max(cursor, b)
    return Reduced(window_s=(w1 - w0) * 1e-9, busy_s=busy * 1e-9 / chips,
                   chips=chips, ops=ops, gaps=gaps)
