"""Post-compile HLO analysis: collective traffic + roofline terms.

``cost_analysis()`` gives per-device FLOPs and bytes accessed but no
collective traffic, so we parse the optimized (post-SPMD-partitioning) HLO
text and sum the wire bytes of every collective op.

Wire-byte model (per device, ring algorithms — the XLA default on ICI):
    all-reduce          2 * N * (g-1)/g      (reduce-scatter + all-gather)
    all-gather          N * (g-1)/g          (N = full result bytes)
    reduce-scatter      N * (g-1)/g          (N = full input bytes)
    all-to-all          N * (g-1)/g
    collective-permute  N

Hardware constants: :data:`DEVICE_PEAKS`, keyed by ``Device.device_kind``;
the static certifier prices programs for the v5e
(:data:`TARGET_KIND`).
"""

from __future__ import annotations

import dataclasses
import math
import re

__all__ = ["CollectiveStats", "parse_collectives", "RooflineTerms",
           "roofline_terms", "fallback_trip", "ring_wire_bytes",
           "DevicePeaks", "DEVICE_PEAKS", "TARGET_KIND", "device_peaks",
           "PEAK_FLOPS", "HBM_BW", "ICI_BW"]


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip peaks."""

    flops: float                 # bf16 FLOP/s
    hbm_bw: float                # HBM bytes/s
    ici_bw: float                # inter-chip bytes/s per link


# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of inter-chip
# interconnect per chip over 4 links (50 GB/s each).
DEVICE_PEAKS = {
    "TPU v5 lite": DevicePeaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}

# the chip the static resource certifier describes
TARGET_KIND = "TPU v5 lite"


def device_peaks(kind: str) -> DevicePeaks:
    """The peaks of a ``device_kind``; a device not in the table raises."""
    try:
        return DEVICE_PEAKS[kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind {kind!r} "
                         f"(known: {sorted(DEVICE_PEAKS)})") from None


PEAK_FLOPS = device_peaks(TARGET_KIND).flops
HBM_BW = device_peaks(TARGET_KIND).hbm_bw
ICI_BW = device_peaks(TARGET_KIND).ici_bw

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1,
    "u64": 8, "u32": 4, "u16": 2, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

# one shape token like  bf16[256,4096]{1,0}  or  f32[]
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\](?:\{[^}]*\})?")
# iota-style replica groups:  [32,16]<=[512]
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")
# explicit groups:  {{0,1,2,3},{4,5,6,7}}
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _shape_bytes(dtype: str, dims: str) -> int:
    bpe = _DTYPE_BYTES.get(dtype)
    if bpe is None:
        return 0                      # token types, opaque
    if not dims:
        return bpe
    return bpe * math.prod(int(d) for d in dims.split(",") if d)


def _result_bytes(lhs: str) -> int:
    """Sum all shape tokens on the result side (handles tuple results)."""
    return sum(_shape_bytes(d, s) for d, s in _SHAPE_RE.findall(lhs))


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len([x for x in m.group(1).split(",") if x.strip() != ""])
    return default


def ring_wire_bytes(kind: str, nbytes: float, group: int) -> float:
    """Per-device wire bytes of one collective under the ring model in the
    module docstring.  ``nbytes`` is the full result (all-gather) / full
    input (everything else) size; shared by the HLO parser below and the
    jaxpr-level certifier (:mod:`repro.analysis.resources`), so both sides
    price a collective identically."""
    g = max(int(group), 1)
    if kind == "all-reduce":
        return 2.0 * nbytes * (g - 1) / g
    if kind == "collective-permute":
        return float(nbytes)
    return float(nbytes) * (g - 1) / g


@dataclasses.dataclass
class CollectiveStats:
    counts: dict
    result_bytes: dict        # sum of result sizes per kind
    wire_bytes: dict          # modeled per-device wire traffic per kind
    loop_corrected: bool = False
    unknown_trips: tuple = ()  # while bodies whose trip could not be parsed

    @property
    def total_wire_bytes(self) -> float:
        return sum(self.wire_bytes.values())

    @property
    def trips_known(self) -> bool:
        return not self.unknown_trips


# -- loop-aware HLO structure -------------------------------------------------
# computation header: `%name (params...) -> result {` (ENTRY optional);
# params may contain nested parens, so match greedily up to `) ->`.
_COMP_HEADER_RE = re.compile(r"^(?:ENTRY\s+)?%([\w\.\-]+)\s*\(.*\)\s*->\s*"
                             r"\S.*\{\s*$")
_WHILE_RE = re.compile(r"while\(.*?\).*?condition=%?([\w\.\-]+),"
                       r"\s*body=%?([\w\.\-]+)", re.DOTALL)
_CONST_RE = re.compile(r"%?([\w\.\-]+)\s*=\s*[su]\d+\[\]\s*constant\((\d+)\)")
_COMPARE_RE = re.compile(r"compare\(([^)]*)\)")


def _split_computations(text: str) -> dict[str, list[str]]:
    comps: dict[str, list[str]] = {}
    current = None
    for line in text.splitlines():
        m = _COMP_HEADER_RE.match(line.strip())
        if m and ("{" in line):
            current = m.group(1)
            comps[current] = []
            continue
        if current is not None:
            if line.strip() == "}":
                current = None
                continue
            comps[current].append(line.strip())
    return comps


def fallback_trip(values) -> int | None:
    """Loop-trip fallback shared by the HLO and jaxpr walkers
    (:mod:`repro.analysis.jaxpr_lint`): a loop condition is tiny — the
    induction limit plus occasional 0/1 constants — so the largest scalar
    integer constant observed in it is the trip count, with a floor of 1.

    A condition with NO integer constants (a data-dependent bound) returns
    ``None`` — the trip is *unknown*.  It used to silently default to 1,
    which under-counted every collective and launch inside such a loop;
    callers must now either propagate the unknown (and fail loudly in
    whatever rule depends on the count) or supply an explicit bound."""
    ints = [int(v) for v in values]
    return max(max(ints), 1) if ints else None


def _trip_count(cond_lines: list[str]) -> int | None:
    """Trip count from a while condition: the constant compared against the
    induction variable.  The compare is frequently wrapped in a fusion, so
    after trying a direct compare we fall back to the largest scalar int
    constant in the condition computation (:func:`fallback_trip`); a
    condition with no constants at all yields ``None`` (unknown trip)."""
    consts = {}
    for ln in cond_lines:
        for name, val in _CONST_RE.findall(ln):
            consts[name] = int(val)
    for ln in cond_lines:
        if " compare(" in ln and "ROOT" in ln:
            m = _COMPARE_RE.search(ln)
            if m:
                for op in m.group(1).split(","):
                    op = op.strip().lstrip("%")
                    op = op.split()[-1].lstrip("%")
                    if op in consts:
                        return max(consts[op], 1)
    return fallback_trip(consts.values())


def _collective_bytes_in(lines: list[str], n_devices: int):
    counts = {k: 0 for k in _COLLECTIVES}
    rbytes = {k: 0.0 for k in _COLLECTIVES}
    wbytes = {k: 0.0 for k in _COLLECTIVES}
    for stripped in lines:
        for kind in _COLLECTIVES:
            # match the op use, not metadata; async pairs: count starts only
            if f" {kind}(" in stripped or f" {kind}-start(" in stripped:
                lhs = stripped.split(f" {kind}")[0]
                n = _result_bytes(lhs)
                g = _group_size(stripped, n_devices)
                counts[kind] += 1
                rbytes[kind] += n
                wbytes[kind] += ring_wire_bytes(kind, n, g)
                break
    return counts, rbytes, wbytes


def parse_collectives(hlo_text: str, n_devices: int = 512,
                      loop_aware: bool = True,
                      unknown_trip: int | None = None) -> CollectiveStats:
    """Sum collective traffic; with ``loop_aware`` every while-body's
    contribution is multiplied by its (statically parsed) trip count,
    including nesting — XLA prints each loop body once.

    A while-loop whose trip count cannot be parsed (data-dependent bound)
    uses the explicit ``unknown_trip`` bound if one is given; otherwise the
    body contributes x1 AND is recorded in ``CollectiveStats.unknown_trips``
    so downstream consumers (:func:`roofline_terms`) fail loudly instead of
    silently under-counting."""
    comps = _split_computations(hlo_text)
    if not comps or not loop_aware:
        counts, rbytes, wbytes = _collective_bytes_in(
            [l.strip() for l in hlo_text.splitlines()], n_devices)
        return CollectiveStats(counts, rbytes, wbytes, loop_corrected=False)

    # map body computation -> trip count, and parent -> child bodies
    body_trip: dict[str, int] = {}
    unknown: list[str] = []
    children: dict[str, list[str]] = {name: [] for name in comps}
    for name, lines in comps.items():
        for ln in lines:
            m = _WHILE_RE.search(ln)
            if m:
                cond, body = m.group(1), m.group(2)
                trip = _trip_count(comps.get(cond, []))
                if trip is None:
                    if unknown_trip is not None:
                        trip = int(unknown_trip)
                    else:
                        unknown.append(body)
                        trip = 1
                body_trip[body] = trip
                children[name].append(body)

    # multiplier for each computation = product of trip counts on the path
    # from the entry; computations not reached from a while get x1
    mult: dict[str, float] = {name: 1.0 for name in comps}

    def visit(name: str, factor: float):
        mult[name] = max(mult.get(name, 1.0), factor)
        for child in children.get(name, []):
            visit(child, factor * body_trip.get(child, 1))

    for name in comps:
        if name not in body_trip:          # roots: entry + non-loop comps
            visit(name, 1.0)

    counts = {k: 0 for k in _COLLECTIVES}
    rbytes = {k: 0.0 for k in _COLLECTIVES}
    wbytes = {k: 0.0 for k in _COLLECTIVES}
    for name, lines in comps.items():
        c, r, w = _collective_bytes_in(lines, n_devices)
        f = mult.get(name, 1.0)
        for k in _COLLECTIVES:
            counts[k] += c[k]
            rbytes[k] += r[k] * f
            wbytes[k] += w[k] * f
    return CollectiveStats(counts, rbytes, wbytes, loop_corrected=True,
                           unknown_trips=tuple(unknown))


@dataclasses.dataclass
class RooflineTerms:
    compute_s: float          # per-device FLOPs / peak
    memory_s: float           # per-device bytes accessed / HBM bw
    collective_s: float       # per-device wire bytes / ICI link bw
    flops_per_device: float
    bytes_per_device: float
    wire_bytes_per_device: float

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        """Lower-bound step time if the three terms fully overlapped."""
        return max(self.compute_s, self.memory_s, self.collective_s)


def roofline_terms(cost: dict, coll: CollectiveStats, *,
                   allow_unknown_trips: bool = False) -> RooflineTerms:
    """Roofline terms from ``cost_analysis()`` numbers + collective stats.

    Refuses stats carrying unparsed while-loop trips — those wire bytes are
    under-counted by an unknown factor, and a roofline built on them would
    quietly report a too-fast bound.  Re-run :func:`parse_collectives` with
    an explicit ``unknown_trip=<bound>`` (or pass ``allow_unknown_trips=True``
    to accept the x1 floor knowingly)."""
    if coll.unknown_trips and not allow_unknown_trips:
        raise ValueError(
            "while-loop trip count unknown for HLO bodies "
            f"{list(coll.unknown_trips)} — collective wire bytes are "
            "under-counted; pass unknown_trip=<bound> to parse_collectives "
            "or allow_unknown_trips=True to accept the x1 floor")
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))
    wire = float(coll.total_wire_bytes)
    return RooflineTerms(
        compute_s=flops / PEAK_FLOPS,
        memory_s=byts / HBM_BW,
        collective_s=wire / ICI_BW,
        flops_per_device=flops,
        bytes_per_device=byts,
        wire_bytes_per_device=wire,
    )
