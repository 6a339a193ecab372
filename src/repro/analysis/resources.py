"""Static resource certifier: compile-time VMEM/HBM/wire-byte accounting.

PR 8 machine-checked *structural* invariants (launch counts, collective
counts, dtype rules); this module derives the *quantities* behind the
paper's Sec. 2.4 / Table-1 cost analysis from the traced program itself —
no execution, no compilation:

* per-``pallas_call`` **VMEM footprint** from the BlockSpecs the call was
  traced with (inputs + outputs, dtype-aware, x2 for Pallas double
  buffering), checked against the per-backend limit in
  :mod:`repro.launch.tiling`;
* per-entry **HBM traffic** under the Pallas fetch-on-change semantics: a
  block is (re)fetched exactly when its index-map output changes between
  consecutive grid steps (last grid axis fastest), so evaluating each
  operand's index map over the whole grid gives the exact read/write bytes
  — the "one tile-load per chunk" claim of the fused path becomes a
  checkable number instead of prose;
* per-kernel **flops** (from the kernel jaxpr: 2mnk per ``dot_general``,
  one per element for VPU arithmetic) and the resulting **arithmetic
  intensity** against the roofline constants shared with
  :mod:`repro.launch.hlo_analysis`;
* per-axis **collective wire bytes** from the merge collectives' operand
  shapes, priced by the same ring model the HLO parser uses
  (:func:`repro.launch.hlo_analysis.ring_wire_bytes`), and reconciled
  *exactly* against the packet ledger's booked merge record
  (:func:`repro.core.costs.merge_record_elems`) — booked == traced,
  extended from runtime tests to static certification.

Budgets are declarative rules in the :mod:`repro.analysis.jaxpr_lint`
style (``check(target) -> RuleReport``) so :mod:`repro.analysis.contracts`
binds them to entry points unchanged: :class:`VmemBudget`,
:class:`HbmTrafficBudget`, :class:`WireBytesBudget`.  Exact per-entry
quantities are pinned by ``analysis/baselines/resources.json`` and
surfaced through ``python -m repro.analysis.check`` with per-quantity
deltas (``--diff``, ``--bless-resources``).
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from typing import Iterator, Mapping

import numpy as np

from repro.analysis.jaxpr_lint import (COLLECTIVE_PRIMITIVES, EqnSite,
                                       RuleReport, UnknownTripError,
                                       _as_jaxpr, iter_eqns)
from repro.launch.hlo_analysis import HBM_BW, PEAK_FLOPS, ring_wire_bytes

__all__ = ["KernelResources", "CollectiveResources", "EntryResources",
           "pallas_resources", "collective_resources", "entry_resources",
           "VmemBudget", "HbmTrafficBudget", "WireBytesBudget",
           "derive_all", "check_against_baseline", "QuantityResult",
           "baseline_path", "REF_REGIONS"]

# reference fleet size for the scaled wire-byte report: the wsn-1m target
# (1e6 sensors / ~1000 per region — DESIGN.md Sec. 13); traced meshes are
# 1-2 devices, so ring wire bytes are reported both at the traced group
# size and scaled to this one
REF_REGIONS = 1024

# grids above this size are not index-map-evaluated step by step; the
# conservative every-step-refetches bound is used instead (flagged in the
# per-operand record).  Contract grids are O(10) cells.
_MAX_EXACT_GRID = 65536


# ---------------------------------------------------------------------------
# Per-pallas_call derivation
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class OperandTraffic:
    """One BlockSpec'd operand of one ``pallas_call``."""

    origin: str                  # the kernel ref it feeds, e.g. "x_ref"
    dtype: str
    block_shape: tuple           # ints; vmapped dims count as 1
    block_bytes: int             # one block residing in VMEM
    array_bytes: int             # the full (padded) operand in HBM
    fetches: int                 # index-map changes over the grid
    fetched_bytes: int           # fetches * block_bytes
    exact: bool                  # False when the grid was too big to walk

    @property
    def passes(self) -> float:
        """fetched bytes / one full pass over the operand."""
        return self.fetched_bytes / max(self.array_bytes, 1)


@dataclasses.dataclass(frozen=True)
class KernelResources:
    """Derived resource bill of one traced ``pallas_call``."""

    name: str                    # kernel function name
    path: str                    # walker path from the entry jaxpr
    mult: float                  # static execution multiplier (loop trips)
    grid: tuple
    inputs: tuple                # OperandTraffic rows
    outputs: tuple
    flops: int                   # one execution, all grid cells

    @property
    def vmem_block_bytes(self) -> int:
        """Single-buffered working set: every operand's live block."""
        return sum(o.block_bytes for o in self.inputs + self.outputs)

    @property
    def vmem_bytes(self) -> int:
        """Double-buffered footprint (Pallas overlaps fetch and compute)."""
        return 2 * self.vmem_block_bytes

    @property
    def hbm_read_bytes(self) -> int:
        return sum(o.fetched_bytes for o in self.inputs)

    @property
    def hbm_write_bytes(self) -> int:
        return sum(o.fetched_bytes for o in self.outputs)

    @property
    def nominal_read_bytes(self) -> int:
        """One full pass over every input operand."""
        return sum(o.array_bytes for o in self.inputs)

    @property
    def nominal_write_bytes(self) -> int:
        return sum(o.array_bytes for o in self.outputs)

    def bytes_by_dtype(self) -> dict[str, int]:
        """HBM traffic split by dtype — separates bf16 tile loads from
        fp32 accumulator traffic on the mixed-precision fused path."""
        acc: dict[str, int] = {}
        for o in self.inputs + self.outputs:
            acc[o.dtype] = acc.get(o.dtype, 0) + o.fetched_bytes
        return acc

    @property
    def intensity(self) -> float:
        """Arithmetic intensity (flops per HBM byte moved)."""
        return self.flops / max(self.hbm_read_bytes + self.hbm_write_bytes, 1)


def _block_dims(block_shape) -> tuple[int, ...]:
    """Block extents as ints: a ``pl.Blocked`` entry gives its
    ``block_size``; a squeezed (vmapped) dim occupies a single slice and
    counts as 1."""
    dims = []
    for d in block_shape:
        size = getattr(d, "block_size", d)
        dims.append(int(size) if isinstance(size, (int, np.integer)) else 1)
    return tuple(dims)


def _grid_steps(grid: tuple) -> Iterator[tuple]:
    """Grid iteration order: row-major with the LAST axis fastest — the
    Pallas sequential-grid execution order that fetch-on-change depends on.
    """
    return np.ndindex(*(int(g) for g in grid))


def _index_map_fetches(bm, grid: tuple) -> tuple[int, bool]:
    """(number of block fetches, exact?) for one block mapping.

    Pallas re-fetches an operand block only when its index-map output
    changes between consecutive grid steps, so the fetch count is the
    number of value changes in the index-map sequence (first step counts).
    Falls back to the conservative one-fetch-per-step bound when the grid
    is too large to enumerate or the index map is not a plain
    grid-indices function.
    """
    from jax.extend.core import jaxpr_as_fun

    cells = int(np.prod([int(g) for g in grid])) if grid else 1
    cj = getattr(bm, "index_map_jaxpr", None)
    if cj is None or cells > _MAX_EXACT_GRID:
        return cells, False
    if len(cj.jaxpr.invars) != len(grid):
        return cells, False            # scalar-prefetch args etc.
    index_map = jaxpr_as_fun(cj)
    fetches, prev = 0, None
    for step in _grid_steps(grid):
        out = index_map(*step)
        idx = tuple(int(v) for v in out)
        if idx != prev:
            fetches += 1
            prev = idx
    return fetches, True


# flop model for kernel jaxprs: one flop per output element for VPU
# arithmetic, one per input element for reductions, 2mnk for dot_general;
# moves/compares/selects are free (deterministic, documented — the same
# curve XLA's cost_analysis uses for elementwise ops)
_EW_FLOPS = frozenset({
    "add", "sub", "mul", "div", "rem", "max", "min", "neg", "abs", "sign",
    "exp", "exp2", "log", "log1p", "logistic", "tanh", "sqrt", "rsqrt",
    "pow", "integer_pow", "atan2", "erf", "cos", "sin", "floor", "ceil",
    "round", "square",
})
_REDUCE_FLOPS = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "cumsum",
    "cumprod", "cummax", "cummin",
})


def _aval_elems(v) -> int:
    shape = getattr(getattr(v, "aval", None), "shape", ())
    return int(np.prod(shape)) if shape else 1


def _jaxpr_flops(target) -> int:
    """Static flop count of a (kernel) jaxpr under the model above; both
    ``cond`` branches count, matching the launch-budget convention."""
    total = 0.0
    for site in iter_eqns(target):
        m = site.mult if site.trip_known else 1.0
        if site.name in _EW_FLOPS:
            total += m * sum(_aval_elems(v) for v in site.eqn.outvars)
        elif site.name in _REDUCE_FLOPS:
            total += m * sum(_aval_elems(v) for v in site.eqn.invars)
        elif site.name == "dot_general":
            (lhs_c, _), _ = site.eqn.params["dimension_numbers"]
            lhs = site.eqn.invars[0]
            k = 1
            for d in lhs_c:
                k *= int(lhs.aval.shape[d])
            out = sum(_aval_elems(v) for v in site.eqn.outvars)
            total += m * 2 * out * k
    return int(total)


def _kernel_name(eqn) -> str:
    if eqn.params.get("name"):
        return str(eqn.params["name"])
    src = getattr(eqn.params["jaxpr"].debug_info, "func_src_info", None)
    return src.split(" ")[0] if src else "?"


def pallas_resources(target) -> list[KernelResources]:
    """Derive the resource bill of every ``pallas_call`` reachable from
    ``target`` (a jaxpr / ``jax.make_jaxpr`` output), one record each."""
    out: list[KernelResources] = []
    for site in iter_eqns(target):
        if site.name != "pallas_call":
            continue
        gm = site.eqn.params["grid_mapping"]
        grid = tuple(int(g) for g in gm.grid)
        n_in = int(gm.num_inputs)
        # operands are named by the kernel's ref parameters (``x_ref``...),
        # which the block mappings follow in order (inputs, then outputs)
        arg_names = getattr(site.eqn.params["jaxpr"].debug_info,
                            "arg_names", None) or ()
        rows: list[OperandTraffic] = []
        for i, bm in enumerate(gm.block_mappings):
            sdt = bm.array_aval
            itemsize = int(np.dtype(sdt.dtype).itemsize)
            dims = _block_dims(bm.block_shape)
            block_bytes = int(np.prod(dims)) * itemsize
            array_bytes = int(np.prod(sdt.shape)) * itemsize
            fetches, exact = _index_map_fetches(bm, grid)
            rows.append(OperandTraffic(
                origin=(arg_names[i] if i < len(arg_names)
                        else str(bm.origin)),
                dtype=str(np.dtype(sdt.dtype)),
                block_shape=dims,
                block_bytes=block_bytes, array_bytes=array_bytes,
                fetches=fetches, fetched_bytes=fetches * block_bytes,
                exact=exact))
        cells = int(np.prod(grid)) if grid else 1
        flops = cells * _jaxpr_flops(site.eqn.params["jaxpr"])
        out.append(KernelResources(
            name=_kernel_name(site.eqn),
            path="/".join(site.path) or "<entry>",
            mult=site.mult, grid=grid,
            inputs=tuple(rows[:n_in]), outputs=tuple(rows[n_in:]),
            flops=flops))
    return out


# ---------------------------------------------------------------------------
# Per-axis collective derivation
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class CollectiveResources:
    """One collective equation's payload, as traced."""

    primitive: str
    axes: tuple[str, ...]
    mult: float
    payload_elems: int           # all operands, local shard
    payload_bytes: int
    scalar_operands: int         # rank-0 operands (e.g. a trace partial)
    records: int                 # leading-dim stack size of a tiled gather
    record_elems: int            # per-record payload of a gather
    group_size: int              # axis size as traced

    def wire_bytes_at(self, group: int) -> float:
        """Ring-model per-device wire bytes at fleet size ``group`` —
        gathers ship one record per peer, reductions the full payload."""
        if self.primitive in ("all_gather", "all_gather_invariant"):
            elem = self.payload_bytes / max(self.payload_elems, 1)
            full = self.record_elems * elem * group
            return ring_wire_bytes("all-gather", full, group)
        if self.primitive in ("psum_scatter", "reduce_scatter"):
            return ring_wire_bytes("reduce-scatter", self.payload_bytes,
                                   group)
        if self.primitive == "ppermute":
            return ring_wire_bytes("collective-permute", self.payload_bytes,
                                   group)
        if self.primitive == "all_to_all":
            return ring_wire_bytes("all-to-all", self.payload_bytes, group)
        return ring_wire_bytes("all-reduce", self.payload_bytes, group)


def _eqn_axes(eqn) -> tuple[str, ...]:
    axes = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if isinstance(axes, str):
        axes = (axes,)
    return tuple(a for a in axes if isinstance(a, str))


def _mesh_axis_sizes(target) -> dict[str, int]:
    """Axis sizes of every mesh visible in the jaxpr (shard_map params)."""
    sizes: dict[str, int] = {}
    for site in iter_eqns(target):
        mesh = site.eqn.params.get("mesh")
        shape = getattr(mesh, "shape", None)
        if isinstance(shape, Mapping):
            for axis, size in shape.items():
                if isinstance(axis, str):
                    sizes[axis] = int(size)
    return sizes


def collective_resources(target) -> list[CollectiveResources]:
    """Derive every collective's traced payload, per mesh axis."""
    sizes = _mesh_axis_sizes(target)
    out: list[CollectiveResources] = []
    for site in iter_eqns(target):
        if site.name not in COLLECTIVE_PRIMITIVES:
            continue
        eqn = site.eqn
        axes = _eqn_axes(eqn)
        elems = bytes_ = scalars = 0
        for v in eqn.invars:
            aval = getattr(v, "aval", None)
            shape = getattr(aval, "shape", None)
            dtype = getattr(aval, "dtype", None)
            if shape is None or dtype is None:
                continue
            n = int(np.prod(shape)) if shape else 1
            elems += n
            bytes_ += n * int(np.dtype(dtype).itemsize)
            if not shape:
                scalars += 1
        records = record_elems = 0
        if site.name in ("all_gather", "all_gather_invariant"):
            dim = int(eqn.params.get("all_gather_dimension", 0))
            aval = eqn.invars[0].aval
            if eqn.params.get("tiled", False) and aval.shape:
                records = int(aval.shape[dim])
                record_elems = elems // max(records, 1)
            else:               # untiled: the whole operand is one record
                records, record_elems = 1, elems
        group = int(eqn.params.get("axis_size", 0)) or max(
            (sizes.get(a, 1) for a in axes), default=1)
        out.append(CollectiveResources(
            primitive=site.name, axes=axes, mult=site.mult,
            payload_elems=elems, payload_bytes=bytes_,
            scalar_operands=scalars, records=records,
            record_elems=record_elems, group_size=group))
    return out


# ---------------------------------------------------------------------------
# Whole-entry aggregation
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class EntryResources:
    """Loop-weighted resource bill of one traced entry point."""

    kernels: tuple
    collectives: tuple

    def _mult(self, k) -> float:
        return k.mult if not math.isnan(k.mult) else 1.0

    @property
    def launches(self) -> int:
        return int(sum(self._mult(k) for k in self.kernels))

    @property
    def vmem_peak_bytes(self) -> int:
        return max((k.vmem_bytes for k in self.kernels), default=0)

    @property
    def hbm_read_bytes(self) -> int:
        return int(sum(self._mult(k) * k.hbm_read_bytes
                       for k in self.kernels))

    @property
    def hbm_write_bytes(self) -> int:
        return int(sum(self._mult(k) * k.hbm_write_bytes
                       for k in self.kernels))

    @property
    def hbm_passes(self) -> float:
        """Derived kernel traffic over one full pass per operand — the
        fused path books ~1 read pass; every extra round trip shows here."""
        nominal = sum(self._mult(k) * (k.nominal_read_bytes
                                       + k.nominal_write_bytes)
                      for k in self.kernels)
        derived = self.hbm_read_bytes + self.hbm_write_bytes
        return derived / nominal if nominal else 0.0

    @property
    def flops(self) -> int:
        return int(sum(self._mult(k) * k.flops for k in self.kernels))

    @property
    def intensity(self) -> float:
        return self.flops / max(self.hbm_read_bytes
                                + self.hbm_write_bytes, 1)

    @property
    def roofline_balance(self) -> float:
        """intensity / machine balance (>1: compute-bound on the target)."""
        return self.intensity / (PEAK_FLOPS / HBM_BW)

    def quantities(self) -> dict[str, float]:
        """Flat {quantity: value} map — the baseline/diff surface."""
        q: dict[str, float] = {
            "launches": self.launches,
            "vmem_peak_bytes": self.vmem_peak_bytes,
            "hbm_read_bytes": self.hbm_read_bytes,
            "hbm_write_bytes": self.hbm_write_bytes,
            "hbm_passes": round(self.hbm_passes, 4),
            "flops": self.flops,
            "intensity": round(self.intensity, 4),
        }
        per_axis: dict[str, list] = {}
        for c in self.collectives:
            for axis in c.axes:
                per_axis.setdefault(axis, []).append(c)
        for axis, colls in sorted(per_axis.items()):
            q[f"wire.{axis}.collectives"] = len(colls)
            q[f"wire.{axis}.payload_bytes"] = sum(c.payload_bytes
                                                  for c in colls)
            q[f"wire.{axis}.bytes_at_{REF_REGIONS}"] = int(sum(
                c.wire_bytes_at(REF_REGIONS) for c in colls))
        return q


def entry_resources(target) -> EntryResources:
    return EntryResources(kernels=tuple(pallas_resources(target)),
                          collectives=tuple(collective_resources(target)))


# ---------------------------------------------------------------------------
# Budget rules (jaxpr_lint form: check(target) -> RuleReport)
# ---------------------------------------------------------------------------
def _fmt_bytes(n: float) -> str:
    if n >= 2**20:
        return f"{n / 2**20:.2f}MiB"
    if n >= 2**10:
        return f"{n / 2**10:.2f}KiB"
    return f"{int(n)}B"


@dataclasses.dataclass(frozen=True)
class VmemBudget:
    """Every traced ``pallas_call``'s double-buffered working set must fit
    the backend VMEM limit (:data:`repro.launch.tiling.VMEM_BYTES` by
    default) — the compile-time guarantee that no kernel the wrappers can
    plan will spill on the TPU target."""

    limit_bytes: int | None = None
    double_buffered: bool = True

    @property
    def name(self) -> str:
        return "budget:vmem"

    def _limit(self) -> int:
        if self.limit_bytes is not None:
            return self.limit_bytes
        from repro.launch.tiling import VMEM_BYTES
        return VMEM_BYTES

    def check(self, target) -> RuleReport:
        limit = self._limit()
        kernels = pallas_resources(target)
        if not kernels:
            return RuleReport(self.name, False,
                              "no pallas_call in trace (nothing to certify)")
        over, worst = [], None
        for k in kernels:
            need = k.vmem_bytes if self.double_buffered else k.vmem_block_bytes
            if worst is None or need > worst[1]:
                worst = (k, need)
            if need > limit:
                over.append(f"{k.name} grid={k.grid} needs "
                            f"{_fmt_bytes(need)} > {_fmt_bytes(limit)} VMEM")
        if over:
            return RuleReport(self.name, False, "; ".join(over))
        k, need = worst
        return RuleReport(
            self.name, True,
            f"peak {k.name}: {_fmt_bytes(need)} of {_fmt_bytes(limit)} VMEM "
            f"({100 * need / limit:.1f}%, x2 double-buffered, "
            f"{len(kernels)} kernel(s))")


@dataclasses.dataclass(frozen=True)
class HbmTrafficBudget:
    """Cap the entry's derived HBM traffic as a multiple of one full pass
    over every kernel operand, and optionally pin named operands to be
    fetched exactly once (the fused path's one-tile-load claim for the
    chunk data).  An extra kernel round trip doubles the pass count and
    fails loudly."""

    max_passes: float
    single_pass: tuple[str, ...] = ()   # operand origins, e.g. ("x_ref",)

    @property
    def name(self) -> str:
        return "budget:hbm"

    def check(self, target) -> RuleReport:
        entry = entry_resources(target)
        if not entry.kernels:
            return RuleReport(self.name, False,
                              "no pallas_call in trace (nothing to certify)")
        for k in entry.kernels:
            if math.isnan(k.mult):
                return RuleReport(
                    self.name, False,
                    f"{k.name} at {k.path}: unknown while trip count — "
                    "HBM traffic cannot be certified without an explicit "
                    "bound (see UnknownTripError)")
        problems = []
        passes = entry.hbm_passes
        if passes > self.max_passes + 1e-9:
            problems.append(
                f"hbm traffic {_fmt_bytes(entry.hbm_read_bytes + entry.hbm_write_bytes)} "
                f"= {passes:.2f} passes over the operands "
                f"(budget <= {self.max_passes:.2f} — an extra kernel "
                f"round trip?)")
        for origin in self.single_pass:
            found = False
            for k in entry.kernels:
                for o in k.inputs:
                    found |= o.origin == origin
                    if o.origin == origin and o.fetched_bytes > o.array_bytes:
                        problems.append(
                            f"{k.name} operand {origin} fetched "
                            f"{o.passes:.2f}x (must be one tile-load: "
                            f"{_fmt_bytes(o.array_bytes)})")
            if not found:
                problems.append(f"no kernel operand named {origin}")
        if problems:
            return RuleReport(self.name, False, "; ".join(problems))
        return RuleReport(
            self.name, True,
            f"hbm {_fmt_bytes(entry.hbm_read_bytes)} read + "
            f"{_fmt_bytes(entry.hbm_write_bytes)} written = {passes:.2f} "
            f"passes (<= {self.max_passes:.2f}); intensity "
            f"{entry.intensity:.2f} flops/B")


@dataclasses.dataclass(frozen=True)
class WireBytesBudget:
    """booked == traced for the hierarchical merge record: the per-region
    payload derived from the merge collectives' shapes must carry exactly
    :func:`repro.core.costs.merge_record_elems` elements — q local
    energies shipped by the tiled ``all_gather`` plus the scalar trace
    partial carried by the ``psum``.  A padded record (or a second
    collective smuggling extra payload) changes the traced count and fails
    with the delta; non-scalar psum operands are per-fleet bookkeeping
    (refresh flags), reported but not booked."""

    axis: str
    record_elems: int            # booked: costs.merge_record_elems(q)
    elem_bytes: int = 4
    at_regions: int = REF_REGIONS

    @property
    def name(self) -> str:
        return f"wire:{self.axis}"

    def check(self, target) -> RuleReport:
        colls = [c for c in collective_resources(target)
                 if self.axis in c.axes]
        if not colls:
            return RuleReport(self.name, False,
                              f"no collectives on axis {self.axis!r} "
                              "(nothing to certify)")
        gathered = sum(c.record_elems for c in colls
                       if c.primitive in ("all_gather",
                                          "all_gather_invariant"))
        reduced_scalars = sum(c.scalar_operands for c in colls
                              if c.primitive == "psum")
        traced = gathered + reduced_scalars
        bookkeeping = sum(
            c.payload_elems - c.scalar_operands for c in colls
            if c.primitive == "psum")
        wire = sum(c.wire_bytes_at(self.at_regions) for c in colls)
        detail = (
            f"merge record {traced} elems (gather {gathered} + psum "
            f"scalars {reduced_scalars}) vs booked {self.record_elems} "
            f"(merge_round_cost); +{bookkeeping} bookkeeping elems; "
            f"ring wire ~{_fmt_bytes(wire)}/device at "
            f"{self.at_regions} regions")
        return RuleReport(self.name, traced == self.record_elems, detail)


# ---------------------------------------------------------------------------
# Baseline: derive, compare, bless
# ---------------------------------------------------------------------------
def baseline_path() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "baselines", "resources.json")


def derive_all(only: str | None = None) -> dict[str, dict[str, float]]:
    """``{"contract[variant]": quantities}`` for every registered contract
    that declares a trace — the full derived-resource surface."""
    from repro.analysis import contracts
    reg = contracts.load_entry_points()
    out: dict[str, dict[str, float]] = {}
    for cid in sorted(reg):
        c = reg[cid]
        if (only and only not in cid) or c.trace is None:
            continue
        for label, jx in c.trace().items():
            out[f"{cid}[{label}]"] = entry_resources(jx).quantities()
    return out


@dataclasses.dataclass(frozen=True)
class QuantityResult:
    """One derived quantity compared against the committed expectation."""

    entry: str                   # "contract[variant]"
    quantity: str
    ok: bool
    measured: float | None
    expected: float | None
    detail: str

    def rule(self) -> str:
        return f"resources:{self.quantity}"


def _values_match(measured, expected) -> bool:
    if isinstance(measured, float) or isinstance(expected, float):
        return math.isclose(float(measured), float(expected),
                            rel_tol=1e-3, abs_tol=1e-9)
    return measured == expected


def _delta(measured: float, expected: float) -> str:
    if expected:
        return f"{100 * (measured - expected) / expected:+.1f}%"
    return f"{measured - expected:+g}"


def check_against_baseline(derived: Mapping[str, Mapping[str, float]]
                           | None = None,
                           path: str | None = None,
                           only: str | None = None) -> list[QuantityResult]:
    """Compare derived quantities against the committed baseline, one
    :class:`QuantityResult` per (entry, quantity) — regressions carry the
    measured-vs-expected delta and the re-bless instruction lives in the
    check driver."""
    if derived is None:
        derived = derive_all(only=only)
    path = path or baseline_path()
    if not os.path.exists(path):
        return [QuantityResult(
            entry="<baseline>", quantity="file", ok=False,
            measured=None, expected=None,
            detail=f"missing baseline {path} — run "
                   "`python -m repro.analysis.check --bless-resources`")]
    with open(path) as fh:
        base = json.load(fh)
    results: list[QuantityResult] = []
    for entry in sorted(set(derived) | set(base)):
        if only and only not in entry:
            continue
        if entry not in base:
            results.append(QuantityResult(
                entry, "entry", False, None, None,
                "new entry not in the committed baseline"))
            continue
        if entry not in derived:
            results.append(QuantityResult(
                entry, "entry", False, None, None,
                "baseline entry no longer derived (contract removed?)"))
            continue
        mine, theirs = derived[entry], base[entry]
        for qty in sorted(set(mine) | set(theirs)):
            m, e = mine.get(qty), theirs.get(qty)
            if m is None or e is None:
                results.append(QuantityResult(
                    entry, qty, False, m, e,
                    "quantity " + ("added" if e is None else "dropped")
                    + " vs baseline"))
            elif not _values_match(m, e):
                results.append(QuantityResult(
                    entry, qty, False, m, e,
                    f"{m} != baseline {e} ({_delta(m, e)})"))
            else:
                results.append(QuantityResult(entry, qty, True, m, e,
                                              f"{m} == baseline"))
    return results


def bless(derived: Mapping[str, Mapping[str, float]] | None = None,
          path: str | None = None) -> str:
    """Write the derived quantities as the new committed expectation."""
    if derived is None:
        derived = derive_all()
    path = path or baseline_path()
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as fh:
        json.dump(derived, fh, indent=2, sort_keys=True)
        fh.write("\n")
    return path
