"""Recursive jaxpr walker + the program-contract rule vocabulary.

One walker replaces the ad-hoc ``_count_primitive``/``_count`` helpers the
test suites grew independently: it descends into every sub-jaxpr a
primitive can carry (``scan``/``while`` bodies, ``cond`` branches, ``pjit``
calls, ``shard_map`` regions, ``custom_vmap``/``custom_jvp`` rules) and
yields each equation with its *static execution multiplier* — scan bodies
multiplied by their ``length`` param, while bodies by the trip count parsed
from the condition (the same largest-int-constant fallback the HLO-side
loop correction uses: :func:`repro.launch.hlo_analysis.fallback_trip`).

``lax.cond`` branches are all visited at the same multiplier: the compiled
program contains both, and the repo's launch-count guarantees are claims
about the traced body ("both branches count" — see
``kernels/ops.py::fused_stream_stages_blocked``).

Rules are small frozen dataclasses with a ``check(jaxpr) -> RuleReport``
method; :mod:`repro.analysis.contracts` binds them to entry points.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Iterable, Iterator, Mapping

import numpy as np
from jax.extend.core import ClosedJaxpr, Jaxpr, Literal

from repro.launch.hlo_analysis import fallback_trip

__all__ = ["EqnSite", "iter_eqns", "count_primitive", "count_primitives",
           "collective_counts", "while_trip_count", "UnknownTripError",
           "COLLECTIVE_PRIMITIVES", "HOST_SYNC_PRIMITIVES", "RuleReport",
           "PrimitiveBudget", "CollectiveBudget", "ForbidInLoops", "NoF64",
           "Fp32Accumulators"]


class UnknownTripError(ValueError):
    """A loop-weighted count hit a ``while`` whose trip count could not be
    parsed from its condition (data-dependent bound).  Rules that price
    per-run work must fail loudly on it rather than under-count — declare
    an explicit bound (restructure to ``scan``/``fori_loop``) or drop
    ``loop_weighted``."""

# collectives as they appear in jaxprs (inside shard_map regions); the
# HLO-side names in launch/hlo_analysis.py are the post-SPMD spellings
COLLECTIVE_PRIMITIVES = frozenset({
    "psum", "all_gather", "all_gather_invariant", "all_to_all", "ppermute",
    "pmax", "pmin", "psum_scatter", "reduce_scatter", "pbroadcast",
})

# host round-trips / staged transfers that must never appear inside a
# device-resident hot loop (the host-sync-free claim, DESIGN.md Sec. 12)
HOST_SYNC_PRIMITIVES = frozenset({
    "device_put", "pure_callback", "io_callback", "debug_callback",
    "callback",
})


@dataclasses.dataclass(frozen=True)
class EqnSite:
    """One equation as seen by the walker."""

    eqn: object                  # jax.extend.core.JaxprEqn
    mult: float                  # static execution multiplier (loop trips);
    #                              NaN when an enclosing while trip is unknown
    loop_depth: int              # > 0 inside a scan/while body
    path: tuple[str, ...]        # sub-jaxpr labels from the entry

    @property
    def name(self) -> str:
        return self.eqn.primitive.name

    @property
    def trip_known(self) -> bool:
        return not math.isnan(self.mult)


def _as_jaxpr(target) -> Jaxpr:
    """Normalize any of (ClosedJaxpr, Jaxpr, make_jaxpr output) to a Jaxpr."""
    if isinstance(target, Jaxpr):
        return target
    inner = getattr(target, "jaxpr", None)
    if isinstance(inner, (Jaxpr, ClosedJaxpr)):
        return _as_jaxpr(inner)
    raise TypeError(f"expected a (Closed)Jaxpr, got {type(target).__name__}")


def while_trip_count(eqn) -> int | None:
    """Static trip count of a ``while`` equation, parsed from its condition.

    Mirrors the HLO-side ``_trip_count`` in :mod:`repro.launch.hlo_analysis`:
    the bound is the integer constant the induction variable is compared
    against; conditions are tiny, so the largest scalar int constant in the
    condition jaxpr (consts + literals) is the bound, with a floor of 1
    (:func:`repro.launch.hlo_analysis.fallback_trip` — the shared policy).
    A condition with NO int constants (a data-dependent bound) returns
    ``None``: the trip is unknown, and loop-weighted counts through it
    raise :class:`UnknownTripError` instead of silently under-counting.
    ``fori_loop`` with static bounds lowers to ``scan`` and never gets here.
    """
    cond = eqn.params.get("cond_jaxpr")
    if cond is None:
        return 1
    ints: list[int] = []
    for c in getattr(cond, "consts", ()):
        arr = np.asarray(c)
        if arr.ndim == 0 and np.issubdtype(arr.dtype, np.integer):
            ints.append(int(arr))
    for sub in _as_jaxpr(cond).eqns:
        for v in sub.invars:
            if isinstance(v, Literal):
                arr = np.asarray(v.val)
                if arr.ndim == 0 and np.issubdtype(arr.dtype, np.integer):
                    ints.append(int(arr))
    return fallback_trip(ints)


def _sub_jaxprs(eqn) -> Iterator[tuple[Jaxpr, float, bool, str]]:
    """Yield (sub_jaxpr, extra_multiplier, is_loop_body, label) for every
    sub-jaxpr carried by ``eqn``'s params."""
    name = eqn.primitive.name
    if name == "scan":
        yield (_as_jaxpr(eqn.params["jaxpr"]),
               float(eqn.params.get("length", 1)), True, "scan")
        return
    if name == "while":
        trip = while_trip_count(eqn)
        factor = float("nan") if trip is None else float(trip)
        yield _as_jaxpr(eqn.params["cond_jaxpr"]), factor, True, "while_cond"
        yield _as_jaxpr(eqn.params["body_jaxpr"]), factor, True, "while_body"
        return
    if name == "cond":
        for i, branch in enumerate(eqn.params["branches"]):
            yield _as_jaxpr(branch), 1.0, False, f"cond_branch{i}"
        return
    for key, val in eqn.params.items():
        for item in (val if isinstance(val, (list, tuple)) else [val]):
            if isinstance(item, (Jaxpr, ClosedJaxpr)):
                yield _as_jaxpr(item), 1.0, False, f"{name}:{key}"


def iter_eqns(target, *, _mult: float = 1.0, _depth: int = 0,
              _path: tuple[str, ...] = ()) -> Iterator[EqnSite]:
    """Depth-first walk over every equation reachable from ``target``
    (a ClosedJaxpr, Jaxpr, or ``jax.make_jaxpr`` output), including all
    sub-jaxprs, with loop multipliers propagated down the path."""
    jaxpr = _as_jaxpr(target)
    for eqn in jaxpr.eqns:
        yield EqnSite(eqn=eqn, mult=_mult, loop_depth=_depth, path=_path)
        for sub, factor, is_loop, label in _sub_jaxprs(eqn):
            yield from iter_eqns(
                sub, _mult=_mult * factor,
                _depth=_depth + (1 if is_loop else 0),
                _path=_path + (label,))


def count_primitives(target, names: Iterable[str] | None = None, *,
                     loop_weighted: bool = False) -> dict[str, int]:
    """Primitive occurrence counts over the whole (recursive) jaxpr.

    ``loop_weighted=True`` multiplies each occurrence by its static loop
    multiplier (scan lengths × while trips along the path) — the per-RUN
    launch count rather than the per-TRACE count.  A counted primitive
    under a ``while`` with an unparseable trip raises
    :class:`UnknownTripError` (the count would be a silent under-estimate).
    """
    wanted = None if names is None else frozenset(names)
    acc: dict[str, int] = {}
    for site in iter_eqns(target):
        if wanted is not None and site.name not in wanted:
            continue
        if loop_weighted and not site.trip_known:
            raise UnknownTripError(
                f"{site.name} at {'/'.join(site.path) or '<entry>'} sits "
                "under a while loop with an unknown (data-dependent) trip "
                "count — a loop-weighted count needs an explicit bound")
        weight = int(site.mult) if loop_weighted else 1
        acc[site.name] = acc.get(site.name, 0) + weight
    return acc


def count_primitive(target, name: str, *, loop_weighted: bool = False) -> int:
    """Count one primitive (the drop-in form the test suites migrate to)."""
    return count_primitives(target, [name],
                            loop_weighted=loop_weighted).get(name, 0)


def _eqn_axes(eqn) -> tuple[str, ...]:
    """Mesh axis names a collective equation operates over."""
    axes = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if isinstance(axes, str):
        axes = (axes,)
    return tuple(a for a in axes if isinstance(a, str))


def collective_counts(target) -> dict[str, dict[str, int]]:
    """Per-mesh-axis collective counts: ``{axis: {primitive: count}}``."""
    out: dict[str, dict[str, int]] = {}
    for site in iter_eqns(target):
        if site.name not in COLLECTIVE_PRIMITIVES:
            continue
        for axis in _eqn_axes(site.eqn):
            out.setdefault(axis, {})
            out[axis][site.name] = out[axis].get(site.name, 0) + 1
    return out


# ---------------------------------------------------------------------------
# Rules
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class RuleReport:
    rule: str
    ok: bool
    detail: str


@dataclasses.dataclass(frozen=True)
class PrimitiveBudget:
    """Pin the occurrence count of one primitive (exact / max / min)."""

    primitive: str
    exact: int | None = None
    max: int | None = None
    min: int | None = None
    loop_weighted: bool = False

    @property
    def name(self) -> str:
        return f"budget:{self.primitive}"

    def check(self, target) -> RuleReport:
        try:
            n = count_primitive(target, self.primitive,
                                loop_weighted=self.loop_weighted)
        except UnknownTripError as e:
            return RuleReport(self.name, False, str(e))
        wants = []
        ok = True
        if self.exact is not None:
            ok &= n == self.exact
            wants.append(f"== {self.exact}")
        if self.max is not None:
            ok &= n <= self.max
            wants.append(f"<= {self.max}")
        if self.min is not None:
            ok &= n >= self.min
            wants.append(f">= {self.min}")
        return RuleReport(
            self.name, ok,
            f"{self.primitive} count {n} (want {' and '.join(wants)})")


@dataclasses.dataclass(frozen=True)
class CollectiveBudget:
    """Pin exact per-axis collective counts — the communication analogue of
    the paper's Table-1 budget, checked on the traced program.

    ``budgets`` is ``((primitive, exact_count), ...)`` for ``axis``; any
    other collective on that axis, and (``exclusive=True``) any collective
    on any OTHER axis, is a violation.
    """

    axis: str
    budgets: tuple[tuple[str, int], ...]
    exclusive: bool = True
    forbid_in_loops: bool = True

    @property
    def name(self) -> str:
        return f"collectives:{self.axis}"

    def check(self, target) -> RuleReport:
        got = collective_counts(target)
        on_axis = got.get(self.axis, {})
        problems = []
        for prim, want in self.budgets:
            have = on_axis.get(prim, 0)
            if have != want:
                problems.append(f"{prim} on {self.axis!r}: {have} != {want}")
        budgeted = {prim for prim, _ in self.budgets}
        for prim, have in sorted(on_axis.items()):
            if prim not in budgeted:
                problems.append(
                    f"unbudgeted {prim} x{have} on axis {self.axis!r}")
        if self.exclusive:
            for axis, prims in sorted(got.items()):
                if axis != self.axis:
                    problems.append(
                        f"collectives on unexpected axis {axis!r}: {prims}")
        if self.forbid_in_loops:
            for site in iter_eqns(target):
                if (site.name in COLLECTIVE_PRIMITIVES
                        and site.loop_depth > 0):
                    problems.append(
                        f"{site.name} inside loop body at "
                        f"{'/'.join(site.path)} (collectives must stay "
                        f"outside the streamed scan)")
        detail = "; ".join(problems) if problems else (
            f"axis {self.axis!r}: " + ", ".join(
                f"{p} x{c}" for p, c in self.budgets) + ", none elsewhere")
        return RuleReport(self.name, not problems, detail)


@dataclasses.dataclass(frozen=True)
class ForbidInLoops:
    """Zero occurrences of the given primitives inside scan/while bodies —
    the host-sync-free hot-loop claim (no staged transfers, no callbacks)."""

    primitives: frozenset = HOST_SYNC_PRIMITIVES
    everywhere: bool = False     # forbid outside loops too

    @property
    def name(self) -> str:
        return "forbid:" + ("program" if self.everywhere else "loops")

    def check(self, target) -> RuleReport:
        hits = []
        for site in iter_eqns(target):
            if site.name in self.primitives and (self.everywhere
                                                 or site.loop_depth > 0):
                where = "/".join(site.path) or "<entry>"
                hits.append(f"{site.name} at {where}")
        scope = "the program" if self.everywhere else "loop bodies"
        detail = "; ".join(hits) if hits else (
            f"none of {sorted(self.primitives)} in {scope}")
        return RuleReport(self.name, not hits, detail)


def _outvar_dtypes(site) -> Iterator[tuple[object, str]]:
    for v in site.eqn.outvars:
        aval = getattr(v, "aval", None)
        dtype = getattr(aval, "dtype", None)
        if dtype is not None:
            yield v, str(dtype)


@dataclasses.dataclass(frozen=True)
class NoF64:
    """No float64/complex128 value anywhere in the program — the repo's
    dtype floor (everything streams fp32 with optional bf16 tiles)."""

    @property
    def name(self) -> str:
        return "dtype:no-f64"

    def check(self, target) -> RuleReport:
        hits = []
        for site in iter_eqns(target):
            for _, dtype in _outvar_dtypes(site):
                if dtype in ("float64", "complex128"):
                    where = "/".join(site.path) or "<entry>"
                    hits.append(f"{site.name} -> {dtype} at {where}")
        detail = "; ".join(hits[:8]) if hits else "no f64/c128 values"
        return RuleReport(self.name, not hits, detail)


@dataclasses.dataclass(frozen=True)
class Fp32Accumulators:
    """The bf16 dtype policy (DESIGN.md Sec. 14): bfloat16 is a *tile*
    format, never an accumulator format.  Statically: no ``pallas_call``
    OUTPUT and no ``scan`` CARRY may be bfloat16 — kernels may load bf16
    tiles, but everything they emit and everything that persists across
    rounds must be fp32."""

    @property
    def name(self) -> str:
        return "dtype:fp32-accumulators"

    def check(self, target) -> RuleReport:
        hits = []
        for site in iter_eqns(target):
            if site.name == "pallas_call":
                for _, dtype in _outvar_dtypes(site):
                    if dtype == "bfloat16":
                        hits.append("pallas_call emits bfloat16 (outputs "
                                    "must accumulate in fp32)")
            elif site.name == "scan":
                sub = _as_jaxpr(site.eqn.params["jaxpr"])
                n_consts = site.eqn.params.get("num_consts", 0)
                n_carry = site.eqn.params.get("num_carry", 0)
                carries = sub.invars[n_consts:n_consts + n_carry]
                for v in carries:
                    dtype = str(getattr(v.aval, "dtype", ""))
                    if dtype == "bfloat16":
                        hits.append("scan carries bfloat16 state (carried "
                                    "state must stay fp32)")
        detail = "; ".join(sorted(set(hits))) if hits else (
            "pallas outputs and scan carries are fp32")
        return RuleReport(self.name, not hits, detail)
