"""Device time under the named scopes of the region fleet's call, from a
reduced trace.

``scopes.py`` reads the serving engine's chunk step.  The fleet cells run
another program, ``hierarchical_stream_run``: ``shard_map`` over the
``region`` mesh of the vmapped chunk scan (``pca.fold``, ``pca.probe``,
``pca.refresh``, ``pca.twin``, ``pca.books``), then the level-2 merge
(``pca.merge``).  After the window the call is lowered again at the
cell's shapes and shardings and its executable found again (the run
compiled it, or loaded it from the persistent compilation cache); its
instructions' ``op_name``s (``scopes.op_names``) name the scopes.  The
TPU compiler's own copies, slices and concatenations of a scope's values
(``slice-start``/``slice-done``, ``ConcatBitcast`` of the merge's shifted
diagonals) carry no ``op_name``: such an instruction takes the scope of
its operands where they all carry the same one.

The chunk scan runs as one loop whose device event spans its whole body,
and that loop carries no scope: the scoped ops are events inside it.  So a
scope is read over the events that carry it alone, and of those only the
outermost (``scopes.outermost``): a scoped loop's body counts once, in its
loop.
"""

from __future__ import annotations

import re
import time

from harness import scopes

_DEFINES = re.compile(r"^\s*(?:ROOT )?%[^\s=]+ = ")
_REFERENCE = re.compile(r"%[\w.\-]+")


def op_names(hlo_text: str) -> dict[tuple, str]:
    """``scopes.op_names``, with each instruction that has no ``op_name``
    given the ``op_name`` of its operands where they all carry the same
    innermost scope (instructions are defined before their uses, so one
    pass in text order follows chains of them)."""
    names = scopes.op_names(hlo_text)
    by_name = {key[0]: key for key in names}
    for line in hlo_text.splitlines():
        key = scopes._key(line)
        if key is None or names[key]:
            continue
        operands = [names[by_name[ref]] for ref in _REFERENCE.findall(
            line[_DEFINES.match(line).end():]) if ref in by_name]
        inner = {(scopes.SCOPE_NAME.findall(op) or [None])[-1]
                 for op in operands}
        if len(inner) == 1 and None not in inner:
            names[key] = operands[0]
    return names


def call_op_names(v) -> dict[tuple, str]:
    """``op_names`` of the fleet's compiled call at the cell's shapes,
    computed once per run (kept on the view that every reader of the run
    shares)."""
    cached = getattr(v, "_call_op_names", None)
    if cached is None:
        import jax
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec

        from harness import system
        from repro.launch.mesh import make_fleet_mesh
        from repro.streaming.hierarchy import (hierarchical_stream_init,
                                               hierarchical_stream_run)
        t0 = time.perf_counter()
        config = v.run.config
        f = config["fleet"]
        cfg = system.stream_config(config)
        mesh = make_fleet_mesh(region=f["chips"], data=1)
        lead = NamedSharding(mesh, PartitionSpec("region"))
        states = jax.eval_shape(
            lambda key: hierarchical_stream_init(cfg, key, f["regions"]),
            jax.random.PRNGKey(0))
        states = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=lead,
                                           weak_type=a.weak_type), states)
        xs = jax.ShapeDtypeStruct(
            (f["regions"], f["rounds_per_call"], v.run.n, cfg.p),
            np.float32, sharding=lead)
        compiled = hierarchical_stream_run.lower(
            cfg, mesh, states, xs, chunk=f["chunk"]).compile()
        cached = op_names(compiled.as_text())
        print(f"fleet scopes: {len(cached)} instructions of the fleet call "
              f"read in {time.perf_counter() - t0!r} s", flush=True)
        v._call_op_names = cached
    return cached


def scope_seconds(reduced, names: dict[tuple, str]) -> dict[str, float]:
    """Device seconds per chip under each named scope: for each scope the
    outermost of the events whose ``op_name`` holds it.  One pass over the
    events, each distinct event name looked up once (a call is tens of
    thousands of device events per chip)."""
    carried: dict[str, list] = {}
    found: dict[str, tuple] = {}
    for e in reduced.ops:
        held = found.get(e.name)
        if held is None:
            op = names.get(scopes._key(e.name), "")
            held = found[e.name] = tuple(set(scopes.SCOPE_NAME.findall(op)))
        for scope in held:
            carried.setdefault(scope, []).append(e)
    return {scope: sum(e.dur_ns for e in scopes.outermost(events)) * 1e-9
            / reduced.chips for scope, events in carried.items()}


def call_seconds(v) -> dict[str, float] | None:
    """``scope_seconds`` of the run's window, computed once per run, or
    None without a trace."""
    if v.reduced is None or not v.reduced.ops:
        return None
    cached = getattr(v, "_call_seconds", None)
    if cached is None:
        cached = scope_seconds(v.reduced, call_op_names(v))
        v._call_seconds = cached
    return cached


def line(reduced, seconds: dict[str, float]) -> str:
    """Each scope with its device seconds per chip, on one line, with the
    window's busy time."""
    text = ", ".join(f"{s} {t!r}" for s, t in sorted(
        seconds.items(), key=lambda kv: -kv[1])) or "no scopes"
    return (f"fleet device seconds per scope: {text}; busy "
            f"{reduced.busy_s!r} s")


def ms_per_call(v, scope: str) -> float | None:
    """Device milliseconds under the named ``scope`` per fleet call in the
    window, or None where no op carries it (a program without the scope,
    or no trace)."""
    calls = v.outcome.notes.get("calls")
    seconds = call_seconds(v) if calls else None
    if not seconds or seconds.get(scope, 0.0) <= 0:
        return None
    return 1e3 * seconds[scope] / calls
