"""Device time under the program's named scopes, from a reduced trace.

A TPU trace names each device op by its HLO instruction
(``%fusion.12 = f32[256,1024,32]{...} fusion(...), kind=kLoop, ...``) and
carries no ``op_name``.  The engine's compiled chunk step does: each of its
instructions has ``metadata={op_name="jit(body)/vmap(pca.refresh)/..."}``,
which holds the program's ``jax.named_scope`` names (``pca.fold``,
``pca.probe``, ``pca.refresh``, ``pca.twin``, ``pca.books``).  So after the
window the step is lowered again and its executable loaded (from the
persistent compilation cache, which the window's step filled), and each
device op is looked up by its instruction name, opcode and result shape.
Ops of the engine's other programs (admission splice, device books,
retirement summary) are not in the step and are counted apart.  A scope
is matched as a whole name: ``pca.re`` names no scope.  The ops of a
loop's body show as events inside the loop's own event: only the
outermost event of each stretch is counted, so the scopes' seconds add
up to the busy time.
"""

from __future__ import annotations

import re
import time

SCOPE_NAME = re.compile(r"(?<![\w.])pca\.[A-Za-z_]+(?![\w.])")
UNSCOPED = "(unscoped)"
OTHER = "(other programs)"

_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?(%\S+) = (.*?) ([a-z][\w\-]*)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}|/\*[^*]*\*/|\s")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')


def _key(text: str) -> tuple | None:
    """(name, opcode, result shape without layouts) of an HLO instruction
    as the compiled text or a trace event prints it, or None."""
    m = _INSTRUCTION.match(text)
    if m is None:
        return None
    name, shape, opcode = m.groups()
    return name, opcode, _LAYOUT.sub("", shape)


def op_names(hlo_text: str) -> dict[tuple, str]:
    """Instruction key -> ``op_name`` (empty where it has none) of every
    instruction of a compiled module's text."""
    out = {}
    for line in hlo_text.splitlines():
        key = _key(line)
        if key is not None:
            name = _OP_NAME.search(line)
            out[key] = name.group(1) if name else ""
    return out


def step_op_names(v) -> dict[tuple, str]:
    """``op_names`` of the engine's compiled chunk step, computed once per
    run (kept on the view that every reader of the run shares)."""
    cached = getattr(v, "_step_op_names", None)
    if cached is None:
        import jax.numpy as jnp
        import numpy as np
        from repro.streaming.driver import engine_chunk_step_fn
        t0 = time.perf_counter()
        eng = v.run.eng
        x = jnp.asarray(np.zeros((eng.slots, eng.chunk, v.run.n, eng.cfg.p),
                                 np.float32))
        rv = jnp.asarray(np.zeros((eng.slots, eng.chunk), np.float32))
        compiled = engine_chunk_step_fn(eng.cfg).lower(eng.states, x,
                                                       rv).compile()
        cached = op_names(compiled.as_text())
        print(f"scopes: {len(cached)} instructions of the chunk step read "
              f"in {time.perf_counter() - t0!r} s", flush=True)
        v._step_op_names = cached
    return cached


def outermost(ops: list) -> list:
    """The ops not inside another op's event on the same device."""
    out, end = [], {}
    for e in sorted(ops, key=lambda e: (e.plane, e.start_ns, -e.end_ns)):
        if e.end_ns > end.get(e.plane, float("-inf")):
            out.append(e)
            end[e.plane] = e.end_ns
    return out


def scope_of(event, names: dict[tuple, str]) -> str:
    """The innermost named scope of a device op, ``UNSCOPED`` for a step
    op outside all of them, ``OTHER`` for an op the step does not have."""
    op_name = names.get(_key(event.name))
    if op_name is None:
        return OTHER
    found = SCOPE_NAME.findall(op_name)
    return found[-1] if found else UNSCOPED


def table(reduced, names: dict[tuple, str]) -> dict[str, float]:
    """Device seconds per chip under each innermost named scope, with the
    step's unscoped ops and the other programs' ops apart."""
    total: dict[str, float] = {}
    for e in outermost(reduced.ops):
        key = scope_of(e, names)
        total[key] = total.get(key, 0.0) + e.dur_ns * 1e-9 / reduced.chips
    return total


def seconds(reduced, names: dict[tuple, str], scope: str) -> float:
    """Device seconds per chip of the ops whose ``op_name`` holds the named
    scope ``scope`` anywhere on its path."""
    whole = re.compile(rf"(?<![\w.]){re.escape(scope)}(?![\w.])")
    return sum(e.dur_ns for e in outermost(reduced.ops)
               if whole.search(names.get(_key(e.name), ""))) \
        * 1e-9 / reduced.chips


def line(reduced, names: dict[tuple, str]) -> str:
    """The per-scope table on one line, with the window's busy time."""
    parts = [f"{name} {s!r}" for name, s in sorted(
        table(reduced, names).items(), key=lambda kv: -kv[1])]
    return (f"device seconds per scope: {', '.join(parts) or 'no ops'}; "
            f"busy {reduced.busy_s!r} s")


def ms_per_folded_step(v, scope: str) -> float | None:
    """Device milliseconds under the named ``scope`` per engine step that
    folded anything, or None where no op carries it (a program without
    the scope, or no trace)."""
    steps = [r for r in v.run.window_steps if r.live > 0]
    if v.reduced is None or not v.reduced.ops or not steps:
        return None
    s = seconds(v.reduced, step_op_names(v), scope)
    return 1e3 * s / len(steps) if s > 0 else None
