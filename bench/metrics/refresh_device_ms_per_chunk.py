"""Drift probe and refresh (streaming/scheduler.py): device milliseconds of
the ops under the program's ``pca.refresh`` scope (the orthogonal
iteration, its ordering and its retained fraction, which under vmap every
slot pays at every decision) per engine step that folded anything, from
the trace.  Also prints the window's device seconds per scope."""

from harness import scopes


def read(v):
    value = scopes.ms_per_folded_step(v, "pca.refresh")
    if v.reduced is not None and v.reduced.ops:
        print(scopes.line(v.reduced, scopes.step_op_names(v)), flush=True)
    return value
