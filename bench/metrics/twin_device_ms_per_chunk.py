"""Drift probe and refresh, its stage twin (streaming/driver.py): device
milliseconds of the ops under the program's ``pca.twin`` scope (the pure-jnp
recompute of the stages against a refreshed basis, which under vmap runs
whether or not any slot fired) per engine step that folded anything, from
the trace."""

from harness import scopes


def read(v):
    return scopes.ms_per_folded_step(v, "pca.twin")
