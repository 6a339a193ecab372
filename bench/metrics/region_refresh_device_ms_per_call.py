"""Drift probe and refresh in the region fleet (streaming/scheduler.py):
device milliseconds of the ops under the fleet call's ``pca.refresh``
scope (the orthogonal iteration, which under the batch path's vmap every
region pays at every decision) per fleet call, from the trace, averaged
over the chips.  Also prints the call's device seconds per scope."""

from harness import fleet_scopes


def read(v):
    value = fleet_scopes.ms_per_call(v, "pca.refresh")
    seconds = fleet_scopes.call_seconds(v)
    if seconds is not None:
        print(fleet_scopes.line(v.reduced, seconds), flush=True)
    return value
