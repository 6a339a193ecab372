"""Region merge (streaming/hierarchy.py): device milliseconds of the ops
under the fleet call's ``pca.merge`` scope (the regions' energy records,
the all-gather and psums, the top-q_fleet selection) per fleet call, from
the trace, averaged over the chips."""

from harness import fleet_scopes


def read(v):
    return fleet_scopes.ms_per_call(v, "pca.merge")
