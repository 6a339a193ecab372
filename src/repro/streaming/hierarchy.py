"""Hierarchical two-level streaming decomposition (DESIGN.md Sec. 13).

The paper distributes power iteration *inside* one sensor network; at the
million-sensor scale of ``configs/wsn_1m.py`` the fleet itself needs a second
level.  Following Wiesel & Hero's decomposable PCA (the global basis can be
computed per-region and merged) and Elgamal & Hefeeda's observation that
synchronization rounds — not FLOPs — bound distributed PCA at scale
(PAPERS.md), the decomposition is:

* **Level 1 (intra-region, no cross-host traffic):** every region streams its
  own online banded covariance and drift-triggered orthogonal-iteration
  refreshes through the sharded fleet driver
  (:func:`repro.streaming.driver.sharded_stream_run`'s ``shard_map`` of
  :func:`repro.streaming.driver.batched_stream_run` — one fused cov-update
  kernel launch per chunk for all of a shard's regions, and a refresh of
  only the regions whose trigger fired, in buckets; with
  compression/detection stages configured the launch is the Sec.-14
  mega-kernel, and
  ``StreamConfig.fused`` / ``StreamConfig.precision`` thread through each
  region's chunk body unchanged — the hierarchy adds no split/fused logic
  of its own).  Under the banded/local-covariance
  hypothesis a region boundary cuts only the ±h cross terms, so per-region
  bases span the global top-q subspace up to the boundary coupling.
* **Level 2 (cross-host, ONE collective per refresh):** the fleet basis is
  the block-diagonal embedding of per-region components, globally *selected*
  by subspace energy.  Each region contributes its (q_local + 1)-element
  record — the live Rayleigh energies ``diag(W^T C W)`` plus its trace
  partial — via ``all_gather``/``psum`` over the ``region`` mesh axis;
  the top ``q_fleet`` components by energy form the fleet basis, and the fleet
  retained fraction is ``sum(selected energies) / psum(trace partials)``.

The merge's packet bill is booked against the Table-1 accounting exactly
like intra-network rounds: one region-level aggregation epoch of a
(q_local + 1)-record per merge (:func:`repro.core.costs.merge_round_cost`),
lossy-scaled by the same ARQ expectation as every other packet.  One merge
is booked per decision boundary at which ANY region refreshed its basis
(a fleet whose regions never refresh pays for exactly one merge — the final
one that produced the returned basis).

With ``regions=1`` the hierarchy is the flat driver bit-exactly (one region
IS the whole fleet; the merge selects the identity) — the differential
anchor in tests/test_hierarchy.py.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import costs
from repro.core.covariance import banded_matmul_ref
from repro.streaming.driver import (RoundMetrics, StreamConfig, StreamState,
                                    _sharded_fleet_run, batched_stream_init)
from repro.streaming.online_cov import online_estimate, online_total_variance

__all__ = ["FleetBasis", "FleetMerge", "region_energies", "merge_fleet",
           "fleet_basis_dense", "hierarchical_stream_init",
           "hierarchical_stream_run"]


class FleetBasis(NamedTuple):
    """The fleet-level basis in compact (region, column) form.

    Component ``j`` of the fleet basis is column ``col[j]`` of region
    ``region[j]``'s local basis, embedded at that region's sensor offset —
    the block-diagonal structure of the decomposable merge means the dense
    (p_fleet, q_fleet) form (:func:`fleet_basis_dense`) is orthonormal by
    construction (disjoint supports, orthonormal within each region) and
    never needs to exist on any single host.
    """

    region: jnp.ndarray          # (q_fleet,) int32 owning region per component
    col: jnp.ndarray             # (q_fleet,) int32 column within that region
    lam: jnp.ndarray             # (q_fleet,) subspace energies, descending
    rho: jnp.ndarray             # () fleet retained fraction of the selection
    lam_table: jnp.ndarray       # (regions, q_local) gathered energy records
    total_variance: jnp.ndarray  # () psum of per-region trace partials


class FleetMerge(NamedTuple):
    """Level-2 output of a hierarchical run: basis + merge accounting."""

    basis: FleetBasis
    merge_epochs: jnp.ndarray    # () int32 cross-host merges performed
    merge_packets: jnp.ndarray   # () region-head Table-1 bill, lossy-scaled


def region_energies(state: StreamState) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The (q_local,) live subspace energies ``diag(W^T C W)`` of a region's
    basis against its online covariance estimate, plus the region's trace
    partial — the exact record one region head sends up the region tree
    (the per-component split of the drift probe's ``(q+1)``-record).
    """
    band = online_estimate(state.cov)
    W = state.sched.W
    lam = jnp.sum(W * banded_matmul_ref(band, W), axis=0)
    return lam, online_total_variance(state.cov)


def merge_fleet(lam_table: jnp.ndarray, total_variance: jnp.ndarray,
                q_fleet: int) -> FleetBasis:
    """Select the global top-``q_fleet`` components by subspace energy.

    ``lam_table`` is the (regions, q_local) gathered energy records;
    ``total_variance`` the psum of trace partials.  Pure jnp and replicated:
    after the all_gather every shard computes the identical selection, so
    the F flood one level down is a single scalar (the energy threshold).
    """
    n_regions, q_local = lam_table.shape
    if q_fleet > n_regions * q_local:
        raise ValueError(
            f"q_fleet={q_fleet} > regions*q_local={n_regions * q_local}")
    flat = lam_table.reshape(-1)
    order = jnp.argsort(-flat)[:q_fleet]
    lam = flat[order]
    return FleetBasis(
        region=(order // q_local).astype(jnp.int32),
        col=(order % q_local).astype(jnp.int32),
        lam=lam,
        rho=jnp.sum(lam) / jnp.maximum(total_variance, 1e-30),
        lam_table=lam_table,
        total_variance=total_variance,
    )


def fleet_basis_dense(basis: FleetBasis,
                      W_regions: jnp.ndarray) -> jnp.ndarray:
    """Materialize the (p_fleet, q_fleet) block-embedded fleet basis.

    ``W_regions`` is the (regions, p_region, q_local) stack of local bases.
    Test/small-fleet utility: at p=1M the compact form is the deployment
    artifact and this dense embed never leaves the differential suite.
    """
    n_regions, p_region, _ = W_regions.shape
    q_fleet = basis.region.shape[0]
    cols = W_regions[basis.region, :, basis.col]          # (q_fleet, p_region)
    dense = jnp.zeros((q_fleet, n_regions * p_region), cols.dtype)
    idx = (basis.region * p_region)[:, None] + jnp.arange(p_region)[None, :]
    dense = dense.at[jnp.arange(q_fleet)[:, None], idx].set(cols)
    return dense.T


def hierarchical_stream_init(cfg: StreamConfig, key: jax.Array,
                             n_regions: int,
                             dtype=jnp.float32) -> StreamState:
    """Per-region states stacked on a leading regions axis (``cfg.p`` is the
    per-REGION sensor count; the fleet has ``n_regions * cfg.p`` sensors)."""
    return batched_stream_init(cfg, key, n_regions, dtype=dtype)


@functools.partial(jax.jit, static_argnums=(0, 1),
                   static_argnames=("q_fleet", "c_regions", "axis", "chunk",
                                    "probe_every"))
def hierarchical_stream_run(cfg: StreamConfig, mesh, states: StreamState,
                            xs: jnp.ndarray,
                            masks: jnp.ndarray | None = None, *,
                            q_fleet: int | None = None,
                            c_regions: int | None = None,
                            axis: str = "region",
                            chunk: int = 1,
                            probe_every: int | None = None,
                            ) -> tuple[StreamState, RoundMetrics, FleetMerge]:
    """Two-level run: per-region streaming + one cross-host fleet merge.

    ``xs`` is (regions, rounds, n, p_region); ``masks`` the optional
    (regions, rounds, p_region) liveness schedule.  Level 1 is
    :func:`~repro.streaming.driver.sharded_stream_run` over mesh axis
    ``axis``: each shard streams its local regions through
    :func:`~repro.streaming.driver.batched_stream_run` (``chunk`` and
    ``probe_every`` threaded through) with NO cross-shard traffic.  Then,
    inside the same ``shard_map``, the merge runs as the run's only
    collectives: one tiled
    ``all_gather`` of the (q_local,) energy records and one ``psum`` call
    over the trace partials and the refresh-boundary flags, which JAX
    binds as one psum per operand (contract ``hierarchy.refresh`` pins
    this budget at the jaxpr level — see the registration at the bottom of
    this module).  The merge (the energy records, the collectives and the
    top-``q_fleet`` selection) runs under the named scope ``pca.merge``,
    which a device trace carries in each op's ``op_name`` path beside the
    chunk body's ``pca.*`` scopes.

    Returns ``(final_states, metrics, fleet)`` where states/metrics are the
    per-region leaves of the flat driver (regions-leading) and ``fleet``
    carries the merged basis plus the merge's Table-1 bill: one
    (q_local + 1)-record region-tree epoch per decision boundary at which
    any region refreshed (min. one — the final merge), at fan-out
    ``c_regions`` (default ``cfg.c_max``), ARQ-scaled like every
    intra-network packet.
    """
    from jax.sharding import PartitionSpec

    n_regions = xs.shape[0]
    qf = cfg.q if q_fleet is None else q_fleet
    cr = cfg.c_max if c_regions is None else c_regions
    if qf > n_regions * cfg.q:
        raise ValueError(f"q_fleet={qf} > regions*q_local="
                         f"{n_regions * cfg.q}")
    merge_price = costs.lossy_merge_cost(
        cfg.q, cr, cfg.link_loss, cfg.max_retries).communication

    def merge(fin, metrics):
        with jax.named_scope("pca.merge"):
            # level-2 records: per-region energies + trace partials
            lam_l, den_l = jax.vmap(region_energies)(fin)
            lam_table = jax.lax.all_gather(lam_l, axis, tiled=True)
            # one psum call over the trace partials and the per-boundary
            # refresh flags (JAX binds it leaf by leaf: two psum
            # equations); the flags' fleet-wide sum books one merge per
            # decision boundary at which ANY region refreshed, plus the
            # final merge when none fired
            total_var, fired = jax.lax.psum(
                (jnp.sum(den_l),
                 jnp.sum(metrics.did_refresh.astype(jnp.float32), axis=0)),
                axis)
            basis = merge_fleet(lam_table, total_var, qf)
        merges = jnp.maximum(jnp.sum(fired > 0), 1).astype(jnp.int32)
        return FleetMerge(basis=basis, merge_epochs=merges,
                          merge_packets=merges * jnp.asarray(merge_price,
                                                             jnp.float32))

    rep = PartitionSpec()
    fleet_specs = FleetMerge(
        basis=FleetBasis(region=rep, col=rep, lam=rep, rho=rep,
                         lam_table=rep, total_variance=rep),
        merge_epochs=rep, merge_packets=rep)
    return _sharded_fleet_run(cfg, mesh, states, xs, masks, axis=axis,
                              chunk=chunk, probe_every=probe_every,
                              merge=merge, merge_specs=fleet_specs)


# ===========================================================================
# Program contract (repro.analysis; DESIGN.md Sec. 15): the PR 6 headline —
# "ONE collective merge per refresh" — pinned at the jaxpr level.
# ===========================================================================
from repro.analysis import contracts as _contracts  # noqa: E402
from repro.analysis import jaxpr_lint as _jl        # noqa: E402
from repro.analysis import resources as _res        # noqa: E402

_CONTRACT_Q = 2                  # q_local of the traced contract config


def _trace_hierarchy_refresh():
    """Trace the two-level run on a 1-device ``region`` mesh (sub-jaxpr
    structure is mesh-size independent; the collectives appear either way)."""
    from repro.launch.mesh import make_fleet_mesh

    cfg = StreamConfig(p=8, q=_CONTRACT_Q, halfwidth=1, warmup_rounds=2)
    mesh = make_fleet_mesh(region=1, data=1)
    states = hierarchical_stream_init(cfg, jax.random.PRNGKey(0), 2)
    xs = jnp.zeros((2, 4, 4, cfg.p), jnp.float32)
    jx = jax.make_jaxpr(
        lambda s, x: hierarchical_stream_run(cfg, mesh, s, x,
                                             chunk=2))(states, xs)
    return {"regions=2": jx}


_contracts.register(_contracts.Contract(
    id="hierarchy.refresh",
    where="repro.streaming.hierarchy.hierarchical_stream_run",
    claim="exactly one all_gather and two psums (trace partial, refresh "
          "flags) on the 'region' axis per run, none inside loop bodies, "
          "no other cross-host collectives anywhere",
    trace=_trace_hierarchy_refresh,
    rules=(_jl.CollectiveBudget(axis="region",
                                budgets=(("all_gather", 1), ("psum", 2))),
           _jl.ForbidInLoops(),
           _jl.NoF64(),
           _res.VmemBudget(),
           # booked == traced: the merge collectives must put exactly the
           # (q+1)-element record merge_round_cost bills on the wire —
           # q gathered energies + the psum'd trace partial (the fired
           # flags ride the same psum as declared bookkeeping)
           _res.WireBytesBudget(
               axis="region",
               record_elems=costs.merge_record_elems(_CONTRACT_Q))),
))
