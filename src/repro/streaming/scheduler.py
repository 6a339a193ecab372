"""Recompute scheduler: drift-triggered basis refreshes (DESIGN.md Sec. 8.2).

The paper refreshes principal components by rerunning the whole PIM pipeline;
on a live stream that is the single most expensive decision the system makes
(Table 1: the eigenvector phase dominates communication).  The scheduler
amortizes it: every round it evaluates the *retained-variance drift* of the
current basis against the live covariance estimate,

    rho(W, C) = trace(W^T C W) / trace(C)            (Eq. 4 on the live C)
    drift     = rho_at_last_refresh - rho(W, C_now)

and only past a configurable threshold recomputes the basis — a fixed-length
blocked orthogonal iteration (EXPERIMENTS.md Sec. Beyond-paper) warm-started
from the stale basis.  Each refresh books its paper-style communication cost
through :func:`repro.core.costs.streaming_refresh_cost` so benchmarks can
report accuracy-vs-communication exactly like Fig. 9/14.

Everything is jittable.  :meth:`RecomputeScheduler.step` is one network's
decision: :meth:`~RecomputeScheduler.decide` (the drift probe and the
trigger), then a ``lax.cond`` on the trigger between
:meth:`~RecomputeScheduler.refreshed` and the state as it was.  Only the
single-network drivers take that path; they are the reference the fleet
is tested against.  Every fleet step (the serving engine's, and the
batched, sharded and hierarchical drivers') instead decides for every
network and then runs :meth:`~RecomputeScheduler.refresh_fired`: the
refresh of only the networks that fired, in fixed-size buckets
(:func:`bucket_size`).  Under ``vmap`` the cond would lower to a select
that evaluates the refresh for every network, so no fleet driver vmaps
``step``.  The cost model, not XLA, is the source of truth for what a WSN
would pay.  The drift probe and the refresh run under the named scopes
``pca.probe`` and ``pca.refresh``, so a device trace gives each its own
time.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import costs
from repro.core.covariance import banded_matmul_ref
from repro.streaming.online_cov import (OnlineCovariance, online_estimate,
                                        online_total_variance)

__all__ = ["RecomputeScheduler", "SchedulerState", "retained_fraction",
           "ortho_refresh", "ortho_refresh_evals", "bucket_size"]


def retained_fraction(band_est: jnp.ndarray, W: jnp.ndarray,
                      total_variance: jnp.ndarray) -> jnp.ndarray:
    """rho = trace(W^T C W) / trace(C) for an orthonormal basis W.

    In the WSN reading this is one aggregation of a (q+1)-element record
    (per-node partial trace + partial variance); the cost is booked by
    :func:`repro.core.costs.streaming_round_cost`.
    """
    cw = banded_matmul_ref(band_est, W)
    num = jnp.sum(W * cw)
    return num / jnp.maximum(total_variance, 1e-30)


def ortho_refresh_evals(band_est: jnp.ndarray, W0: jnp.ndarray,
                        iters: int, eps: float = 1e-8,
                        ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fixed-length blocked orthogonal iteration, warm-started from W0.

    A ``fori_loop`` (static trip count) rather than the convergence
    ``while_loop`` of :func:`repro.core.power_iteration.orthogonal_iteration`:
    the scheduler's refresh must be vmappable across networks with a
    deterministic per-refresh cost, and the warm start means a handful of
    iterations track a slowly rotating subspace (EXPERIMENTS.md Sec.
    Streaming).  Orthonormalization is the replicated-Cholesky ``inv(L)^T``
    form (EXPERIMENTS.md Sec. Perf hillclimb 1).

    Returns ``(W, evals)``: the ordered orthonormal basis AND the Rayleigh
    quotients (descending) of its columns against the live band.  The
    ordering step computes these eigenvalue estimates anyway; keeping them
    (instead of discarding them, as the pre-detection code did) is what
    feeds the event tier's per-component variance estimates λ̂ — in the WSN
    reading they are the q scalars the refresh flood already carries.
    """
    q = W0.shape[1]
    eye = eps * jnp.eye(q, dtype=W0.dtype)

    def orthonormalize(V):
        G = V.T @ V
        L = jnp.linalg.cholesky(G + eye)
        return V @ jnp.linalg.inv(L).T

    def body(_, V):
        return orthonormalize(banded_matmul_ref(band_est, V))

    V = jax.lax.fori_loop(0, iters, body, orthonormalize(W0))
    # order by Rayleigh quotient (replicated q x q solve)
    H = V.T @ banded_matmul_ref(band_est, V)
    evals, U = jnp.linalg.eigh(H)
    order = jnp.argsort(-evals)
    return V @ U[:, order], evals[order]


def ortho_refresh(band_est: jnp.ndarray, W0: jnp.ndarray,
                  iters: int, eps: float = 1e-8) -> jnp.ndarray:
    """Basis-only form of :func:`ortho_refresh_evals` (kept as the public
    refresh entrypoint for callers that do not track eigenvalues)."""
    return ortho_refresh_evals(band_est, W0, iters, eps)[0]


class SchedulerState(NamedTuple):
    W: jnp.ndarray            # (p, q) current orthonormal basis
    rho_ref: jnp.ndarray      # () retained fraction measured at last refresh
    refreshes: jnp.ndarray    # () int32 — number of refreshes triggered
    comm_packets: jnp.ndarray  # () accumulated communication (packets)
    lam: jnp.ndarray          # (q,) per-component variance estimates λ̂
    #                           (Rayleigh quotients at the last refresh;
    #                           ones before the first — consumers clamp)


def bucket_size(slots: int) -> int:
    """Slots refreshed per pass of :meth:`RecomputeScheduler.refresh_fired`
    in a fleet of ``slots``: a sixteenth of the fleet, at least 8, rounded
    up to a multiple of 8 and at most the fleet.  A pass costs about what
    B slots' refreshes cost, so a step pays ``ceil(fired / B)`` of them:
    one or two at the usual fired share of about a tenth, S/B when every
    slot fires.  On a v5e at 256 slots of the wsn-1m region shape a
    sixteenth beat an eighth and a quarter (PERF.md Sec. 6)."""
    return min(slots, -(-max(8, slots // 16) // 8) * 8)


@dataclasses.dataclass(frozen=True)
class RecomputeScheduler:
    """Policy + cost parameters (static; the state is the pytree above).

    Parameters
    ----------
    q: number of principal components maintained.
    drift_threshold: refresh when retained variance has dropped this much
        (absolute fraction) since the last refresh.
    refresh_iters: orthogonal-iteration length per refresh (fixed).
    warmup_rounds: no refresh before this many rounds (the covariance needs
        an effective window before the estimate is meaningful); the FIRST
        refresh after warmup is unconditional (the initial basis is random).
    n_max, c_max: WSN topology constants for the Table-1 cost model.
    link_loss, max_retries: per-hop packet-loss model — every booked packet
        is scaled by the expected ARQ transmissions
        (:func:`repro.core.costs.lossy_round_cost`); zero loss books the
        reliable Table-1 figures exactly.
    """

    q: int
    drift_threshold: float = 0.02
    refresh_iters: int = 8
    warmup_rounds: int = 10
    n_max: int = 8
    c_max: int = 4
    link_loss: float = 0.0
    max_retries: int = 3

    def init(self, p: int, key: jax.Array, dtype=jnp.float32) -> SchedulerState:
        W0 = jnp.linalg.qr(jax.random.normal(key, (p, self.q), dtype))[0]
        return SchedulerState(
            W=W0,
            rho_ref=jnp.zeros((), dtype),
            refreshes=jnp.zeros((), jnp.int32),
            comm_packets=jnp.zeros((), dtype),
            lam=jnp.ones((self.q,), dtype),
        )

    def round_cost(self) -> float:
        return costs.lossy_round_cost(
            self.n_max, self.q, self.c_max,
            self.link_loss, self.max_retries).communication

    def refresh_cost(self, p: int) -> float:
        return costs.lossy_refresh_cost(
            p, self.q, self.n_max, self.c_max, self.refresh_iters,
            self.link_loss, self.max_retries).communication

    def _probe(self, state: SchedulerState, cov_state: OnlineCovariance):
        with jax.named_scope("pca.probe"):
            band_est = online_estimate(cov_state)
            total_var = online_total_variance(cov_state)
            rho = retained_fraction(band_est, state.W, total_var)
        return band_est, total_var, rho

    def _trigger(self, state: SchedulerState, rho: jnp.ndarray,
                 round_index: jnp.ndarray, churn) -> jnp.ndarray:
        past_warmup = round_index >= self.warmup_rounds
        never_fit = state.refreshes == 0
        drifted = (state.rho_ref - rho) > self.drift_threshold
        return past_warmup & (never_fit | drifted | jnp.asarray(churn))

    def _refresh(self, state: SchedulerState, band_est: jnp.ndarray,
                 total_var: jnp.ndarray) -> SchedulerState:
        W_new, lam_new = ortho_refresh_evals(band_est, state.W,
                                             self.refresh_iters)
        rho_new = retained_fraction(band_est, W_new, total_var)
        return SchedulerState(
            W=W_new,
            rho_ref=rho_new,
            refreshes=state.refreshes + 1,
            comm_packets=state.comm_packets
            + self.refresh_cost(state.W.shape[0]),
            lam=lam_new,
        )

    def decide(self, state: SchedulerState, cov_state: OnlineCovariance,
               round_index: jnp.ndarray, churn: jnp.ndarray | bool = False,
               ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """The drift probe and the trigger of :meth:`step`, without the
        refresh: ``(rho, trigger)``."""
        rho = self._probe(state, cov_state)[2]
        return rho, self._trigger(state, rho, round_index, churn)

    def refreshed(self, state: SchedulerState,
                  cov_state: OnlineCovariance) -> SchedulerState:
        """The state of one network after a refresh against its live
        covariance: the new basis and λ̂, the retained fraction it measures,
        one more refresh and its bill (no round cost: :meth:`book_round`)."""
        with jax.named_scope("pca.refresh"):
            return self._refresh(state, online_estimate(cov_state),
                                 online_total_variance(cov_state))

    def book_round(self, state: SchedulerState) -> SchedulerState:
        """Book one round's drift record."""
        return state._replace(
            comm_packets=state.comm_packets + self.round_cost())

    def step(self, state: SchedulerState, cov_state: OnlineCovariance,
             round_index: jnp.ndarray,
             churn: jnp.ndarray | bool = False,
             ) -> tuple[SchedulerState, jnp.ndarray, jnp.ndarray]:
        """One scheduling decision against the live covariance, for the
        single-network drivers (the fleet steps run :meth:`decide` and
        :meth:`refresh_fired`).

        Returns ``(new_state, rho, did_refresh)`` where ``rho`` is the
        retained fraction of the basis in effect *before* any refresh (the
        quantity the trigger saw).

        ``churn`` flags a topology change this round (node death/revival,
        see DESIGN.md Sec. 9): the live covariance's support just moved, so
        drift is certain — the scheduler treats churn as an unconditional
        trigger (after warmup) instead of waiting for the retained-variance
        estimate to catch up over the forgetting window.
        """
        band_est, total_var, rho = self._probe(state, cov_state)
        trigger = self._trigger(state, rho, round_index, churn)

        @jax.named_scope("pca.refresh")
        def do_refresh(_):
            return self._refresh(state, band_est, total_var)

        def keep(_):
            return state

        new_state = jax.lax.cond(trigger, do_refresh, keep, operand=None)
        return self.book_round(new_state), rho, trigger

    def refresh_fired(self, states: SchedulerState,
                      cov_states: OnlineCovariance, fired: jnp.ndarray,
                      ) -> tuple[SchedulerState, jnp.ndarray]:
        """:meth:`refreshed` for the slots of a fleet whose trigger fired,
        and for no other: the fleet-level refresh of every fleet chunk
        step (the serving engine's and the batch drivers').

        ``states``/``cov_states`` carry a leading slots axis S and ``fired``
        is the (S,) trigger.  The slot indices are ordered fired first, and
        a ``while_loop`` of ``ceil(n_fired / B)`` passes (B =
        :func:`bucket_size` of S) gathers the next B of them, refreshes them
        under ``vmap`` and scatters them back; entries past ``n_fired`` in
        the last pass (slots that did not fire, or padding) are computed and
        dropped (an out-of-range scatter index), so a slot that did not fire
        keeps its state bit for bit.
        The pass count never leaves the device.  Returns the new states and
        the slots computed (passes × B, an int32 scalar).  No round cost is
        booked (:meth:`book_round`).
        """
        S = fired.shape[0]
        B = bucket_size(S)
        passes_max = -(-S // B)
        with jax.named_scope("pca.refresh"):
            order = jnp.pad(jnp.argsort(jnp.logical_not(fired), stable=True),
                            (0, passes_max * B - S))
            n_fired = jnp.sum(fired, dtype=jnp.int32)
            passes = (n_fired + (B - 1)) // B

            def one_pass(carry):
                i, out = carry
                idx = jax.lax.dynamic_slice(order, (i * B,), (B,))
                part = jax.vmap(self.refreshed)(
                    jax.tree.map(lambda a: a[idx], states),
                    jax.tree.map(lambda a: a[idx], cov_states))
                dst = jnp.where(i * B + jnp.arange(B) < n_fired, idx, S)
                out = jax.tree.map(
                    lambda full, new: full.at[dst].set(new, mode="drop"),
                    out, part)
                return i + 1, out

            _, new_states = jax.lax.while_loop(
                lambda carry: carry[0] < passes, one_pass,
                (jnp.zeros((), jnp.int32), states))
        return new_states, passes * B
