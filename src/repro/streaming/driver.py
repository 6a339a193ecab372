"""Stream drivers: single network, batched fleet, shard_map-sharded fleet.

Layering (DESIGN.md Sec. 8.3):

* :func:`stream_step` — one round for ONE network: fold the round's
  measurements into the online covariance (Pallas cov-update kernel), then
  one scheduler decision (drift probe + possible basis refresh).
* :func:`stream_run` — ``lax.scan`` of the step over a (rounds, n, p) stream;
  this is the jittable single-network driver.
* :func:`chunk_stream_step` / :func:`chunked_stream_run` — the
  chunk-granular forms (DESIGN.md Sec. 12): K rounds per dispatch through
  the fused multi-round cov-update kernel, one scheduler decision per
  ``probe_every`` rounds, per-epoch cost booking kept exact.
  ``probe_every=1`` is bit-identical to the per-round driver.
  These four are the per-network reference that the tests hold the fleet
  body against.
* :func:`_fleet_chunk_step` — one chunk for a whole fleet: the chunk body
  split at the decision, with the refresh computed only for the networks
  that fired (:meth:`repro.streaming.scheduler.RecomputeScheduler.
  refresh_fired`) instead of a select over the whole fleet.  Every fleet
  driver runs it.
* :func:`batched_stream_run` — hundreds of independent sensor networks
  stream concurrently in one program, over a leading networks axis: a
  ``lax.scan`` of :func:`_fleet_chunk_step` over the decision steps
  (``chunk`` rounds each, one by default), so a decision refreshes only
  the networks that fired.  The *booked* WSN cost stays per-network exact.
* :func:`engine_chunk_step_fn` — the serving engine's jitted step:
  :func:`_fleet_chunk_step` with the fleet state donated.
* :func:`sharded_stream_run` — the batched run inside ``shard_map`` with the
  networks axis split over a mesh axis
  (:func:`repro.distributed.sharding.network_axis_spec`); per-network state
  never crosses devices, so the fleet scales linearly with chips.
* :func:`repro.streaming.hierarchy.hierarchical_stream_run` — the two-level
  fleet form (DESIGN.md Sec. 13): the sharded run over a cross-host
  ``region`` mesh axis (its level 1), topped by one ``all_gather``/``psum``
  merge of region bases per refresh — the million-sensor shape where every
  band fold stays a local problem and only (q+1)-element energy records
  ever cross hosts.

With ``StreamConfig.compression`` set, every round additionally runs the
ε-supervised compression stage (:mod:`repro.streaming.compressor`) against
the slot's current basis: the fused Pallas kernel emits the scores the
sink decodes, the ε-true sink view, and the notification mask, and the
Sec.-2.4.1 packet bill (scores A + feedback F + flagged raws, lossy-scaled)
is booked into the same per-network communication account as the
scheduler's Table-1 costs.

With ``StreamConfig.detection`` set, every round also runs the T²/SPE
event-detection stage (:mod:`repro.streaming.detector`) against the same
live basis and the scheduler's per-component variance estimates λ̂: the
fused Pallas monitoring pass emits the two per-epoch statistics, the
detector thresholds (recalibrated over a healthy window after every
refresh) turn them into alarms, and the Sec.-2.4.3 bill — one extra
scalar on the per-round drift record plus one F alarm flood per alarmed
epoch, lossy-scaled — is booked into the same account.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.faults import expected_transmissions
from repro.kernels import ops
from repro.streaming.compressor import (CompressionConfig, RoundCompression,
                                        compress_round, compression_books,
                                        compression_round_cost,
                                        epoch_packet_split)
from repro.streaming.detector import (DetectionConfig, DetectorState,
                                      RoundDetection, detect_apply,
                                      detect_round, detection_packet_split,
                                      detector_init, inv_lambda,
                                      row_liveness)
from repro.streaming.online_cov import (OnlineCovariance, online_apply_chunk,
                                        online_chunk_stats, online_init,
                                        online_update, online_update_chunk)
from repro.streaming.scheduler import RecomputeScheduler, SchedulerState

__all__ = ["StreamConfig", "StreamState", "RoundMetrics", "stream_init",
           "stream_step", "chunk_stream_step", "engine_chunk_step_fn",
           "stream_run", "chunked_stream_run", "batched_stream_run",
           "sharded_stream_run"]


@dataclasses.dataclass(frozen=True)
class StreamConfig:
    """Static configuration shared by every network of a fleet."""

    p: int                          # sensors per network
    q: int                          # principal components maintained
    halfwidth: int                  # covariance band half-width
    forgetting: float = 1.0         # per-round exponential forgetting factor
    drift_threshold: float = 0.02   # refresh trigger (retained-variance drop)
    refresh_iters: int = 8          # orthogonal-iteration length per refresh
    warmup_rounds: int = 10         # rounds before the first refresh
    n_max: int = 8                  # |N_i*| for the cost model
    c_max: int = 4                  # C_i* for the cost model
    link_loss: float = 0.0          # per-hop packet loss (cost booking)
    max_retries: int = 3            # ARQ retransmission budget per packet
    interpret: bool | None = None   # Pallas interpret override (None = auto)
    compression: CompressionConfig | None = None  # ε-supervised stage
    detection: DetectionConfig | None = None      # T²/SPE monitoring stage
    fused: bool = True              # one-pass mega-kernel on the chunk path
    precision: str = "fp32"         # fused tile-load dtype: "fp32" | "bf16"

    def __post_init__(self):
        if self.precision not in ("fp32", "bf16"):
            raise ValueError(
                f"precision must be 'fp32' or 'bf16', got {self.precision!r}")

    def scheduler(self) -> RecomputeScheduler:
        return RecomputeScheduler(
            q=self.q, drift_threshold=self.drift_threshold,
            refresh_iters=self.refresh_iters,
            warmup_rounds=self.warmup_rounds,
            n_max=self.n_max, c_max=self.c_max,
            link_loss=self.link_loss, max_retries=self.max_retries)


class StreamState(NamedTuple):
    cov: OnlineCovariance
    sched: SchedulerState
    rounds: jnp.ndarray             # () int32 rounds streamed so far
    alive: jnp.ndarray              # (p,) 0/1 liveness seen last round
    det: DetectorState | None = None  # T²/SPE thresholds + healthy window


class RoundMetrics(NamedTuple):
    """Per-round observability record (stacked by scan over time).

    ``compression``/``detection`` are ``None`` when the config carries no
    such stage (None is an empty pytree node, so every variant
    scan/vmap/shards cleanly — the pytree structure is fixed per
    StreamConfig).
    """

    rho: jnp.ndarray                # retained fraction before any refresh
    did_refresh: jnp.ndarray        # bool — scheduler fired this round
    refreshes: jnp.ndarray          # cumulative refresh count
    comm_packets: jnp.ndarray       # cumulative communication (packets)
    compression: RoundCompression | None = None  # ε-supervised output
    detection: RoundDetection | None = None      # T²/SPE monitoring output
    refresh_slots: jnp.ndarray | None = None     # engine fleet step only:
    #                       () int32 slots its bucketed refresh computed


def _metrics_template(cfg: "StreamConfig") -> RoundMetrics:
    """A structure-only RoundMetrics matching cfg (for shard_map out_specs)."""
    comp = None
    if cfg.compression is not None:
        emit = cfg.compression.emit_reconstruction
        comp = RoundCompression(
            z=0, x_sink=0 if emit else None, flagged=0 if emit else None,
            max_err=0, extra_packets=0, score_packets=0,
            feedback_packets=0, bits_on_air=0)
    det = None
    if cfg.detection is not None:
        emit = cfg.detection.emit_statistics
        det = RoundDetection(
            t2=0 if emit else None, spe=0 if emit else None,
            events=0 if emit else None, alarms=0,
            t2_threshold=0, spe_threshold=0, calibrating=0)
    return RoundMetrics(rho=0, did_refresh=0, refreshes=0, comm_packets=0,
                        compression=comp, detection=det)


def _fp32_matmuls(step):
    """Trace ``step`` with every fp32 matmul at full fp32 precision.

    The TPU's default precision rounds fp32 matmul inputs to bf16: on the
    chip that left the refreshed basis ~1e-3 off orthonormal (the
    retained fraction of the wsn-1m region shape moved by up to 1.9e-3)
    and would round the forgetting-weighted mean sums the same way.  On
    the CPU the setting changes nothing.
    """
    @functools.wraps(step)
    def traced(*args, **kwargs):
        with jax.default_matmul_precision("float32"):
            return step(*args, **kwargs)
    return traced


def stream_init(cfg: StreamConfig, key: jax.Array,
                dtype=jnp.float32) -> StreamState:
    return StreamState(
        cov=online_init(cfg.p, cfg.halfwidth, dtype=dtype),
        sched=cfg.scheduler().init(cfg.p, key, dtype=dtype),
        rounds=jnp.zeros((), jnp.int32),
        alive=jnp.ones((cfg.p,), dtype=dtype),
        det=detector_init(dtype) if cfg.detection is not None else None,
    )


@_fp32_matmuls
def stream_step(cfg: StreamConfig, state: StreamState, x_round: jnp.ndarray,
                mask: jnp.ndarray | None = None,
                ) -> tuple[StreamState, RoundMetrics]:
    """One round for one network: covariance fold + scheduling decision.

    ``mask`` is the round's (p,) sensor-liveness vector (1 = alive).  Dead
    sensors contribute no outer products and no mean sums (the masked Pallas
    path in :func:`repro.streaming.online_cov.online_update`), and a change
    of liveness between consecutive rounds — a death or a revival, i.e.
    topology churn — is reported to the scheduler as an unconditional drift
    trigger.  ``mask=None`` is the fault-free path, bit-identical to the
    pre-fault behavior.

    A per-network reference: the fleet drivers run :func:`_fleet_chunk_step`,
    which at K=1 gives the same results (tests/test_chunked_streaming.py).
    """
    if mask is None:
        cov = online_update(state.cov, x_round, forgetting=cfg.forgetting,
                            interpret=cfg.interpret)
        churn = jnp.zeros((), bool)
        alive = state.alive
    else:
        mask = jnp.asarray(mask, dtype=state.alive.dtype)
        cov = online_update(state.cov, x_round, forgetting=cfg.forgetting,
                            mask=mask, interpret=cfg.interpret)
        churn = jnp.any(mask != state.alive)
        alive = mask
    sched, rho, fired = cfg.scheduler().step(state.sched, cov, state.rounds,
                                             churn=churn)
    # live per-sensor mean estimate of the online covariance — normalized
    # by each sensor's OWN effective count (the masked-statistics bugfix:
    # dividing by the round count biased dropout-ridden sensors to zero)
    mean_est = cov.s / jnp.maximum(cov.t_i, 1.0)
    factor = expected_transmissions(cfg.link_loss, cfg.max_retries)
    compression = None
    if cfg.compression is not None:
        # compress this round against the slot's CURRENT basis (post-step W)
        # and the live mean estimate — the same quantities the deployment
        # would have flooded to the nodes
        compression = compress_round(
            sched.W, mean_est, x_round, cfg.compression, cfg.c_max,
            mask=mask, interpret=cfg.interpret)
        # book the Sec.-2.4.1 epoch: scores A + feedback F (with the scale
        # flood at the quantized budget), plus the flagged raws — every
        # packet paying the same expected ARQ retransmissions as the
        # scheduler's bill
        flagfree = compression_round_cost(cfg.q, cfg.c_max, cfg.compression)
        bill = (flagfree + compression.extra_packets) * factor
        sched = sched._replace(comm_packets=sched.comm_packets + bill)
    det_state, detection = state.det, None
    if cfg.detection is not None:
        # monitor this round against the same post-step basis and the
        # scheduler's λ̂; a refresh this round opens a fresh healthy window
        det_state, detection = detect_round(
            sched.W, mean_est, sched.lam, x_round, state.det, cfg.detection,
            refreshed=fired, mask=mask, interpret=cfg.interpret)
        # book the Sec.-2.4.3 epoch: one extra scalar on the per-round
        # (q+1) drift record plus one F alarm flood per alarmed epoch,
        # lossy-scaled like every other packet of the round
        flagfree, per_alarm = detection_packet_split(cfg.q, cfg.c_max)
        bill = (flagfree + detection.alarms * per_alarm) * factor
        sched = sched._replace(comm_packets=sched.comm_packets + bill)
    new = StreamState(cov=cov, sched=sched, rounds=state.rounds + 1,
                      alive=alive, det=det_state)
    metrics = RoundMetrics(rho=rho, did_refresh=fired,
                           refreshes=sched.refreshes,
                           comm_packets=sched.comm_packets,
                           compression=compression,
                           detection=detection)
    return new, metrics


class _Folded(NamedTuple):
    """What the pre-decision half of a chunk (:func:`_chunk_fold`) hands
    the post-decision half (:func:`_chunk_finish`)."""

    cov: OnlineCovariance
    churn: jnp.ndarray              # () bool: liveness changed in the chunk
    alive: jnp.ndarray              # (p,) liveness after the chunk
    mean_est: jnp.ndarray           # (p,) post-fold mean estimate
    mask_view: jnp.ndarray | None   # (K·n, p) epoch liveness of the stages
    stages: tuple                   # fused path: the kernel's (z, x_hat,
    #                                 flags, t2, spe) against the old basis


def _live_rounds(K: int, round_valid):
    """``(rv, live, live_i)``: the chunk's round validity and its count of
    live rounds (the static K when every round is real)."""
    if round_valid is None:
        return None, K, K
    rv = jnp.asarray(round_valid, jnp.float32)
    live = jnp.sum(rv)
    return rv, live, live.astype(jnp.int32)


def _uses_fused(cfg: StreamConfig, masks) -> bool:
    return (cfg.fused
            and (cfg.compression is not None or cfg.detection is not None)
            and (cfg.compression is None or cfg.compression.score_bits == 0)
            and (masks is None or masks.ndim == 2))


def _decision_round(state: StreamState, x_chunk: jnp.ndarray, round_valid):
    """The round a chunk's decision is indexed at: its LAST folded round
    (the same warmup arithmetic the per-round path would apply there)."""
    live_i = _live_rounds(x_chunk.shape[0], round_valid)[2]
    return state.rounds + (live_i - 1)


def _chunk_fold(cfg: StreamConfig, state: StreamState, x_chunk: jnp.ndarray,
                masks: jnp.ndarray | None, round_valid: jnp.ndarray | None,
                ) -> _Folded:
    """The pre-decision half of :func:`chunk_stream_step`: the covariance
    fold (with the fused kernel's speculative stages), the analytic half
    and the churn flag."""
    K, n, p = x_chunk.shape
    if masks is not None:
        masks = jnp.asarray(masks, state.alive.dtype)
    has_stage = cfg.compression is not None or cfg.detection is not None
    use_fused = _uses_fused(cfg, masks)
    rv = _live_rounds(K, round_valid)[0]
    with jax.named_scope("pca.fold"):
        if masks is None:
            churn = jnp.zeros((), bool)
            alive = state.alive
        else:
            churn = jnp.zeros((), bool)
            alive = state.alive
            for t in range(K):                  # static unroll, K is small
                changed = jnp.any(masks[t] != alive)
                if rv is None:
                    churn = churn | changed
                    alive = masks[t]
                else:
                    v_t = rv[t] > 0
                    churn = churn | (v_t & changed)
                    alive = jnp.where(v_t, masks[t], alive)
        # the stages already vectorize over epochs: they see the (K·n, p)
        # chunk view, with pad/idle rounds masked out (a padded epoch is a
        # dead epoch: no record, no flag)
        x_view = x_chunk.reshape(K * n, p)
        mask_view = None
        if has_stage and (masks is not None or rv is not None):
            m3 = jnp.ones((K, n, p), x_view.dtype) if masks is None \
                else jnp.broadcast_to(masks[:, None, :], (K, n, p))
            if rv is not None:
                m3 = m3 * rv[:, None, None].astype(m3.dtype)
            mask_view = m3.reshape(K * n, p)

        stages = ()
        if use_fused:
            with_c = cfg.compression is not None
            with_m = cfg.detection is not None
            # analytic half of the fold first: the kernel needs the POST-fold
            # mean estimate as a stage operand, and s/t_band never touch a
            # kernel (online_apply_chunk shares the arithmetic, so the split
            # path produces the same bits)
            w, beta_eff, delta_s, delta_tb = online_chunk_stats(
                state.cov, x_chunk, forgetting=cfg.forgetting, masks=masks,
                round_valid=round_valid)
            s_new = beta_eff * state.cov.s + delta_s
            t_i_new = (beta_eff * state.cov.t_band + delta_tb)[cfg.halfwidth]
            mean_est = s_new / jnp.maximum(t_i_new, 1.0)
            il = inv_lambda(state.sched.lam, cfg.detection) if with_m \
                else jnp.ones((cfg.q,), jnp.float32)
            eps = cfg.compression.epsilon if with_c else 0.0
            # ONE kernel launch: band fold + stages against the pre-decision
            # basis (== post-decision whenever the scheduler does not fire)
            band_delta, *stages = ops.fused_stream_update(
                x_view, jnp.repeat(w, n), state.sched.W, mean_est, il,
                halfwidth=cfg.halfwidth, epsilon=eps, with_compress=with_c,
                with_monitor=with_m, mask=mask_view, precision=cfg.precision,
                interpret=cfg.interpret)
            stages = tuple(stages)
            cov = online_apply_chunk(state.cov, band_delta, w, beta_eff,
                                     delta_s, delta_tb, n)
        else:
            cov = online_update_chunk(state.cov, x_chunk,
                                      forgetting=cfg.forgetting, masks=masks,
                                      round_valid=round_valid,
                                      interpret=cfg.interpret)
            mean_est = cov.s / jnp.maximum(cov.t_i, 1.0)
    return _Folded(cov=cov, churn=churn, alive=alive, mean_est=mean_est,
                   mask_view=mask_view, stages=stages)


def _chunk_finish(cfg: StreamConfig, state: StreamState,
                  x_chunk: jnp.ndarray, masks: jnp.ndarray | None,
                  round_valid: jnp.ndarray | None, folded: _Folded,
                  sched: SchedulerState, rho: jnp.ndarray,
                  fired: jnp.ndarray) -> tuple[StreamState, RoundMetrics]:
    """The post-decision half of :func:`chunk_stream_step`, given the
    decision (``sched`` with the refresh, if it fired, and one round
    booked): the remaining rounds' records, the stage twin, the books and
    the metrics."""
    K, n, p = x_chunk.shape
    if masks is not None:
        masks = jnp.asarray(masks, state.alive.dtype)
    use_fused = _uses_fused(cfg, masks)
    live, live_i = _live_rounds(K, round_valid)[1:]
    x_view = x_chunk.reshape(K * n, p)
    mean_est, mask_view = folded.mean_est, folded.mask_view
    # step() booked one per-round record; book the chunk's remaining live
    # rounds (static no-op at K=1)
    extra = live - 1
    if not (isinstance(extra, int) and extra == 0):
        sched = sched._replace(
            comm_packets=sched.comm_packets
            + extra * cfg.scheduler().round_cost())
    factor = expected_transmissions(cfg.link_loss, cfg.max_retries)
    z = x_hat = flags = t2 = spe = None
    if use_fused:
        with_c = cfg.compression is not None
        with_m = cfg.detection is not None
        eps = cfg.compression.epsilon if with_c else 0.0

        # the decision fired: the stages must reflect the rotated basis
        # (and its λ̂) — the pure-jnp twin recomputes them bit-identically
        # to what the kernel would produce, without a second pallas_call
        # in the traced body (lax.cond branches both count)
        def _pack(z_, xh_, fl_, t2_, spe_):
            out = [z_]
            if with_c:
                out += [xh_, fl_]
            if with_m:
                out += [t2_, spe_]
            return tuple(out)

        @jax.named_scope("pca.twin")
        def _recompute(_):
            il2 = inv_lambda(sched.lam, cfg.detection) if with_m \
                else jnp.ones((cfg.q,), jnp.float32)
            return _pack(*ops.fused_stream_stages_blocked(
                x_view, sched.W, mean_est, il2, epsilon=eps,
                with_compress=with_c, with_monitor=with_m, mask=mask_view,
                precision=cfg.precision))

        staged = jax.lax.cond(fired, _recompute,
                              lambda _: _pack(*folded.stages),
                              operand=None)
        z = staged[0]
        k_out = 1
        if with_c:
            x_hat, flags = staged[k_out], staged[k_out + 1]
            k_out += 2
        if with_m:
            t2, spe = staged[k_out], staged[k_out + 1]
    # the compression/detection books and their bills (on the split path
    # the stage passes themselves run here too)
    with jax.named_scope("pca.books"):
        compression = None
        if cfg.compression is not None:
            if use_fused:
                mask2d = mask_view if mask_view is not None \
                    else jnp.ones((K * n, p), jnp.float32)
                compression = compression_books(
                    jnp.asarray(x_view, jnp.float32), z, x_hat, flags, mask2d,
                    cfg.compression, cfg.q, cfg.c_max)
            else:
                compression = compress_round(
                    sched.W, mean_est, x_view, cfg.compression, cfg.c_max,
                    mask=mask_view, interpret=cfg.interpret)
            flagfree = compression_round_cost(cfg.q, cfg.c_max,
                                              cfg.compression)
            bill = (flagfree * live + compression.extra_packets) * factor
            sched = sched._replace(comm_packets=sched.comm_packets + bill)
            # compress_round's fixed A/F record (and its bits) covers ONE
            # epoch round; this metrics row covers the chunk's live rounds —
            # scale the per-round constants so the books a consumer sums from
            # metrics (the engine's bits_on_air account) stay per-epoch exact
            # like comm_packets above (static no-op at K=1)
            if not (isinstance(live, int) and live == 1):
                a_pk, f_pk = epoch_packet_split(cfg.q, cfg.c_max,
                                                cfg.compression)
                compression = compression._replace(
                    score_packets=compression.score_packets * live,
                    feedback_packets=compression.feedback_packets * live,
                    bits_on_air=compression.bits_on_air
                    + (live - 1) * (a_pk + f_pk) * cfg.compression.word_bits)
        det_state, detection = state.det, None
        if cfg.detection is not None:
            if use_fused:
                det_state, detection = detect_apply(
                    t2, spe, row_liveness(mask_view, K * n, t2.dtype), cfg.q,
                    state.det, cfg.detection, refreshed=fired)
            else:
                det_state, detection = detect_round(
                    sched.W, mean_est, sched.lam, x_view, state.det,
                    cfg.detection, refreshed=fired, mask=mask_view,
                    interpret=cfg.interpret)
            flagfree, per_alarm = detection_packet_split(cfg.q, cfg.c_max)
            bill = (flagfree * live + detection.alarms * per_alarm) * factor
            sched = sched._replace(comm_packets=sched.comm_packets + bill)
    new = StreamState(cov=folded.cov, sched=sched,
                      rounds=state.rounds + live_i, alive=folded.alive,
                      det=det_state)
    metrics = RoundMetrics(rho=rho, did_refresh=fired,
                           refreshes=sched.refreshes,
                           comm_packets=sched.comm_packets,
                           compression=compression,
                           detection=detection)
    return new, metrics


@_fp32_matmuls
def chunk_stream_step(cfg: StreamConfig, state: StreamState,
                      x_chunk: jnp.ndarray,
                      masks: jnp.ndarray | None = None,
                      round_valid: jnp.ndarray | None = None,
                      ) -> tuple[StreamState, RoundMetrics]:
    """K rounds for one network in ONE dispatch: fused covariance fold of
    the whole (K, n, p) chunk (:func:`online_update_chunk` — one kernel
    launch, one HBM band writeback), then ONE scheduler decision at the
    chunk boundary, then one compression/detection pass over the chunk's
    (K·n, p) epoch view.

    The Table-1 bill stays per-EPOCH exact: the chunk books K per-round
    drift records (and K flag-free compression/monitoring epochs) even
    though only one decision is evaluated — the WSN would still aggregate
    every epoch; only the eigenvector-phase *decisions* are amortized.

    ``masks`` is the chunk's (K, p) per-round liveness schedule; any
    liveness change across the chunk (vs. the state's last-seen liveness)
    raises the scheduler's churn trigger at the boundary.  ``round_valid``
    (K,) flags which rounds are real — 0 rounds (stream tail padding, or
    an engine slot whose stream ends mid-chunk) contribute nothing to the
    fold, the stages, the books, or the round counter.

    At K=1 this is bit-identical to :func:`stream_step` (the chunk kernel
    with weight 1 is the per-round kernel, and every booking term reduces
    to the per-round expression exactly) — the differential guarantee
    behind ``chunked_stream_run(..., probe_every=1)``.

    With a compression and/or detection stage configured, the chunk body
    takes the FUSED path by default (``cfg.fused``; DESIGN.md Sec. 14):
    one mega-kernel (:func:`repro.kernels.ops.fused_stream_update`) loads
    each chunk tile into VMEM once and emits the band delta AND the stage
    outputs — 1 ``pallas_call`` per chunk body instead of 3.  The stages
    are speculated against the pre-decision basis (bit-identical to the
    post-decision basis whenever the scheduler does not fire — no refresh
    path touches a state that did not fire); on the refresh rounds a
    pure-jnp twin (:func:`repro.kernels.ops.fused_stream_stages_blocked`,
    the kernel's stage arithmetic to fp32 rounding) recomputes them
    against the rotated basis under ``lax.cond``.  At fp32 the fused path is bit-identical to
    the split path; ``cfg.precision="bf16"`` halves the kernel's tile
    traffic (fp32 accumulation) at tolerance-level divergence — note the
    ε flag decision then happens in tile precision, so the fp32-measured
    sink error can overshoot ε by the bf16 rounding (~1e-3 relative);
    deployments that need the bound exact in fp32 keep the default
    precision.  Quantized
    compression (``score_bits > 0`` — the quantizer needs the whole
    round's scores between projection and reconstruction) and (K, n, p)
    per-reading dropout masks (their pairwise counts need a second kernel
    pass anyway) keep the split path.

    The fold (with the fused kernel), the stage twin and the books run
    under the named scopes ``pca.fold``, ``pca.twin`` and ``pca.books``
    (the scheduler adds ``pca.probe`` and ``pca.refresh``), which a device
    trace carries in each op's ``op_name`` path.

    The body is two halves around the scheduler's decision:
    :func:`_chunk_fold` (the fold, the analytic half, churn) and
    :func:`_chunk_finish` (the twin, the books, the metrics), joined here
    by :meth:`~repro.streaming.scheduler.RecomputeScheduler.step`'s cond.
    It is the per-network reference: the fleet body
    (:func:`_fleet_chunk_step`, which the engine's step and every batch
    driver run) joins the same halves with a fleet-level refresh of only
    the networks that fired.
    """
    folded = _chunk_fold(cfg, state, x_chunk, masks, round_valid)
    sched, rho, fired = cfg.scheduler().step(
        state.sched, folded.cov, _decision_round(state, x_chunk, round_valid),
        churn=folded.churn)
    return _chunk_finish(cfg, state, x_chunk, masks, round_valid, folded,
                         sched, rho, fired)


@_fp32_matmuls
def _fleet_chunk_step(cfg: StreamConfig, states: StreamState,
                      x: jnp.ndarray, masks: jnp.ndarray | None,
                      rv: jnp.ndarray | None,
                      ) -> tuple[StreamState, RoundMetrics, jnp.ndarray]:
    """One chunk for every network of a fleet: the fleet form of
    :func:`chunk_stream_step`, shared by the engine's step
    (:func:`engine_chunk_step_fn`) and the batch drivers' scan
    (:func:`batched_stream_run`).

    ``states`` carries a leading networks axis S; ``x`` is (S, K, n, p),
    ``masks`` (S, K, p) or None and ``rv`` (S, K) or None.  The body is
    ``vmap`` of the pre-decision half (:func:`_chunk_fold` and the
    scheduler's ``decide``), then the fleet-level
    :meth:`~repro.streaming.scheduler.RecomputeScheduler.refresh_fired`
    (only the networks that fired, in buckets of
    :func:`~repro.streaming.scheduler.bucket_size` of S), then ``vmap`` of
    the post-decision half (:func:`_chunk_finish`).  A network's result
    equals the vmapped :func:`chunk_stream_step`'s, whose cond would lower
    to a select that refreshes every network, up to the rounding of
    batched ``eigh``/``cholesky`` at another batch size; a network that did
    not fire keeps its scheduler state bit for bit.  Returns the new
    states, the metrics and the networks the refresh computed (an int32
    scalar).
    """
    sch = cfg.scheduler()

    def pre(s, xc, m, r):
        folded = _chunk_fold(cfg, s, xc, m, r)
        rho, fired = sch.decide(s.sched, folded.cov,
                                _decision_round(s, xc, r), folded.churn)
        return folded, rho, fired

    def post(s, xc, m, r, folded, sched, rho, fired):
        return _chunk_finish(cfg, s, xc, m, r, folded, sch.book_round(sched),
                             rho, fired)

    folded, rho, fired = jax.vmap(pre)(states, x, masks, rv)
    sched, computed = sch.refresh_fired(states.sched, folded.cov, fired)
    new, metrics = jax.vmap(post)(states, x, masks, rv, folded, sched, rho,
                                  fired)
    return new, metrics, computed


@functools.lru_cache(maxsize=None)
def engine_chunk_step_fn(cfg: StreamConfig, *, masked: bool = False):
    """The serving engine's jitted chunk body (DESIGN.md Sec. 8.2, 17):
    :func:`_fleet_chunk_step` with the stacked fleet state DONATED.  The
    metrics carry ``refresh_slots``, the slots the bucketed refresh
    computed.

    Memoized per (cfg, masked): every engine instance with the same config
    shares ONE jitted callable — and therefore one compilation cache —
    instead of re-tracing per engine (a benchmark sweeping modes would
    otherwise spend most of its wall time compiling identical programs).

    This is the donation-safe consumer of the engine's double-buffered
    staging path: argument 0 (the per-slot state pytree) is donated so XLA
    updates the fleet in place every step, while the staged chunk batch
    (argument 1) and mask batch are deliberately NOT donated — they are
    engine-owned uploads that the staging fence may still be waiting on
    when the next chunk is dispatched, so the engine must keep the right
    to hold references to them.  Built here (not in ``serve/engine.py``)
    so the engine and the ``engine.step*`` analysis contracts trace the
    exact same callable.
    """
    def fleet(states, x, masks, rv):
        new, metrics, computed = _fleet_chunk_step(cfg, states, x, masks, rv)
        return new, metrics._replace(refresh_slots=computed)

    if masked:
        def body(s, x, m, rv):
            return fleet(s, x, m, rv)
    else:
        def body(s, x, rv):
            return fleet(s, x, None, rv)
    return jax.jit(body, donate_argnums=(0,))


@functools.partial(jax.jit, static_argnums=0)
def stream_run(cfg: StreamConfig, state: StreamState, xs: jnp.ndarray,
               masks: jnp.ndarray | None = None,
               ) -> tuple[StreamState, RoundMetrics]:
    """Jittable scan driver: stream ``xs`` of shape (rounds, n, p).

    ``masks`` (rounds, p), if given, carries the per-round sensor-liveness
    schedule (e.g. from :meth:`repro.core.faults.NodeChurn.liveness`).

    The per-network reference of :func:`batched_stream_run` at ``chunk=1``.
    """
    if masks is None:
        def step(carry, x_round):
            return stream_step(cfg, carry, x_round)
        return jax.lax.scan(step, state, xs)

    def step(carry, xm):
        x_round, mask = xm
        return stream_step(cfg, carry, x_round, mask)

    return jax.lax.scan(step, state, (xs, masks))


def _decision_steps(xs: jnp.ndarray, masks: jnp.ndarray | None,
                    chunk: int, probe_every: int | None, *, axis: int):
    """The chunk drivers' decision steps over the rounds axis ``axis`` of
    ``xs`` (and of ``masks``): ``(xs, masks, rv, steps, S)``, with S rounds
    per decision (``probe_every``, default ``chunk``) and ``steps`` =
    ceil(rounds / S).  A stream whose length is not divisible by S comes
    back padded with zero rounds, and ``rv`` is then the (steps, S) round
    validity that keeps them out of the fold and the books; ``rv`` is None
    where nothing was padded."""
    R = xs.shape[axis]
    S = chunk if probe_every is None else probe_every
    if chunk < 1 or S < 1:
        raise ValueError(f"chunk/probe_every must be >= 1, got "
                         f"{chunk}/{probe_every}")
    if chunk % S != 0:
        raise ValueError(f"probe_every ({S}) must divide chunk ({chunk})")
    steps = -(-R // S)
    pad = steps * S - R
    if not pad:
        return xs, masks, None, steps, S

    def padded(a):
        widths = [(0, 0)] * a.ndim
        widths[axis] = (0, pad)
        return jnp.pad(a, widths)

    rv = (jnp.arange(steps * S) < R).astype(jnp.float32).reshape(steps, S)
    return (padded(xs), None if masks is None else padded(masks), rv,
            steps, S)


@functools.partial(jax.jit, static_argnums=0,
                   static_argnames=("chunk", "probe_every"))
def chunked_stream_run(cfg: StreamConfig, state: StreamState,
                       xs: jnp.ndarray,
                       masks: jnp.ndarray | None = None, *,
                       chunk: int = 8,
                       probe_every: int | None = None,
                       ) -> tuple[StreamState, RoundMetrics]:
    """Chunk-granular scan driver: K rounds per dispatch.

    ``xs`` is (rounds, n, p) like :func:`stream_run`; the scan advances
    ``probe_every`` rounds per step (default: the whole ``chunk``), each
    step one fused covariance fold + one scheduler decision + one
    compression/detection pass (:func:`chunk_stream_step`).  A decision
    needs the covariance at its own boundary, so the fold granularity IS
    the decision granularity: with ``probe_every`` set below ``chunk``
    (it must divide it) every dispatch fuses ``probe_every`` rounds —
    ``chunk`` then only names the K the caller is A/B-ing against.
    Metrics come back with one entry per DECISION, i.e.
    ``ceil(rounds / probe_every)`` rows; ``comm_packets`` still accounts
    every epoch (per-round booking is exact, only decisions are
    amortized).

    ``probe_every=1`` reproduces today's per-round trajectory bit-exactly
    (states and metrics identical to :func:`stream_run` — the differential
    suite in tests/test_chunked_streaming.py pins this), so the decision
    cadence is a pure perf/accuracy knob, not a semantic fork.  A stream
    whose length is not divisible by the step is padded with invalid
    rounds that contribute nothing (the tail chunk folds and books only
    its real rounds).
    """
    xs, masks, rv, steps, S = _decision_steps(xs, masks, chunk, probe_every,
                                              axis=0)
    xs_c = xs.reshape(steps, S, *xs.shape[1:])
    masks_c = None if masks is None \
        else masks.reshape(steps, S, *masks.shape[1:])

    def step(carry, xmr):
        return chunk_stream_step(cfg, carry, *xmr)

    return jax.lax.scan(step, state, (xs_c, masks_c, rv))


def batched_stream_init(cfg: StreamConfig, key: jax.Array, n_networks: int,
                        dtype=jnp.float32) -> StreamState:
    """Per-network states stacked on a leading networks axis."""
    keys = jax.random.split(key, n_networks)
    return jax.vmap(lambda k: stream_init(cfg, k, dtype=dtype))(keys)


@functools.partial(jax.jit, static_argnums=0,
                   static_argnames=("chunk", "probe_every"))
def batched_stream_run(cfg: StreamConfig, states: StreamState,
                       xs: jnp.ndarray,
                       masks: jnp.ndarray | None = None, *,
                       chunk: int = 1,
                       probe_every: int | None = None,
                       ) -> tuple[StreamState, RoundMetrics]:
    """Stream a fleet: ``xs`` is (networks, rounds, n, p).

    ``masks`` (networks, rounds, p), if given, is the per-network liveness
    schedule.

    Every network gets :func:`chunked_stream_run`'s semantics (decisions
    every ``probe_every`` rounds, default ``chunk``; a tail that does not
    fill a decision step padded with rounds that count for nothing)
    through a ``lax.scan`` over the decision steps of the fleet body
    :func:`_fleet_chunk_step`: one fused cov launch per step for the whole
    fleet, and a refresh of only the networks whose trigger fired (in
    buckets of :func:`~repro.streaming.scheduler.bucket_size` of the
    fleet), where a vmapped :func:`chunked_stream_run` would refresh every
    network.  The default ``chunk=1`` decides once per round, the
    semantics of :func:`stream_run` per network.  Each step slices its
    rounds out of ``xs`` in place (the data block keeps its
    networks-leading layout).  Metrics come back networks-leading at
    decision granularity, (networks, decisions); ``refresh_slots`` stays
    None.
    """
    xs, masks, rv, steps, S = _decision_steps(xs, masks, chunk, probe_every,
                                              axis=1)
    networks = xs.shape[0]

    def rounds_of(a, i):
        return None if a is None \
            else jax.lax.dynamic_slice_in_dim(a, i * S, S, axis=1)

    def step(carry, ir):
        i, r = ir
        rc = None if r is None else jnp.broadcast_to(r, (networks, S))
        new, metrics, _ = _fleet_chunk_step(cfg, carry, rounds_of(xs, i),
                                            rounds_of(masks, i), rc)
        return new, metrics

    fin, metrics = jax.lax.scan(step, states, (jnp.arange(steps), rv))
    return fin, jax.tree.map(lambda a: jnp.swapaxes(a, 0, 1), metrics)


def _sharded_fleet_run(cfg: StreamConfig, mesh, states: StreamState,
                       xs: jnp.ndarray, masks: jnp.ndarray | None, *,
                       axis: str, chunk: int, probe_every: int | None,
                       merge=None, merge_specs=None):
    """The level-1 ``shard_map`` of the sharded fleet drivers: each device
    runs :func:`batched_stream_run` on its networks, with no collective
    on per-network state (checked with ``check_vma=False`` because the
    body is collective-free by construction).

    ``merge``, if given, is called as ``merge(final_states, metrics)`` on
    each shard after its run, inside the same ``shard_map`` (the
    hierarchy's level 2), and its output, with out-specs ``merge_specs``,
    is returned as a third result.
    """
    from repro.distributed.sharding import network_axis_spec

    spec = network_axis_spec(mesh, axis)
    n_networks = xs.shape[0]
    axis_size = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    if n_networks % axis_size != 0:
        raise ValueError(
            f"{n_networks} networks not divisible by axis {axis!r} "
            f"of size {axis_size}")

    def local_run(states_l, xs_l, masks_l=None):
        fin, metrics = batched_stream_run(cfg, states_l, xs_l, masks_l,
                                          chunk=chunk,
                                          probe_every=probe_every)
        if merge is None:
            return fin, metrics
        return fin, metrics, merge(fin, metrics)

    state_specs = jax.tree.map(lambda _: spec, states)
    out_specs = (state_specs,
                 jax.tree.map(lambda _: spec, _metrics_template(cfg)))
    if merge is not None:
        out_specs += (merge_specs,)
    args = (states, xs) if masks is None else (states, xs, masks)
    fm = jax.shard_map(local_run, mesh=mesh,
                       in_specs=(state_specs,) + (spec,) * (len(args) - 1),
                       out_specs=out_specs, check_vma=False)
    return fm(*args)


def sharded_stream_run(cfg: StreamConfig, mesh, states: StreamState,
                       xs: jnp.ndarray, masks: jnp.ndarray | None = None, *,
                       axis: str = "data",
                       chunk: int = 1,
                       probe_every: int | None = None,
                       ) -> tuple[StreamState, RoundMetrics]:
    """The batched run with the networks axis sharded over ``axis``.

    Each device streams its local slice of the fleet through
    :func:`batched_stream_run` (``masks``, ``chunk`` and ``probe_every``
    thread through per shard); no collective touches per-network state.
    Requires the number of networks to be divisible by the axis size.
    This is the level 1 of
    :func:`repro.streaming.hierarchy.hierarchical_stream_run`.
    """
    return _sharded_fleet_run(cfg, mesh, states, xs, masks, axis=axis,
                              chunk=chunk, probe_every=probe_every)


# ===========================================================================
# Program contracts (repro.analysis; DESIGN.md Sec. 15).
#
# Every structural claim the docs/benchmarks make about the chunk body is
# declared here, next to the code it describes, and machine-checked by
# ``python -m repro.analysis.check`` (a dedicated CI job) — the Table-1
# discipline of the paper, applied to the traced program instead of the
# WSN packet ledger.
# ===========================================================================
from repro.analysis import contracts as _contracts  # noqa: E402
from repro.analysis import jaxpr_lint as _jl        # noqa: E402
from repro.analysis import resources as _res        # noqa: E402

_CONTRACT_P, _CONTRACT_Q, _CONTRACT_H, _CONTRACT_N = 12, 3, 2, 4


def _contract_cfg(*, fused: bool = True, stages: bool = True,
                  precision: str = "fp32") -> StreamConfig:
    comp = CompressionConfig(epsilon=0.5) if stages else None
    det = DetectionConfig(alpha=1e-3, calib_rounds=3) if stages else None
    return StreamConfig(p=_CONTRACT_P, q=_CONTRACT_Q,
                        halfwidth=_CONTRACT_H, warmup_rounds=4,
                        compression=comp, detection=det,
                        fused=fused, precision=precision)


def _trace_chunk_body(cfg: StreamConfig, ks=(1, 4, 8)):
    st = stream_init(cfg, jax.random.PRNGKey(0))
    out = {}
    for k in ks:
        xc = jnp.zeros((k, _CONTRACT_N, cfg.p), jnp.float32)
        out[f"K={k}"] = jax.make_jaxpr(
            lambda s, x: chunk_stream_step(cfg, s, x))(st, xc)
    return out


def _trace_chunked_run():
    cfg = _contract_cfg(stages=False)
    st = stream_init(cfg, jax.random.PRNGKey(0))
    xs = jnp.zeros((8, _CONTRACT_N, cfg.p), jnp.float32)
    return {"R=8,chunk=4": jax.make_jaxpr(
        lambda s, x: chunked_stream_run(cfg, s, x, chunk=4))(st, xs)}


def _trace_dtype_policy():
    st32 = stream_init(_contract_cfg(stages=False), jax.random.PRNGKey(0))
    xs = jnp.zeros((4, _CONTRACT_N, _CONTRACT_P), jnp.float32)
    cfg32 = _contract_cfg(stages=False)
    cfg_f = _contract_cfg()
    cfg_bf = _contract_cfg(precision="bf16")
    st_f = stream_init(cfg_f, jax.random.PRNGKey(0))
    return {
        "stream_run": jax.make_jaxpr(
            lambda s, x: stream_run(cfg32, s, x))(st32, xs),
        "chunked-fp32": jax.make_jaxpr(
            lambda s, x: chunked_stream_run(cfg_f, s, x, chunk=4))(st_f, xs),
        "chunked-bf16": jax.make_jaxpr(
            lambda s, x: chunked_stream_run(cfg_bf, s, x, chunk=4))(
            stream_init(cfg_bf, jax.random.PRNGKey(0)), xs),
    }


_contracts.register(_contracts.Contract(
    id="chunk.body",
    where="repro.streaming.driver.chunk_stream_step",
    claim="one cov pallas launch and at most one eigh per chunk body, "
          "independent of K (PR 5)",
    trace=lambda: _trace_chunk_body(_contract_cfg(stages=False)),
    rules=(_jl.PrimitiveBudget("pallas_call", exact=1),
           _jl.PrimitiveBudget("eigh", max=1),
           _jl.ForbidInLoops(everywhere=True),
           _jl.NoF64(),
           _res.VmemBudget(),
           _res.HbmTrafficBudget(max_passes=1.0)),
))

_contracts.register(_contracts.Contract(
    id="chunk.fused.fp32",
    where="repro.streaming.driver.chunk_stream_step",
    claim="1 pallas_call per fused chunk body with both stages configured "
          "(was 3 on the split path; PR 7) — lax.cond branches included",
    trace=lambda: _trace_chunk_body(_contract_cfg()),
    rules=(_jl.PrimitiveBudget("pallas_call", exact=1),
           _jl.PrimitiveBudget("eigh", max=1),
           _jl.ForbidInLoops(everywhere=True),
           _jl.NoF64(),
           _res.VmemBudget(),
           # one tile-load per chunk: the whole entry moves exactly one
           # pass of HBM traffic, and the chunk data/mask halo slabs in
           # particular are read once (one feature block at these widths)
           _res.HbmTrafficBudget(max_passes=1.0,
                                 single_pass=("xpad_ref", "mpad_ref"))),
))

_contracts.register(_contracts.Contract(
    id="chunk.fused.bf16",
    where="repro.streaming.driver.chunk_stream_step",
    claim="the bf16 fused body still launches once and keeps every "
          "accumulator fp32 (bf16 is a tile format only; PR 7)",
    trace=lambda: _trace_chunk_body(_contract_cfg(precision="bf16")),
    rules=(_jl.PrimitiveBudget("pallas_call", exact=1),
           _jl.Fp32Accumulators(),
           _jl.NoF64(),
           _res.VmemBudget(),
           _res.HbmTrafficBudget(max_passes=1.0,
                                 single_pass=("xpad_ref", "mpad_ref"))),
))

_contracts.register(_contracts.Contract(
    id="chunk.body.split",
    where="repro.streaming.driver.chunk_stream_step",
    claim="the split (fused=False) chunk body pays exactly the three "
          "launches the mega-kernel collapsed (the fused path's oracle)",
    trace=lambda: _trace_chunk_body(_contract_cfg(fused=False), ks=(4,)),
    rules=(_jl.PrimitiveBudget("pallas_call", exact=3),
           _jl.PrimitiveBudget("eigh", max=1),
           _res.VmemBudget(),
           # each of the three split launches is itself one-pass; the
           # fused win is fewer launches, not fewer passes per launch
           _res.HbmTrafficBudget(max_passes=1.0)),
))

_contracts.register(_contracts.Contract(
    id="driver.hot-loop",
    where="repro.streaming.driver.chunked_stream_run",
    claim="the streamed scan is host-sync-free (no device_put/callbacks in "
          "the loop body) and launches scan-length x 1 pallas kernels",
    trace=_trace_chunked_run,
    rules=(_jl.ForbidInLoops(),
           # loop-weighted: 8 rounds / chunk 4 = 2 scan trips x 1 launch
           _jl.PrimitiveBudget("pallas_call", exact=2, loop_weighted=True),
           _jl.NoF64(),
           _res.VmemBudget(),
           _res.HbmTrafficBudget(max_passes=1.0)),
))

_contracts.register(_contracts.Contract(
    id="dtype.policy",
    where="repro.streaming.driver",
    claim="no f64 anywhere on the streaming paths; bf16 never escapes the "
          "tile loads (pallas outputs and scan carries stay fp32)",
    trace=_trace_dtype_policy,
    rules=(_jl.NoF64(), _jl.Fp32Accumulators()),
))
