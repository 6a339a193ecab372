"""Serving engine: streaming-PCA fleets with continuous batching.

:class:`StreamingPCAEngine` is slot based in the vLLM style — *fixed device
batch, host-side slot management, jitted steps* (DESIGN.md Sec. 8.4/12/17):
each slot holds one live sensor network; every engine step stages the
next K-round chunk per slot and folds it through the jitted batched chunk
step (:func:`repro.streaming.driver.engine_chunk_step_fn` — the fleet
form of :func:`~repro.streaming.driver.chunk_stream_step` with the fleet
state donated so XLA updates it in place), drift-triggered basis
refreshes happen at chunk boundaries inside the step, computed only for
the slots whose trigger fired, and exhausted streams retire with their
final basis + Table-1 communication bill.
``StreamConfig.fused``/``precision`` flow straight through the vmapped
step: with stages configured each slot's chunk body is the one-launch
mega-kernel (DESIGN.md Sec. 14), and ``precision="bf16"`` stages the
chunk tiles in bf16 while all engine-visible state stays fp32.

With ``pipeline=True`` the hot loop is fully pipelined (DESIGN.md Sec. 17):
staging runs through two pinned, engine-owned host buffers whose uploads
are explicit owned copies (the device batch never aliases staging memory),
and chunk t+1 is filled and uploaded WHILE the donated jitted step folds
chunk t — the only waits in the loop are the transfer fence on a buffer's
previous upload (never on the compute) and the per-slot result pull at
retirement.  Overlap only reorders host work, never device math, so the
pipelined engine is bit-identical to the synchronous one — pinned by the
differential suite in tests/test_engine_async.py.

Admission runs through a priority queue front end
(:class:`repro.serve.queue.AdmissionQueue`): higher priority admits first,
oldest-first within a priority, per-tenant concurrent-slot quotas, and a
bounded queue that rejects (backpressures) external submits when full.
Structured telemetry (:class:`repro.serve.telemetry.TelemetryRecorder`)
records per-step wall time and host phase times, staged-vs-compute
overlap, queue depth, admissions/retirements (with each stream's queue
wait and service time) and per-slot bills into a ring buffer with an
optional JSONL sink.  The host phases are ``engine.*`` profiler spans and
the engine's own device programs run under ``pca.admit``, ``pca.books``
and ``pca.retire`` named scopes, so a trace puts names on both.

The engine is fault-aware (DESIGN.md Sec. 9): each slot carries a
:class:`repro.runtime.health.HealthMonitor` driven by a *logical* clock (one
tick per engine step, so verdicts are deterministic).  A slot whose network
reports too few alive sensors stops heartbeating; once the monitor rules the
slot stalled, the network is **retired dead** — and if its liveness schedule
shows a later revival, a continuation request is re-queued from the revival
round.  Whenever the live-network count changes, the engine re-plans its
device mesh through :func:`repro.runtime.elastic.plan_mesh` (the WSN-fleet
analogue of elastic rescale after host death).
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime.elastic import RescalePlan, plan_mesh
from repro.runtime.health import HealthMonitor, StragglerPolicy
from repro.serve.queue import AdmissionQueue, QueuePolicy
from repro.serve.telemetry import PhaseTimer, StepRecord, TelemetryRecorder
from repro.streaming.driver import (StreamConfig, StreamState,
                                    engine_chunk_step_fn, stream_init)

__all__ = ["StreamRequest", "StreamResult", "FleetSummary",
           "StreamingPCAEngine"]


@dataclasses.dataclass(eq=False)       # identity equality: requests hold arrays
class StreamRequest:
    """One live sensor network: a finite stream of measurement rounds.

    ``liveness`` is an optional (R, p) per-round sensor-liveness schedule
    (1 = alive), e.g. from :meth:`repro.core.faults.NodeChurn.liveness`;
    ``None`` means every sensor is alive for the whole stream.

    ``region`` tags the network with its region id in a two-level fleet
    (DESIGN.md Sec. 13): slots are region-aware — the engine tracks which
    region each slot is streaming, and :meth:`StreamingPCAEngine.fleet_summary`
    merges the retired regions' bases into the fleet-level basis with the
    merge's Table-1 bill.  The default region 0 keeps flat fleets unchanged.

    ``priority``/``tenant`` feed the admission queue (DESIGN.md Sec. 17):
    higher priority admits first (oldest-first within a class), and a
    tenant never holds more concurrent slots than the engine's
    ``QueuePolicy.max_slots_per_tenant``.  The defaults reproduce plain
    FIFO admission.
    """

    rounds: np.ndarray               # (R, n, p) float32 measurement rounds
    liveness: np.ndarray | None = None   # (R, p) per-round sensor liveness
    region: int = 0                  # region id in the two-level fleet
    priority: int = 0                # admission priority (higher first)
    tenant: str | None = None        # quota bucket (None: unmetered)
    # filled by the engine:
    result: "StreamResult | None" = None
    done: bool = False
    # early (dead-network) retirements collected before the final result;
    # each entry covers the rounds streamed up to that retirement
    retirements: list = dataclasses.field(default_factory=list)
    # engine-internal: round to resume from after a revival re-admission
    resume_at: int = 0
    # perf_counter stamps of the current segment: when it entered the
    # queue (a revival continuation re-enters it), when a slot took it,
    # and when its result was built
    submitted_s: float | None = None
    admitted_s: float | None = None
    finished_s: float | None = None


@dataclasses.dataclass(frozen=True)
class StreamResult:
    """Final per-network summary returned when a stream retires.

    The ``compression_*`` fields are populated only when the engine's
    StreamConfig carries a compression stage: the worst sink error over
    every round streamed in this segment (the ε guarantee holds iff
    ``compression_max_err <= ε``), the flagged-raw extras sent, and the
    score bits put on air at the quantized budget.

    The ``detection_*`` fields are populated only when the StreamConfig
    carries a detection stage: the alarmed-epoch count of this segment,
    the Sec.-2.4.3 alarm-flood packets those alarms billed (lossy-scaled,
    on top of the per-round monitoring scalar already inside
    ``comm_packets``), and the T²/SPE thresholds in effect at retirement.
    """

    components: np.ndarray           # (p, q) final basis
    retained: float                  # rho of the final basis on the live cov
    refreshes: int                   # scheduled basis recomputations
    comm_packets: float              # Table-1 communication bill (packets)
    rounds: int                      # rounds streamed
    reason: str = "completed"        # "completed" | "dead"
    # the region head's level-2 merge record (DESIGN.md Sec. 13): live
    # per-component subspace energies diag(W^T C W) and the trace partial —
    # exactly what fleet_summary aggregates across regions
    energies: np.ndarray | None = None    # (q,) subspace energies
    total_variance: float | None = None   # trace(C) partial
    compression_max_err: float | None = None
    compression_extra_packets: float | None = None
    compression_bits_on_air: float | None = None
    detection_events: float | None = None
    detection_alarm_packets: float | None = None
    detection_t2_threshold: float | None = None
    detection_spe_threshold: float | None = None


@dataclasses.dataclass(frozen=True)
class FleetSummary:
    """The two-level fleet basis merged from retired region results.

    ``basis`` is the dense block-embedded (p_fleet, q_fleet) fleet basis
    (orthonormal by construction — disjoint region supports); ``region``/
    ``col``/``lam`` the compact selection; ``merge_packets`` the Table-1
    bill of the merge epoch that produced it (one (q+1)-record region-tree
    aggregation, ARQ-scaled — :func:`repro.core.costs.lossy_merge_cost`).
    """

    basis: np.ndarray                # (p_fleet, q_fleet)
    region: np.ndarray               # (q_fleet,) owning region per component
    col: np.ndarray                  # (q_fleet,) column within that region
    lam: np.ndarray                  # (q_fleet,) energies, descending
    rho: float                       # fleet retained fraction
    regions: tuple                   # region ids merged, ascending
    merge_packets: float             # region-head bill of this merge epoch


@functools.lru_cache(maxsize=None)
def _slot_summary_fn(cfg: StreamConfig):
    """One jitted per-slot retirement summary per StreamConfig (the slot
    index is a traced argument, so every retirement of every engine with
    this config reuses a single compilation).  The eager alternative — a
    dozen small host-dispatched ops per retirement — costs ~25 ms per
    retired slot at serving time, which under churn dwarfs the chunk fold
    itself."""
    from repro.streaming.hierarchy import region_energies
    from repro.streaming.online_cov import (online_estimate,
                                            online_total_variance)
    from repro.streaming.scheduler import retained_fraction

    @jax.named_scope("pca.retire")
    def summarize(states, comp, det, refresh, i):
        st = jax.tree.map(lambda a: a[i], states)
        out = dict(
            W=st.sched.W,
            rho=retained_fraction(online_estimate(st.cov), st.sched.W,
                                  online_total_variance(st.cov)),
            refreshes=st.sched.refreshes,
            comm_packets=st.sched.comm_packets,
            rounds=st.rounds,
            refresh_fired=refresh[0], refresh_computed=refresh[1])
        out["lam"], out["total"] = region_energies(st)
        if cfg.compression is not None:
            out.update(comp_max=comp[0][i], comp_extra=comp[1][i],
                       comp_bits=comp[2][i])
        if cfg.detection is not None:
            out.update(det_events=det[0][i], det_alarms=det[1][i],
                       det_t2=st.det.t2_threshold,
                       det_spe=st.det.spe_threshold)
        return out

    return jax.jit(summarize)


@functools.lru_cache(maxsize=None)
def _admit_splice_fn(slots: int):
    """The admission splice as one jitted program: fresh states for the
    FULL fleet selected per slot by a (slots,) mask, and the admitted
    slots' device books zeroed.  Fixed shapes, so one compile ever: a
    variable-length ``at[idx].set`` would retrace once per distinct admit
    count.  ``jnp.where`` writes the identical values, so admission stays
    bit-identical.  The fleet state is donated: the splice updates it in
    place, as the chunk step does."""

    @jax.named_scope("pca.admit")
    def splice(states, fresh, comp_books, det_books, mask):
        def leaf(full, f):
            sel = mask.reshape((slots,) + (1,) * (f.ndim - 1))
            return jnp.where(sel, f, full)

        def zero(books):
            return tuple(jnp.where(mask, 0.0, b) for b in books)

        return (jax.tree.map(leaf, states, fresh), zero(comp_books),
                zero(det_books))

    return jax.jit(splice, donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _books_fn(with_compression: bool, with_detection: bool,
              alarm_price: float):
    """Fold one step's stage outputs into the per-slot device books, and
    its refresh into the fleet's two refresh counts (slots that fired,
    slots the bucketed refresh computed), as one jitted program.  Idle
    slots fold zero rounds: they are masked out of the stage books (where,
    not multiply — robust to any NaN in an idle slot)."""

    @jax.named_scope("pca.books")
    def books(comp_books, det_books, refresh_books, comp, det, fired,
              computed, live):
        if computed is None:             # a vmapped select refreshes all
            computed = fired.shape[0]
        refresh_books = (
            refresh_books[0] + jnp.sum(fired, dtype=jnp.int32),
            refresh_books[1] + computed)
        on = live > 0
        if with_compression:
            max_err, extras, bits = comp_books
            comp_books = (
                jnp.maximum(max_err, jnp.where(on, comp.max_err, 0.0)),
                extras + jnp.where(on, comp.extra_packets, 0.0),
                bits + jnp.where(on, comp.bits_on_air, 0.0))
        if with_detection:
            events, alarm_packets = det_books
            alarms = jnp.where(on, det.alarms, 0.0)
            det_books = (events + alarms,
                         alarm_packets + alarms * alarm_price)
        return comp_books, det_books, refresh_books

    return jax.jit(books)


@dataclasses.dataclass
class _StagedChunk:
    """One staged chunk upload: device batches plus the host-side plan
    they were built from.  ``signature`` pins the slot plan (per-slot
    request identity + cursor) so a prestaged chunk is consumed only if
    admissions/retirements/submissions did not move the plan under it."""

    batch: jax.Array                 # (slots, K, n, p) owned device copy
    masks: jax.Array | None          # (slots, K, p) or None (no schedules)
    rv: jax.Array                    # (slots, K) round validity
    start: np.ndarray                # cursor snapshot at staging time
    consumed: np.ndarray             # rounds each slot will fold
    signature: tuple                 # plan token (see _plan_signature)


class StreamingPCAEngine:
    """Continuous batching over sensor-network streams, fault-aware.

    Parameters
    ----------
    cfg: the per-network :class:`~repro.streaming.driver.StreamConfig`
        (every slot shares p, n, band half-width and scheduler policy —
        the fleet is shape-homogeneous like a decode batch).
    slots: device batch size (networks streamed concurrently).
    health_policy: per-slot :class:`~repro.runtime.health.StragglerPolicy`;
        ``stall_timeout`` is measured in *engine steps* (the logical clock
        ticks once per step, keeping verdicts deterministic).
    min_alive_fraction: a slot heartbeats only while at least this fraction
        of its sensors is alive; below it the network is considered
        unresponsive and the monitor's stall verdict retires it.
    chunk: rounds folded per engine step (K).  Each step stages every
        slot's next K rounds device-side in ONE upload, folds them through
        the fused chunk kernel, and evaluates ONE scheduler decision per
        slot — the per-dispatch overhead (launches, refresh decisions,
        host→device transfers, slot bookkeeping) is amortized over K
        measurement epochs while the Table-1 bill stays per-epoch exact.
        Admission and retirement happen at chunk boundaries; a stream
        whose tail is shorter than K folds only its real rounds (the
        chunk step's per-round validity).  ``chunk=1`` reproduces the
        per-round engine bit-exactly.
    pipeline: pipelined double-buffered staging (DESIGN.md Sec. 17) —
        chunk t+1 is filled and uploaded while the jitted step folds
        chunk t.  Overlap reorders host work only; results are
        bit-identical to ``pipeline=False``.
    queue: a :class:`~repro.serve.queue.QueuePolicy` (or a prebuilt
        :class:`~repro.serve.queue.AdmissionQueue`) for the admission
        front end; None is an unbounded FIFO, bit-compatible with the
        pre-queue engine.
    telemetry: a :class:`~repro.serve.telemetry.TelemetryRecorder`, or
        ``True`` for a default ring recorder; None disables recording.
    """

    def __init__(self, cfg: StreamConfig, slots: int = 8, seed: int = 0,
                 health_policy: StragglerPolicy | None = None,
                 min_alive_fraction: float = 0.25, chunk: int = 1,
                 pipeline: bool = False,
                 queue: QueuePolicy | AdmissionQueue | None = None,
                 telemetry: TelemetryRecorder | bool | None = None):
        if chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        self.cfg = cfg
        self.slots = slots
        self.chunk = chunk
        self.pipeline = pipeline
        self.min_alive_fraction = min_alive_fraction
        self.health_policy = health_policy or StragglerPolicy(
            stall_timeout=2.5)          # logical steps, not seconds
        key = jax.random.PRNGKey(seed)
        self._slot_keys = jax.random.split(key, slots)
        self.states: StreamState = jax.vmap(
            lambda k: stream_init(cfg, k))(self._slot_keys)
        # per-slot re-admission template: slot s always re-initializes
        # from key s, so the fresh fleet is computed once and cached
        self._fresh_states: StreamState | None = None
        self.active: list[StreamRequest | None] = [None] * slots
        self.cursor = np.zeros(slots, np.int64)     # next round per slot
        self.queue: AdmissionQueue = (
            queue if isinstance(queue, AdmissionQueue)
            else AdmissionQueue(queue))
        self.telemetry: TelemetryRecorder | None = (
            TelemetryRecorder() if telemetry is True else telemetry or None)
        # region-aware slots (DESIGN.md Sec. 13): which region each slot is
        # streaming right now (-1 = idle), and the latest final result per
        # region — the merge inputs of fleet_summary()
        self.slot_region = np.full(slots, -1, np.int64)
        self.region_results: dict[int, StreamResult] = {}
        # two jitted chunk steps (repro.streaming.driver.engine_chunk_step_fn
        # — shared with the engine.step* analysis contracts): the masked one
        # only runs when some active request actually carries a liveness
        # schedule — fault-free fleets never build or upload a mask batch at
        # all (and stay on the unmasked kernel); the two are bit-identical
        # under an all-ones mask, so the switch is invisible to results.
        # The fleet state is DONATED: XLA updates the slot pytree in place
        # instead of allocating a fresh copy every step (the states are
        # never read after the call — the returned buffers replace them).
        self._step_fn = engine_chunk_step_fn(cfg)
        self._step_fn_masked = engine_chunk_step_fn(cfg, masked=True)
        self._n: int | None = None       # epochs/round, fixed fleet-wide
        # double-buffered staging (DESIGN.md Sec. 17): two pinned,
        # engine-owned host buffers filled alternately; every upload is an
        # EXPLICIT OWNED COPY (jnp.asarray(copy=True)), so the device batch
        # never aliases staging memory (the CPU device_put aliasing hazard,
        # pinned by the poisoning regression test).  The transfer reads the
        # host buffer asynchronously, so the per-buffer transfer fence
        # (_uploads) is waited on before a REFILL — a wait on the copy-out,
        # never on the chunk fold.
        self._host_bufs: list[np.ndarray | None] = [None, None]
        self._mask_bufs: list[np.ndarray | None] = [None, None]
        self._uploads: list[tuple | None] = [None, None]
        self._parity = 0
        self._staged: _StagedChunk | None = None
        self._phases = PhaseTimer()      # host phase times of this step
        # hot-loop hygiene counters (checked by the engine.step.pipelined
        # contract): every device→host conversion in the engine goes
        # through _pull with a ledger key — "hot" must stay 0 forever
        self.pulls = {"hot": 0, "retire": 0}
        self._transfer_fences = 0
        self._prestage_hits = 0
        self._prestage_misses = 0
        # ε-supervised compression accounting (cfg.compression only):
        # per-slot running worst sink error / flagged-raw extras / bits on
        # air for the current segment.  Accumulated ON DEVICE (jnp ops, no
        # per-step host sync — the step stays async-dispatchable like the
        # decode path); the scalars are pulled to host only at retirement.
        # last_compression keeps the most recent round's full device output
        # (scores, sink view, flags) for observability — one round's
        # arrays, bounded.
        zeros = functools.partial(jnp.zeros, slots, jnp.float32)
        self._comp_books: tuple = ((zeros(), zeros(), zeros())
                                   if cfg.compression is not None else ())
        self.last_compression = None
        # T²/SPE detection accounting (cfg.detection only): per-slot running
        # alarmed-epoch count and alarm-flood bill for the current segment,
        # accumulated on device like the compression books; last_detection
        # keeps the most recent round's device output for observability.
        # The per-alarm packet price and ARQ factor are engine-lifetime
        # constants (cfg is fixed), resolved once here.
        self._det_books: tuple = ((zeros(), zeros())
                                  if cfg.detection is not None else ())
        self.last_detection = None
        # fleet-wide refresh counts since the engine started, on device
        # like the books: slots whose trigger fired, and slots the bucketed
        # refresh computed (fired / computed is the bucket fill).  Pulled
        # with each retirement's summary into refresh_slots.
        self._refresh_books = (jnp.zeros((), jnp.int32),
                               jnp.zeros((), jnp.int32))
        self.refresh_slots = {"fired": 0, "computed": 0}
        self._det_alarm_price = 0.0
        if cfg.detection is not None:
            from repro.core.faults import expected_transmissions
            from repro.streaming.detector import detection_packet_split
            _, per_alarm = detection_packet_split(cfg.q, cfg.c_max)
            self._det_alarm_price = per_alarm * expected_transmissions(
                cfg.link_loss, cfg.max_retries)
        # fault machinery: logical clock, per-slot monitors, retirement log
        self._clock = 0
        self.health: list[HealthMonitor | None] = [None] * slots
        self.retired_log: list[tuple[StreamRequest, str]] = []
        # elastic fleet mesh: one virtual device per live network; re-planned
        # through runtime.elastic whenever the live count changes (the
        # initial plan assumes a full fleet, so a first step at full
        # occupancy appends nothing)
        self._last_live = slots
        self.plan: RescalePlan = plan_mesh(max(1, slots), prefer_model=1,
                                           global_batch=max(1, slots))
        self.plan_history: list[RescalePlan] = [self.plan]

    # -- request lifecycle ---------------------------------------------------
    def submit(self, req: StreamRequest) -> bool:
        """Enqueue a stream for admission; returns False when the bounded
        queue rejected it (backpressure — the caller owns the retry)."""
        r, n, p = req.rounds.shape
        if p != self.cfg.p:
            raise ValueError(f"stream p={p} != engine p={self.cfg.p}")
        if r == 0:
            raise ValueError("stream has no rounds")
        if req.liveness is not None and req.liveness.shape != (r, p):
            raise ValueError(
                f"liveness shape {req.liveness.shape} != {(r, p)}")
        # the device batch is shape-homogeneous: every stream must share the
        # epochs-per-round of the first submitted stream
        if self._n is None:
            self._n = n
        elif n != self._n:
            raise ValueError(f"stream n={n} != engine n={self._n}")
        req.submitted_s = time.perf_counter()
        ok = self.queue.submit(req, priority=req.priority, tenant=req.tenant)
        if not ok and self.telemetry is not None:
            self.telemetry.record_event("rejected", step=self._clock,
                                        priority=req.priority,
                                        tenant=req.tenant,
                                        queue_depth=len(self.queue))
        return ok

    def _tenant_load(self) -> dict:
        load: dict = {}
        for req in self.active:
            if req is not None and req.tenant is not None:
                load[req.tenant] = load.get(req.tenant, 0) + 1
        return load

    def _admit(self) -> int:
        """Fill empty slots from the queue front end (priority order,
        oldest-first within a priority, per-tenant quotas respected), then
        reset every admitted slot's device state in ONE batched splice
        (:func:`_admit_splice_fn`, however many slots were admitted).
        Returns the number admitted."""
        newly: list[int] = []
        load = self._tenant_load()
        for slot in range(self.slots):
            if self.active[slot] is not None:
                continue
            entry = self.queue.pop_admissible(load)
            if entry is None:
                break
            req = entry.req
            req.admitted_s = time.perf_counter()
            self.active[slot] = req
            self.cursor[slot] = req.resume_at
            self.slot_region[slot] = req.region
            if req.tenant is not None:
                load[req.tenant] = load.get(req.tenant, 0) + 1
            newly.append(slot)
            monitor = HealthMonitor(self.health_policy,
                                    clock=lambda: float(self._clock))
            monitor.heartbeat(step=self._clock, duration=1.0)
            self.health[slot] = monitor
            if self.telemetry is not None:
                self.telemetry.record_event(
                    "admitted", step=self._clock, slot=slot,
                    priority=entry.priority, tenant=entry.tenant,
                    resume_at=int(req.resume_at))
        if not newly:
            return 0
        mask = np.zeros(self.slots, bool)
        mask[newly] = True
        if self._fresh_states is None:
            self._fresh_states = jax.vmap(
                lambda k: stream_init(self.cfg, k))(self._slot_keys)
        self.states, self._comp_books, self._det_books = _admit_splice_fn(
            self.slots)(self.states, self._fresh_states, self._comp_books,
                        self._det_books, mask)
        return len(newly)

    # -- retirement (classify host-side now, pull device scalars later) ------
    def _pull(self, x, where: str = "hot"):
        """The engine's ONLY device→host conversion point: every float()/
        np.asarray() of a device value routes through here with a ledger
        key, so the pipelined-hot-loop contract can assert pulls["hot"]==0
        (results are pulled at retirement, nowhere else)."""
        self.pulls[where] = self.pulls.get(where, 0) + 1
        return x

    def _result_slices(self, slot: int):
        """Dispatch the retiring slot's device-side summary, BEFORE any
        admission scatter can overwrite the slot.  Async dispatch only;
        nothing is pulled to host here."""
        return _slot_summary_fn(self.cfg)(self.states, self._comp_books,
                                          self._det_books,
                                          self._refresh_books, np.int32(slot))

    def _finalize_result(self, slices, reason: str) -> StreamResult:
        """Pull a retiring slot's device scalars and build its
        StreamResult — the only blocking device→host sync of the loop.
        ONE device_get for the whole summary dict: pulling the ~17 fields
        individually pays ~0.3 ms dispatch latency each, which under
        churn would dominate the chunk fold itself."""
        with self._phases.span("pull"):
            out = self._pull(jax.device_get(slices), "retire")
        self.refresh_slots = {"fired": int(out["refresh_fired"]),
                              "computed": int(out["refresh_computed"])}
        extra: dict = {}
        if self.cfg.compression is not None:
            extra = dict(
                compression_max_err=float(out["comp_max"]),
                compression_extra_packets=float(out["comp_extra"]),
                compression_bits_on_air=float(out["comp_bits"]),
            )
        if self.cfg.detection is not None:
            extra.update(
                detection_events=float(out["det_events"]),
                detection_alarm_packets=float(out["det_alarms"]),
                detection_t2_threshold=float(out["det_t2"]),
                detection_spe_threshold=float(out["det_spe"]),
            )
        return StreamResult(
            components=np.asarray(out["W"]),
            retained=float(out["rho"]),
            refreshes=int(out["refreshes"]),
            comm_packets=float(out["comm_packets"]),
            rounds=int(out["rounds"]),
            reason=reason,
            energies=np.asarray(out["lam"]),
            total_variance=float(out["total"]),
            **extra,
        )

    def _begin_retire(self, slot: int, reason: str) -> dict:
        """Host-side half of retirement: snapshot the slot's device slices
        (lazy), free the slot, and — for a dead retirement whose liveness
        schedule shows a revival — re-queue the continuation (an internal
        submit, exempt from the queue bound: the work was already
        admitted once).  The StreamResult pull happens in
        :meth:`_finish_retire`, AFTER the pipelined loop has staged the
        next chunk, so the pull never blocks the staging overlap."""
        req = self.active[slot]
        pending = dict(req=req, reason=reason, slot=slot,
                       region=int(self.slot_region[slot]),
                       slices=self._result_slices(slot), revive=None)
        self.active[slot] = None
        self.slot_region[slot] = -1
        self.health[slot] = None
        if reason == "dead":
            revive = None
            if req.liveness is not None:
                frac = req.liveness[int(self.cursor[slot]):].mean(axis=1)
                ahead = np.nonzero(frac >= self.min_alive_fraction)[0]
                if ahead.size:
                    revive = int(self.cursor[slot]) + int(ahead[0])
            pending["revive"] = revive
            if revive is not None:
                req.resume_at = revive
                req.submitted_s = time.perf_counter()
                self.queue.submit(req, priority=req.priority,
                                  tenant=req.tenant, internal=True)
        return pending

    def _finish_retire(self, pending: dict) -> None:
        req = pending["req"]
        reason = pending["reason"]
        result = self._finalize_result(pending["slices"], reason)
        req.finished_s = time.perf_counter()
        self.retired_log.append((req, reason))
        if reason == "dead" and pending["revive"] is not None:
            # a continuation will follow: this segment is an early retirement
            req.retirements.append(result)
        else:
            # final result (dead retirements without a revival ahead stay
            # out of `retirements` so segment bills sum without
            # double-counting)
            req.result = result
            req.done = True
            self.region_results[pending["region"]] = result
        if self.telemetry is not None:
            self.telemetry.record_refresh(self.refresh_slots["fired"],
                                          self.refresh_slots["computed"])
            self.telemetry.record_event(
                "retired", step=self._clock, slot=pending["slot"],
                reason=reason, tenant=req.tenant,
                rounds=result.rounds, comm_packets=result.comm_packets,
                refreshes=result.refreshes,
                revive=pending["revive"],
                queue_wait_s=req.admitted_s - req.submitted_s,
                service_s=req.finished_s - req.admitted_s)

    def _replan(self, n_live: int) -> None:
        """Elastic fleet mesh: one virtual device per live network."""
        if n_live != self._last_live and n_live > 0:
            self.plan = plan_mesh(n_live, prefer_model=1,
                                  global_batch=n_live)
            self.plan_history.append(self.plan)
        self._last_live = n_live

    # -- staging (double-buffered) -------------------------------------------
    def _plan_signature(self) -> tuple:
        """The slot plan a staged chunk depends on: per-slot request
        identity + cursor.  Any admission, retirement or resumed
        continuation moves it, invalidating a prestaged chunk."""
        return tuple(
            (id(self.active[s]), int(self.cursor[s]))
            if self.active[s] is not None else None
            for s in range(self.slots))

    def _upload(self, host_buf: np.ndarray) -> jax.Array:
        """Owned-copy upload: the device buffer never aliases the pinned
        staging memory.  The copy is made by the transfer, which reads the
        host buffer asynchronously, so the buffer is free to be refilled
        only once the copy-out fence (the upload's readiness) clears."""
        return jnp.asarray(host_buf, copy=True)

    def _stage(self) -> _StagedChunk:
        """Fill the next pinned host buffer with every active slot's next
        K rounds and upload it as an owned device copy.  Idle slots carry
        a zero chunk with zero round-validity (they fold nothing and book
        nothing); a live slot whose stream ends mid-chunk stages only its
        real tail rounds.  The mask batch is neither built nor uploaded
        unless some active request actually carries a liveness schedule
        (the masked and unmasked steps are bit-identical under all-ones
        masks, so the switch is invisible to results)."""
        K, p = self.chunk, self.cfg.p
        i = self._parity
        self._parity ^= 1
        if self._host_bufs[i] is None:
            self._host_bufs[i] = np.zeros((self.slots, K, self._n, p),
                                          np.float32)
            self._mask_bufs[i] = np.ones((self.slots, K, p), np.float32)
        elif self._uploads[i] is not None:
            # transfer fence: wait for this buffer's PREVIOUS upload to
            # finish copying out of the host memory we are about to
            # overwrite.  This is the pipeline's only wait besides the
            # retirement pull — and it is on the device_put, never on the
            # chunk fold.
            self._transfer_fences += 1
            with self._phases.span("fence"):
                jax.block_until_ready(self._uploads[i])
        buf = self._host_bufs[i]
        rv = np.zeros((self.slots, K), np.float32)
        consumed = np.zeros(self.slots, np.int64)
        start = self.cursor.copy()
        any_schedule = False
        for s in range(self.slots):
            req = self.active[s]
            if req is None:
                buf[s] = 0.0
                continue
            c = int(start[s])
            take = min(K, req.rounds.shape[0] - c)
            buf[s, :take] = req.rounds[c:c + take]
            if take < K:
                buf[s, take:] = 0.0
            rv[s, :take] = 1.0
            consumed[s] = take
            any_schedule |= req.liveness is not None
        masks_dev = None
        if any_schedule:
            mbuf = self._mask_bufs[i]
            for s in range(self.slots):
                req = self.active[s]
                if req is None or req.liveness is None:
                    mbuf[s] = 1.0
                    continue
                c, take = int(start[s]), int(consumed[s])
                mbuf[s, :take] = req.liveness[c:c + take]
                if take < K:
                    mbuf[s, take:] = 1.0
            masks_dev = self._upload(mbuf)
        batch_dev = self._upload(buf)
        self._uploads[i] = (batch_dev,) if masks_dev is None \
            else (batch_dev, masks_dev)
        return _StagedChunk(batch=batch_dev, masks=masks_dev,
                            rv=jnp.asarray(rv), start=start,
                            consumed=consumed,
                            signature=self._plan_signature())

    def _accumulate_books(self, metrics, live: list[int]) -> None:
        """Fold the step's stage outputs and refresh counts into the
        device accounts (:func:`_books_fn`): one async dispatch, no host
        sync."""
        with_c = self.cfg.compression is not None
        with_m = self.cfg.detection is not None
        lm = np.zeros(self.slots, np.float32)
        lm[live] = 1.0
        self.last_compression = metrics.compression   # (slots, ...) device
        self.last_detection = metrics.detection       # arrays, or None
        self._comp_books, self._det_books, self._refresh_books = _books_fn(
            with_c, with_m, self._det_alarm_price)(
            self._comp_books, self._det_books, self._refresh_books,
            metrics.compression, metrics.detection, metrics.did_refresh,
            metrics.refresh_slots, lm)

    # -- main loop ------------------------------------------------------------
    def step(self) -> int:
        """Fold the next K-round chunk for every active slot; returns
        #active.

        The loop is host-sync-free in steady state: the staged batch is
        an owned device copy, the jitted step updates the donated fleet
        state in place, and the accounting stays on device — scalars are
        pulled to host only at retirement.  With ``pipeline=True`` the
        chunk consumed by step t+1 was filled and uploaded DURING step t,
        while the device folded chunk t (staged-vs-compute overlap); a
        prestaged chunk is dropped and restaged inline if the slot plan
        moved under it (new admission, retirement, or a submission that
        fills a free slot).  Per step, each live slot heartbeats its
        HealthMonitor iff enough of its sensors were alive over the
        chunk's rounds; slots ruled stalled afterwards are retired dead
        (and re-queued from their revival round, if any).

        Each host phase is a :class:`~repro.serve.telemetry.PhaseTimer`
        span (``engine.admit``, ``engine.stage`` with ``engine.fence``
        inside, ``engine.dispatch``, ``engine.bookkeep``, ``engine.retire``
        with one ``engine.pull`` per result), timed into the step's
        :class:`StepRecord`.
        """
        t0 = time.perf_counter()
        phases = self._phases = PhaseTimer()
        with phases.span("admit"):
            admitted = self._admit()
        self._clock += 1
        live = [s for s in range(self.slots) if self.active[s]]
        self._replan(len(live))
        if not live:
            self._staged = None
            if self.telemetry is not None:
                self.telemetry.record_step(StepRecord(
                    step=self._clock, wall_s=time.perf_counter() - t0,
                    stage_s=0.0, overlap_s=0.0, prestaged=False, live=0,
                    rounds=0, queue_depth=len(self.queue),
                    admitted=admitted, retired=0, start_s=t0,
                    admit_s=phases.get("admit")))
            return 0
        # -- chunk t: consume the prestaged upload, or stage inline --------
        staged, self._staged = self._staged, None
        prestaged = (staged is not None
                     and staged.signature == self._plan_signature())
        if prestaged:
            self._prestage_hits += 1
        else:
            self._prestage_misses += 1
            with phases.span("stage"):
                staged = self._stage()
        # -- dispatch: nothing below blocks on the fold --------------------
        with phases.span("dispatch"):
            if staged.masks is not None:
                self.states, metrics = self._step_fn_masked(
                    self.states, staged.batch, staged.masks, staged.rv)
            else:
                self.states, metrics = self._step_fn(
                    self.states, staged.batch, staged.rv)
            self._accumulate_books(metrics, live)
        # -- host bookkeeping: heartbeats, cursors, retirement verdicts ----
        pendings: list[dict] = []
        with phases.span("bookkeep"):
            for s in live:
                req = self.active[s]
                c, take = int(staged.start[s]), int(staged.consumed[s])
                frac = 1.0 if req.liveness is None \
                    else float(req.liveness[c:c + take].mean())
                if frac >= self.min_alive_fraction:
                    self.health[s].heartbeat(step=self._clock, duration=1.0)
                self.cursor[s] += take
                if self.cursor[s] >= req.rounds.shape[0]:
                    pendings.append(self._begin_retire(s, "completed"))
                elif self.health[s].stalled():
                    pendings.append(self._begin_retire(s, "dead"))
        # -- pipelined prestage: chunk t+1 overlaps the in-flight fold -----
        overlap_s = 0.0
        if self.pipeline:
            with phases.span("admit"):
                admitted += self._admit()
            if any(r is not None for r in self.active):
                with phases.span("stage") as prestage:
                    self._staged = self._stage()
                overlap_s = prestage.seconds
        # -- retirement results: the loop's only device→host pulls ---------
        with phases.span("retire"):
            for pending in pendings:
                self._finish_retire(pending)
        if self.telemetry is not None:
            self.telemetry.record_step(StepRecord(
                step=self._clock, wall_s=time.perf_counter() - t0,
                stage_s=phases.get("stage"), overlap_s=overlap_s,
                prestaged=prestaged, live=len(live),
                rounds=int(staged.consumed.sum()),
                queue_depth=len(self.queue), admitted=admitted,
                retired=len(pendings), start_s=t0,
                admit_s=phases.get("admit"), fence_s=phases.get("fence"),
                dispatch_s=phases.get("dispatch"),
                bookkeep_s=phases.get("bookkeep"),
                retire_s=phases.get("retire")))
        return len(live)

    def run_until_done(self, max_steps: int = 100_000) -> None:
        for _ in range(max_steps):
            if self.step() == 0 and not self.queue:
                return

    # -- two-level fleet merge (DESIGN.md Sec. 13) ---------------------------
    def fleet_summary(self, q_fleet: int | None = None,
                      c_regions: int | None = None) -> FleetSummary:
        """Merge the retired regions' bases into the fleet-level basis.

        One level-2 merge epoch over the region results collected so far
        (latest final result per region id): global top-``q_fleet``
        selection by subspace energy (:func:`repro.streaming.hierarchy.
        merge_fleet` — the same jittable core the cross-host driver runs
        after its ``all_gather``), dense block embedding, and the merge's
        Table-1 bill at region-tree fan-out ``c_regions`` (default
        ``cfg.c_max``), ARQ-scaled like every intra-network packet.
        """
        from repro.core import costs
        from repro.streaming.hierarchy import fleet_basis_dense, merge_fleet
        if not self.region_results:
            raise ValueError("no retired region results to merge")
        regions = sorted(self.region_results)
        results = [self.region_results[r] for r in regions]
        lam_table = jnp.asarray(np.stack([r.energies for r in results]))
        total = jnp.asarray(sum(r.total_variance for r in results),
                            jnp.float32)
        qf = self.cfg.q if q_fleet is None else q_fleet
        basis = merge_fleet(lam_table, total, qf)
        W_regions = jnp.asarray(np.stack([r.components for r in results]))
        cr = self.cfg.c_max if c_regions is None else c_regions
        bill = costs.lossy_merge_cost(self.cfg.q, cr, self.cfg.link_loss,
                                      self.cfg.max_retries).communication
        return FleetSummary(
            basis=np.asarray(fleet_basis_dense(basis, W_regions)),
            region=np.asarray(basis.region),
            col=np.asarray(basis.col),
            lam=np.asarray(basis.lam),
            rho=float(basis.rho),
            regions=tuple(regions),
            merge_packets=float(bill),
        )


# ===========================================================================
# Program contracts (repro.analysis; DESIGN.md Sec. 15/17): the engine hot
# loop, synchronous and pipelined.  Static rules pin the vmapped chunk body
# (one launch per step, no host-sync primitive anywhere in the traced
# program); the runtime checks need the lowered/compiled artifact — buffer
# donation is a lowering property, retraces a jit-cache property, and the
# pipelined loop's no-host-pull claim lives on the engine's pull ledger —
# so they run a tiny interpret-mode fleet for a few steps.
# ===========================================================================
from repro.analysis import contracts as _contracts  # noqa: E402
from repro.analysis import jaxpr_lint as _jl        # noqa: E402
from repro.analysis import resources as _res        # noqa: E402

_CONTRACT_SLOTS, _CONTRACT_K, _CONTRACT_N = 2, 2, 4


def _contract_engine(pipeline: bool = False) -> StreamingPCAEngine:
    cfg = StreamConfig(p=8, q=2, halfwidth=1, warmup_rounds=2,
                       interpret=True)
    eng = StreamingPCAEngine(cfg, slots=_CONTRACT_SLOTS, seed=0,
                             chunk=_CONTRACT_K, pipeline=pipeline)
    rng = np.random.default_rng(0)
    for _ in range(_CONTRACT_SLOTS):
        eng.submit(StreamRequest(rounds=rng.normal(
            size=(6, _CONTRACT_N, cfg.p)).astype(np.float32)))
    return eng


def _contract_engine_batch(eng: StreamingPCAEngine):
    batch = jnp.zeros((eng.slots, eng.chunk, _CONTRACT_N, eng.cfg.p),
                      jnp.float32)
    rv = jnp.ones((eng.slots, eng.chunk), jnp.float32)
    return batch, rv


def _trace_engine_step():
    eng = _contract_engine()
    batch, rv = _contract_engine_batch(eng)
    jx = jax.make_jaxpr(lambda s, x, r: eng._step_fn(s, x, r))(
        eng.states, batch, rv)
    return {f"slots={eng.slots},K={eng.chunk}": jx}


def _engine_runtime_checks():
    eng = _contract_engine()
    # a FRESH (un-memoized) jitted step: the factory cache shares one
    # callable per config across all engines, so the retrace check needs
    # its own instance to see an isolated jit cache
    eng._step_fn = engine_chunk_step_fn.__wrapped__(eng.cfg)
    batch, rv = _contract_engine_batch(eng)
    results = [_contracts.donation_report(eng._step_fn, eng.states, batch,
                                          rv, argnum=0,
                                          contract="engine.step")]
    for _ in range(3):               # 6 rounds / chunk 2 = 3 same-shape steps
        eng.step()
    results.append(_contracts.retrace_report(eng._step_fn, 3,
                                             contract="engine.step"))
    return results


_contracts.register(_contracts.Contract(
    id="engine.step",
    where="repro.serve.engine.StreamingPCAEngine.step",
    claim="the vmapped chunk step launches one pallas kernel per engine "
          "step, the fleet state is donated (in-place update), and "
          "same-shape steps never retrace",
    trace=_trace_engine_step,
    rules=(_jl.PrimitiveBudget("pallas_call", exact=1),
           _jl.PrimitiveBudget("eigh", max=1),
           _jl.ForbidInLoops(everywhere=True),
           _jl.NoF64(),
           _res.VmemBudget(),
           _res.HbmTrafficBudget(max_passes=1.0)),
    runtime=_engine_runtime_checks,
))


def _trace_engine_step_pipelined():
    eng = _contract_engine(pipeline=True)
    batch, rv = _contract_engine_batch(eng)
    jx = jax.make_jaxpr(lambda s, x, r: eng._step_fn(s, x, r))(
        eng.states, batch, rv)
    return {f"slots={eng.slots},K={eng.chunk}": jx}


def _pipelined_runtime_checks():
    """The async-loop half of the contract (DESIGN.md Sec. 17): donation
    and no-retrace as on the sync path, PLUS the pipeline hygiene only an
    actual run can show — zero device→host pulls in the hot path (the
    engine's pull ledger keys every conversion), retirement being the one
    place that pulls, and prestaged chunks actually being consumed in
    steady state (the overlap exists structurally, not just in timings)."""
    eng = _contract_engine(pipeline=True)
    eng._step_fn = engine_chunk_step_fn.__wrapped__(eng.cfg)   # isolated cache
    batch, rv = _contract_engine_batch(eng)
    results = [_contracts.donation_report(eng._step_fn, eng.states, batch,
                                          rv, argnum=0,
                                          contract="engine.step.pipelined")]
    eng.run_until_done()             # 6 rounds / chunk 2 = 3 steps + drain
    results.append(_contracts.retrace_report(eng._step_fn, 3,
                                             contract="engine.step.pipelined"))
    cid = "engine.step.pipelined"
    results.append(_contracts.RuleResult(
        cid, "hot-loop:no-host-pull", eng.pulls["hot"] == 0,
        f"{eng.pulls['hot']} device pulls in the pipelined hot path over "
        f"{eng._clock} steps (want 0; retirement pulled "
        f"{eng.pulls['retire']})"))
    results.append(_contracts.RuleResult(
        cid, "hot-loop:retire-pulls-only", eng.pulls["retire"] > 0,
        f"retirement pulled {eng.pulls['retire']} scalars — the loop's "
        f"only device→host sync point"))
    results.append(_contracts.RuleResult(
        cid, "hot-loop:prestage", eng._prestage_hits >= 1,
        f"{eng._prestage_hits} prestaged chunks consumed, "
        f"{eng._prestage_misses} inline stages (want >=1 hit: the "
        f"pipeline must actually pipeline)"))
    return results


_contracts.register(_contracts.Contract(
    id="engine.step.pipelined",
    where="repro.serve.engine.StreamingPCAEngine.step",
    claim="the pipelined loop's chunk body is the same single-launch "
          "donated step (no host-sync primitive anywhere in the traced "
          "program), and at runtime the hot path makes zero device->host "
          "pulls — results are pulled at retirement only, and prestaged "
          "chunks are consumed in steady state",
    trace=_trace_engine_step_pipelined,
    rules=(_jl.PrimitiveBudget("pallas_call", exact=1),
           _jl.PrimitiveBudget("eigh", max=1),
           _jl.ForbidInLoops(everywhere=True),
           _jl.NoF64(),
           _res.VmemBudget(),
           _res.HbmTrafficBudget(max_passes=1.0)),
    runtime=_pipelined_runtime_checks,
))
