"""The async double-buffered serving engine (DESIGN.md Sec. 17).

Four claims, each pinned:

1. **Bit-identical parity** — ``pipeline=True`` reorders host work only,
   never device math: sync and pipelined engines produce byte-equal
   results (bases, metrics, Table-1 bills) across the full differential
   matrix — masked and unmasked streams, partial tail chunks, mid-chunk
   dead retirement with revival, multiple submission waves, compression
   and detection books.
2. **Queue semantics** — priority ordering, FIFO within a priority,
   per-tenant quota enforcement, bounded-queue backpressure, and full
   determinism of the admission sequence given an arrival schedule.
3. **No aliasing** — uploads are owned copies: scribbling over the pinned
   host staging buffers immediately after upload never changes device
   results (the CPU ``device_put`` zero-copy hazard).
4. **Telemetry** — the ring recorder observes the loop without touching
   it: step records, JSONL lines, latency percentiles, overlap/prestage
   accounting.
"""

from __future__ import annotations

import json

import jax
import numpy as np
import pytest

from repro.serve.engine import StreamingPCAEngine, StreamRequest
from repro.serve.queue import AdmissionQueue, QueuePolicy
from repro.serve.telemetry import StepRecord, TelemetryRecorder
from repro.streaming import CompressionConfig, DetectionConfig, StreamConfig

P, Q, N = 8, 2, 4


def _cfg(**kw):
    base = dict(p=P, q=Q, halfwidth=1, forgetting=0.9, drift_threshold=0.1,
                warmup_rounds=2, interpret=True)
    base.update(kw)
    return StreamConfig(**base)


def _req(rng, rounds=6, liveness=None, **kw):
    x = rng.normal(size=(rounds, N, P)).astype(np.float32)
    return StreamRequest(rounds=x, liveness=liveness, **kw)


def _result_fields(res):
    return {f: getattr(res, f) for f in (
        "retained", "refreshes", "comm_packets", "rounds", "reason",
        "total_variance", "compression_max_err",
        "compression_extra_packets", "compression_bits_on_air",
        "detection_events", "detection_alarm_packets",
        "detection_t2_threshold", "detection_spe_threshold")}


def assert_results_identical(a: StreamRequest, b: StreamRequest):
    assert a.done == b.done
    assert (a.result is None) == (b.result is None)
    pairs = list(zip(a.retirements, b.retirements, strict=True))
    if a.result is not None:
        pairs.append((a.result, b.result))
    for ra, rb in pairs:
        np.testing.assert_array_equal(ra.components, rb.components)
        np.testing.assert_array_equal(ra.energies, rb.energies)
        assert _result_fields(ra) == _result_fields(rb)


# ===========================================================================
# 1. Differential matrix: sync vs pipelined, bit-identical
# ===========================================================================
def _run_matrix(pipeline: bool, *, cfg=None, schedule=None, seed=3,
                slots=3, chunk=2):
    """One deterministic serving run.  ``schedule`` is a list of
    per-step submission waves (step index -> list of request builders);
    wave 0 is submitted before the first step."""
    cfg = cfg or _cfg()
    eng = StreamingPCAEngine(cfg, slots=slots, seed=0, chunk=chunk,
                             pipeline=pipeline, telemetry=True)
    rng = np.random.default_rng(seed)
    schedule = schedule or {0: [dict(rounds=6) for _ in range(6)]}
    reqs = []
    step = 0
    for wave_step in sorted(schedule):
        while step < wave_step:
            eng.step()
            step += 1
        for kw in schedule[wave_step]:
            r = _req(rng, **kw)
            reqs.append(r)
            eng.submit(r)
    eng.run_until_done()
    return eng, reqs


def _assert_parity(**kw):
    e_sync, r_sync = _run_matrix(False, **kw)
    e_pipe, r_pipe = _run_matrix(True, **kw)
    for a, b in zip(r_sync, r_pipe, strict=True):
        assert_results_identical(a, b)
    # same retirement ledger (request index + reason, in order)
    ledger = lambda eng, reqs: [(reqs.index(q), why)
                                for q, why in eng.retired_log]
    assert ledger(e_sync, r_sync) == ledger(e_pipe, r_pipe)
    assert e_pipe.pulls["hot"] == 0
    return e_sync, e_pipe


class TestParity:
    def test_unmasked(self):
        _assert_parity()

    def test_partial_tail_chunks(self):
        # lengths 5..10 against chunk=2 and 3: tails of 1 and 2 rounds
        for chunk in (2, 3):
            sched = {0: [dict(rounds=5 + i) for i in range(6)]}
            _assert_parity(schedule=sched, chunk=chunk)

    def test_masked_liveness(self):
        rng = np.random.default_rng(7)
        waves = []
        for i in range(5):
            lv = (rng.uniform(size=(7, P)) > 0.2).astype(np.float32) \
                if i % 2 == 0 else None
            waves.append(dict(rounds=7, liveness=lv))
        _assert_parity(schedule={0: waves})

    def test_mid_chunk_dead_retirement_and_revival(self):
        # all sensors die at round 3 (mid-chunk at K=2) and revive at
        # round 11: long enough dead for the 2.5-step stall verdict; the
        # network must retire dead and re-admit from the revival round
        lv = np.ones((16, P), np.float32)
        lv[3:11] = 0.0
        sched = {0: [dict(rounds=16, liveness=lv), dict(rounds=16)]}
        e_sync, e_pipe = _assert_parity(
            schedule=sched, slots=2,
            cfg=_cfg())
        reasons = [why for _, why in e_sync.retired_log]
        assert "dead" in reasons       # the schedule actually killed it

    def test_multiple_submission_waves(self):
        # late submissions land mid-serving.  A wave that arrives while a
        # slot is IDLE fills it at the next step's admission, changing the
        # slot plan under the prestaged chunk: the pipelined engine must
        # detect the signature mismatch and restage inline, never fold a
        # stale batch.  (A wave landing while all slots are busy only
        # queues — the end-of-step admit handles it before prestaging, so
        # it costs no miss.)
        sched = {0: [dict(rounds=6)],
                 2: [dict(rounds=5), dict(rounds=7)],
                 4: [dict(rounds=6)]}
        e_sync, e_pipe = _assert_parity(schedule=sched, slots=2)
        assert e_pipe._prestage_misses > 1   # waves really invalidated plans

    def test_compression_and_detection_books(self):
        cfg = _cfg(compression=CompressionConfig(epsilon=0.5,
                                                 emit_reconstruction=False),
                   detection=DetectionConfig(alpha=1e-3, calib_rounds=2))
        _assert_parity(cfg=cfg, schedule={0: [dict(rounds=8)
                                              for _ in range(5)]})

    def test_pipelined_prestages_in_steady_state(self):
        _, e_pipe = _assert_parity(
            schedule={0: [dict(rounds=10) for _ in range(3)]}, slots=3)
        assert e_pipe._prestage_hits >= 3
        assert e_pipe._transfer_fences >= 1   # double buffers really cycle


# ===========================================================================
# 2. Queue semantics
# ===========================================================================
class TestAdmissionQueue:
    def test_priority_order_fifo_within_class(self):
        q = AdmissionQueue()
        for name, pri in (("a", 0), ("b", 5), ("c", 0), ("d", 5)):
            q.submit(name, priority=pri)
        order = [q.pop_admissible({}).req for _ in range(4)]
        assert order == ["b", "d", "a", "c"]

    def test_capacity_backpressure(self):
        q = AdmissionQueue(QueuePolicy(capacity=2))
        assert q.submit("a") and q.submit("b")
        assert not q.submit("c")           # full -> rejected
        assert q.rejected == 1 and len(q) == 2
        assert q.submit("d", internal=True)   # continuations bypass
        assert len(q) == 3

    def test_tenant_quota_skips_in_place(self):
        q = AdmissionQueue(QueuePolicy(max_slots_per_tenant=1))
        q.submit("t1-a", tenant="t1", priority=9)
        q.submit("t2-a", tenant="t2")
        # t1 over quota: its top-priority entry is skipped, NOT dropped
        got = q.pop_admissible({"t1": 1})
        assert got.req == "t2-a"
        assert len(q) == 1
        # quota freed -> the skipped entry admits
        assert q.pop_admissible({"t1": 0}).req == "t1-a"

    def test_depth_by_priority(self):
        q = AdmissionQueue()
        for pri in (0, 1, 1, 2):
            q.submit("x", priority=pri)
        assert q.depth_by_priority() == {0: 1, 1: 2, 2: 1}

    def test_policy_validation(self):
        with pytest.raises(ValueError, match="capacity"):
            QueuePolicy(capacity=-1)
        with pytest.raises(ValueError, match="max_slots_per_tenant"):
            QueuePolicy(max_slots_per_tenant=0)


class TestEngineQueueFrontEnd:
    def test_priority_admission_order(self):
        eng = StreamingPCAEngine(_cfg(), slots=1, seed=0, chunk=2)
        rng = np.random.default_rng(0)
        lo, hi = _req(rng, 4, priority=0), _req(rng, 4, priority=3)
        eng.submit(lo)
        eng.submit(hi)
        eng.step()
        assert eng.active[0] is hi         # higher priority won the slot
        eng.run_until_done()
        assert lo.done and hi.done

    def test_tenant_quota_enforced_across_steps(self):
        eng = StreamingPCAEngine(
            _cfg(), slots=3, seed=0, chunk=2,
            queue=QueuePolicy(max_slots_per_tenant=1))
        rng = np.random.default_rng(1)
        mine = [_req(rng, 6, tenant="noisy") for _ in range(3)]
        other = _req(rng, 6, tenant="quiet")
        for r in mine:
            eng.submit(r)
        eng.submit(other)
        max_held = 0
        while eng.step() or eng.queue:
            held = sum(1 for q in eng.active
                       if q is not None and q.tenant == "noisy")
            max_held = max(max_held, held)
        assert max_held == 1               # never more than the quota
        assert all(r.done for r in mine) and other.done

    def test_backpressure_rejects_and_records(self):
        eng = StreamingPCAEngine(_cfg(), slots=1, seed=0, chunk=2,
                                 queue=QueuePolicy(capacity=2),
                                 telemetry=True)
        rng = np.random.default_rng(2)
        assert eng.submit(_req(rng, 4))        # queued
        assert eng.submit(_req(rng, 4))        # queued (at capacity now)
        rejected = _req(rng, 4)
        assert not eng.submit(rejected)        # bounded queue full
        assert eng.queue.rejected == 1
        kinds = [e["kind"] for e in eng.telemetry.events]
        assert "rejected" in kinds
        eng.run_until_done()
        assert not rejected.done               # caller owns the retry

    def test_revival_requeue_bypasses_capacity(self):
        lv = np.ones((14, P), np.float32)
        lv[2:10] = 0.0                         # dies, revives at round 10
        eng = StreamingPCAEngine(_cfg(), slots=1, seed=0, chunk=2,
                                 queue=QueuePolicy(capacity=0))
        rng = np.random.default_rng(3)
        req = _req(rng, 14, liveness=lv)
        # capacity 0: external submit is rejected...
        assert not eng.submit(req)
        eng2 = StreamingPCAEngine(_cfg(), slots=1, seed=0, chunk=2,
                                  queue=QueuePolicy(capacity=1))
        assert eng2.submit(req)
        eng2.run_until_done()
        # ...but the engine's own continuation re-queue is exempt: the
        # dead segment retired AND the revival segment completed
        assert req.done
        assert [r.reason for r in req.retirements] == ["dead"]

    def test_determinism_replay(self):
        def once():
            eng = StreamingPCAEngine(_cfg(), slots=2, seed=0, chunk=2,
                                     pipeline=True,
                                     queue=QueuePolicy(capacity=4),
                                     telemetry=True)
            rng = np.random.default_rng(5)
            lv = np.ones((9, P), np.float32)
            lv[3:7] = 0.0
            waves = {0: [dict(rounds=6, priority=1),
                         dict(rounds=9, liveness=lv)],
                     1: [dict(rounds=5), dict(rounds=7, priority=2)],
                     3: [dict(rounds=6)]}
            reqs, step = [], 0
            for ws in sorted(waves):
                while step < ws:
                    eng.step()
                    step += 1
                for kw in waves[ws]:
                    r = _req(rng, **kw)
                    reqs.append(r)
                    eng.submit(r)
            eng.run_until_done()
            admits = [(e["slot"], e["resume_at"], e["priority"])
                      for e in eng.telemetry.events
                      if e["kind"] == "admitted"]
            ledger = [(reqs.index(q), why) for q, why in eng.retired_log]
            return admits, ledger, [_result_fields(r.result) for r in reqs]

        assert once() == once()


# ===========================================================================
# 3. The device_put aliasing hazard (owned double buffers)
# ===========================================================================
class TestNoAliasing:
    def test_upload_is_owned_copy(self):
        eng = StreamingPCAEngine(_cfg(), slots=1, seed=0)
        host = np.ones((4, 4), np.float32)
        dev = eng._upload(host)
        # the transfer reads the host buffer asynchronously; the engine
        # waits on it (the transfer fence) before any refill, and so does
        # this poisoning — after that the device copy must not alias host
        jax.block_until_ready(dev)
        host[:] = 777.0
        np.testing.assert_array_equal(np.asarray(dev),
                                      np.ones((4, 4), np.float32))

    @pytest.mark.parametrize("pipeline", [False, True])
    def test_poisoned_staging_buffers_leave_results_unchanged(self,
                                                              pipeline):
        def run(poison):
            eng = StreamingPCAEngine(_cfg(), slots=2, seed=0, chunk=2,
                                     pipeline=pipeline)
            rng = np.random.default_rng(11)
            lv = (rng.uniform(size=(7, P)) > 0.2).astype(np.float32)
            reqs = [_req(rng, 7, liveness=lv), _req(rng, 6), _req(rng, 5)]
            for r in reqs:
                eng.submit(r)
            while eng.step() or eng.queue:
                if poison:
                    # scribble over BOTH pinned staging buffers right
                    # after the step, once their uploads' transfer fences
                    # cleared (the wait _stage makes before a refill):
                    # owned-copy uploads mean the in-flight device batches
                    # (and the prestaged chunk, in pipelined mode) must
                    # not move
                    for up in eng._uploads:
                        if up is not None:
                            jax.block_until_ready(up)
                    for buf in eng._host_bufs + eng._mask_bufs:
                        if buf is not None:
                            buf.fill(np.float32(1e9))
            return reqs

        for a, b in zip(run(False), run(True), strict=True):
            assert_results_identical(a, b)


# ===========================================================================
# 4. Telemetry
# ===========================================================================
class TestTelemetry:
    def _rec(self, i, **kw):
        base = dict(step=i, wall_s=0.01, stage_s=0.004, overlap_s=0.003,
                    prestaged=True, live=2, rounds=4, queue_depth=1,
                    admitted=0, retired=0)
        base.update(kw)
        return StepRecord(**base)

    def test_ring_is_bounded_but_totals_are_lifetime(self):
        t = TelemetryRecorder(capacity=8)
        for i in range(20):
            t.record_step(self._rec(i, rounds=2))
        assert len(t.steps) == 8
        assert t.total_steps == 20 and t.total_rounds == 40

    def test_percentiles_and_overlap(self):
        t = TelemetryRecorder()
        for i in range(10):
            t.record_step(self._rec(i, wall_s=0.01 * (i + 1)))
        pct = t.step_latency_percentiles()
        assert pct["p50"] == pytest.approx(0.055)
        assert pct["p99"] <= 0.1
        # wall-weighted overlap: 10 * 0.003 / sum(walls)
        assert t.mean_overlap_fraction() == pytest.approx(0.03 / 0.55)
        assert t.prestage_hit_rate() == 1.0

    def test_jsonl_sink(self, tmp_path):
        path = tmp_path / "telemetry.jsonl"
        with TelemetryRecorder(jsonl_path=str(path)) as t:
            t.record_step(self._rec(0))
            t.record_event("admitted", step=0, slot=1)
        lines = [json.loads(line) for line in path.read_text().splitlines()]
        assert [ln["kind"] for ln in lines] == ["step", "admitted"]
        assert lines[0]["overlap_fraction"] == pytest.approx(0.3)

    def test_reset_clears_window(self):
        t = TelemetryRecorder()
        t.record_step(self._rec(0))
        t.reset()
        assert t.total_steps == 0 and len(t.steps) == 0

    def test_sync_engine_has_zero_overlap(self):
        eng = StreamingPCAEngine(_cfg(), slots=2, seed=0, chunk=2,
                                 telemetry=True)
        rng = np.random.default_rng(0)
        for _ in range(3):
            eng.submit(_req(rng, 6))
        eng.run_until_done()
        s = eng.telemetry.summary()
        assert s["overlap_fraction"] == 0.0
        assert s["prestage_hit_rate"] == 0.0
        assert s["retired"] == 3
        assert s["rounds"] == 18

    def test_pipelined_engine_reports_overlap_and_hits(self):
        eng = StreamingPCAEngine(_cfg(), slots=2, seed=0, chunk=2,
                                 pipeline=True, telemetry=True)
        rng = np.random.default_rng(0)
        for _ in range(3):
            eng.submit(_req(rng, 6))
        eng.run_until_done()
        s = eng.telemetry.summary()
        assert s["prestage_hit_rate"] > 0.5
        assert s["overlap_fraction"] > 0.0
        assert eng.pulls["hot"] == 0
        assert eng.pulls["retire"] > 0


# ===========================================================================
# Benchmark smoke: one tiny sustained-load drive through the bench helper
# ===========================================================================
def test_engine_bench_drive_smoke():
    from benchmarks.engine_bench import _drive, _requests

    rng = np.random.default_rng(0)
    reqs = _requests(rng, 4, 6, masked=True, jitter=3)
    for r in reqs:          # bench helpers emit engine-shaped requests
        assert r.rounds.dtype == np.float32
    cfg = _cfg()
    # the bench drives (p=32) fleets; reuse its helper on the tiny config
    reqs = [_req(np.random.default_rng(1), 6) for _ in range(4)]
    warm = _req(np.random.default_rng(2), 4)
    m = _drive(cfg, slots=2, chunk=2, pipeline=True, reqs=reqs,
               warm_req=warm)
    assert m["requests_per_s"] > 0
    assert 0.0 <= m["overlap"] <= 1.0
    assert m["prestage_hit_rate"] > 0.0
