"""The engine's instrumentation: named scopes on its device programs, host
phase spans on the profiler's clock, the phase times of each StepRecord,
the request stamps on retirement events, and the telemetry summary's
rate."""

from __future__ import annotations

import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.serve.engine import (StreamingPCAEngine, StreamRequest,
                                _admit_splice_fn, _books_fn,
                                _slot_summary_fn)
from repro.serve.telemetry import PhaseTimer, StepRecord, TelemetryRecorder
from repro.streaming.compressor import CompressionConfig
from repro.streaming.detector import DetectionConfig
from repro.streaming.driver import (StreamConfig, engine_chunk_step_fn,
                                    stream_init)

P, Q, N, SLOTS, K = 16, 2, 4, 2, 2


def _cfg() -> StreamConfig:
    return StreamConfig(p=P, q=Q, halfwidth=2, forgetting=0.9,
                        warmup_rounds=2, interpret=True,
                        compression=CompressionConfig(epsilon=0.5),
                        detection=DetectionConfig(alpha=1e-3,
                                                  calib_rounds=3))


def _op_names(lowered) -> set[str]:
    return set(re.findall(r'loc\("([^"]*)"',
                          lowered.as_text(debug_info=True)))


@pytest.fixture(scope="module")
def fleet():
    cfg = _cfg()
    keys = jax.random.split(jax.random.PRNGKey(0), SLOTS)
    states = jax.vmap(lambda k: stream_init(cfg, k))(keys)
    return cfg, states


@pytest.fixture(scope="module")
def step_op_names(fleet):
    cfg, states = fleet
    x = jnp.zeros((SLOTS, K, N, P), jnp.float32)
    rv = jnp.ones((SLOTS, K), jnp.float32)
    return _op_names(engine_chunk_step_fn(cfg).lower(states, x, rv))


@pytest.mark.parametrize("scope", ["pca.fold", "pca.probe", "pca.refresh",
                                   "pca.twin", "pca.books"])
def test_chunk_step_ops_carry_each_scope(step_op_names, scope):
    assert any(re.search(rf"(?<![\w.]){re.escape(scope)}(?![\w.])", name)
               for name in step_op_names), scope


def test_the_fold_kernel_sits_in_the_fold_scope(step_op_names):
    kernel = [n for n in step_op_names if "jit(_fused_stream)" in n]
    assert kernel and all("vmap(pca.fold)/jit(_fused_stream)" in n
                          for n in kernel)
    # no scope name can be read as the kernel's name
    assert not any(re.search(r"pca\.\w*fused", n) for n in step_op_names)


def test_engine_programs_carry_their_scopes(fleet):
    cfg, states = fleet
    books = tuple(jnp.zeros(SLOTS, jnp.float32) for _ in range(3))
    det_books = books[:2]
    mask = np.zeros(SLOTS, bool)
    admit = _op_names(_admit_splice_fn(SLOTS).lower(
        states, states, books, det_books, mask))
    assert any("pca.admit" in n for n in admit)
    refresh_books = (jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32))
    retire = _op_names(_slot_summary_fn(cfg).lower(
        states, books, det_books, refresh_books, np.int32(0)))
    assert any("pca.retire" in n for n in retire)
    x = jnp.zeros((SLOTS, K, N, P), jnp.float32)
    rv = jnp.ones((SLOTS, K), jnp.float32)
    _, metrics = engine_chunk_step_fn(cfg)(
        jax.tree.map(jnp.copy, states), x, rv)
    acc = _op_names(_books_fn(True, True, 1.5).lower(
        books, det_books, refresh_books, metrics.compression,
        metrics.detection, metrics.did_refresh, metrics.refresh_slots,
        np.ones(SLOTS, np.float32)))
    assert any("pca.books" in n for n in acc)


def _engine(pipeline: bool, telemetry=True) -> StreamingPCAEngine:
    eng = StreamingPCAEngine(_cfg(), slots=SLOTS, seed=0, chunk=K,
                             pipeline=pipeline, telemetry=telemetry)
    rng = np.random.default_rng(0)
    for rounds in (6, 4, 8):
        eng.submit(StreamRequest(rounds=rng.normal(
            size=(rounds, N, P)).astype(np.float32)))
    return eng


PHASES = ("admit_s", "stage_s", "overlap_s", "fence_s", "dispatch_s",
          "bookkeep_s", "retire_s")


@pytest.mark.parametrize("pipeline", [False, True])
def test_step_record_phases(pipeline, monkeypatch):
    eng = _engine(pipeline)
    stages = []
    real_stage = eng._stage

    def counted_stage():
        stages.append(eng._clock)
        return real_stage()
    monkeypatch.setattr(eng, "_stage", counted_stage)
    eng.run_until_done()
    recs = list(eng.telemetry.steps)
    assert recs
    for r in recs:
        assert all(getattr(r, f) >= 0.0 for f in PHASES), r
        assert r.fence_s <= r.stage_s
        # the phases are disjoint (the fence lies inside the stage)
        assert (r.admit_s + r.stage_s + r.dispatch_s + r.bookkeep_s
                + r.retire_s) <= r.wall_s
        staged_here = r.step in stages
        assert (r.stage_s > 0) == staged_here
        if not pipeline:
            assert r.overlap_s == 0.0
        elif r.prestaged:
            # no inline stage: the step's staging is all prestage
            assert r.stage_s == r.overlap_s
        if r.live:
            assert r.dispatch_s > 0 and r.bookkeep_s > 0
        if r.retired:
            assert r.retire_s > 0
    if pipeline:
        assert any(r.fence_s > 0 for r in recs)


def test_phase_timer_nests_and_sums():
    timer = PhaseTimer()
    with timer.span("stage") as outer:
        with timer.span("fence") as inner:
            pass
    with timer.span("stage") as again:
        pass
    assert inner.seconds <= outer.seconds
    assert timer.get("stage") == outer.seconds + again.seconds
    assert timer.get("fence") == inner.seconds
    assert timer.get("retire") == 0.0


def test_retired_events_carry_queue_wait_and_service():
    eng = _engine(pipeline=True)
    eng.run_until_done()
    retired = [e for e in eng.telemetry.events if e["kind"] == "retired"]
    assert len(retired) == 3
    for e in retired:
        assert e["queue_wait_s"] >= 0.0 and e["service_s"] > 0.0
    for req, _ in eng.retired_log:
        assert req.submitted_s <= req.admitted_s <= req.finished_s


def test_summary_rate_is_over_elapsed_time():
    t = TelemetryRecorder()
    base = dict(stage_s=0.0, overlap_s=0.0, prestaged=False, live=1,
                queue_depth=0, admitted=0, retired=0)
    # two steps of 10 ms each, 80 ms apart: 20 rounds over 90 ms
    t.record_step(StepRecord(step=1, wall_s=0.01, rounds=10, start_s=5.0,
                             **base))
    t.record_step(StepRecord(step=2, wall_s=0.01, rounds=10, start_s=5.08,
                             **base))
    s = t.summary()
    assert t.elapsed_s() == pytest.approx(0.09)
    assert s["rounds_per_s"] == pytest.approx(20 / 0.09)
    assert s["wall_s"] == pytest.approx(0.02)
    t.reset()
    assert t.elapsed_s() == 0.0 and t.summary()["rounds_per_s"] == 0.0


def _spans(trace_dir) -> list[tuple[str, float, float]]:
    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    assert len(path) == 1
    out = []
    for plane in ProfileData.from_file(path[0]).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("bench.", "engine.")):
                    out.append((e.name, float(e.start_ns),
                                float(e.start_ns + e.duration_ns)))
    return out


def test_engine_spans_nest_under_the_callers_span(tmp_path):
    eng = _engine(pipeline=True)
    eng.step()                                   # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        with TraceAnnotation("bench.window"):
            for _ in range(3):
                with TraceAnnotation("bench.step"):
                    eng.step()
    spans = _spans(tmp_path)
    steps = [s for s in spans if s[0] == "bench.step"]
    engine = [s for s in spans if s[0].startswith("engine.")]
    assert len(steps) == 3
    names = {s[0] for s in engine}
    assert {"engine.admit", "engine.dispatch", "engine.bookkeep",
            "engine.stage", "engine.retire"} <= names
    assert "engine.pull" in names and "engine.fence" in names
    for _, a, b in engine:
        assert any(sa <= a and b <= sb for _, sa, sb in steps)
    for _, a, b in (s for s in engine if s[0] == "engine.pull"):
        assert any(ra <= a and b <= rb for n, ra, rb in engine
                   if n == "engine.retire")
