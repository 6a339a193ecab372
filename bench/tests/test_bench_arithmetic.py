"""The rate, latency and per-layer arithmetic, over every stream and every
step of the window."""

import math
import types

import pytest

from bench_tiny import spec
from harness import latency, runner


def _stream(due, admitted, done, rounds=8, refreshes=1):
    result = types.SimpleNamespace(refreshes=refreshes, reason="completed")
    req = types.SimpleNamespace(done=done is not None, result=result)
    return runner.Stream(req=req, rounds=rounds, due=due, submitted=due,
                         admitted=admitted, done=done)


def _step(rounds, live, stage_s=0.0, prestaged=False):
    return types.SimpleNamespace(rounds=rounds, live=live, stage_s=stage_s,
                                 prestaged=prestaged)


def _view(streams=(), steps=(), window=(10.0, 12.0), epochs=0,
          spans=(), n=4, chunk=4):
    run = types.SimpleNamespace(
        window=window, window_steps=list(steps), step_spans=list(spans), n=n,
        config={"engine": {"chunk": chunk}},
        in_window=lambda t: t is not None and window[0] <= t < window[1])
    outcome = runner.Outcome(due=list(streams), lateness_s=[], epochs=epochs)
    return runner.View(run=run, outcome=outcome, reduced=None,
                       kind="TPU v5 lite")


def _read(metric, view):
    return spec.load_module("metrics", metric).read(view)


def test_epochs_per_s_is_all_epochs_over_the_whole_window():
    assert _read("epochs_per_s", _view(epochs=1000)) == pytest.approx(500.0)
    assert _read("epochs_per_s", _view(epochs=0)) is None


def test_latency_percentiles_over_every_owed_stream():
    streams = [_stream(due=float(i), admitted=i + 0.5, done=i + 1.0 + i)
               for i in range(20)]
    lat = [1.0 + i for i in range(20)]
    v = _view(streams)
    assert _read("stream_latency_p50_s", v) == pytest.approx(10.5)
    assert _read("stream_latency_p95_s", v) == pytest.approx(
        lat[18] + 0.05 * (lat[19] - lat[18]))
    # an answer that never came is infinitely late, never left out
    v = _view(streams + [_stream(due=0.0, admitted=None, done=None)] * 2)
    assert math.isinf(_read("stream_latency_p95_s", v))
    assert math.isinf(_read("queue_wait_p95_s", v))
    assert latency.percentile([], 50) is None


def test_queue_wait_is_due_to_admission():
    streams = [_stream(due=0.0, admitted=0.1 * i, done=5.0)
               for i in range(1, 21)]
    assert _read("queue_wait_p95_s", _view(streams)) == pytest.approx(
        1.9 + 0.05 * 0.1)


def test_host_step_ms_counts_steps_begun_in_the_window():
    spans = [(9.0, 10.5), (10.0, 10.25), (11.0, 11.75), (12.0, 12.5)]
    assert _read("host_step_ms.latency", _view(spans=spans)) == \
        pytest.approx(500.0)


def test_staging_readers_skip_idle_steps():
    steps = [_step(16, 4, 0.010, False), _step(16, 4, 0.030, True),
             _step(0, 0, 0.500, False)]
    v = _view(steps=steps)
    assert _read("stage_ms_per_chunk.epochs", v) == pytest.approx(20.0)
    assert _read("prestage_hit_rate.epochs", v) == pytest.approx(50.0)


def test_refresh_share_is_refreshes_over_chunk_decisions():
    streams = [_stream(0.0, 0.0, 1.0, rounds=8, refreshes=1),
               _stream(0.0, 0.0, 1.0, rounds=9, refreshes=2)]
    # decisions: ceil(8/4) + ceil(9/4) = 2 + 3
    assert _read("refresh_fired_share", _view(streams)) == \
        pytest.approx(60.0)


def test_trace_readers_read_nothing_without_a_trace():
    v = _view(steps=[_step(16, 4)])
    for m in ("fold_kernel_ms_per_chunk", "fold_kernel_roofline",
              "nonkernel_device_ms_per_chunk", "idle_share.epochs",
              "idle_share.latency"):
        assert _read(m, v) is None
