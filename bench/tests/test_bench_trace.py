"""The reduction from trace events to busy time, kernel time and idle gaps,
on a hand-made trace with known answers."""

import re

import pytest

from bench_tiny import BENCH
from harness import trace

FIXTURES = BENCH / "tests" / "fixtures"


def test_hand_made_trace():
    r = trace.reduce(trace.read_dump(FIXTURES / "trace_hand.json"))
    assert r.window_s == pytest.approx(9000e-9)
    # device ops clipped to the window: [1000, 2500] [3000, 3500]
    # [8500, 10000] ns
    assert r.busy_s == pytest.approx(3500e-9)
    assert r.idle_share == pytest.approx(1 - 3500 / 9000)
    assert r.op_seconds(re.compile("_fused_kernel")) == \
        pytest.approx(2500e-9)
    assert r.top_ops(2) == [["_fused_kernel", pytest.approx(2500e-9)],
                           ["fusion.1", pytest.approx(1000e-9)]]
    assert r.top_gaps() == [["bench.sleep", pytest.approx(5000e-9)],
                            ["bench.step", pytest.approx(500e-9)]]


def test_load_reads_the_harness_spans_of_a_profile(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation
    f = jax.jit(lambda x: (x * 2.0).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with TraceAnnotation(trace.WINDOW):
            for _ in range(3):
                with TraceAnnotation("bench.step"):
                    f(x).block_until_ready()
    events = trace.load(str(tmp_path))
    names = [e.name for e in events]
    assert names.count(trace.WINDOW) == 1 and names.count("bench.step") == 3
    r = trace.reduce(events)          # the CPU has no TPU plane: no ops
    assert r.window_s > 0 and r.busy_s == 0 and not r.ops


def test_a_kernel_is_matched_by_its_stats_when_its_name_is_generic():
    window = trace.Event("/host:CPU", "python", trace.WINDOW, 0.0, 100.0)
    ops = [trace.Event("/device:TPU:0", trace.OPS_LINE, "custom-call.7", 10.0,
                       30.0, "long_name=%_fused_kernel.2 = f32[8,256] "
                             "custom-call(...)"),
           trace.Event("/device:TPU:0", trace.OPS_LINE, "fusion.3", 50.0,
                       20.0, "long_name=%fusion.3 = f32[8] fusion(...)")]
    r = trace.reduce([window, *ops])
    assert r.op_seconds(re.compile("_fused_kernel")) == pytest.approx(30e-9)
    assert r.busy_s == pytest.approx(50e-9)
