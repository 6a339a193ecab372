"""Device time under the program's named scopes and the readers built on
it: a hand-made trace whose device ops are named as a TPU names them (by
their HLO instruction), beside the compiled text of the step they came
from; and the real compiled step of a tiny engine."""

import types

import numpy as np
import pytest

from bench_tiny import BENCH
from harness import runner, scopes, spec, trace

FIXTURES = BENCH / "tests" / "fixtures"
NS = 1e-9


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(trace.read_dump(FIXTURES / "trace_scoped.json"))


@pytest.fixture(scope="module")
def names():
    return scopes.op_names((FIXTURES / "step_scoped.hlo.txt").read_text())


def _view(reduced, steps, names=None, run=None):
    run = run or types.SimpleNamespace()
    run.window_steps = steps
    v = runner.View(run=run, outcome=None, reduced=reduced,
                    kind="TPU v5 lite")
    if names is not None:
        v._step_op_names = names
    return v


def _step(live, retire_s=0.0):
    return types.SimpleNamespace(live=live, retire_s=retire_s)


def test_the_reduction_reads_the_kernel_as_before(reduced):
    # busy: [0, 4000] [4600, 5200] [8000, 9500]; the loop body's op
    # [2600, 3300] lies inside the loop's event [2500, 4000]
    assert reduced.window_s == pytest.approx(10000 * NS)
    assert reduced.busy_s == pytest.approx(6100 * NS)
    roof = spec.load_module("roofline", "fused_stream")
    assert reduced.op_seconds(roof.TRACE_NAME) == pytest.approx(1000 * NS)


def test_instruction_keys_ignore_layouts_and_operands():
    event = ("%while.2 = (s32[]{:T(128)}, f32[8,16,2]{2,1,0:T(8,128)}) "
             "while((s32[]{:T(128)}, f32[8,16,2]{2,1,0:T(8,128)}) %t), "
             "condition=%c, body=%b")
    line = ("  ROOT %while.2 = (s32[], f32[8,16,2]{2,1,0}) while(%t), "
            "condition=%c, body=%b, metadata={op_name=\"a/pca.refresh/w\"}")
    assert scopes._key(event) == scopes._key(line) == \
        ("%while.2", "while", "(s32[],f32[8,16,2])")
    assert scopes.op_names(line) == {scopes._key(line): "a/pca.refresh/w"}
    assert scopes._key("ENTRY %main.1 (p: f32[2]) -> f32[2] {") is None


@pytest.mark.parametrize("scope,ns", [("pca.probe", 1000),
                                      ("pca.fold", 1500),
                                      ("pca.refresh", 1800),
                                      ("pca.twin", 400),
                                      ("pca.books", 200),
                                      ("pca.refreshed", 200),
                                      ("pca.re", 0),
                                      ("pca.admit", 0)])
def test_scope_seconds(reduced, names, scope, ns):
    assert scopes.seconds(reduced, names, scope) == pytest.approx(ns * NS)


def test_scope_table_and_line(reduced, names):
    table = scopes.table(reduced, names)
    # copy.1 is the step's, outside every scope; the second fusion.1 has
    # another shape: another program's op of the same name
    assert table == pytest.approx({
        "pca.probe": 1000 * NS, "pca.fold": 1500 * NS,
        "pca.refresh": 1800 * NS, "pca.twin": 400 * NS,
        "pca.books": 200 * NS, "pca.refreshed": 200 * NS,
        scopes.UNSCOPED: 500 * NS, scopes.OTHER: 500 * NS})
    line = scopes.line(reduced, names)
    # the loop body's op is counted once, in its loop: the table adds up
    # to the busy time
    assert sum(table.values()) == pytest.approx(reduced.busy_s)
    assert line.startswith("device seconds per scope: pca.refresh ")
    assert "(unscoped) " in line and line.endswith(" s")


def test_device_scope_readers(reduced, names):
    read = {name: spec.load_module("metrics", name).read
            for name in ("refresh_device_ms_per_chunk",
                         "twin_device_ms_per_chunk")}
    steps = [_step(2), _step(0), _step(1)]
    assert read["refresh_device_ms_per_chunk"](
        _view(reduced, steps, names)) == pytest.approx(1e3 * 1800 * NS / 2)
    assert read["twin_device_ms_per_chunk"](
        _view(reduced, steps, names)) == pytest.approx(1e3 * 400 * NS / 2)
    # a step without the scopes, no folded step, no trace, no device op:
    # nothing to read
    bare = scopes.op_names("  %fusion.1 = f32[8,16,2]{2,1,0} fusion(%p), "
                           "metadata={op_name=\"jit(body)/mul\"}")
    no_ops = trace.reduce(trace.read_dump(FIXTURES / "trace_scoped.json")[:8])
    for f in read.values():
        assert f(_view(reduced, [_step(2)], bare)) is None
        assert f(_view(reduced, [_step(0)], names)) is None
        assert f(_view(None, [_step(2)], names)) is None
        assert f(_view(no_ops, [_step(2)])) is None


def test_retire_reader():
    read = spec.load_module("metrics", "retire_ms_per_chunk.epochs").read
    steps = [_step(2, 0.004), _step(0, 0.5), _step(1, 0.002)]
    assert read(_view(None, steps)) == pytest.approx(3.0)
    assert read(_view(None, [_step(0)])) is None
    # telemetry without the phase (a program that does not time it)
    assert read(_view(None, [types.SimpleNamespace(live=2)])) is None


def test_the_engine_step_is_read_back_with_its_scopes():
    from repro.serve.engine import StreamingPCAEngine
    from repro.streaming.compressor import CompressionConfig
    from repro.streaming.detector import DetectionConfig
    from repro.streaming.driver import StreamConfig
    cfg = StreamConfig(p=16, q=2, halfwidth=2, warmup_rounds=2,
                       interpret=True,
                       compression=CompressionConfig(epsilon=0.5),
                       detection=DetectionConfig(alpha=1e-3, calib_rounds=3))
    eng = StreamingPCAEngine(cfg, slots=2, chunk=2, pipeline=True)
    v = _view(None, [], run=types.SimpleNamespace(eng=eng, n=4))
    names = scopes.step_op_names(v)
    assert scopes.step_op_names(v) is names         # read once per run
    found = {s for op in names.values() for s in scopes.SCOPE_NAME.findall(op)}
    assert {"pca.fold", "pca.probe", "pca.refresh", "pca.twin",
            "pca.books"} <= found
    # device ops named by instructions of the step carry their scopes
    while_ops = [k for k, op in names.items()
                 if k[1] == "while" and "pca.refresh" in op]
    assert while_ops
    name, opcode, shape = while_ops[0]
    event = trace.Event("/device:TPU:0", trace.OPS_LINE,
                        f"{name} = {shape} {opcode}(%t), body=%b", 0.0, 50.0)
    window = trace.Event("/host:CPU", "python", trace.WINDOW, 0.0, 100.0)
    r = trace.reduce([window, event])
    assert scopes.seconds(r, names, "pca.refresh") == pytest.approx(50 * NS)
    assert np.isclose(scopes.table(r, names)["pca.refresh"], 50 * NS)
