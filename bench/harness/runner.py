"""One run of one cell: set up, warm up, measure, check, print one line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The run refuses to measure anything but a TPU with at least the cell's
chips (exit 3, no result line).  Set-up builds the data from the seed, the
engine and its compiled programs (JAX's persistent compilation cache lives
in the checkout: ``repro.launch.compile_cache``), then the cell's driver
(``bench/drivers/<driver>.py``) warms up the cell's own shapes and runs the
measured window.  ``--trace 1`` wraps the window in a profiler trace and
reports the cell's per-layer metrics; ``--trace 0`` its end-to-end
metrics.  After the window the answers are checked against the float64
reference (``check.py``); every number compared is printed beside its
limit, last on standard error and last in the result line.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import shutil
import sys
import tempfile
import time

import numpy as np

from harness import check, spec
from harness import trace as tracing

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    pass


def sub_seed(seed: int, *tag: int) -> int:
    """A 31-bit seed derived from the run's seed (any size) and a tag."""
    return int(np.random.SeedSequence([seed, *tag]).generate_state(1)[0]
               & 0x7FFFFFFF)


def require_chips(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX platform is {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees "
                     f"{len(devices)}")
    return devices


@dataclasses.dataclass(eq=False)
class Stream:
    """One stream the driver offered, with the harness's host stamps."""

    req: object                     # the StreamRequest handed to the engine
    rounds: int
    due: float                      # when the schedule says it arrives
    submitted: float | None = None
    admitted: float | None = None   # start of the step that admitted it
    done: float | None = None       # end of the step that set its result


class CompileCounter:
    """Timestamps of every executable JAX compiles or loads."""

    def __init__(self):
        import jax
        self.times: list[float] = []

        def listener(event, duration, **_):
            if event == BACKEND_COMPILE_EVENT:
                self.times.append(time.perf_counter())
        self._listener = listener
        jax.monitoring.register_event_duration_secs_listener(listener)

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.times if t0 <= t < t1)

    def close(self):
        from jax._src import monitoring
        monitoring.unregister_event_duration_listener(self._listener)


class Run:
    """What a driver drives: the engine, the data, the clock and the
    measured window.  Drivers call ``new_stream``/``submit``/``step``/
    ``sleep_until`` and mark the window; everything is stamped here."""

    def __init__(self, cell: spec.Cell, seed: int, seconds: float,
                 traced: bool, config: dict | None = None):
        import jax
        from jax.profiler import TraceAnnotation
        self.jax = jax
        self.annotate = TraceAnnotation
        self.config = config or cell.config
        self.traffic = cell.traffic
        self.seed = seed
        self.seconds = seconds
        self.traced = traced
        self.rng = np.random.default_rng(sub_seed(seed, 1))
        datagen = spec.load_module("datagen", self.config["data"]["kind"])
        self.source = datagen.build(self.config, sub_seed(seed, 2))
        self.n = self.config["epochs_per_round"]
        self._eng = None
        self.compiles = CompileCounter()
        self.streams: list[Stream] = []
        self._by_req: dict[int, Stream] = {}
        self._retired_seen = 0
        self.step_spans: list[tuple[float, float]] = []
        self.window: tuple[float, float] | None = None
        self.window_steps: list = []
        self._trace_dir: str | None = None
        self._window_span = None
        self.events: list | None = None

    @property
    def eng(self):
        """The engine under test, built on first use (a driver of another
        entry point never builds it)."""
        if self._eng is None:
            from harness import system
            self._eng = system.build_engine(self.config,
                                            sub_seed(self.seed, 3))
        return self._eng

    def block(self, tree=None) -> None:
        """Wait for the device to finish ``tree`` (default: the fleet
        state of the engine)."""
        self.jax.block_until_ready(self.eng.states if tree is None else tree)

    # -- the clock and the host spans ------------------------------------
    @staticmethod
    def now() -> float:
        return time.perf_counter()

    def sleep_until(self, t: float) -> None:
        dt = t - self.now()
        if dt > 0:
            with self.annotate("bench.sleep"):
                time.sleep(dt)

    # -- streams -----------------------------------------------------------
    def new_stream(self, rounds: int, due: float) -> Stream:
        from repro.serve.engine import StreamRequest
        data = self.source.take(self.rng, rounds)
        s = Stream(req=StreamRequest(rounds=data), rounds=rounds, due=due)
        self.streams.append(s)
        self._by_req[id(s.req)] = s
        return s

    def submit(self, streams: list[Stream]) -> None:
        with self.annotate("bench.submit"):
            for s in streams:
                s.submitted = self.now()
                if not self.eng.submit(s.req):
                    raise RuntimeError("the admission queue rejected a "
                                       "stream")

    def step(self) -> int:
        """One engine step, stamped: admissions at its start, results at
        its end."""
        t0 = self.now()
        with self.annotate("bench.step"):
            live = self.eng.step()
        t1 = self.now()
        self.step_spans.append((t0, t1))
        with self.annotate("bench.scan"):
            for req in self.eng.active:
                if req is not None:
                    s = self._by_req[id(req)]
                    if s.admitted is None:
                        s.admitted = t0
            log = self.eng.retired_log
            for req, _ in log[self._retired_seen:]:
                s = self._by_req[id(req)]
                if s.admitted is None:          # admitted and retired here
                    s.admitted = t0
                if req.done and s.done is None:
                    s.done = t1
            self._retired_seen = len(log)
        return live

    def warm_up(self, step, settled, tree=None) -> None:
        """Call ``step`` until ``settled()`` holds and three calls in a row
        compiled nothing: every program the cell's traffic uses is then
        loaded.  Ends when the device has finished ``tree()`` (default:
        the engine's fleet state)."""
        quiet = 0
        for _ in range(1000):
            n = len(self.compiles.times)
            step()
            quiet = quiet + 1 if len(self.compiles.times) == n else 0
            if quiet >= 3 and settled():
                self.block(None if tree is None else tree())
                return
        raise RuntimeError("warm-up did not settle in 1000 steps")

    # -- the window --------------------------------------------------------
    def begin_trace(self) -> None:
        if self.traced:
            self._trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            self.jax.profiler.start_trace(self._trace_dir)

    def end_trace(self) -> None:
        if self._trace_dir is not None:
            self.jax.profiler.stop_trace()
            try:
                self.events = tracing.load(self._trace_dir)
            finally:
                shutil.rmtree(self._trace_dir, ignore_errors=True)
            self._trace_dir = None

    def open_window(self) -> float:
        if self._eng is not None:
            self._eng.telemetry.reset()
        self._window_span = self.annotate("bench.window")
        self._window_span.__enter__()
        t0 = self.now()
        self.window = (t0, math.inf)
        return t0

    def close_window(self, block: bool, tree=None) -> float:
        """End the window; with ``block`` it ends when the device has
        finished ``tree`` (default: the engine's fleet state)."""
        if block:
            self.block(tree)
        t1 = self.now()
        self._window_span.__exit__(None, None, None)
        self.window = (self.window[0], t1)
        if self._eng is not None:
            self.window_steps = list(self._eng.telemetry.steps)
        return t1

    def in_window(self, t: float | None) -> bool:
        return t is not None and self.window[0] <= t < self.window[1]


@dataclasses.dataclass
class Outcome:
    """What a driver hands back: the streams that owed an answer in the
    window, and how late the generator ran for each."""

    due: list[Stream]
    lateness_s: list[float]
    epochs: int                     # epochs folded inside the window
    notes: dict = dataclasses.field(default_factory=dict)
    readings: dict = dataclasses.field(default_factory=dict)  # compared
    #                                 numbers the driver reads itself


def run_cell(cell: spec.Cell, seed: int, seconds: float, traced: bool,
             t_start: float, config: dict | None = None,
             require_chip: bool = True, patch=None,
             dump_trace: str | None = None) -> dict:
    """Set up, warm up, measure and check one run; return the result
    line's fields plus ``"lines"``, the earlier lines to print.
    ``patch(run)``, if given, is applied to the built run before the
    window (the tests use it to break the timed path)."""
    import jax
    cache = "off"
    if require_chip:
        devices = require_chips(cell.chips)
        from repro.launch.compile_cache import enable_compile_cache
        cache = enable_compile_cache()
        # every program, however quick to compile, so that a second run
        # compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    else:
        devices = jax.devices()
    lines = [f"device: platform {devices[0].platform} device_kind "
             f"{devices[0].device_kind!r} count {len(devices)}; compile "
             f"cache {cache}"]
    run = Run(cell, seed, seconds, traced, config=config)
    if patch is not None:
        patch(run)
    driver = spec.load_module("drivers", run.traffic["driver"])
    outcome: Outcome = driver.drive(run)
    setup_s = run.window[0] - t_start
    mem = devices[0].memory_stats() or {}
    peak = int(mem.get("peak_bytes_in_use", 0))
    compiles = run.compiles.between(*run.window)
    run.compiles.close()
    lines.append(f"compiles inside the window: {compiles}")
    late = np.asarray(outcome.lateness_s or [0.0])
    lines.append(f"generator lateness p95 {float(np.percentile(late, 95))!r}"
                 f" s, max {float(late.max())!r} s over {late.size} "
                 "arrivals")
    lines.append(f"window {run.window[1] - run.window[0]!r} s, "
                 f"{len(run.window_steps)} steps, {len(outcome.due)} "
                 f"answers owed; {outcome.notes}")

    # -- metrics --------------------------------------------------------
    reduced = None
    if run.events is not None:
        if dump_trace:
            tracing.dump(run.events, dump_trace)
        reduced = tracing.reduce(run.events)
    view = View(run=run, outcome=outcome, reduced=reduced,
                kind=devices[0].device_kind)
    metrics = {}
    entries = cell.per_layer if traced else cell.end_to_end
    for m in entries:
        if m["name"] == "setup_s":
            value = setup_s
        else:
            value = spec.load_module("metrics", m["name"]).read(view)
        if value is None or not math.isfinite(value):
            lines.append(f"metric {m['name']}: nothing to read ({value})")
            continue
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    out = {"metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        out["breakdown"] = {"device_ops": reduced.top_ops(10),
                            "idle_gaps": reduced.top_gaps(10)}

    # -- correctness, after the window, with the engine's state freed ------
    streams = outcome.due
    del view, run, driver
    nums = check.evaluate(streams, config or cell.config, cell.check,
                          np.random.default_rng(sub_seed(seed, 4)))
    nums.update(outcome.readings)
    ok, table = check.verdict(nums, cell.limits)
    for row in table.values():
        if row["value"] is not None and not math.isfinite(row["value"]):
            row["value"] = str(row["value"])
    for name, v in nums.items():
        if name not in table:
            lines.append(f"reading {name} {v!r} (not compared)")
    failed = sum(1 for s in streams if not s.req.done
                 or s.req.result.reason != "completed")
    return {"correct": ok, "attempted": len(streams), "failed": failed,
            **out, "checks": table, "lines": lines, "readings": nums}


@dataclasses.dataclass
class View:
    """What a metric reader reads (``bench/metrics/<name>.py``)."""

    run: Run
    outcome: Outcome
    reduced: tracing.Reduced | None
    kind: str                       # device_kind, for the peaks table


def main(argv: list[str], t_start: float) -> int:
    ap = argparse.ArgumentParser(description="one benchmark run of a cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--dump-trace", metavar="PATH",
                    help="with --trace 1, also write the flattened trace "
                         "events as JSON (the tests' fixture format)")
    args = ap.parse_args(argv)
    try:
        cell = spec.load_cell(args.workload)
        res = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       t_start, dump_trace=args.dump_trace)
    except (NoChip, spec.SpecError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    res.pop("readings")
    for line in res.pop("lines"):
        print(line, flush=True)
    for name, row in res["checks"].items():
        print(f"check {name} {row['value']!r} (limit {row['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(res), flush=True)
    return 0
