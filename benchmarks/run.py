# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV rows; --full widens the CV folds and range sweeps to paper scale;
# --smoke shrinks every sweep to a seconds-scale pass and adds the resource
# certifier.  A benchmark that raises fails the run (exit 1) in every mode.
from __future__ import annotations

import argparse
import os
import re
import sys

# committed streaming throughput baseline (smoke settings); regenerate with
#   python benchmarks/run.py --only streaming --smoke \
#       --streaming-json benchmarks/baselines/BENCH_streaming.json
_STREAMING_BASELINE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "baselines",
    "BENCH_streaming.json")

# committed serving-engine throughput baseline (smoke settings); regenerate
#   python benchmarks/run.py --only engine --smoke \
#       --engine-json benchmarks/baselines/BENCH_engine.json
_ENGINE_BASELINE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "baselines",
    "BENCH_engine.json")

# a measured rounds/s below this fraction of the committed baseline fails
# the run — the fail-loud guard against silently shipping a slow hot loop
_REGRESSION_FLOOR = 0.8

# pipelined rows on fleets at least this large must show at least this
# staged-vs-compute overlap — the pipeline must actually pipeline (gate
# arms only on hosts where overlap is physically possible)
_OVERLAP_MIN_SLOTS = 16
_OVERLAP_FLOOR = 0.10


def _rounds_per_sec(derived: str) -> float | None:
    m = re.match(r"^(\d+(?:\.\d+)?) rounds/s", str(derived))
    return float(m.group(1)) if m else None


def check_streaming_regression(rows: list,
                               baseline_path: str) -> list[tuple[str, str]]:
    """Compare this run's rounds/s rows against the committed baseline.

    Returns one ``(rule, detail)`` failure per row whose throughput fell
    below ``_REGRESSION_FLOOR`` x baseline — ``rule`` names the offending
    row (``regression:<row-name>``), ``detail`` carries measured vs.
    baseline in one line.  Rows without a rounds/s figure (the
    threshold-frontier rows) and names absent from the baseline (new
    sweeps, different fleet sizes) are skipped — the gate only ever
    compares like with like.
    """
    import json
    with open(baseline_path) as fh:
        base = {r["name"]: _rounds_per_sec(r["derived"])
                for r in json.load(fh)}
    failures = []
    for r in rows:
        rps = _rounds_per_sec(r["derived"])
        ref = base.get(r["name"])
        if rps is None or ref is None or ref <= 0:
            continue
        if rps < _REGRESSION_FLOOR * ref:
            failures.append((
                f"regression:{r['name']}",
                f"measured {rps:.0f} rounds/s vs baseline {ref:.0f} rounds/s "
                f"({rps / ref:.2f}x < {_REGRESSION_FLOOR:.2f}x floor)"))
    return failures


def check_engine_regression(rows: list,
                            baseline_path: str) -> list[tuple[str, str]]:
    """Compare this run's engine requests/s against the committed baseline.

    Engine rows carry machine-readable fields (``requests_per_s``), so the
    gate reads numbers instead of parsing the derived string.  Speedup
    rows and names absent from the baseline are skipped — the gate only
    compares like with like.
    """
    import json
    with open(baseline_path) as fh:
        base = {r["name"]: r.get("requests_per_s") for r in json.load(fh)}
    failures = []
    for r in rows:
        rps = r.get("requests_per_s")
        ref = base.get(r["name"])
        if rps is None or ref is None or ref <= 0:
            continue
        if rps < _REGRESSION_FLOOR * ref:
            failures.append((
                f"regression:{r['name']}",
                f"measured {rps:.0f} req/s vs baseline {ref:.0f} req/s "
                f"({rps / ref:.2f}x < {_REGRESSION_FLOOR:.2f}x floor)"))
    return failures


def check_engine_overlap(rows: list) -> list[tuple[str, str]]:
    """Pipelined rows on fleets >= 16 slots must measure >= 10% overlap
    — parity alone doesn't prove the pipeline pipelines.  The gate arms
    only where overlap is physically possible (``pipeline_capable``: an
    accelerator backend or a multi-core host); on a single-core CPU host
    staging and compute share the core, so the gate prints its verdict as
    informational instead of silently passing."""
    failures = []
    for r in rows:
        if r.get("mode") != "pipe" or r.get("slots", 0) < _OVERLAP_MIN_SLOTS:
            continue
        overlap = r.get("overlap")
        if overlap is None:
            continue
        if not r.get("pipeline_capable", False):
            print(f"run.py/INFO,overlap:{r['name']},single-core host "
                  f"(cores={r.get('cores')}): overlap gate vacuous, "
                  f"measured {overlap:.3f}")
            continue
        if overlap < _OVERLAP_FLOOR:
            failures.append((
                f"overlap:{r['name']}",
                f"measured overlap {overlap:.3f} < {_OVERLAP_FLOOR:.2f} "
                f"floor on a {r.get('slots')}-slot fleet "
                f"(the pipeline isn't pipelining)"))
    return failures


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", help="substring filter on benchmark module")
    ap.add_argument("--full", action="store_true",
                    help="paper-scale settings (10-fold CV, all ranges)")
    ap.add_argument("--smoke", action="store_true",
                    help="minimal settings plus the resource certifier "
                         "(CI mode)")
    ap.add_argument("--compression-json",
                    help="also write the compression rows gathered during "
                         "this run to a JSON artifact (avoids re-running "
                         "the sweep just for the CI artifact)")
    ap.add_argument("--events-json",
                    help="also write the event-detection rows gathered "
                         "during this run to a JSON artifact")
    ap.add_argument("--streaming-json",
                    help="also write the streaming-fleet rows (throughput, "
                         "chunk sweep) gathered during this run to a JSON "
                         "artifact")
    ap.add_argument("--scale-json",
                    help="also write the hierarchical weak-scaling rows "
                         "(regions sweep + wsn-1m smoke replica) gathered "
                         "during this run to a JSON artifact")
    ap.add_argument("--engine-json",
                    help="also write the serving-engine sustained-load "
                         "rows (requests/s, p99, overlap fraction) "
                         "gathered during this run to a JSON artifact")
    ap.add_argument("--streaming-baseline", default=_STREAMING_BASELINE,
                    help="committed rounds/s baseline to gate against "
                         "(>20%% regression fails the run); pass an empty "
                         "string to skip the gate")
    ap.add_argument("--engine-baseline", default=_ENGINE_BASELINE,
                    help="committed requests/s baseline to gate against "
                         "(>20%% regression fails the run); pass an empty "
                         "string to skip the gate")
    args = ap.parse_args()
    if args.full and args.smoke:
        ap.error("--full and --smoke are mutually exclusive")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (compression_bench, engine_bench, event_bench,
                            fault_bench, fig7_retained_variance,
                            fig9_comm_costs, fig11_local_cov,
                            fig13_pim_convergence, fig14_load_vs_q,
                            kernels_bench, scale_bench, streaming_bench,
                            table1_complexity)

    modules = {
        "fig7": lambda: fig7_retained_variance.run(
            k_folds=10 if args.full else (2 if args.smoke else 3)),
        "fig9": fig9_comm_costs.run,
        "fig11": fig11_local_cov.run,
        "fig13": fig13_pim_convergence.run,
        "fig14": fig14_load_vs_q.run,
        "table1": table1_complexity.run,
        "kernels": lambda: kernels_bench.run(smoke=args.smoke),
        "streaming": lambda: streaming_bench.run(smoke=args.smoke),
        "fault": lambda: fault_bench.run(smoke=args.smoke),
        "compression": lambda: compression_bench.run(smoke=args.smoke),
        "events": lambda: event_bench.run(smoke=args.smoke),
        "scale": lambda: scale_bench.run(smoke=args.smoke),
        "engine": lambda: engine_bench.run(smoke=args.smoke),
    }

    # every gate failure is a named (rule, detail) pair so the final verdict
    # can say exactly which rule/row failed and why, in one line each
    bench_errors: list[tuple[str, str]] = []
    artifact_errors: list[tuple[str, str]] = []
    regressions: list[tuple[str, str]] = []
    gathered: dict[str, list] = {"compression": [], "events": [],
                                 "streaming": [], "scale": [], "engine": []}
    print("name,us_per_call,derived")
    for name, fn in modules.items():
        if args.only and args.only not in name:
            continue
        try:
            for r in fn():
                print(f"{r['name']},{r['us_per_call']},{r['derived']}")
                if name in gathered:
                    gathered[name].append(r)
        except Exception as e:  # noqa: BLE001 — report and continue
            bench_errors.append((f"bench-error:{name}",
                                 f"{type(e).__name__}: {e}"))
    # a requested JSON artifact with NO gathered rows means the benchmark
    # silently never ran (filtered out, or it errored above): fail loudly —
    # an empty BENCH_* trajectory is indistinguishable from a healthy one
    for name, path, rows in (
            ("compression", args.compression_json, gathered["compression"]),
            ("events", args.events_json, gathered["events"]),
            ("streaming", args.streaming_json, gathered["streaming"]),
            ("scale", args.scale_json, gathered["scale"]),
            ("engine", args.engine_json, gathered["engine"])):
        if not path:
            continue
        if not rows:
            artifact_errors.append((
                f"empty-artifact:{name}",
                f"requested JSON artifact {path} but the benchmark emitted "
                f"no rows (never ran?)"))
            continue
        import json
        with open(path, "w") as fh:
            json.dump(rows, fh, indent=2)
    # rounds/s regression gate: ANY streaming row more than 20% below the
    # committed baseline fails the run outright (not just under --smoke) —
    # a quiet throughput cliff on the hot loop must never merge silently
    if (gathered["streaming"] and args.streaming_baseline
            and os.path.exists(args.streaming_baseline)):
        regressions = check_streaming_regression(gathered["streaming"],
                                                 args.streaming_baseline)
    # serving-engine gates: requests/s regression vs the committed baseline
    # (structured fields, no regex) and the overlap floor on big pipelined
    # fleets — both always fatal, like the streaming gate
    if (gathered["engine"] and args.engine_baseline
            and os.path.exists(args.engine_baseline)):
        regressions += check_engine_regression(gathered["engine"],
                                               args.engine_baseline)
    if gathered["engine"]:
        regressions += check_engine_overlap(gathered["engine"])
    # static resource certifier (repro.analysis.resources): under --smoke
    # the derived VMEM/HBM/wire bills must still match the committed
    # analysis/baselines/resources.json — a perf run whose traced resource
    # bill drifted from the blessed one is reporting numbers for a
    # different program, so the drift is as fatal as a bench error
    resource_errors: list[tuple[str, str]] = []
    if args.smoke:
        try:
            from repro.analysis.check import resource_failures
            resource_errors = resource_failures()
        except Exception as e:  # noqa: BLE001 — certifier crash is a finding
            resource_errors = [("resources:driver",
                                f"{type(e).__name__}: {e}")]
    # every failure is fatal: a benchmark that raised, a missing artifact,
    # a regression, a certifier drift (--smoke)
    fatal = regressions + bench_errors + artifact_errors + resource_errors
    for rule, detail in fatal:
        print(f"run.py/FAIL,{rule},{detail}", file=sys.stdout)
    if fatal:
        print("run.py verdict: FAILED — "
              + "; ".join(rule for rule, _ in fatal), file=sys.stdout)
    sys.stdout.flush()
    return 1 if fatal else 0


if __name__ == "__main__":
    sys.exit(main())
