"""Compile a cell's engine step for a described TPU v5e, without a chip.

    JAX_PLATFORMS=cpu python3 bench/tools/compile_v5e.py --workload <cell>
    JAX_PLATFORMS=cpu python3 bench/tools/compile_v5e.py --fleet4 <config>

The engine step (the vmapped chunk step the window drives, fleet state
donated) is lowered at the cell's real sizes and compiled by the TPU
compiler for one device of a described ``v5e:2x2``; ``--fleet4`` compiles
``hierarchical_stream_run`` of a fleet configuration over all four devices
of the ``region`` mesh.  Prints the compile time, whether a Mosaic kernel
is in the program, and ``memory_analysis()``.  Nothing runs, so nothing
here is a time or a result of the chip.

The kernels pick their tiling and interpret mode from
``jax.default_backend()``, which is the CPU here; the tool answers "tpu"
while it traces, so the program is the one the chip would compile.
"""

import os
import pathlib
import sys
import time
from unittest import mock

os.environ.setdefault("TPU_LOG_DIR", "disabled")
_BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(_BENCH), str(_BENCH.parent / "src")]

from harness import spec, system  # noqa: E402


def _report(compiled, t0: float) -> None:
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    gib = 2.0 ** 30
    print(f"compile {time.perf_counter() - t0:.1f} s; tpu_custom_call "
          f"{'tpu_custom_call' in text}; all-gather {'all-gather' in text}; "
          f"all-reduce {'all-reduce' in text}")
    print(f"memory per device: arguments {mem.argument_size_in_bytes / gib:.3f}"
          f" GiB, outputs {mem.output_size_in_bytes / gib:.3f} GiB, "
          f"temporaries {mem.temp_size_in_bytes / gib:.3f} GiB, aliased "
          f"{mem.alias_size_in_bytes / gib:.3f} GiB, generated code "
          f"{mem.generated_code_size_in_bytes / gib:.4f} GiB")


def engine_step(cell: spec.Cell, device) -> None:
    import jax
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from repro.streaming.driver import engine_chunk_step_fn, stream_init
    cfg = system.stream_config(cell.config)
    e = cell.config["engine"]
    slots, k, n = e["slots"], e["chunk"], cell.config["epochs_per_round"]
    one = SingleDeviceSharding(device)
    states = jax.eval_shape(
        lambda key: jax.vmap(lambda s: stream_init(cfg, s))(
            jax.random.split(key, slots)), jax.random.PRNGKey(0))
    states = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
        states)
    batch = jax.ShapeDtypeStruct((slots, k, n, cfg.p), np.float32,
                                 sharding=one)
    rv = jax.ShapeDtypeStruct((slots, k), np.float32, sharding=one)
    t0 = time.perf_counter()
    compiled = engine_chunk_step_fn(cfg).lower(states, batch, rv).compile()
    _report(compiled, t0)


def fleet4(config: dict, devices) -> None:
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.streaming.hierarchy import (hierarchical_stream_init,
                                           hierarchical_stream_run)
    cfg = system.stream_config(config)
    f = config["fleet"]
    mesh = Mesh(np.asarray(devices[:f["chips"]]), ("region",))
    lead = NamedSharding(mesh, P("region"))
    states = jax.eval_shape(lambda key: hierarchical_stream_init(
        cfg, key, f["regions"]), jax.random.PRNGKey(0))
    states = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=lead),
        states)
    xs = jax.ShapeDtypeStruct(
        (f["regions"], f["rounds_per_call"], config["epochs_per_round"],
         cfg.p), np.float32, sharding=lead)
    t0 = time.perf_counter()
    compiled = hierarchical_stream_run.lower(
        cfg, mesh, states, xs, chunk=f["chunk"]).compile()
    _report(compiled, t0)


def main() -> int:
    import argparse
    ap = argparse.ArgumentParser()
    group = ap.add_mutually_exclusive_group(required=True)
    group.add_argument("--workload")
    group.add_argument("--fleet4", metavar="CONFIG")
    args = ap.parse_args()
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    as_tpu = mock.patch("jax.default_backend", return_value="tpu")
    if args.workload:
        with as_tpu:
            engine_step(spec.load_cell(args.workload), topo.devices[0])
    else:
        bench = spec.benchmark()
        entry = next(c for c in bench["configs"] if c["name"] == args.fleet4) \
            if any(c["name"] == args.fleet4 for c in bench["configs"]) \
            else {"file": f"bench/configs/{args.fleet4}.json"}
        import json
        config = json.loads((spec.ROOT / entry["file"]).read_text())
        with as_tpu:
            fleet4(config, topo.devices)
    return 0


if __name__ == "__main__":
    sys.exit(main())
