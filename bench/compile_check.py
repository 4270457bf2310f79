"""Compile every cell's engine program for a described TPU v5e, no chip.

    JAX_PLATFORMS=cpu python3 bench/compile_check.py [cell ...]

For each cell, builds its configuration on the CPU and compiles the
engine step at every shape the cell's traffic sends (the closed loop's
batch, or each serving bucket) for one chip of a described ``v5e:2x2``
topology: what the chip's compiler would refuse is refused here, at no
chip time.  Prints one JSON line per program with its Pallas kernels and
its memory analysis.  Nothing runs, so nothing is timed.
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench.harness import spec
    from repro.kernels import ops

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cells = [w["name"] for w in json.load(f)["workloads"]]
    cells = (argv or sys.argv[1:]) or cells
    jax.config.update("jax_enable_compilation_cache", False)
    one_chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    interpret = ops.default_interpret
    for name in cells:
        cell = spec.load(name)
        cfg, tr = cell.config, cell.traffic
        ops.default_interpret = interpret  # the build runs on this CPU
        acc = cell.family.build(cfg, cell.family.init_params(cfg, 0))
        ops.default_interpret = lambda: False  # compile as the chip runs
        jax.clear_caches()  # drop what the build traced in interpret mode
        eng = acc.engine
        sizes = ([tr["batch"]] if tr["loop"] == "closed"
                 else list(cfg["build"]["buckets"]))
        params = jax.tree.map(lambda p: jax.ShapeDtypeStruct(
            p.shape, p.dtype, sharding=one_chip), eng.params)
        for b in sizes:
            x = jax.ShapeDtypeStruct((b, *cfg["input"]["shape"]), jnp.int32,
                                     sharding=one_chip)
            compiled = eng._jit.lower(params, x, eng.plan(b).n_micro).compile()
            mem = compiled.memory_analysis()
            kernels = ops.tpu_kernel_names(compiled.as_text())
            print(json.dumps({
                "cell": name, "batch": b, "n_micro": eng.plan(b).n_micro,
                "kernels": sorted(set(kernels)), "kernel_calls": len(kernels),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
