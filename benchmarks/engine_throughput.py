"""Fused engine vs unfused interpreter throughput on the NID-MLP config.

Builds the paper's Table 6 MLP (600-64-64-64-1, 2-bit activations) through
the ``repro.build`` step pipeline with the paper's PE/SIMD folding.  The
build keeps bn/quant as standalone nodes in the reference graph, so the
same :class:`~repro.build.accelerator.Accelerator` exposes both sides of
the comparison:

  unfused   ``acc.interpret``: eager per-node interpreter, one dispatch
            per node, float BN/quant epilogues between the MVU kernels
  fused     ``acc.engine``: epilogues folded into the MVU threshold
            epilogue, whole chain jit-compiled once, microbatch streaming
            per the dataflow schedule (paper section 5.3 analog)

Emits one JSON record (default experiments/bench/engine_throughput.json)
with both timings, the speedup, and the stream plan.  ``--quick`` shrinks
the batch/reps for CI smoke runs.
"""

from __future__ import annotations

import argparse
import json
import os

import jax.numpy as jnp
import numpy as np

from benchmarks.common import paired_times
from repro.build import Accelerator, build
from repro.configs import nid_mlp
from repro.core.ir import Graph


# the Table 6 chain definition moved to the config package so the
# explorer and examples can build it without importing benchmarks
def build_nid_graph(seed: int = 0) -> Graph:
    return nid_mlp.build_graph(seed)


def nid_accelerator(seed: int = 0, **overrides) -> Accelerator:
    """The NID-MLP dataflow build every benchmark/example shares: the
    paper's per-layer PE/SIMD folding, standard weight coding."""
    kw = dict(target="engine", mode="standard", weight_bits=8,
              act_bits=nid_mlp.INPUT_BITS, folding=nid_mlp.foldings(),
              name="nid_mlp")
    kw.update(overrides)
    return build(build_nid_graph(seed), **kw)


def run(*, batch: int = 4096, reps: int = 5, seed: int = 0,
        out: str | None = "experiments/bench/engine_throughput.json") -> dict:
    acc = nid_accelerator(seed)
    engine = acc.engine
    rng = np.random.default_rng(seed + 1)
    x = jnp.asarray(
        rng.integers(0, 2**nid_mlp.INPUT_BITS, (batch, 600)), jnp.int32)

    plan = engine.plan(batch)

    want = np.asarray(acc.interpret(x))
    got = np.asarray(engine(x))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    t_unfused, t_fused, speedup = paired_times(
        lambda v: acc.interpret(v), engine, x, reps=reps)

    record = {
        "config": "nid_mlp_600_64_64_64_1_2bit",
        "batch": batch,
        "reps": reps,
        "unfused_us": t_unfused * 1e6,
        "fused_us": t_fused * 1e6,
        "speedup": speedup,
        "unfused_samples_per_s": batch / t_unfused,
        "fused_samples_per_s": batch / t_fused,
        "n_micro": plan.n_micro,
        "microbatch": plan.microbatch,
        "interval_cycles": plan.interval_cycles,
        "fifo_bound": plan.fifo_bound,
        "bottleneck": engine.schedule.bottleneck.name,
        "fused_nodes": sum(1 for n in engine.graph if n.attrs.get("fused")),
        "bit_exact": bool(np.array_equal(got, want)),
    }
    if out:
        out_dir = os.path.dirname(out)
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
        with open(out, "w") as f:
            json.dump(record, f, indent=2)
    return record


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--batch", type=int, default=4096)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--quick", action="store_true",
                    help="small batch / few reps (CI smoke)")
    ap.add_argument("--out", default="experiments/bench/engine_throughput.json")
    args = ap.parse_args()
    if args.quick:
        # 5 reps + best-of timing: the regression gate needs a stable
        # estimator on loaded CI runners to hold a 20% threshold.
        args.batch, args.reps = min(args.batch, 512), 5

    rec = run(batch=args.batch, reps=args.reps, out=args.out)
    print(json.dumps(rec, indent=2))
    print(f"# fused {rec['fused_us']:.0f}us vs unfused {rec['unfused_us']:.0f}us "
          f"-> {rec['speedup']:.2f}x ({rec['fused_samples_per_s']:.0f} samples/s)")


if __name__ == "__main__":
    main()
