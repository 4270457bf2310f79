"""Residual (skip-connection) MLP through the DAG build flow.

The chain IR could never express this workload; the DAG IR builds it
end-to-end: fan-out at the trunk activation, an elementwise-add join
(FINN's streaming elementwise-binary node), the branch-aware dataflow
schedule (skew FIFO at the join), and the fused engine -- held bit-exact
against the DAG reference interpreter.  The record claims:

  * ``bit_exact``: FusedEngine == dataflow.execute on the branched graph,
  * ``speedup`` >= 1.2x (``min_speedup``): the fused single-program engine
    must beat the per-node eager interpreter on the residual topology too
    (a conservative floor -- the measured margin is far larger; the chain
    benchmarks commit to 2x on deeper graphs),
  * the join's skew-FIFO depth and the branch labels, so a regression in
    the branch-balanced schedule shows up as a diff.

Discovered by ``benchmarks.run`` (exposes ``run_quick``); the committed
baseline lives at ``experiments/bench/residual_mlp.json``.
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from benchmarks.common import emit_json, paired_times
from repro.build import Accelerator, build
from repro.configs import residual_mlp


def residual_accelerator(seed: int = 0, **overrides) -> Accelerator:
    """The skip-connection build every benchmark/example/test shares."""
    kw = dict(target="engine", mode="standard",
              weight_bits=residual_mlp.WEIGHT_BITS,
              act_bits=residual_mlp.INPUT_BITS,
              folding=residual_mlp.foldings(), name="residual_mlp")
    kw.update(overrides)
    return build(residual_mlp.build_graph(seed), **kw)


def run_quick(out_dir: str | None = None, *, batch: int = 512,
              reps: int = 3) -> dict:
    acc = residual_accelerator()
    engine = acc.engine
    k_in = residual_mlp.LAYERS[0][0]
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.integers(0, 2**residual_mlp.INPUT_BITS,
                                 (batch, k_in)), jnp.int32)

    want = np.asarray(acc.interpret(x))
    got = np.asarray(engine(x))
    bit_exact = bool(np.array_equal(got, want))

    t_int, t_eng, speedup = paired_times(
        lambda v: acc.interpret(v), engine, x, reps=reps)

    sched = engine.schedule
    joins = sched.summary().get("joins", [])
    record = {
        "name": "residual_mlp",
        "batch": batch,
        "reps": reps,
        "speedup": round(speedup, 3),
        "min_speedup": 1.2,
        "bit_exact": bit_exact,
        "interpreter_us": round(t_int * 1e6, 1),
        "engine_us": round(t_eng * 1e6, 1),
        "interval_cycles": sched.steady_state_interval,
        "bottleneck": sched.bottleneck.name,
        "critical_path_cycles": sched.latency_cycles,
        "joins": joins,
        "edges": acc.report.edges,
        "branches": sorted({n.branch for n in acc.report.nodes}),
        "summary": f"skip-connection DAG: engine {speedup:.2f}x vs DAG "
                   f"interpreter, bit_exact={bit_exact}, join skew FIFO "
                   f"depth {joins[0]['fifo_depth'] if joins else 0}",
    }
    if not bit_exact:
        raise AssertionError(
            "residual engine diverged from the DAG reference interpreter")
    if out_dir:
        emit_json(record, f"{out_dir}/residual_mlp.json")
    return record


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    print(run_quick(out_dir="experiments/bench"))
