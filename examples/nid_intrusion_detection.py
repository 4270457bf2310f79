"""The paper's full example (Section 6.5): network intrusion detection.

End-to-end FINN flow on the Table 6 MLP (600-64-64-64-1, 2-bit):

  1. train the float MLP with quantization-aware STE on a synthetic
     UNSW-NB15 stand-in (offline container; same feature/label geometry),
  2. compile it through the ``repro.build`` step pipeline (lowering,
     streamlining, the paper's Table 6 PE/SIMD folding, per-step
     verification against the reference interpreter),
  3. run integer inference through the Pallas MVU kernels and verify it
     matches the float teacher,
  4. print the dataflow schedule: per-layer cycles reproduce Table 7,
  5. serve the fused engine through the continuous batcher, and write the
     BuildReport JSON (the software analog of the paper's resource and
     synthesis-time tables).

Run:  PYTHONPATH=src python examples/nid_intrusion_detection.py [--fast]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.nid_mlp import PIPELINE_DEPTH, accuracy_check
from repro.configs import nid_mlp
from repro.core.folding import Folding
from repro.core.resource_model import mvu_resources


def main(fast: bool = False):
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    print("== NID MLP (paper Table 6): 600-64-64-64-1 @ 2-bit ==")
    for i, (k, n, pe, simd) in enumerate(nid_mlp.LAYERS):
        fold = Folding(pe, simd)
        res = mvu_resources(n, k, fold, mode="standard", weight_bits=2,
                            act_bits=2, n_thresh=3)
        cycles = fold.cycles(n, k, 1) + PIPELINE_DEPTH
        paper = [17, 13, 13, 13][i]
        print(f"  layer {i}: K={k:4d} N={n:3d} PE={pe:3d} SIMD={simd:3d} "
              f"| cycles {cycles} (paper RTL: {paper}) "
              f"| wmem_depth={res.weight_mem_depth} inbuf={res.input_buffer_depth}")

    print("== train (QAT) -> build(streamline steps) -> integer inference ==")
    out = accuracy_check(steps=120 if fast else 300)
    print(f"  float teacher accuracy : {out['float_acc']:.3f}")
    print(f"  integer MVU accuracy   : {out['mvu_int_acc']:.3f}")
    print(f"  pipeline interval      : {out['pipeline_interval_cycles']} cycles "
          f"(bottleneck {out['bottleneck']})")
    print(f"  pipeline latency       : {out['pipeline_latency_cycles']} cycles")
    assert out["mvu_int_acc"] > 0.95, "integer pipeline must match the teacher"
    print("OK: end-to-end FINN flow reproduced on the NID use case")

    print("== repro.build: one call replaces the manual lowering chain ==")
    import numpy as np
    import jax.numpy as jnp

    from benchmarks.engine_throughput import nid_accelerator

    # target="serving" = the engine pipeline + measured cycle-time
    # calibration; every step is verified bit-exact against the reference
    # interpreter and the BuildReport lands next to the autotune cache.
    acc = nid_accelerator(target="serving", output_dir="experiments/build")
    engine = acc.engine
    plan = engine.plan(256)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, 4, (256, 600)), jnp.int32)
    same = np.array_equal(np.asarray(engine(x)), np.asarray(acc.interpret(x)))
    print(f"  build steps            : {' -> '.join(acc.report.step_names)}")
    print(f"  verified steps         : "
          f"{sum(1 for s in acc.report.steps if s.verified)} "
          f"(bit-exact vs the reference interpreter, per transform)")
    print(f"  epilogues fused        : {sum(1 for n in engine.graph if n.attrs.get('fused'))} "
          f"bn+quant pairs -> MVU thresholds")
    print(f"  stream plan (B=256)    : {plan.n_micro} microbatches x {plan.microbatch} "
          f"(II {plan.interval_cycles} cycles)")
    print(f"  build report           : {acc.report.path}")
    print(f"  bit-exact vs interpret : {same}")
    assert same

    import warnings

    from repro.launch.serve import EngineServer

    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)  # legacy shim
        server = EngineServer(engine, batch_buckets=(1, 8, 32))
    rids = [server.submit(np.asarray(x[i])) for i in range(11)]
    done = {r.rid: r for r in server.flush()}
    ok = all(np.array_equal(done[r].out, np.asarray(engine(x[:11]))[i])
             for i, r in enumerate(rids))
    print(f"  served 11 requests in {server.stats['flushes']} bucketed flushes "
          f"(padding {server.stats['padded_samples']}): correct={ok}")
    assert ok
    print("OK: fused engine serves the NID workload bit-exactly")

    print("== continuous-batching serving subsystem (Accelerator.serve) ==")
    batcher = acc.serve(batch_buckets=(1, 8, 32), slo_s=0.05)
    rids = [batcher.submit(np.asarray(x[i])) for i in range(11)]
    batcher.drain()
    ok = all(np.array_equal(batcher.pop_result(r).out,
                            np.asarray(engine(x[:11]))[i])
             for i, r in enumerate(rids))
    snap = batcher.metrics.snapshot()
    budget = batcher.budgets[batcher.bucket_for(1)]
    cal = acc.calibration
    print(f"  admission queue         : bounded at {batcher.queue.capacity} "
          f"samples, validated against input spec {batcher.spec.shape}")
    ii = engine.schedule.steady_state_interval
    print(f"  flush budget (bucket 1) : {budget * 1e3:.3f} ms "
          f"(II {ii} cycles x measured {cal['s_per_cycle'] * 1e6:.1f} us/cycle "
          f"x 2.0 safety)")
    print(f"  replicas                : {len(batcher.pool)} device(s), "
          f"least-loaded async dispatch")
    print(f"  metrics snapshot        : p99 {snap['p99_ms']:.2f} ms, "
          f"{snap['flushes']} flushes, padding {snap['padding_overhead']:.0%}, "
          f"correct={ok}")
    assert ok
    print("OK: continuous batcher serves the NID workload bit-exactly")


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true",
                    help="fewer QAT steps (CI smoke)")
    main(fast=ap.parse_args().fast)
