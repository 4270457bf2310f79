"""Fused streaming conv path vs the eager interpreter on the CNV topology.

One ``repro.build`` run of the CNV chain yields both executions (the
reference graph keeps standalone batchnorm/quant_act nodes, the unfused
form):

  unfused   ``acc.interpret``: one dispatch per node; every conv runs
            SWU-then-MVU with the full (B, OH*OW, Kd^2*C) im2col matrix
            materialized between them -- the buffering blow-up FINN's
            line-buffer SWU exists to avoid
  fused     ``acc.engine``: bn/quant folded into threshold epilogues,
            swu+mvu pairs collapsed into the line-buffer conv kernel
            (``kernels.swu_mvu``), whole chain one jit'd microbatch stream

Emits one JSON record (default experiments/bench/conv_throughput.json) with
both timings, the speedup, the bit-exactness flag, and the analytic
peak-activation-memory comparison (im2col bytes vs line-buffer resident
bytes at the worst conv layer).  ``--quick`` shrinks batch/reps for CI.
"""

from __future__ import annotations

import argparse
import json
import os

import jax.numpy as jnp
import numpy as np

from benchmarks.common import paired_times
from repro.build import Accelerator, build
from repro.configs import cnv_bnn
from repro.core import ir
from repro.core.engine import FusedEngine


def cnv_accelerator(spec=cnv_bnn.QUICK, *, mode: str = "xnor", seed: int = 0,
                    **overrides) -> Accelerator:
    """The CNV dataflow build (heuristic per-layer folding, as the
    committed baselines were measured)."""
    kw = dict(target="engine", mode=mode, weight_bits=spec.weight_bits,
              act_bits=spec.act_bits, folding="none",
              name=f"cnv_bnn_{spec.image}px")
    kw.update(overrides)
    return build(cnv_bnn.build_graph(spec, seed=seed), **kw)


def conv_memory_model(engine: FusedEngine, batch: int, microbatch: int) -> dict:
    """Analytic peak activation bytes at the worst conv layer.

    Interpreter: the SWU materializes the whole im2col matrix (int32 gather
    output) for the full batch before the MVU consumes it.  Fused kernel:
    one (H, W, C) int8 image tile plus one (rt*OW, K) int8 window tile per
    microbatch -- the line-buffer residency.
    """
    im2col = fused = 0
    for node, ins, out_shape in ir.io_shapes(engine.graph):
        if node.op != "conv_mvu":
            continue
        h, w, c = ins[0]
        oh, ow, _ = out_shape
        kd = node.attrs["kernel"]
        pad = node.attrs["pad"]
        k = kd * kd * c
        im2col = max(im2col, batch * oh * ow * k * 4)
        from repro.kernels.swu_mvu import conv_rows_per_tile

        cfg = node.attrs["config"]
        rt = conv_rows_per_tile(oh, ow, cfg.block_m)
        resident = (h + 2 * pad) * (w + 2 * pad) * c + rt * ow * k
        fused = max(fused, microbatch * resident)
    return {
        "im2col_peak_bytes": im2col,
        "fused_peak_bytes": fused,
        "peak_memory_ratio": (im2col / fused) if fused else 0.0,
    }


def run(*, batch: int = 256, reps: int = 5, seed: int = 0, mode: str = "xnor",
        spec=None, quick: bool = False,
        out: str | None = "experiments/bench/conv_throughput.json") -> dict:
    if spec is None:
        spec = cnv_bnn.QUICK if quick else cnv_bnn.FULL
    acc = cnv_accelerator(spec, mode=mode, seed=seed)
    engine = acc.engine
    rng = np.random.default_rng(seed + 1)
    x = jnp.asarray(
        rng.integers(0, 2**spec.act_bits, (batch, spec.image, spec.image, 3)),
        jnp.int32)

    plan = engine.plan(batch)

    want = np.asarray(acc.interpret(x))
    got = np.asarray(engine(x))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    t_unfused, t_fused, speedup = paired_times(
        lambda v: acc.interpret(v), engine, x, reps=reps)

    n_conv = sum(1 for n in engine.graph if n.op == "conv_mvu")
    record = {
        "config": f"cnv_bnn_{spec.image}px_{'x'.join(map(str, spec.channels))}",
        "mode": mode,
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
        "bottleneck": engine.schedule.bottleneck.name,
        "conv_stages": n_conv,
        "bit_exact": bool(np.array_equal(got, want)),
        **conv_memory_model(engine, batch, plan.microbatch),
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
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--mode", default="xnor",
                    choices=("xnor", "binary", "standard"))
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized CNV + small batch / few reps")
    ap.add_argument("--out", default="experiments/bench/conv_throughput.json")
    args = ap.parse_args()
    if args.quick:
        # 5 reps + best-of timing for stability under the regression gate
        args.batch, args.reps = min(args.batch, 64), 5

    rec = run(batch=args.batch, reps=args.reps, mode=args.mode,
              quick=args.quick, out=args.out)
    print(json.dumps(rec, indent=2))
    print(f"# fused {rec['fused_us']:.0f}us vs unfused {rec['unfused_us']:.0f}us "
          f"-> {rec['speedup']:.2f}x, peak-mem ratio "
          f"{rec['peak_memory_ratio']:.1f}x at {rec['bottleneck']}")


if __name__ == "__main__":
    main()
