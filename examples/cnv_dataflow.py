"""CNV (the FINN BNN convnet) streaming through the fused dataflow engine.

The conv quickstart, now one ``repro.build`` call: build the CNV topology
(conv/conv/pool/.../dense), and let the step pipeline lower conv layers to
SWU+MVU pairs, rate-balance the folding, collapse the pairs into
line-buffer conv kernels, and compile the whole network as ONE jit'd
microbatch stream -- every transform verified bit-exact against the eager
behavioural interpreter, with the `(B, OH*OW, Kd^2*C)` im2col matrix never
materializing.  The BuildReport (per-step timing, per-stage folding +
resource estimates) lands in ``experiments/build/``.

Run:  PYTHONPATH=src python examples/cnv_dataflow.py
"""

import numpy as np
import jax.numpy as jnp

from repro.build import build
from repro.configs import cnv_bnn


def main():
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    spec = cnv_bnn.QUICK  # 1/8-channel CNV on 16x16 inputs; FULL = the real one
    acc = build(
        cnv_bnn.build_graph(spec, seed=0),
        target="engine", mode="xnor",
        weight_bits=spec.weight_bits, act_bits=spec.act_bits,
        folding="balance", tune="cache",
        name="cnv_quick", output_dir="experiments/build",
    )
    engine = acc.engine
    print(f"[cnv] build steps: {' -> '.join(acc.report.step_names)}")
    print(f"[cnv] verified steps: "
          f"{[s.name for s in acc.report.steps if s.verified]}")
    print(f"[cnv] lowered ops: {[n.op for n in engine.graph]}")
    print(f"[cnv] schedule: {engine.schedule.summary()}")
    print(f"[cnv] per-stage folding: "
          f"{[(n.name, n.pe, n.simd, n.cycles) for n in acc.report.nodes]}")
    print(f"[cnv] build report -> {acc.report.path}")

    rng = np.random.default_rng(1)
    x = jnp.asarray(
        rng.integers(0, 2**spec.act_bits, (32, spec.image, spec.image, 3)),
        jnp.int32)
    plan = engine.plan(x.shape[0])
    print(f"[cnv] stream plan: {plan.n_micro} microbatches of "
          f"{plan.microbatch} image(s), II = {plan.interval_cycles} cycles")

    logits = np.asarray(engine(x))
    want = np.asarray(acc.interpret(x))
    assert np.array_equal(logits, want), "engine diverged from interpreter"
    print(f"[cnv] logits {logits.shape}, bit-exact with the reference interpreter")
    print(f"[cnv] predictions: {logits.argmax(-1)[:10]} ...")
    print("OK: CNV streamed through the fused conv path")


if __name__ == "__main__":
    main()
