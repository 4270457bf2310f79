"""Quickstart: the paper's MVU in five minutes.

1. Build a quantized MVU layer (three SIMD datapaths).
2. Run the Pallas kernels against the XLA reference (bit-exact).
3. Fold a BatchNorm+quantizer into integer thresholds (streamlining).
4. Use the FINN-style folding pass + resource model.
5. Compile a whole MLP chain with the ``repro.build`` step pipeline.

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.folding import Folding, choose_folding
from repro.core.mvu import MVUConfig, MVULayer
from repro.core.thresholds import bn_quant_thresholds, integerize_thresholds
from repro.kernels import ops, packing


def main():
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    key = jax.random.PRNGKey(0)
    m, n, k = 64, 64, 256

    print("== 1. three SIMD datapaths (paper Fig. 4) ==")
    for mode in ("xnor", "binary", "standard"):
        cfg = MVUConfig(in_features=k, out_features=n, mode=mode,
                        folding=Folding(32, 32))
        layer = MVULayer(cfg)
        params = layer.init_params(key)
        if mode == "xnor":
            x = packing.pack_bits(
                jax.random.bernoulli(key, 0.5, (m, k)).astype(jnp.int32))
        else:
            x = jax.random.randint(key, (m, k), -8, 8, jnp.int8)
        y = layer(params, x)
        res = layer.resources()
        print(f"  {mode:9s} out={y.shape} {y.dtype} | "
              f"cycles/pixel={res.cycles} wmem_depth={res.weight_mem_depth} "
              f"inbuf_depth={res.input_buffer_depth}")

    print("== 2. Pallas kernel == XLA reference (bit exact) ==")
    a = jax.random.randint(key, (37, 300), -8, 8, jnp.int8)
    w = jax.random.randint(jax.random.PRNGKey(1), (53, 300), -8, 8, jnp.int8)
    via_pallas = ops.mvu(a, w, "standard", block_m=32, block_n=32, block_k=64)
    via_xla = ops.mvu(a, w, "standard", backend="xla")
    assert (np.asarray(via_pallas) == np.asarray(via_xla)).all()
    print(f"  exact match on {via_pallas.shape}")

    print("== 3. BN+quant -> integer thresholds (streamlining) ==")
    gamma, beta = jnp.ones((4,)), jnp.zeros((4,))
    mean, var = jnp.zeros((4,)), jnp.ones((4,)) - 1e-5
    t, flip = bn_quant_thresholds(gamma, beta, mean, var, bits=2)
    print(f"  thresholds (2-bit):\n{integerize_thresholds(t)}")

    print("== 4. folding pass (FINN 'Folding and Resource Estimation') ==")
    fold = choose_folding(64, 600, target_cycles=16)
    print(f"  N=64 K=600 target 16 cycles -> PE={fold.pe} SIMD={fold.simd} "
          f"cycles={fold.cycles(64, 600)}")

    print("== 5. the build pipeline (FINN build_dataflow analog) ==")
    from repro.build import build, default_steps
    from repro.core.ir import Node

    rng = np.random.default_rng(0)
    g = [Node("input", "in", {"shape": (64,), "bits": 2})]
    for i, (kk, nn) in enumerate(((64, 32), (32, 8))):
        g.append(Node("linear", f"fc{i}", {},
                      {"w": jnp.asarray(rng.normal(0, 0.5, (nn, kk)),
                                        jnp.float32)}))
        if i == 0:
            g.append(Node("quant_act", "act0", {"bits": 2, "act_scale": 1.0}))
    acc = build(g, target="engine", mode="standard", weight_bits=4, act_bits=2)
    xb = jnp.asarray(rng.integers(0, 4, (16, 64)), jnp.int32)
    assert np.array_equal(np.asarray(acc(xb)), np.asarray(acc.interpret(xb)))
    print(f"  default steps ('engine'): {' -> '.join(default_steps('engine'))}")
    print(f"  verified transforms     : "
          f"{[s.name for s in acc.report.steps if s.verified]}")
    print(f"  schedule                : {acc.report.schedule}")
    print("  engine == interpreter on a probe batch (verified per step)")


if __name__ == "__main__":
    main()
