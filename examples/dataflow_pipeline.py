"""FINN streaming dataflow on a TPU mesh: the pipeline-parallel executor.

FINN instantiates one MVU per layer and streams activations through AXI
links (paper Fig. 6).  This example runs the same discipline on a device
mesh: four pipeline stages (one per device), microbatches streaming through
ppermute links, and the FINN folding pass rate-balancing the stages.

Run:  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
      PYTHONPATH=src python examples/dataflow_pipeline.py
"""

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.folding import balance_pipeline
from repro.distributed.pipeline import (
    pipeline_apply,
    sequential_reference,
    stage_params_split,
)
from repro.launch.mesh import make_mesh


def main():
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    n_dev = len(jax.devices())
    stages = 4 if n_dev >= 4 else n_dev
    L, d = 8, 64
    n_micro, mb = 8, 4

    key = jax.random.PRNGKey(0)
    params = {
        "w": jax.random.normal(key, (L, d, d)) * (1.0 / np.sqrt(d)),
        "b": jnp.zeros((L, d)),
    }
    x = jax.random.normal(jax.random.PRNGKey(1), (n_micro, mb, d))

    def layer_fn(p, h):
        return jnp.tanh(h @ p["w"] + p["b"])

    # FINN folding: rate-balance the (identical) layers -> equal stage cycles
    folds = balance_pipeline([(d, d, 1)] * L, max_pe=64, max_simd=64)
    cycles = [f.cycles(d, d) for f in folds]
    print(f"[dataflow] {L} layers on {stages} stages; per-layer cycles "
          f"{cycles[0]} (balanced: {len(set(cycles)) == 1})")
    print(f"[dataflow] steady-state interval = {max(cycles)} cycles, "
          f"fill/drain bubbles = {stages - 1} microbatch ticks")

    mesh = make_mesh((stages,), ("stage",))
    out = pipeline_apply(layer_fn, stage_params_split(params, stages), x, mesh)
    want = sequential_reference(layer_fn, params, x)
    err = float(jnp.abs(out - want).max())
    print(f"[dataflow] pipeline output == sequential reference "
          f"(max err {err:.2e})")
    assert err < 1e-5
    print("OK: FINN dataflow schedule reproduced with ppermute streams")


if __name__ == "__main__":
    main()
