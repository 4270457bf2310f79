"""Paper Fig 16: total synthesis time vs design size.

The paper's mechanism: HLS synthesizes the *whole generated design* and its
compile time grows superlinearly with design size, while RTL units are
modular (each MVU instance is the same hand-written module, synthesized
once per parameterization).  The TPU analog:

  HLS side = XLA compile of the full generated dataflow graph (a chain of
             L MVU layers lowered from the jnp reference) -- one monolithic
             compile whose time grows with L and with PE/SIMD-dependent
             shapes.
  RTL side = Pallas kernel compiles: one per distinct (mode, block-shape)
             parameterization, CACHED across instances -- adding layers
             with the same folding adds zero compile time.

Two sweeps feed the Fig 16 bars: (a) chain length L at fixed folding,
(b) PE/SIMD at fixed L=1.  The end-to-end caching result (cold autotune
sweep vs warm cache replay, the paper's ~10x out-of-context saving) lives
in the design-space explorer's record (``repro.explore`` ->
``experiments/explore/``); this benchmark isolates the compile-time
mechanism.  ``run_quick`` writes the JSON record the regression gate pairs
with the committed baseline.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks.common import compile_probe, emit_json, rtl_kernel_fn
from repro.core.folding import Folding, to_tpu_blocks
from repro.kernels import ref


def _chain_fn(l: int, n: int):
    def f(a, ws):
        h = a
        for i in range(l):
            h = ref.mvu_int_ref(h, ws[i]).astype(jnp.int8)  # requantize analog
        return h
    return f


def run_chain(lengths=(1, 2, 4, 8, 16, 32), n=64) -> list[dict]:
    rows = []
    rtl_cache: dict = {}
    for l in lengths:
        # n != k would break chaining; use square layers (n == k) past layer 0
        hls = compile_probe(_chain_fn(l, n),
                            jax.ShapeDtypeStruct((128, n), jnp.int8),
                            jax.ShapeDtypeStruct((l, n, n), jnp.int8))
        # RTL: one kernel parameterization reused by every layer in the chain
        t0 = time.perf_counter()
        key = ("standard", 32, 32)
        if key not in rtl_cache:
            blocks = to_tpu_blocks(Folding(32, 32), "standard", n=n, k=n)
            rtl_cache[key] = compile_probe(
                rtl_kernel_fn("standard", n, blocks),
                jax.ShapeDtypeStruct((128, n), jnp.int8),
                jax.ShapeDtypeStruct((n, n), jnp.int8),
            )["total_s"]
        rtl_s = rtl_cache[key] + (time.perf_counter() - t0)
        rows.append({
            "sweep": "chain_length", "value": l,
            "hls_compile_s": round(hls["total_s"], 4),
            "rtl_compile_s": round(rtl_s, 4),
            "hls_over_rtl": round(hls["total_s"] / max(rtl_s, 1e-9), 2),
        })
    return rows


def run_folding(values=(2, 8, 32, 64), n=64, k=1024) -> list[dict]:
    """PE/SIMD sweep at one layer: each folding is a new RTL
    parameterization (compiled) but the same HLS reference shape."""
    rows = []
    for v in values:
        blocks = to_tpu_blocks(Folding(v, 64), "standard", n=n, k=k)
        a_s = jax.ShapeDtypeStruct((128, k), jnp.int8)
        w_s = jax.ShapeDtypeStruct((n, k), jnp.int8)
        hls = compile_probe(lambda a, w: ref.mvu_int_ref(a, w), a_s, w_s)
        rtl = compile_probe(rtl_kernel_fn("standard", k, blocks), a_s, w_s)
        rows.append({
            "sweep": "pe", "value": v,
            "hls_compile_s": round(hls["total_s"], 4),
            "rtl_compile_s": round(rtl["total_s"], 4),
        })
    return rows


def run(lengths=(1, 2, 4, 8, 16, 32), folding_values=(2, 8, 32, 64),
        quick: bool = False, out: str | None = None) -> dict:
    chain = run_chain(lengths)
    folding = run_folding(folding_values)
    first, last = chain[0], chain[-1]
    record = {
        "name": "synthesis_time",
        "quick": quick,
        "chain": chain,
        "folding": folding,
        # wall-clock shapes vary across runners, so these stay informational
        # (not gated); the mechanism claim -- modular RTL reuse beats the
        # monolithic compile at depth -- is what the figure renders
        "hls_growth": round(last["hls_compile_s"] /
                            max(first["hls_compile_s"], 1e-9), 2),
        "hls_over_rtl_at_depth": last["hls_over_rtl"],
        "summary": f"chain L={first['value']}..{last['value']}: "
                   f"hls {first['hls_compile_s']}s -> {last['hls_compile_s']}s, "
                   f"rtl flat {last['rtl_compile_s']}s "
                   f"({last['hls_over_rtl']}x at depth)",
    }
    emit_json(record, out)
    return record


def run_quick(out_dir: str | None = None) -> dict:
    out = f"{out_dir}/synthesis_time.json" if out_dir else None
    return run(lengths=(1, 4, 8), folding_values=(2, 32), quick=True, out=out)


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default="experiments/bench/synthesis_time.json")
    args = ap.parse_args()
    rec = (run(lengths=(1, 4, 8), folding_values=(2, 32), quick=True,
               out=args.out) if args.quick else run(out=args.out))
    print(f"# {rec['summary']}")


if __name__ == "__main__":
    main()
