"""Bit-packed XNOR/popcount datapath vs the canonical MAC datapath.

Two ``repro.build`` runs over the SAME binarized (mode="xnor") NID-MLP
chain (paper Table 6 shapes, 1-bit weights and activations):

  packed     ``build(graph, tune="cache", pack="auto")``: the committed
             autotune cache routes every layer to the packed datapath --
             the blocked XNOR/popcount XLA path or the natively-packed
             Pallas kernel (paper Fig. 4a) -- and the ``pack_weights``
             lowering pass marks the nodes packed
  canonical  ``build(graph, backend="xla", tune="off", pack="never")``:
             the generic MAC datapath every packed kernel is verified
             against -- unpack the uint32 weight words to +/-1 rows and
             run a dense int matmul (``kernels.ref`` semantics)

Both engines must be bit-exact with the eager interpreter; the paired
interleaved timer reports the packed-over-canonical speedup.  The packed
datapath is memory-bandwidth-bound where the canonical one is
compute-bound, so the gain grows with N*K (the 600x64 input layer
dominates here).

The record also commits the storage side of the story: a binary-coded
(``mode="binary"``, {0,1} bitplanes x n-bit activations) build of the same
chain with ``pack="always"`` cuts HBM-resident weight bytes ~8x
(int8 rows -> uint32 bitplanes); ``weight_bytes_reduction`` is gated as an
absolute floor (``floor_only``) because it is a deterministic storage
ratio, not a timing.  ``packed_nodes`` gates that the committed cache
really selects the packed datapath (the autotuner chose it, nothing forced
it).

``--retune`` re-runs the empirical search (``tune="auto"``) into a fresh
cache and saves it so nightly CI exercises the packed axis of the search
space end to end.
"""

from __future__ import annotations

import argparse
import json
import os

import numpy as np

from benchmarks.common import paired_times
from repro.build import build
from repro.configs import nid_mlp
from repro.core import autotune

MIN_SPEEDUP = 1.15  # committed floor for the packed-over-canonical gain
MIN_WEIGHT_BYTES_REDUCTION = 4.0  # binary bitplanes vs int8 rows (~8x here)
MIN_PACKED_NODES = 1  # the cache must route >= 1 node to the packed datapath


def binarized_accelerator(seed: int = 0, **overrides):
    """The Table 6 chain lowered with 1-bit XNOR weights/activations."""
    kw = dict(target="engine", mode="xnor", weight_bits=1, act_bits=1,
              folding=nid_mlp.foldings(), name="nid_mlp_xnor")
    kw.update(overrides)
    return build(nid_mlp.build_graph(seed), **kw)


def run(*, batch: int = 4096, reps: int = 5, seed: int = 0,
        retune: bool = False, cache_out: str | None = None,
        out: str | None = "experiments/bench/packed_gain.json") -> dict:
    if retune:
        cache = autotune.ScheduleCache()
        binarized_accelerator(seed, tune="auto", cache=cache)
        if cache_out:
            cache.save(cache_out)
            print(f"# saved {len(cache)} tuned entries -> {cache_out}")
    else:
        cache = autotune.default_cache()

    packed_acc = binarized_accelerator(seed, tune="cache", cache=cache)
    canonical_acc = binarized_accelerator(
        seed, backend="xla", tune="off", pack="never",
        name="nid_mlp_xnor_canonical")
    packed, canonical = packed_acc.engine, canonical_acc.engine

    x = autotune.synth_input(packed_acc.ref_graph, batch, seed=seed + 1)
    want = np.asarray(packed_acc.interpret(x))
    got_p = np.asarray(packed(x))
    got_c = np.asarray(canonical(x))
    np.testing.assert_array_equal(got_p, want)
    np.testing.assert_array_equal(got_c, want)

    t_canon, t_packed, speedup = paired_times(canonical, packed, x, reps=reps)

    packed_nodes = [
        n.name for n in packed.graph
        if n.op in ("mvu", "conv_mvu") and n.attrs["config"].packed]
    total_nodes = sum(1 for n in packed.graph if n.op in ("mvu", "conv_mvu"))

    # storage story: binary coding ({0,1} bitplanes) of the same chain --
    # the xnor variant stores packed words either way, so the byte cut is
    # measured where canonical storage really is int8 rows
    bin_packed = binarized_accelerator(
        seed, mode="binary", act_bits=4, tune="off", pack="always",
        name="nid_mlp_binary_packed")
    bin_canon = binarized_accelerator(
        seed, mode="binary", act_bits=4, tune="off", pack="never",
        name="nid_mlp_binary_canonical")
    xb = autotune.synth_input(bin_packed.ref_graph, min(batch, 256),
                              seed=seed + 2)
    bin_exact = bool(np.array_equal(np.asarray(bin_packed.engine(xb)),
                                    np.asarray(bin_canon.engine(xb))))
    w_packed = sum(n.weight_bytes for n in bin_packed.report.nodes)
    w_canon = sum(n.canonical_weight_bytes for n in bin_packed.report.nodes)
    reduction = w_canon / max(1, w_packed)

    record = {
        "config": "nid_mlp_xnor_600_64_64_64_1_1bit",
        "batch": batch,
        "reps": reps,
        "canonical_us": t_canon * 1e6,
        "packed_us": t_packed * 1e6,
        "speedup": speedup,
        "min_speedup": MIN_SPEEDUP,
        "canonical_samples_per_s": batch / t_canon,
        "packed_samples_per_s": batch / t_packed,
        "packed_nodes": len(packed_nodes),
        "packed_node_names": packed_nodes,
        "total_nodes": total_nodes,
        "packed_backends": sorted({
            n.attrs["config"].backend for n in packed.graph
            if n.op in ("mvu", "conv_mvu") and n.attrs["config"].packed}),
        "binary_weight_bytes_packed": w_packed,
        "binary_weight_bytes_canonical": w_canon,
        "weight_bytes_reduction": reduction,
        "min_weight_bytes_reduction": MIN_WEIGHT_BYTES_REDUCTION,
        "min_packed_nodes": MIN_PACKED_NODES,
        "floor_only": ["weight_bytes_reduction", "packed_nodes"],
        "cache_entries": len(cache),
        "bit_exact": bool(np.array_equal(got_p, want)
                          and np.array_equal(got_c, want)
                          and bin_exact),
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
    ap.add_argument("--retune", action="store_true",
                    help="re-run the empirical search (packed axis included) "
                         "instead of using the committed cache")
    ap.add_argument("--cache-out", default=autotune.DEFAULT_CACHE_PATH,
                    help="where --retune saves the fresh cache")
    ap.add_argument("--quick", action="store_true",
                    help="small batch / few reps (CI smoke)")
    ap.add_argument("--out", default="experiments/bench/packed_gain.json")
    args = ap.parse_args()
    if args.quick:
        args.batch, args.reps = min(args.batch, 1024), 9

    rec = run(batch=args.batch, reps=args.reps, retune=args.retune,
              cache_out=args.cache_out, out=args.out)
    print(json.dumps(rec, indent=2))
    print(f"# packed {rec['packed_us']:.0f}us vs canonical "
          f"{rec['canonical_us']:.0f}us -> {rec['speedup']:.2f}x "
          f"({rec['packed_nodes']}/{rec['total_nodes']} nodes packed, "
          f"backends {rec['packed_backends']}, "
          f"weights {rec['weight_bytes_reduction']:.1f}x smaller)")


if __name__ == "__main__":
    main()
