"""Cache-tuned FusedEngine vs the heuristic-default engine, end to end.

Two ``repro.build`` runs over the SAME raw chain:

  heuristic  ``build(graph, tune="off")``: every kernel schedule from the
             one-shot ``choose_folding`` + ``to_tpu_blocks`` defaults
  tuned      ``build(graph, tune="cache")``: per-node schedules from the
             committed autotune cache (``repro.configs.*.TUNED_SCHEDULES``)
             -- pure lookup, zero measurement at construction

Both must be bit-exact with the eager ``dataflow.execute`` interpreter; the
paired interleaved timer reports the tuned-over-heuristic speedup.  The
committed record (default ``experiments/bench/autotune_gain.json``) carries
``min_speedup`` so the CI regression gate holds this benchmark to its own
floor (1.15x) instead of the global fused-vs-interpreter 2x floor.

``--retune`` re-runs the empirical search (``tune="auto"`` + engine-level
microbatch tuning) into a fresh cache and saves it (default
``experiments/autotune/cache.json``; nightly CI uploads it as an artifact).
"""

from __future__ import annotations

import argparse
import json
import os

import numpy as np

from benchmarks.common import paired_times
from benchmarks.conv_throughput import cnv_accelerator
from benchmarks.engine_throughput import nid_accelerator
from repro.configs import cnv_bnn
from repro.core import autotune

MIN_SPEEDUP = 1.15  # the committed-gain floor the CI gate enforces


def build_accelerator(config: str, seed: int, **overrides):
    if config == "nid_mlp":
        return nid_accelerator(seed, **overrides), "nid_mlp_600_64_64_64_1_2bit"
    spec = cnv_bnn.QUICK
    acc = cnv_accelerator(spec, mode="xnor", seed=seed, **overrides)
    name = f"cnv_bnn_{spec.image}px_{'x'.join(map(str, spec.channels))}"
    return acc, name


def run(*, config: str = "nid_mlp", batch: int = 4096, reps: int = 5,
        seed: int = 0, retune: bool = False,
        cache_out: str | None = None,
        out: str | None = "experiments/bench/autotune_gain.json") -> dict:
    heur_acc, name = build_accelerator(config, seed)
    heuristic = heur_acc.engine
    x = autotune.synth_input(heur_acc.ref_graph, batch, seed=seed + 1)

    if retune:
        cache = autotune.ScheduleCache()
        # fill per-node entries by measuring, then search the microbatch tile
        build_accelerator(config, seed, tune="auto", cache=cache)
        autotune.tune_engine(heur_acc.graph, batch, cache=cache)
        if cache_out:
            cache.save(cache_out)
            print(f"# saved {len(cache)} tuned entries -> {cache_out}")
    else:
        cache = autotune.default_cache()

    tuned_acc, _ = build_accelerator(config, seed, tune="cache", cache=cache)
    tuned = tuned_acc.engine

    want = np.asarray(heur_acc.interpret(x))
    got_h = np.asarray(heuristic(x))
    got_t = np.asarray(tuned(x))
    np.testing.assert_allclose(got_h, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got_t, want, rtol=1e-5, atol=1e-5)

    t_heur, t_tuned, speedup = paired_times(heuristic, tuned, x, reps=reps)

    tuned_nodes = sum(
        1 for n in tuned.graph
        if n.op in ("mvu", "conv_mvu") and n.attrs["config"].blocks is not None)
    total_nodes = sum(1 for n in tuned.graph if n.op in ("mvu", "conv_mvu"))
    record = {
        "config": name,
        "batch": batch,
        "reps": reps,
        "heuristic_us": t_heur * 1e6,
        "tuned_us": t_tuned * 1e6,
        "speedup": speedup,
        "min_speedup": MIN_SPEEDUP,
        "heuristic_samples_per_s": batch / t_heur,
        "tuned_samples_per_s": batch / t_tuned,
        "tuned_nodes": tuned_nodes,
        "total_nodes": total_nodes,
        "tuned_backends": sorted({
            n.attrs["config"].backend for n in tuned.graph
            if n.op in ("mvu", "conv_mvu")}),
        "microbatch_tile": tuned._tile,
        "cache_entries": len(cache),
        "bit_exact": bool(np.array_equal(got_t, want)
                          and np.array_equal(got_h, want)),
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
    ap.add_argument("--config", default="nid_mlp", choices=("nid_mlp", "cnv"))
    ap.add_argument("--batch", type=int, default=4096)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--retune", action="store_true",
                    help="re-run the empirical search instead of using the "
                         "committed cache")
    ap.add_argument("--cache-out", default=autotune.DEFAULT_CACHE_PATH,
                    help="where --retune saves the fresh cache")
    ap.add_argument("--quick", action="store_true",
                    help="small batch / few reps (CI smoke)")
    ap.add_argument("--out", default="experiments/bench/autotune_gain.json")
    args = ap.parse_args()
    if args.quick:
        # tuned-vs-heuristic gaps are tighter than fused-vs-interpreter
        # ones, so the quick gate run spends more paired reps (median of 9
        # interleaved ratios) to hold the regression band on noisy runners
        args.batch, args.reps = min(args.batch, 1024), 9

    rec = run(config=args.config, batch=args.batch, reps=args.reps,
              retune=args.retune, cache_out=args.cache_out, out=args.out)
    print(json.dumps(rec, indent=2))
    print(f"# tuned {rec['tuned_us']:.0f}us vs heuristic "
          f"{rec['heuristic_us']:.0f}us -> {rec['speedup']:.2f}x "
          f"({rec['tuned_nodes']}/{rec['total_nodes']} nodes tuned, "
          f"backends {rec['tuned_backends']})")


if __name__ == "__main__":
    main()
