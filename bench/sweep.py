"""Find the highest rate a stream cell's server sustains: one process
builds the cell once, then offers its open-loop traffic at each rate.

    python3 bench/sweep.py --workload nid_mlp.stream --seconds 5 \
        --rates 2000 5000 10000 20000

Prints one JSON line per rate: offered and completed requests, how many
were still unanswered when the offered window closed (the backlog),
refused ones, latency p50/p99 from the due time, and the generator's lag.
The cell's ``rate_hz`` is set once, from such a sweep, at about 0.8 of the
knee; the benchmark itself never searches for a rate.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--drain", type=float, default=15.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import numpy as np

    from bench.harness import cell as cell_mod, load, spec, stats
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving import ReplicaPool

    c = spec.load(args.workload)
    devices = cell_mod.devices_for(c.chips, require_chip=True)
    enable_compile_cache()
    cfg, fam = c.config, c.family
    acc = fam.build(cfg, fam.init_params(cfg, args.seed))
    records = np.asarray(fam.make_inputs(cfg, c.traffic["distinct_records"],
                                         args.seed))
    pool = ReplicaPool(acc.engine, devices=devices)
    batcher = acc.serve(batch_buckets=tuple(cfg["build"]["buckets"]),
                        pool=pool)
    rng = np.random.default_rng(args.seed)
    for rate in args.rates:
        offs = load.fixed_count_schedule(rate, args.seconds, rng)
        rows = rng.integers(0, len(records), len(offs))
        c0 = dict(batcher.metrics.counters)
        with cell_mod._frozen_heap():
            t0 = time.perf_counter()
            w = load.open_loop(batcher, records, rows, offs, t0, args.seconds,
                               drain_s=args.drain)
        lat = w.latency_s * 1e3
        done = np.isfinite(w.done)
        cnt = {k: v - c0.get(k, 0) for k, v in batcher.metrics.counters.items()}
        print(json.dumps({
            "rate_hz": rate, "offered": len(offs),
            "answered": int(done.sum()),
            "backlog_at_close": int(np.sum(~(w.done <= w.t_end))),
            "refused": cnt.get("rejected", 0),
            "p50_ms": stats.percentile(lat, 50),
            "p99_ms": stats.percentile(lat, 99),
            "lag_p99_ms": stats.percentile(w.lag_s[np.isfinite(w.lag_s)] * 1e3, 99),
            "padding_share": cnt["padded_samples"] / max(1, cnt["dispatched_samples"]),
            "flushes": cnt["flushes"],
            "dispatches": pool.load(),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
