"""The comparison's control at a cell's own size: the configuration's
reference one precision down (its float path in bfloat16), put in the
program's place and judged by the same comparison as a run.

    python3 bench/control.py --workload cnv_full.batch --seeds 1 2 3

Prints one JSON line per seed with the compared numbers and their limits;
the control has to come out not correct on every seed.  The benchmark's
own runs never run it.
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
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    import numpy as np

    from bench.harness import cell, spec

    c = spec.load(args.workload)
    cfg, fam, tr = c.config, c.family, c.traffic
    # the rows a run compares: a closed-loop sample, or the whole pool of
    # records the stream draws its requests from
    n = cell.SAMPLED_ROWS if tr["loop"] == "closed" else tr["distinct_records"]
    ok = True
    for seed in args.seeds:
        t0 = time.perf_counter()
        ref = fam.Reference(cfg, jax.device_get(fam.init_params(cfg, seed)))
        x = np.asarray(fam.make_inputs(cfg, n, seed))
        bad, note = ref.judge(x, ref.control(x))
        wrong = int(bad.sum())
        failed = wrong > cell.LIMITS["wrong_rows"]
        ok &= failed
        print(json.dumps({"workload": args.workload, "seed": seed, "rows": n,
                          "wrong_rows": wrong,
                          "limit": cell.LIMITS["wrong_rows"],
                          "control_failed": failed, "reference": note,
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
