"""Chaos benchmark: the hardened serving path vs the pre-hardening baseline
under the same deterministic fault plan.

The same open-loop Poisson load (80% gold / 20% best-effort tier) is driven
through two ``ContinuousBatcher`` arms over a 4-logical-replica pool on one
device, both injected with the identical committed :class:`FaultPlan`:

  hardened   the default ``FaultPolicy`` plus hedging: retries with
             deadline awareness, dispatch timeouts, integrity guard,
             canary-probe recovery, brownout tiering.
  baseline   ``FaultPolicy.disabled()`` -- the pre-hardening behavior
             (failed dispatches still resolve as shed; that fix is
             unconditional).

The fault plan mixes background rates (dispatch errors, output corruption,
stragglers) with three explicit events: a replica dies, a replica hangs
once, and a guaranteed output corruption.  The committed claims
(``scripts/check_bench_regression.py`` absolute gates):

  * ``corrupted_delivered`` == 0: the hardened arm never delivers a
    corrupted result (every delivered row bit-exact with the engine),
  * ``gold_completion_rate`` >= 0.99: gold-tier requests complete within
    their deadline despite the chaos,
  * ``baseline_failure_modes`` >= 1: the SAME plan demonstrably breaks the
    baseline (corrupted deliveries, stuck requests on the hung replica,
    and/or gold completion collapse) -- the A/B proof the hardening is
    load-bearing, not incidental.

The hardened arm always runs with telemetry wired (a
:class:`repro.telemetry.Tracer` plus a :class:`DriftMonitor`): the chaos
run doubles as the observability acceptance test.  Two more gated claims:

  * ``straggler_flagged`` >= 1: the scripted straggle on replica 1 is
    flagged by the drift monitor (``flagged_ever`` latches even though
    hedging hides the straggler's completion -- censored lower bounds),
  * ``trace_fault_annotations`` >= 3: retries / hedges / timeouts /
    corrupt batches / quarantines appear as instant events on the trace.

``--trace PATH`` additionally exports the Chrome trace-event JSON
(perfetto-viewable; CI uploads it as an artifact).

The record embeds the full fault-plan JSON: re-running with it reproduces
the identical fault schedule (draws are pure functions of
``(seed, replica, dispatch_index)``), which is what makes a chaos failure
on CI debuggable instead of a flake.

Usage:
    python -m benchmarks.chaos_serving [--quick] [--soak] [--out PATH]
                                       [--trace PATH]
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.engine_throughput import nid_accelerator
from repro.serving import (
    BEST_EFFORT,
    GOLD,
    ContinuousBatcher,
    FaultEvent,
    FaultPlan,
    FaultPolicy,
    ReplicaPool,
)
from repro.telemetry import DriftMonitor, Tracer

POLL_SLEEP_S = 2e-4
N_REPLICAS = 4


def build_fault_plan(seed: int, t_exec: float, *, soak: bool = False) -> FaultPlan:
    """Background chaos + four scripted catastrophes.  ``soak`` raises the
    background rates for the nightly long run."""
    scale = 2.0 if soak else 1.0
    return FaultPlan(
        seed=seed,
        rates={"error": 0.04 * scale, "corrupt": 0.05 * scale,
               "straggle": 0.04 * scale},
        straggle_delay_s=max(6.0 * t_exec, 0.02),
        events=[
            FaultEvent("corrupt", replica=0, at_dispatch=1),
            # the drift-monitor acceptance case: a scripted straggle well
            # past the drift band (8x the calibrated max-bucket time; the
            # dispatch timeout is 10x so it resolves, late) -- whether the
            # late completion is observed directly or hidden by a winning
            # hedge, replica 1 must end up in ``flagged_ever``
            FaultEvent("straggle", replica=1, at_dispatch=1,
                       delay_s=max(8.0 * t_exec, 0.03)),
            FaultEvent("hang", replica=2, at_dispatch=1),
            FaultEvent("die", replica=3, at_dispatch=2),
        ],
    )


def drive(batcher: ContinuousBatcher, xs, arrivals, tiers, *,
          horizon_s: float) -> dict:
    """Open-loop drive: submit each arrival on its own clock, poll
    continuously, stop when everything resolved or the horizon passes
    (the baseline's hung flight never resolves -- the horizon is what
    lets the un-hardened arm terminate at all)."""
    n = len(arrivals)
    rids: list[int] = []
    t0 = time.perf_counter()
    i = 0
    while True:
        now = time.perf_counter()
        if i < n and now >= t0 + arrivals[i]:
            rids.append(batcher.submit(xs[i], tier=tiers[i]))
            i += 1
            batcher.poll()
            continue
        batcher.poll()
        if i >= n and (batcher.outstanding == 0 or now - t0 > horizon_s):
            break
        time.sleep(POLL_SLEEP_S)
    wall_s = time.perf_counter() - t0
    return {"rids": rids, "wall_s": wall_s,
            "snapshot": batcher.metrics.snapshot(),
            "health": batcher.pool.health_snapshot()}


def evaluate(run: dict, batcher: ContinuousBatcher, tiers, want) -> dict:
    """Per-arm outcome accounting against the golden engine outputs."""
    rids = run["rids"]
    delivered = corrupted = 0
    gold_total = gold_ok = 0
    stuck = 0
    for i, rid in enumerate(rids):
        r = batcher.results.get(rid)
        if tiers[i] == GOLD:
            gold_total += 1
        if r is None:
            stuck += 1  # never resolved: parked on a hung replica
            continue
        if r.out is None:
            continue  # shed (counted via availability)
        delivered += 1
        if not np.array_equal(r.out, want[i]):
            corrupted += 1
        elif tiers[i] == GOLD and not r.missed_deadline:
            gold_ok += 1
    return {
        "delivered": delivered,
        "corrupted_delivered": corrupted,
        "stuck_requests": stuck,
        "gold_completion_rate": gold_ok / gold_total if gold_total else 1.0,
        "availability": run["snapshot"]["availability"],
    }


FAULT_ANNOTATIONS = ("retry", "hedge", "timeout", "corrupt_batch",
                     "quarantine", "dispatch_failure")


def run(*, requests: int = 160, seed: int = 0, load: float = 0.25,
        soak: bool = False, trace: str | None = None,
        out: str | None = "experiments/bench/chaos_serving.json") -> dict:
    buckets = (1, 8, 32)
    acc = nid_accelerator(seed, target="serving",
                          calibrate_batch=buckets[-1], calibrate_reps=3)
    engine = acc.engine
    cal = acc.calibration
    t_exec = cal["measured_s"]  # one max-bucket engine call, this machine

    rng = np.random.default_rng(seed + 1)
    xs = rng.integers(0, 4, (requests, 600)).astype(np.int32)
    want = np.asarray(jax.block_until_ready(engine(jnp.asarray(xs))))
    rate_hz = min(load * buckets[-1] / t_exec, 2000.0)
    arrivals = np.cumsum(rng.exponential(1.0 / rate_hz, requests))
    tiers = [BEST_EFFORT if rng.uniform() < 0.2 else GOLD
             for _ in range(requests)]

    slo_s = max(40.0 * t_exec, 0.25)
    plan = build_fault_plan(seed + 2201, t_exec, soak=soak)
    horizon_s = float(arrivals[-1]) + max(80.0 * t_exec, 2.0)
    device = jax.local_devices()[0]

    hardened_policy = FaultPolicy(
        max_retries=4, retry_backoff_s=0.0,
        dispatch_timeout_s=max(10.0 * t_exec, 0.05),
        hedging=True, hedge_after_s=max(4.0 * t_exec, 0.02),
        probe_backoff_s=max(2.0 * t_exec, 0.01),
    )

    def make_batcher(policy: FaultPolicy, *, tracer=None,
                     drift=None) -> ContinuousBatcher:
        pool = ReplicaPool(engine, devices=[device] * N_REPLICAS,
                           faults=plan, policy=policy, tracer=tracer)
        return ContinuousBatcher(
            engine, batch_buckets=buckets, slo_s=slo_s, pool=pool,
            fault_policy=policy, cache=acc.cache,
            queue_capacity=max(256, requests),
            result_capacity=max(8192, 4 * requests),
            tracer=tracer, drift=drift).warmup()

    # the hardened arm carries full telemetry (the chaos run doubles as the
    # observability acceptance test); the baseline arm stays untraced
    tracer = Tracer(capacity=1 << 18,
                    meta={"benchmark": "chaos_serving", "seed": seed,
                          "fault_seed": plan.seed})
    drift = DriftMonitor()
    hardened = make_batcher(hardened_policy, tracer=tracer, drift=drift)
    h_run = drive(hardened, xs, arrivals, tiers, horizon_s=horizon_s)
    h = evaluate(h_run, hardened, tiers, want)

    flagged_ever = sorted(drift.flagged_ever())
    annotations = {name: 0 for name in FAULT_ANNOTATIONS}
    for ev in tracer.events():
        if ev["ph"] == "i" and ev["name"] in annotations:
            annotations[ev["name"]] += 1
    if trace:
        tracer.save(trace)

    baseline = make_batcher(FaultPolicy.disabled())
    b_run = drive(baseline, xs, arrivals, tiers, horizon_s=horizon_s)
    b = evaluate(b_run, baseline, tiers, want)

    baseline_failure_modes = sum([
        b["corrupted_delivered"] > 0,
        b["stuck_requests"] > 0,
        b["gold_completion_rate"] < 0.99,
    ])

    snap = h_run["snapshot"]
    record = {
        "config": "nid_mlp_600_64_64_64_1_2bit",
        "requests": requests,
        "replicas": N_REPLICAS,
        "buckets": list(buckets),
        "seed": seed,
        "soak": bool(soak),
        "rate_hz": float(rate_hz),
        "slo_ms": slo_s * 1e3,
        "gold_fraction": tiers.count(GOLD) / requests,
        # the committed chaos schedule: re-running with this plan replays
        # the identical fault at the identical (replica, dispatch) slots
        "fault_plan": plan.to_json(),
        # gated claims -------------------------------------------------
        "bit_exact": h["corrupted_delivered"] == 0 and h["delivered"] > 0,
        "ceiling_only": ["corrupted_delivered"],
        "corrupted_delivered": h["corrupted_delivered"],
        "max_corrupted_delivered": 0,
        "floor_only": ["gold_completion_rate", "baseline_failure_modes",
                       "straggler_flagged", "trace_fault_annotations"],
        "gold_completion_rate": h["gold_completion_rate"],
        "min_gold_completion_rate": 0.99,
        "baseline_failure_modes": baseline_failure_modes,
        "min_baseline_failure_modes": 1,
        # telemetry claims: the scripted straggle on replica 1 is flagged
        # by the drift monitor, and the fault machinery is visible on the
        # trace as instant annotations
        "straggler_flagged": int("replica:1" in flagged_ever),
        "min_straggler_flagged": 1,
        "trace_fault_annotations": sum(annotations.values()),
        "min_trace_fault_annotations": 3,
        # hardened-arm outcome ------------------------------------------
        "availability": h["availability"],
        "stuck_requests": h["stuck_requests"],
        "retries": snap["retries"],
        "hedges": snap["hedges"],
        "hedge_wins": snap["hedge_wins"],
        "timeouts": snap["timeouts"],
        "corrupt_batches_caught": snap["corrupt_batches"],
        "dispatch_failures": snap["dispatch_failures"],
        "quarantines": snap["quarantines"],
        "probes": snap["probes"],
        "recoveries": snap["recoveries"],
        "brownout_shed": snap["brownout_shed"],
        "p99_ms": snap["p99_ms"],
        "wall_s": h_run["wall_s"],
        # baseline arm under the SAME plan ------------------------------
        "baseline_corrupted_delivered": b["corrupted_delivered"],
        "baseline_stuck_requests": b["stuck_requests"],
        "baseline_gold_completion_rate": b["gold_completion_rate"],
        "baseline_availability": b["availability"],
        "baseline_wall_s": b_run["wall_s"],
        "t_exec_s": t_exec,
        "s_per_cycle": cal["s_per_cycle"],
        # telemetry detail (informational) ------------------------------
        "trace_annotations": annotations,
        "trace_events": len(tracer),
        "trace_dropped": tracer.dropped,
        "drift_flagged_ever": flagged_ever,
        "drift": drift.status(),
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
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--load", type=float, default=0.25,
                    help="fraction of one-replica capacity for the rate")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--quick", action="store_true",
                    help="small request count (CI)")
    ap.add_argument("--soak", action="store_true",
                    help="nightly long run: more requests, higher fault rates")
    ap.add_argument("--out", default="experiments/bench/chaos_serving.json")
    ap.add_argument("--trace", default=None,
                    help="write the hardened arm's Chrome trace JSON here")
    args = ap.parse_args()
    requests = args.requests
    if requests is None:
        requests = 600 if args.soak else (128 if args.quick else 160)
    record = run(requests=requests, seed=args.seed, load=args.load,
                 soak=args.soak, trace=args.trace, out=args.out)
    pretty = {k: v for k, v in record.items() if k not in ("fault_plan", "drift")}
    print(json.dumps(pretty, indent=2))


if __name__ == "__main__":
    main()
