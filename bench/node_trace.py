"""Trace a closed-loop cell with the program's own spans, and read the
engine's per-node device time and its idle gaps by cause.

    python3 bench/node_trace.py --workload nid_mlp.batch --seed 1 \
        [--seconds 2] [--window-seconds 10]

Builds the cell as ``bench/run.py`` does, warms it up, then runs a traced
tail of its closed loop in which each call goes through
``acc.dispatch(batch, tracer=...)``: the program's ``engine.dispatch``
span lands in the profiler trace beside the device ops, whose op names
carry their graph node.  ``bench/harness/nodes.py`` reduces the trace.
Prints one JSON line: the engine's dispatch span, the launch, fetch and
host parts of the idle gap per call, the plumbing share of busy time, the
node with the most device time per sample, and the checks that the parts
add up.  With ``--window-seconds``, it also times what the tracer costs:
microseconds per span with and without a recording profiler, and the
closed loop's rate with and without a tracer passed, windows alternated.
Without a TPU it exits 2.
"""

import argparse
import json
import os
import shutil
import statistics
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPANS_TIMED = 100_000  # spans per cost reading


def _traced_tail(call, batches, seconds, clock):
    """One closed loop under the profiler, inside a ``bench.window``."""
    import jax

    from bench.harness import load, trace

    log_dir = tempfile.mkdtemp(prefix="node_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    try:
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(trace.WINDOW):
                load.closed_loop(call, batches, seconds, clock=clock,
                                 span=jax.profiler.TraceAnnotation)
        finally:
            jax.profiler.stop_trace()
        return trace.load(log_dir)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)


def _span_us(recording: bool) -> float:
    import jax

    from repro.telemetry import Tracer

    tr = Tracer(capacity=SPANS_TIMED)
    log_dir = tempfile.mkdtemp(prefix="span_cost_")
    try:
        if recording:
            jax.profiler.start_trace(log_dir)
        t0 = time.perf_counter()
        for _ in range(SPANS_TIMED):
            with tr.span("engine.dispatch", cat="engine", batch=4096,
                         n_micro=32):
                pass
        dt = time.perf_counter() - t0
        if recording:
            jax.profiler.stop_trace()
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    return 1e6 * dt / SPANS_TIMED


def _overhead(acc, batches, seconds, clock) -> dict:
    from bench.harness import cell, load
    from repro.telemetry import Tracer

    b = len(batches[0])
    calls = {"untraced": acc,
             "traced": lambda x: acc.dispatch(x, tracer=Tracer())[0]}
    rates: dict = {k: [] for k in calls}
    for arm in ("untraced", "traced", "traced", "untraced"):
        with cell._frozen_heap():
            w = load.closed_loop(calls[arm], batches, seconds, clock=clock)
        rates[arm].append(len(w.calls) * b / (w.t1 - w.t0))
    return {"span_us": {"no_profiler": _span_us(False),
                        "profiler_recording": _span_us(True)},
            "samples_per_s": rates}


def run(workload: str, seed: int, seconds: float, window_seconds: float,
        *, require_chip: bool = True) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench.harness import cell, nodes, spec, trace
    from repro.kernels import ops
    from repro.launch.compile_cache import enable_compile_cache
    from repro.telemetry import Tracer

    clock = time.perf_counter
    c = spec.load(workload)
    if c.traffic["loop"] != "closed":
        raise SystemExit(f"node_trace: {workload} is not a closed-loop cell")
    devices = cell.devices_for(c.chips, require_chip)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cfg, fam, tr = c.config, c.family, c.traffic
    b, p = tr["batch"], tr["distinct_batches"]
    with jax.default_device(devices[0]):
        acc = fam.build(cfg, fam.init_params(cfg, seed))
        x = jnp.asarray(fam.make_inputs(cfg, b * p, seed))
        batches = [x[i * b:(i + 1) * b] for i in range(p)]
        jax.block_until_ready(batches)
        tracer = Tracer()
        traced = lambda xb: acc.dispatch(xb, tracer=tracer)[0]  # noqa: E731
        for _ in range(2):
            np.asarray(acc(batches[0]))
            np.asarray(traced(batches[0]))
        engine = acc.engine
        hlo = engine._jit.lower(engine.params, batches[0],
                                engine.plan(b).n_micro).compile().as_text()
        profile = _traced_tail(traced, batches, seconds, clock)
        overhead = (_overhead(acc, batches, window_seconds, clock)
                    if window_seconds > 0 else None)

    s = nodes.reduce(profile, nodes=[n.name for n in engine.graph],
                     hlo_ops=nodes.hlo_op_names(hlo))
    harness = trace.reduce(profile, host_spans=cell.HOST_SPANS
                           | {nodes.ENGINE_DISPATCH})
    bottleneck = s.bottleneck(b)
    gap_s = sum(g.launch_s + g.fetch_s + g.host_s for g in s.gaps)
    dev = devices[0]
    return {
        "workload": workload, "seed": seed, "batch": b,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices)},
        "metrics": {
            "engine_dispatch_ms": s.dispatch_ms(),
            "launch_gap_ms": s.gap_ms("launch"),
            "fetch_gap_ms": s.gap_ms("fetch"),
            "host_gap_ms": s.gap_ms("host"),
            "plumbing_share": s.plumbing_share(),
            "bottleneck_node_us": bottleneck and bottleneck[1],
        },
        "bottleneck_node": bottleneck and bottleneck[0],
        "device_nodes": s.device_nodes(),
        "node_us_per_sample": s.us_per_sample(b),
        "unscoped_ops": s.unscoped,
        "checks": {
            "calls": len(s.gaps), "runs_in_window": s.runs,
            "gap_sum_s": gap_s, "idle_s": s.idle_s,
            "node_plus_plumbing_s": sum(s.node_s.values()) + s.plumbing_s,
            "busy_s": s.busy_s, "harness_busy_s": harness.busy_s,
        },
        "gap_ms_quartiles": {
            cause: statistics.quantiles(
                [1e3 * getattr(g, cause + "_s") for g in s.gaps], n=4)
            for cause in ("launch", "fetch", "host") if len(s.gaps) > 1},
        "kernels": {k: {"seconds_calls": harness.kernel(k),
                        "unscoped_events": s.unscoped.get(k, 0)}
                    for k in sorted(set(ops.tpu_kernel_names(hlo)))},
        "idle_by_host": harness.idle_by_host,
        "overhead": overhead,
    }


def main(argv=None, *, require_chip: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--window-seconds", type=float, default=0.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench.harness import cell

    try:
        result = run(args.workload, args.seed, args.seconds,
                     args.window_seconds, require_chip=require_chip)
    except cell.NoChip as e:
        print(f"node_trace: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
