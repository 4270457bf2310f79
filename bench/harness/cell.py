"""One run of one cell: set up, warm up, measure, check, report.

The end-to-end metrics (``--trace 0``) come from one measured window on
the host clock.  A traced run (``--trace 1``) measures the same window for
the counters and host spans, then a short tail of the same traffic under
the JAX profiler for the device numbers; it reports the per-layer metrics.
Either way every answer due in the window is held to the configuration's
plain reference once the window has closed and the program is freed.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import shutil
import sys
import tempfile
import time

import numpy as np

from bench.harness import load, spec, stats, trace as trace_mod

TRACE_S = 2.0  # the traced tail: a few seconds of the cell's own traffic
SAMPLED_ROWS = 512  # closed-loop answers compared with the reference
HOST_SPANS = frozenset({"enqueue", "block", "submit", "poll", "dispatch",
                        "resolve"})
# one event per program lowered: a jit cache miss, compiled or read back
PROGRAM_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
CACHE_EVENTS = ("/jax/compilation_cache/cache_hits",
                "/jax/compilation_cache/cache_misses")
LIMITS = {"wrong_rows": 0, "unanswered": 0}  # exact comparisons


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Measured:
    e2e: dict  # end-to-end metric name -> value
    ctx: "Context"
    attempted: int
    failed: int
    xs: np.ndarray  # inputs of the answers to compare
    got: np.ndarray  # the program's answers, one row per input
    weights: np.ndarray  # how many requests each compared answer stands for
    window_programs: int  # programs lowered inside the measured window
    unanswered: int = 0  # admitted requests that never got an answer


@dataclasses.dataclass
class Context:
    """What the per-layer metric readers (``bench/metrics/*.py``) read.
    A field a cell does not produce stays ``None``."""

    config: dict
    family: object
    device_kind: str
    rate: float | None = None  # samples per second over the window
    lag_s: np.ndarray | None = None  # send time minus due time, per request
    counters: dict | None = None  # ServingMetrics counters over the window
    dispatch_s: list | None = None  # batcher ``dispatch`` span durations
    replica_load: dict | None = None  # dispatches per replica, window
    plan: dict | None = None  # the engine's stream plan for one call
    trace: trace_mod.TraceSummary | None = None

    def peaks(self) -> dict:
        from bench.roofline import work

        return work.peaks(self.device_kind)


class _Events:
    """Counts programs lowered and compile-cache hits and misses."""

    def __init__(self):
        self.programs = 0
        self.cache = {e.rsplit("/", 1)[-1]: 0 for e in CACHE_EVENTS}

    def _duration(self, event, duration, **kw):
        if event == PROGRAM_EVENT:
            self.programs += 1

    def _event(self, event, **kw):
        if event in CACHE_EVENTS:
            self.cache[event.rsplit("/", 1)[-1]] += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


def devices_for(chips: int, require_chip: bool) -> list:
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no accelerator: {e}") from e
    if require_chip:
        if devs[0].platform != "tpu":
            raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r}); "
                         "the benchmark measures on the chip only")
        if len(devs) < chips:
            raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
        return devs[:chips]
    # a rehearsal off the chip: replicas may share one device
    return [devs[i % len(devs)] for i in range(chips)]


@contextlib.contextmanager
def _frozen_heap():
    """Move every object set-up made to the collector's permanent
    generation for the window: a full collection then walks only what the
    window itself allocates, instead of stalling the one thread that both
    sends the load and serves it for tens of milliseconds."""
    gc.collect()
    gc.freeze()
    try:
        yield
    finally:
        gc.unfreeze()


def _profiled(fn):
    """Run ``fn`` under the JAX profiler; return its result and the trace
    summary of the ``bench.window`` it ran in."""
    import jax

    log_dir = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    try:
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(trace_mod.WINDOW):
                out = fn(jax.profiler.TraceAnnotation)
        finally:
            jax.profiler.stop_trace()
        summary = trace_mod.reduce(trace_mod.load(log_dir),
                                   host_spans=HOST_SPANS)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    return out, summary


# ----------------------------------------------------------- closed loop
def _closed(cell, acc, seed, seconds, traced, devices, t_process, clock,
            events):
    import jax
    import jax.numpy as jnp

    cfg, tr, fam = cell.config, cell.traffic, cell.family
    b, p = tr["batch"], tr["distinct_batches"]
    x = jnp.asarray(fam.make_inputs(cfg, b * p, seed))
    batches = [x[i * b:(i + 1) * b] for i in range(p)]
    jax.block_until_ready(batches)
    for _ in range(2):  # the window's one shape: compile, then settle
        np.asarray(acc(batches[0]))
    with _frozen_heap():
        setup_s = clock() - t_process
        p0 = events.programs
        w = load.closed_loop(acc, batches, seconds, clock=clock)
        window_programs = events.programs - p0
    n = len(w.calls) * b
    rate = n / (w.t1 - w.t0)
    plan = acc.plan(b)
    ctx = Context(cfg, fam, devices[0].device_kind, rate=rate,
                  plan={"batch": b, "n_micro": plan.n_micro,
                        "microbatch": plan.microbatch})
    if traced:
        with _frozen_heap():
            _, ctx.trace = _profiled(lambda span: load.closed_loop(
                acc, batches, TRACE_S, clock=clock, span=span,
                first=len(w.calls)))
    rng = np.random.default_rng([int(seed), 3])
    picks = np.sort(rng.choice(n, size=min(SAMPLED_ROWS, n), replace=False))
    xs = np.stack([np.asarray(batches[w.calls[i // b][0]][i % b])
                   for i in picks])
    got = np.stack([w.calls[i // b][1][i % b] for i in picks])
    return Measured({tr["rate_metric"]: rate, "setup_s": setup_s}, ctx, n, 0,
                    xs, got, np.ones(len(picks), np.int64), window_programs)


# ------------------------------------------------------------- open loop
def _annotate_pool(pool, span):
    """Host spans around the batcher's calls into the replica pool."""
    dispatch = pool.dispatch

    def annotated_dispatch(*args, **kw):
        with span("dispatch"):
            pending = dispatch(*args, **kw)
        resolve = pending.resolve

        def annotated_resolve():
            with span("resolve"):
                return resolve()

        pending.resolve = annotated_resolve
        return pending

    pool.dispatch = annotated_dispatch


def _open(cell, acc, seed, seconds, traced, devices, t_process, clock,
          events):
    import jax

    from repro.serving import ReplicaPool
    from repro.telemetry import Tracer

    cfg, tr, fam = cell.config, cell.traffic, cell.family
    records = np.asarray(fam.make_inputs(cfg, tr["distinct_records"], seed))
    buckets = tuple(cfg["build"]["buckets"])
    tracer = Tracer() if traced else None
    pool = ReplicaPool(acc.engine, devices=devices)
    batcher = acc.serve(batch_buckets=buckets, pool=pool, tracer=tracer)
    for row in records[:2 * buckets[-1] + sum(buckets[:-1])]:
        batcher.submit(row)  # warm the host path through every bucket
        batcher.poll()
    for rid in batcher.drain(timeout=tr["drain_s"]):
        batcher.pop_result(rid)
    rng = np.random.default_rng([int(seed), 4])
    offsets = load.fixed_count_schedule(tr["rate_hz"], seconds, rng)
    rows = rng.integers(0, len(records), len(offsets))
    if tracer is not None:
        tracer.clear()
    counters0, load0 = dict(batcher.metrics.counters), pool.load()
    with _frozen_heap():
        p0 = events.programs
        t0 = clock()
        setup_s = t0 - t_process
        w = load.open_loop(batcher, records, rows, offsets, t0, seconds,
                           drain_s=tr["drain_s"], clock=clock)
        window_programs = events.programs - p0
    lat_ms = w.latency_s * 1e3
    e2e = {"latency_p99_ms": stats.percentile(lat_ms, 99),
           "latency_p50_ms": stats.percentile(lat_ms, 50),
           "setup_s": setup_s}
    counters = {k: v - counters0.get(k, 0)
                for k, v in batcher.metrics.counters.items()}
    after = pool.load()
    ctx = Context(cfg, fam, devices[0].device_kind, lag_s=w.lag_s,
                  counters=counters,
                  replica_load={k: after[k] - load0[k] for k in after})
    if tracer is not None:
        ctx.dispatch_s = [s["dur"] for s in tracer.spans(name="dispatch")]
    windows = [w]
    if traced:
        _annotate_pool(pool, jax.profiler.TraceAnnotation)
        offs = load.fixed_count_schedule(tr["rate_hz"], TRACE_S, rng)
        rows2 = rng.integers(0, len(records), len(offs))
        with _frozen_heap():
            tail, ctx.trace = _profiled(lambda span: load.open_loop(
                batcher, records, rows2, offs, clock(), TRACE_S,
                drain_s=tr["drain_s"], clock=clock, span=span))
        windows.append(tail)
    # an admitted request that never got an answer is lost; one refused at
    # the door got an explicit refusal: a failure and a +inf latency, not a
    # wrong or missing answer
    failed = int(np.sum(~np.isfinite(w.done)))
    lost = sum(int(np.sum(~np.isfinite(x.done) & ~x.refused)) for x in windows)
    came = np.concatenate([np.isfinite(x.done) for x in windows])
    rows_all = np.concatenate([x.rows for x in windows])[came]
    outs = np.concatenate([x.outs for x in windows])[came]
    xs, got, weights = _unique_answers(records, rows_all, outs)
    return Measured(e2e, ctx, len(offsets), failed, xs, got, weights,
                    window_programs, lost)


def _unique_answers(records, rows, outs):
    """The answers that came, collapsed over requests that carried the
    same record and got the same answer: the reference runs once per
    distinct (record, answer), weighted by how many requests it covers."""
    key = np.concatenate([rows[:, None].astype(np.float64), outs], axis=1)
    uniq, counts = np.unique(key, axis=0, return_counts=True)
    return records[uniq[:, 0].astype(np.int64)], uniq[:, 1:], counts


# ------------------------------------------------------------------ run
def run(workload: str, seed: int, seconds: float, traced: bool, *,
        t_process: float | None = None, root: str = spec.ROOT,
        require_chip: bool = True, log=sys.stderr) -> tuple[dict, dict]:
    """One run of ``workload``; returns ``(result line, checks)``."""
    clock = time.perf_counter
    t_process = clock() if t_process is None else t_process
    cell = spec.load(workload, root)
    devices = devices_for(cell.chips, require_chip)

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    # every program, however quick to compile, is read back on later runs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    fam = cell.family
    runner = _closed if cell.traffic["loop"] == "closed" else _open
    with _Events() as events, jax.default_device(devices[0]):
        params = fam.init_params(cell.config, seed)
        params_host = jax.device_get(params)
        acc = fam.build(cell.config, params)
        m = runner(cell, acc, seed, seconds, traced, devices, t_process,
                   clock, events)
    peak = _memory_peak(devices)
    del acc, params
    gc.collect()

    ref = fam.Reference(cell.config, params_host)
    bad, note = ref.judge(m.xs, m.got)
    wrong = int(np.sum(m.weights[bad]))
    checks = {"wrong_rows": {"value": wrong, "limit": LIMITS["wrong_rows"]}}
    if cell.traffic["loop"] == "open":
        checks["unanswered"] = {"value": m.unanswered,
                                "limit": LIMITS["unanswered"]}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": int(m.attempted),
              "failed": int(m.failed)}
    if traced:
        metrics = {}
        for entry in cell.per_layer:
            value = spec.metric_reader(entry["name"], root)(m.ctx)
            if value is not None:
                metrics[entry["name"]] = {"value": float(value),
                                          "unit": entry["unit"]}
        t = m.ctx.trace
        device.update(busy_s=t.busy_s, window_s=t.window_s)
        result.update(metrics=metrics, device=device, breakdown=t.breakdown())
    else:
        units = {e["name"]: e["unit"] for e in cell.end_to_end}
        result.update(metrics={k: {"value": float(v), "unit": units[k]}
                               for k, v in m.e2e.items() if k in units},
                      device=device)
    result.update(window_programs=m.window_programs,
                  setup_cache=events.cache, compile_cache=cache_dir,
                  reference=note, checks=checks)
    if m.window_programs:
        print(f"bench: {m.window_programs} program(s) lowered inside the "
              "measured window", file=log)
    return result, checks


def _memory_peak(devices) -> int:
    peak = 0
    for d in {d.id: d for d in devices}.values():
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return peak
