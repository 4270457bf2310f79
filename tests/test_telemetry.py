"""repro.telemetry tests: tracer span discipline (nesting, bounded buffer,
Chrome export), log-bucketed histograms + windowed rates + Prometheus
exposition, the drift monitor (censored observations, latched flags),
thread-safe ServingMetrics, engine/pipeline instrumentation invariants
(traced == untraced bit-exactness, per-node spans sum within the enclosing
span), and the regression gate's None tolerance."""

import importlib.util
import json
import math
import os
import subprocess
import sys
import textwrap
import threading

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core.engine import FusedEngine
from repro.distributed.pipeline import emit_schedule_spans, pipeline_occupancy
from repro.serving import ContinuousBatcher, ServingMetrics
from repro.telemetry import (
    DEFAULT_BAND,
    DriftMonitor,
    LogHistogram,
    Tracer,
    WindowedRate,
    render_prometheus,
)
from tests.test_serving import _mlp_graph, _samples

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class FakeClock:
    """Deterministic monotone clock: each call advances by ``step``."""

    def __init__(self, step=1.0):
        self.t = 0.0
        self.step = step

    def __call__(self):
        self.t += self.step
        return self.t


def assert_no_overlap_within_thread(spans):
    """Within one thread, duration spans must nest or be disjoint -- a pair
    that partially overlaps would mean the stack discipline broke."""
    by_tid = {}
    for sp in spans:
        by_tid.setdefault(sp["tid"], []).append(sp)
    for tid, sps in by_tid.items():
        sps = sorted(sps, key=lambda s: (s["t0"], -s["t1"]))
        for a, b in zip(sps, sps[1:]):
            nested = b["t0"] >= a["t0"] and b["t1"] <= a["t1"]
            disjoint = b["t0"] >= a["t1"]
            assert nested or disjoint, (
                f"spans overlap without nesting on tid {tid}: {a} vs {b}")


# ------------------------------------------------------------------- tracer
def test_spans_nest_and_never_overlap_within_a_thread():
    tr = Tracer(clock=FakeClock(step=1.0))
    with tr.span("outer", cat="t"):
        with tr.span("inner1", cat="t"):
            pass
        with tr.span("inner2", cat="t"):
            with tr.span("leaf", cat="t"):
                pass
    spans = tr.spans()
    assert [s["name"] for s in spans] == ["inner1", "leaf", "inner2", "outer"]
    depths = {s["name"]: s["depth"] for s in spans}
    assert depths == {"outer": 0, "inner1": 1, "inner2": 1, "leaf": 2}
    assert_no_overlap_within_thread(spans)
    outer = next(s for s in spans if s["name"] == "outer")
    for s in spans:
        assert outer["t0"] <= s["t0"] and s["t1"] <= outer["t1"]


def test_tracer_buffer_bounded_and_drop_accounted():
    tr = Tracer(capacity=8)
    for i in range(20):
        tr.instant("tick", n=i)
    assert len(tr) == 8
    assert tr.dropped == 12
    # oldest dropped: the survivors are the 8 newest
    assert [ev["args"]["n"] for ev in tr.events()] == list(range(12, 20))
    tr.clear()
    assert len(tr) == 0 and tr.dropped == 0


def test_span_args_mutable_while_open_and_land_in_event():
    tr = Tracer()
    with tr.span("dispatch", cat="serving", bucket=8) as sp:
        sp.args["replica"] = 3
    ev = tr.spans(name="dispatch")[0]
    assert ev["args"] == {"bucket": 8, "replica": 3}


def test_chrome_export_is_valid_json_with_named_lanes():
    tr = Tracer(meta={"run": "test"})
    with tr.span("work", cat="engine"):
        tr.instant("mark", cat="engine", k=1)
    tr.begin_async("request", 7, cat="request")
    tr.end_async("request", 7, cat="request")
    tr.counter("queue_depth", 3, cat="serving")
    tr.emit_span("micro0", 0.0, 1.0, cat="pipeline", tid="stage0", stage=0)
    doc = json.loads(json.dumps(tr.to_chrome()))  # strict-JSON round trip
    evs = doc["traceEvents"]
    phases = sorted(e["ph"] for e in evs)
    assert phases == sorted(["X", "i", "b", "e", "C", "X", "M"])
    meta = [e for e in evs if e["ph"] == "M"]
    assert meta[0]["args"]["name"] == "stage0"
    lane_tid = meta[0]["tid"]
    assert any(e["ph"] == "X" and e["tid"] == lane_tid for e in evs)
    assert doc["metadata"]["run"] == "test"
    async_evs = [e for e in evs if e["ph"] in ("b", "e")]
    assert {e["id"] for e in async_evs} == {7}


def test_tracer_summary_aggregates_per_name():
    clock = FakeClock(step=1.0)
    tr = Tracer(clock=clock)
    for _ in range(3):
        with tr.span("step"):
            pass
    s = tr.summary()
    assert s["spans"]["step"]["count"] == 3
    assert s["events"]["X"] == 3
    assert s["dropped"] == 0


# ---------------------------------------------------------------- histogram
def test_log_histogram_percentiles_within_bucket_width():
    h = LogHistogram()
    rng = np.random.default_rng(0)
    vals = rng.lognormal(mean=-5.0, sigma=1.0, size=5000)
    for v in vals:
        h.observe(float(v))
    for p in (50, 95, 99):
        want = float(np.percentile(vals, p, method="inverted_cdf"))
        assert h.percentile(p) == pytest.approx(want, rel=0.05)
    assert h.count == 5000
    assert h.mean() == pytest.approx(float(vals.mean()))


def test_log_histogram_single_sample_exact_and_empty_none():
    h = LogHistogram()
    assert h.percentile(50) is None and h.mean() is None
    h.observe(0.123)
    # the midpoint estimate is clamped into [min, max]
    assert h.percentile(50) == pytest.approx(0.123)
    assert h.percentile(99) == pytest.approx(0.123)


def test_log_histogram_merge_and_json_round_trip():
    a, b = LogHistogram(), LogHistogram()
    for v in (0.001, 0.002, 0.004):
        a.observe(v)
    for v in (0.008, 0.016):
        b.observe(v)
    a.merge(b)
    assert a.count == 5 and a.max == 0.016
    rt = LogHistogram.from_json(json.loads(json.dumps(a.to_json())))
    assert rt.buckets == a.buckets and rt.count == a.count
    assert rt.percentile(50) == a.percentile(50)
    with pytest.raises(ValueError, match="merge"):
        a.merge(LogHistogram(lo=1e-3))


def test_log_histogram_underflow_bucket():
    h = LogHistogram(lo=1e-3)
    h.observe(1e-9)  # below lo: underflow bucket, counted, percentile = lo..
    assert h.buckets == {-1: 1}
    assert h.count == 1
    # ..clamped to the observed range
    assert h.percentile(50) == pytest.approx(1e-9)


# ------------------------------------------------------------ windowed rate
def test_windowed_rate_slides():
    t = {"now": 0.0}
    rate = WindowedRate(10.0, slots=20, clock=lambda: t["now"])
    for i in range(50):
        t["now"] = i * 0.1
        rate.add()
    assert rate.rate() == pytest.approx(5.0, rel=0.15)  # 50 events in 5 s
    t["now"] = 30.0  # window slid past everything
    assert rate.rate() == 0.0


# --------------------------------------------------------------- prometheus
def test_render_prometheus_exposition():
    h = LogHistogram()
    h.observe(0.002)
    h.observe(0.004)
    text = render_prometheus(
        counters={"completed": 2}, gauges={"depth": 3, "p99": None},
        histograms={"latency_seconds": h}, prefix="t")
    assert "# TYPE t_completed_total counter" in text
    assert "t_completed_total 2" in text
    assert "t_depth 3.0" in text
    assert "t_p99 NaN" in text  # Prometheus spells missing values NaN
    assert 't_latency_seconds_bucket{le="+Inf"} 2' in text
    assert "t_latency_seconds_count 2" in text
    # cumulative le buckets are monotone
    cums = [int(line.rsplit(" ", 1)[1]) for line in text.splitlines()
            if "_bucket{" in line]
    assert cums == sorted(cums)


# ------------------------------------------------------------ drift monitor
def test_drift_monitor_flags_sustained_high_ratio_only():
    dm = DriftMonitor({"stage0": 1.0}, min_samples=2)
    dm.observe("stage0", 1.1)
    assert dm.flagged() == []  # in band
    dm.observe("stage0", 1.2)
    assert dm.flagged() == []
    for _ in range(6):
        dm.observe("stage0", 10.0)  # EWMA climbs out of the band
    assert dm.flagged() == ["stage0"]
    assert dm.flagged_ever() == ["stage0"]
    # recovery clears the live flag but not the latch
    for _ in range(30):
        dm.observe("stage0", 1.0)
    assert dm.flagged() == []
    assert dm.flagged_ever() == ["stage0"]


def test_drift_monitor_censored_semantics():
    dm = DriftMonitor({"r": 1.0})
    # a lower bound inside the band proves nothing: dropped, no state, no flag
    assert dm.observe("r", 2.0, censored=True) is None
    assert dm.flagged_ever() == []
    # a lower bound above band-high is conclusive: recorded AND latched,
    # even though later clean samples pull the EWMA back into the band
    assert dm.observe("r", 10.0, censored=True) == pytest.approx(10.0)
    assert dm.flagged_ever() == ["r"]
    assert dm.observe("r", 2.0, censored=True) is None  # counted this time
    for _ in range(30):
        dm.observe("r", 1.0)
    assert dm.flagged() == []
    assert dm.flagged_ever() == ["r"]
    st = dm.status()
    assert st["keys"]["r"]["censored_hits"] == 1
    assert st["keys"]["r"]["censored_dropped"] >= 1
    json.dumps(st)  # JSON-safe


def test_drift_monitor_unknown_key_discarded():
    dm = DriftMonitor()
    assert dm.observe("nobody", 1.0) is None  # no prediction, no explicit
    assert dm.observe("x", 5.0, predicted_s=1.0) == pytest.approx(5.0)
    assert dm.flagged_ever() == ["x"]
    assert DEFAULT_BAND[0] < 1.0 < DEFAULT_BAND[1]


def test_drift_monitor_from_schedule():
    from repro.core import dataflow

    g = _mlp_graph()
    sched = dataflow.schedule(g)
    dm = DriftMonitor.from_schedule(sched, 1e-8)
    assert dm.predictions
    for s in sched.stages:
        assert dm.predictions[s.name] == pytest.approx(s.cycles * 1e-8)


# ----------------------------------------------------------- serving metrics
def test_serving_metrics_concurrent_increments_lose_nothing():
    """Regression: ServingMetrics is shared across harvest / monitor
    threads; concurrent count() and observe_latency() must never lose an
    increment (the pre-lock implementation did)."""
    m = ServingMetrics()
    N, T = 2000, 8

    def work():
        for i in range(N):
            m.count("retries")
            m.observe_latency(0.001 * (1 + i % 7))
            if i % 64 == 0:
                m.snapshot()  # concurrent reads must not throw either

    threads = [threading.Thread(target=work) for _ in range(T)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert m.counters["retries"] == N * T
    assert m.counters["completed"] == N * T
    assert m.latency.count == N * T


def test_serving_metrics_empty_percentiles_are_json_null_not_nan():
    m = ServingMetrics()
    snap = m.snapshot()
    assert snap["p50_ms"] is None and snap["p99_ms"] is None
    text = json.dumps(snap)  # NaN would raise with allow_nan=False
    json.loads(text)
    json.dumps(snap, allow_nan=False)
    assert not math.isnan(snap["availability"])


def test_serving_metrics_percentiles_and_prometheus():
    m = ServingMetrics()
    for ms in range(1, 101):
        m.observe_latency(ms / 1e3)
    pct = m.latency_percentiles()
    assert pct["p50_ms"] == pytest.approx(50.0, rel=0.05)
    assert pct["p99_ms"] == pytest.approx(99.0, rel=0.05)
    text = m.prometheus()
    assert "repro_serving_completed_total 100" in text
    assert 'repro_serving_latency_seconds_bucket{le="+Inf"} 100' in text


# ------------------------------------------------- engine instrumentation
def test_engine_profile_bit_exact_and_node_spans_nest():
    engine = FusedEngine(_mlp_graph(), microbatches=2)
    x = jnp.asarray(_samples(6))
    want = np.asarray(engine(x))
    tr = Tracer()
    drift = DriftMonitor.from_schedule(engine.schedule, 1e-8)
    got, plan = engine.profile(x, tr, drift=drift)
    np.testing.assert_array_equal(np.asarray(got), want)

    spans = tr.spans()
    assert_no_overlap_within_thread(spans)
    outer = tr.spans(name="engine.profile")[0]
    node_spans = tr.spans(cat="node")
    assert len(node_spans) == plan.n_micro * len(engine.graph)
    # per-node spans sum to no more than the enclosing profile span
    assert sum(s["dur"] for s in node_spans) <= outer["dur"] + 1e-9
    for s in node_spans:
        assert outer["t0"] <= s["t0"] and s["t1"] <= outer["t1"]
    # every scheduled stage's observation reached the drift monitor (the
    # input node has no schedule stage, so no prediction: dropped)
    assert set(drift.status()["keys"]) == {s.name for s in engine.schedule.stages}


def test_engine_dispatch_traced_matches_untraced():
    engine = FusedEngine(_mlp_graph())
    x = jnp.asarray(_samples(5))
    plain, _ = engine.dispatch(x)
    tr = Tracer()
    traced, plan = engine.dispatch(x, tracer=tr)
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(traced))
    sp = tr.spans(name="engine.dispatch")
    assert len(sp) == 1
    assert sp[0]["args"]["batch"] == 5
    assert sp[0]["args"]["n_micro"] == plan.n_micro


# ------------------------------------------------- serving instrumentation
def test_traced_serving_bit_exact_with_untraced():
    engine = FusedEngine(_mlp_graph())
    xs = _samples(12)
    want = np.asarray(engine(jnp.asarray(xs)))

    plain = ContinuousBatcher(engine, batch_buckets=(1, 4))
    rids_p = plain.submit_batch(xs)
    plain.drain()

    tr = Tracer()
    drift = DriftMonitor()
    traced = ContinuousBatcher(engine, batch_buckets=(1, 4),
                               tracer=tr, drift=drift)
    rids_t = traced.submit_batch(xs)
    traced.drain()

    for rid_p, rid_t, y in zip(rids_p, rids_t, want):
        np.testing.assert_array_equal(plain.results[rid_p].out, y)
        np.testing.assert_array_equal(traced.results[rid_t].out, y)

    # full request lifecycle on the trace: every admitted rid opens and
    # closes exactly one async interval
    begins = [e for e in tr.events() if e["ph"] == "b"]
    ends = [e for e in tr.events() if e["ph"] == "e"]
    assert {e["id"] for e in begins} == set(rids_t)
    assert {e["id"] for e in ends} == set(rids_t)
    assert tr.spans(name="dispatch") and tr.spans(name="resolve")
    assert_no_overlap_within_thread(tr.spans())
    # resolved latencies fed the drift monitor (per-replica keys)
    assert any(k.startswith("replica:") for k in drift.status()["keys"])


# ------------------------------------------------------------------ pipeline
def test_pipeline_occupancy_accounting():
    occ = pipeline_occupancy(4, 8)
    assert occ["ticks"] == 11
    assert occ["bubble_ticks_per_stage"] == 3
    assert occ["occupancy"] == pytest.approx(8 / 11)
    assert pipeline_occupancy(1, 8)["occupancy"] == 1.0


def test_emit_schedule_spans_reconstructs_lanes():
    tr = Tracer()
    occ = emit_schedule_spans(tr, n_stages=3, n_micro=4, t0=0.0, t1=6.0)
    assert occ["ticks"] == 6
    spans = tr.spans(cat="pipeline")
    assert len(spans) == 3 * 6  # every stage emits every tick
    for s in range(3):
        lane = [sp for sp in spans if sp["tid"] == f"stage{s}"]
        busy = [sp for sp in lane if sp["name"] != "bubble"]
        assert len(busy) == 4 and len(lane) - len(busy) == 2
        # stage s runs microbatch m at tick s + m
        for sp in busy:
            assert sp["args"]["tick"] == s + sp["args"]["micro"]
        # lane ticks tile [t0, t1] exactly
        lane.sort(key=lambda sp: sp["t0"])
        assert lane[0]["t0"] == 0.0 and lane[-1]["t1"] == pytest.approx(6.0)
        for a, b in zip(lane, lane[1:]):
            assert a["t1"] == pytest.approx(b["t0"])


def test_pipeline_traced_multidevice_occupancy():
    """Traced as_pipeline on a 4-stage host mesh: bit-exact with the fused
    engine AND the trace carries one lane per stage with the static GPipe
    occupancy (subprocess so XLA_FLAGS never leaks into this process)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import lowering
        from repro.core.engine import FusedEngine
        from repro.core.ir import Node
        from repro.launch.mesh import make_mesh
        from repro.telemetry import Tracer

        rng = np.random.default_rng(0)
        d, L, bits = 32, 4, 2
        g = [Node("input", "in", {"shape": (d,), "bits": bits})]
        for i in range(L):
            w = rng.normal(0, 0.5, (d, d)).astype(np.float32)
            g.append(Node("linear", f"fc{i}", {}, {"w": jnp.asarray(w)}))
            g.append(Node("quant_act", f"act{i}",
                          {"bits": bits, "act_scale": 1.0}))
        fin = lowering.finalize(
            lowering.lower_to_mvu(g, mode="standard", weight_bits=4,
                                  act_bits=bits))
        eng = FusedEngine(fin)
        x = jnp.asarray(rng.integers(0, 2**bits, (8, 4, d)), jnp.int32)
        tr = Tracer()
        run = eng.as_pipeline(make_mesh((4,), ("stage",)), tracer=tr)
        got = np.asarray(run(x))
        want = np.asarray(eng(x.reshape(32, d))).reshape(8, 4, d)
        assert np.array_equal(got, want)

        runs = tr.spans(name="pipeline.run")
        assert len(runs) == 1
        occ = runs[0]["args"]["occupancy"]
        assert abs(occ - 8 / 11) < 1e-9, occ
        lanes = {sp["tid"] for sp in tr.spans(cat="pipeline")
                 if isinstance(sp["tid"], str)}
        assert lanes == {f"stage{s}" for s in range(4)}, lanes
        chrome = tr.to_chrome()
        names = [e["args"]["name"] for e in chrome["traceEvents"]
                 if e["ph"] == "M"]
        assert sorted(names) == [f"stage{s}" for s in range(4)]
        print("OK")
    """)
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=600,
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")},
    )
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    assert "OK" in proc.stdout


# --------------------------------------------------------------------- build
def test_build_telemetry_embeds_step_spans_in_report():
    import repro.build as build
    from repro.build import BuildConfig

    g = _mlp_graph()
    acc = build.build(g, config=BuildConfig(target="engine", telemetry=True))
    tele = acc.report.telemetry
    assert tele["spans"]  # one span per executed step
    assert set(tele["spans"]) == {f"step.{s}" for s in acc.report.step_names}
    json.dumps(acc.report.to_json())
    # telemetry off: no tracer, empty report section (the default)
    acc2 = build.build(g, config=BuildConfig(target="engine"))
    assert acc2.tracer is None and acc2.report.telemetry == {}


def test_accelerator_drift_monitor_requires_calibration():
    import repro.build as build
    from repro.build import BuildConfig
    from repro.build.config import BuildError

    acc = build.build(_mlp_graph(), config=BuildConfig(target="engine"))
    with pytest.raises(BuildError, match="calibrated"):
        acc.drift_monitor()


# ------------------------------------------------------- CI regression gate
def _gate():
    spec = importlib.util.spec_from_file_location(
        "check_bench_regression",
        os.path.join(REPO, "scripts", "check_bench_regression.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_regression_gate_tolerates_null_metrics():
    """Percentiles over zero samples serialize as JSON null; the gate's
    informational prints must render them as n/a, not crash formatting."""
    gate = _gate()
    base = {"bit_exact": True, "speedup": 2.5,
            "fused_samples_per_s": None, "unfused_samples_per_s": 100.0}
    fresh = {"bit_exact": True, "speedup": 2.5,
             "fused_samples_per_s": 123.0, "unfused_samples_per_s": None}
    assert gate.check_record("r", base, fresh,
                             max_regression=0.2, min_speedup=2.0) == []
