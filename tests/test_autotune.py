"""Autotuner tests: candidate enumeration + model pruning, cache
persistence, tune_graph purity, engine integration, and the zero-
measurement guarantee of ``FusedEngine(tune="cache")``."""

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import autotune, dataflow, lowering
from repro.core.engine import FusedEngine
from repro.core.ir import Graph, Node
from repro.core.mvu import KernelBlocks, MVUConfig


def _mlp_graph(rng, dims, bits=2) -> Graph:
    g: Graph = [Node("input", "in", {"shape": (dims[0],), "bits": bits})]
    for i, (k, n) in enumerate(zip(dims[:-1], dims[1:])):
        w = rng.normal(0, 0.5, (n, k)).astype(np.float32)
        g.append(Node("linear", f"fc{i}", {}, {"w": jnp.asarray(w)}))
        if i < len(dims) - 2:
            g.append(Node("quant_act", f"act{i}", {"bits": bits, "act_scale": 1.0}))
    return g


def _finalized(rng, dims, mode="standard", bits=2) -> Graph:
    g = _mlp_graph(rng, dims, bits)
    return lowering.finalize(
        lowering.lower_to_mvu(g, mode=mode, weight_bits=4, act_bits=bits))


def _no_timer(*a, **kw):
    raise AssertionError("timer must not run in cache mode")


# ------------------------------------------------------------ candidates
def test_candidates_pruned_and_ordered():
    cfg = MVUConfig(in_features=96, out_features=24)
    cands = autotune.enumerate_candidates(cfg, vmem_bytes=1 << 30)
    pallas = [c for c in cands if c.backend == "pallas"]
    # ordered by the analytic cycle model: measurement starts from the
    # model's best guess
    measured_order = [c.predicted_cycles for c in pallas[:-1] or pallas]
    assert measured_order == sorted(measured_order)
    # the xla backend is always in the design space
    assert any(c.backend == "xla" for c in cands)
    # block shapes are legal: clamped to the TPU minima
    assert all(c.blocks.block_n >= 8 and c.blocks.block_k >= 8 for c in pallas)


def test_candidates_vmem_pruning_rejects_over_budget():
    cfg = MVUConfig(in_features=2048, out_features=512)
    tight = autotune.enumerate_candidates(cfg, vmem_bytes=64 * 1024)
    loose = autotune.enumerate_candidates(cfg, vmem_bytes=1 << 30)
    # the shortlists exclude the heuristic/xla fallbacks appended at the end
    tight_measured = [c for c in tight if c.vmem_bytes > 0]
    loose_measured = [c for c in loose if c.vmem_bytes > 0]
    assert all(c.vmem_bytes <= 64 * 1024 for c in tight_measured)
    assert len(tight_measured) < len(loose_measured)


def test_conv_candidates_use_conv_working_set():
    cfg = MVUConfig(in_features=27, out_features=8, mode="xnor")
    cands = autotune.enumerate_candidates(
        cfg, n_pixels=36, in_shape=(8, 8, 3),
        conv={"kernel": 3, "stride": 1, "pad": 0}, vmem_bytes=1 << 30)
    pallas = [c for c in cands if c.backend == "pallas" and c.vmem_bytes > 0]
    assert pallas, "conv enumeration produced no measurable candidates"
    # conv schedules only vary block_m x block_n
    assert {c.blocks.block_n for c in pallas} >= {8}


# ----------------------------------------------------------------- cache
def test_cache_roundtrip(tmp_path):
    cache = autotune.ScheduleCache()
    key = "cpu|standard|n8|k16|thresh|px1"
    cache.put(key, {"backend": "xla", "block_m": 32, "block_n": 8,
                    "block_k": 16, "block_kw": 8})
    path = str(tmp_path / "cache.json")
    cache.save(path)
    back = autotune.ScheduleCache.load(path)
    assert back.get(key) == cache.get(key)
    assert key in back and len(back) == 1


def test_cache_version_mismatch_raises(tmp_path):
    path = tmp_path / "cache.json"
    path.write_text('{"version": 99, "entries": {}}')
    with pytest.raises(ValueError):
        autotune.ScheduleCache.load(str(path))


def test_default_cache_contains_committed_schedules():
    cache = autotune.default_cache()
    from repro.configs import cnv_bnn, nid_mlp

    for key in nid_mlp.TUNED_SCHEDULES:
        assert key in cache
    for key in cnv_bnn.TUNED_SCHEDULES:
        assert key in cache


# ------------------------------------------------------------ tune_graph
def test_tune_graph_cache_mode_is_pure_lookup():
    rng = np.random.default_rng(0)
    fin = _finalized(rng, [16, 8])
    key = autotune.node_key(fin[1].attrs["config"], epilogue="scale")
    cache = autotune.ScheduleCache({key: {
        "backend": "xla", "block_m": 64, "block_n": 8, "block_k": 16,
        "block_kw": 8}})
    tuned = autotune.tune_graph(fin, cache=cache, mode="cache",
                                timer=_no_timer)
    cfg = tuned[1].attrs["config"]
    assert cfg.backend == "xla"
    assert cfg.blocks == KernelBlocks(block_m=64, block_n=8, block_k=16,
                                      block_kw=8)
    assert cfg.block_m == 64
    # purity: the input graph keeps its heuristic config
    assert fin[1].attrs["config"].blocks is None
    assert fin[1].attrs["config"].backend == "pallas"


def test_tune_graph_cache_miss_keeps_heuristic():
    rng = np.random.default_rng(1)
    fin = _finalized(rng, [16, 8])
    tuned = autotune.tune_graph(fin, cache=autotune.ScheduleCache(),
                                mode="cache", timer=_no_timer)
    assert tuned[1].attrs["config"].blocks is None


def test_tune_graph_auto_fills_cache_and_stays_bit_exact():
    rng = np.random.default_rng(2)
    fin = _finalized(rng, [24, 12, 8])
    cache = autotune.ScheduleCache()
    tuned = autotune.tune_graph(fin, cache=cache, mode="auto",
                                sample_m=32, reps=1, max_measure=2)
    assert len(cache) == 2  # one entry per mvu node
    x = jnp.asarray(rng.integers(0, 4, (9, 24)), jnp.int32)
    want = np.asarray(dataflow.execute(fin, x))
    got = np.asarray(dataflow.execute(tuned, x))
    np.testing.assert_array_equal(got, want)


def test_tune_graph_rejects_unknown_mode():
    rng = np.random.default_rng(3)
    fin = _finalized(rng, [16, 8])
    with pytest.raises(ValueError):
        autotune.tune_graph(fin, cache=autotune.ScheduleCache(), mode="always")


# ---------------------------------------------------------------- engine
def test_engine_cache_mode_zero_measurement(monkeypatch):
    """Acceptance: tune="cache" is a pure cache lookup -- constructing the
    engine must never invoke the timer, even on a fully-populated cache."""
    monkeypatch.setattr(autotune, "paired_timer", _no_timer)
    rng = np.random.default_rng(4)
    fin = _finalized(rng, [24, 12, 8])
    cache = autotune.ScheduleCache()
    for node in lowering.fuse_epilogues(fin):
        if node.op != "mvu":
            continue
        key = autotune.node_key(
            node.attrs["config"],
            epilogue=autotune.epilogue_form(node.params["mvu"]))
        cache.put(key, {"backend": "xla", "block_m": 32, "block_n": 16,
                        "block_k": 32, "block_kw": 8})
    engine = FusedEngine(fin, tune="cache", cache=cache)
    cfgs = [n.attrs["config"] for n in engine.graph if n.op == "mvu"]
    assert all(c.backend == "xla" and c.blocks is not None for c in cfgs)
    # ... and tune="auto" on a cache miss WOULD measure (the stub trips),
    # proving the stub observes the measurement path
    with pytest.raises(AssertionError, match="timer must not run"):
        FusedEngine(fin, tune="auto", cache=autotune.ScheduleCache())


def test_engine_tuned_bit_exact_with_heuristic():
    rng = np.random.default_rng(5)
    fin = _finalized(rng, [32, 16, 8])
    cache = autotune.ScheduleCache()
    FusedEngine(fin, tune="auto", cache=cache,  # fill by measuring once
                tune_kwargs={"sample_m": 32, "reps": 1, "max_measure": 3})
    x = jnp.asarray(rng.integers(0, 4, (21, 32)), jnp.int32)
    want = np.asarray(FusedEngine(fin)(x))
    got = np.asarray(FusedEngine(fin, tune="cache", cache=cache)(x))
    np.testing.assert_array_equal(got, want)


def test_engine_rejects_unknown_tune_mode():
    rng = np.random.default_rng(6)
    fin = _finalized(rng, [16, 8])
    with pytest.raises(ValueError):
        FusedEngine(fin, tune="yes")


def test_engine_microbatch_entry_overrides_plan():
    rng = np.random.default_rng(7)
    fin = _finalized(rng, [16, 8])
    engine = FusedEngine(fin)
    key = autotune.engine_key(engine.graph)
    cache = autotune.ScheduleCache({key: {"microbatch": 4, "batch": 64}})
    tuned = FusedEngine(fin, tune="cache", cache=cache)
    assert tuned._tile == 4
    assert tuned.plan(64).n_micro == 16
    x = jnp.asarray(rng.integers(0, 4, (13, 16)), jnp.int32)
    np.testing.assert_array_equal(np.asarray(tuned(x)),
                                  np.asarray(engine(x)))


def test_tune_engine_records_entry():
    rng = np.random.default_rng(8)
    fin = _finalized(rng, [16, 8])
    cache = autotune.ScheduleCache()
    calls = []

    def fake_timer(fa, fb, *args, **kw):
        calls.append(1)
        return (1.0, 0.5, 2.0)  # candidate "wins" by 2x

    entry = autotune.tune_engine(fin, 32, cache=cache, timer=fake_timer)
    assert calls, "tune_engine measured no candidates"
    key = autotune.engine_key(FusedEngine(fin).graph)
    assert cache.get(key) == entry
    assert entry["microbatch"] >= 1 and entry["speedup"] == 2.0


def test_tune_engine_baseline_ignores_prior_engine_entry():
    """Re-tuning must baseline against the heuristic plan, not the previous
    engine entry -- otherwise the recorded speedup silently becomes
    relative-to-last-tuning and candidate tiles drift."""
    rng = np.random.default_rng(10)
    fin = _finalized(rng, [16, 8])
    heur_tile = FusedEngine(fin).plan(32).microbatch
    key = autotune.engine_key(FusedEngine(fin).graph)
    cache = autotune.ScheduleCache({key: {"microbatch": 999, "batch": 32,
                                          "speedup": 9.9}})

    def never_wins(fa, fb, *args, **kw):
        return (1.0, 1.0, 1.0)

    entry = autotune.tune_engine(fin, 32, cache=cache, timer=never_wins)
    assert entry["microbatch"] == heur_tile  # not 999 or a 999-multiple
    assert entry["speedup"] == 1.0


# ------------------------------------------- config-time schedule legality
def test_illegal_explicit_folding_fails_at_config_time():
    """Regression: an MVUConfig with a non-divisor PE/SIMD folding must fail
    when the folding is resolved (config time), not silently mis-tile."""
    from repro.core.folding import Folding

    bad_pe = MVUConfig(in_features=64, out_features=64, folding=Folding(3, 2))
    with pytest.raises(ValueError, match="PE=3"):
        bad_pe.resolved_folding()
    with pytest.raises(ValueError):
        bad_pe.kernel_blocks()
    bad_simd = MVUConfig(in_features=600, out_features=64,
                         folding=Folding(64, 7))
    with pytest.raises(ValueError, match="SIMD=7"):
        bad_simd.kernel_blocks()
    # legal foldings (the paper's Table 6 choices) still resolve
    ok = MVUConfig(in_features=600, out_features=64, folding=Folding(64, 50))
    assert ok.resolved_folding() == Folding(64, 50)
    assert ok.kernel_blocks()["block_n"] == 64


def test_resource_model_uses_actual_kernel_blocks():
    """Regression: the VMEM estimate must reflect the clamped blocks the
    kernel really allocates, not the raw PE/SIMD folding."""
    from repro.core.folding import Folding
    from repro.core.resource_model import mvu_resources

    n, k = 4, 6
    fold = Folding(1, 1)  # raw model would claim a ~1-byte weight tile
    res = mvu_resources(n, k, fold, mode="standard", weight_bits=4,
                        block_m=32)
    # to_tpu_blocks clamps to block_n=8, block_k=8; K pads to one 8-step
    a_tile = 32 * 8          # block_m x padded-K int8
    w_tile = 8 * 8           # block_n x block_k int8
    acc = 32 * 8 * 4         # int32 accumulators
    out = 32 * 8 * 4
    assert res.lut_bytes == a_tile + w_tile + acc + out
    # an explicit (tuned) schedule overrides the derived one
    res2 = mvu_resources(n, k, fold, mode="standard", weight_bits=4,
                         blocks={"block_m": 8, "block_n": 8, "block_k": 8})
    assert res2.lut_bytes == 8 * 8 + 8 * 8 + 8 * 8 * 4 + 8 * 8 * 4
    # BRAM/cycle terms stay on the folding abstraction
    assert res.cycles == fold.cycles(n, k)
    assert res.bram_bytes == res2.bram_bytes


def test_explicit_blocks_override_folding_derivation():
    cfg = MVUConfig(in_features=1024, out_features=512,
                    blocks=KernelBlocks(block_m=64, block_n=256, block_k=256))
    assert cfg.kernel_blocks() == {"block_m": 64, "block_n": 256, "block_k": 256}
    xcfg = MVUConfig(in_features=8192, out_features=512, mode="xnor",
                     blocks=KernelBlocks(block_m=64, block_n=256, block_kw=128))
    assert xcfg.kernel_blocks() == {"block_m": 64, "block_n": 256, "block_kw": 128}
    # an explicit schedule is still rounded to the tile the chip runs
    small = MVUConfig(in_features=64, out_features=32,
                      blocks=KernelBlocks(block_m=64, block_n=16, block_k=32))
    assert small.kernel_blocks() == {"block_m": 64, "block_n": 32, "block_k": 64}


# ------------------------------------------------------------------ keys
def test_node_key_fields():
    cfg = MVUConfig(in_features=600, out_features=64, mode="standard")
    key = autotune.node_key(cfg, epilogue="thresh", n_pixels=3, device="cpu")
    assert key == "cpu|mvu|standard|n64|k600|thresh|px3"


def test_node_key_separates_conv_geometry():
    """Two conv layers with equal (mode, N, K, px) but different geometry
    must not collide on one schedule entry."""
    cfg = MVUConfig(in_features=36, out_features=8)
    a = Node("conv_mvu", "a", {"kernel": 3, "stride": 1, "pad": 0,
                               "config": cfg})
    b = Node("conv_mvu", "b", {"kernel": 3, "stride": 2, "pad": 1,
                               "config": cfg})
    ka = autotune.node_key(cfg, device="cpu", op=autotune.op_tag(a, (14, 14, 4)))
    kb = autotune.node_key(cfg, device="cpu", op=autotune.op_tag(b, (28, 28, 4)))
    assert ka != kb
    assert "conv3s1p0@14x14x4" in ka and "conv3s2p1@28x28x4" in kb
    # dense nodes tag as plain mvu
    assert autotune.op_tag(Node("mvu", "d", {"config": cfg})) == "mvu"


def test_engine_key_stable_and_device_scoped():
    rng = np.random.default_rng(9)
    fin = _finalized(rng, [16, 8])
    k1 = autotune.engine_key(fin, device="cpu")
    k2 = autotune.engine_key(fin, device="cpu")
    k3 = autotune.engine_key(fin, device="tpu-v5e")
    assert k1 == k2 and k1 != k3
    assert k1.startswith("engine|cpu|")
