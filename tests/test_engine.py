"""Fused streaming engine tests: the jit-compiled engine must be bit-exact
with the eager ``dataflow.execute`` interpreter on MLP and conv (SWU) graphs
across all three MVU modes, with bn/quant epilogues fused away."""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import dataflow, lowering
from repro.core.engine import FusedEngine
from repro.core.ir import Graph, Node

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mlp_graph(rng, dims, bits, *, signed_gamma=True) -> Graph:
    g: Graph = [Node("input", "in", {"shape": (dims[0],), "bits": bits})]
    for i, (k, n) in enumerate(zip(dims[:-1], dims[1:])):
        w = rng.normal(0, 0.5, (n, k)).astype(np.float32)
        g.append(Node("linear", f"fc{i}", {}, {"w": jnp.asarray(w)}))
        if i < len(dims) - 2:
            lo = -1.5 if signed_gamma else 0.5
            g.append(Node("batchnorm", f"bn{i}", {}, {
                "gamma": jnp.asarray(rng.uniform(lo, 1.5, n).astype(np.float32)),
                "beta": jnp.asarray(rng.uniform(-0.5, 0.5, n).astype(np.float32)),
                "mean": jnp.asarray(rng.normal(0, 2, n).astype(np.float32)),
                "var": jnp.asarray(rng.uniform(0.5, 2, n).astype(np.float32)),
            }))
            g.append(Node("quant_act", f"act{i}", {"bits": bits, "act_scale": 1.0}))
    return g


def _finalized(g, mode, bits):
    lowered = lowering.lower_to_mvu(g, mode=mode, weight_bits=4, act_bits=bits)
    return lowering.finalize(lowered)


@pytest.mark.parametrize("mode,bits", [("standard", 2), ("binary", 2), ("xnor", 1)])
def test_fused_engine_matches_interpreter_mlp(mode, bits):
    """Engine output == unfused interpreter output, all three datapaths
    (negative BN gammas included: flipped rows exercise weight negation in
    every weight coding)."""
    rng = np.random.default_rng(7)
    dims = [64, 32, 16, 8]
    fin = _finalized(_mlp_graph(rng, dims, bits), mode, bits)
    x = jnp.asarray(rng.integers(0, 2**bits, (13, dims[0])), jnp.int32)

    want = np.asarray(dataflow.execute(fin, x))
    engine = FusedEngine(fin)
    got = np.asarray(engine(x))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("mode,bits", [("standard", 2), ("binary", 2), ("xnor", 1)])
def test_epilogue_fusion_removes_bn_quant_nodes(mode, bits):
    rng = np.random.default_rng(3)
    fin = _finalized(_mlp_graph(rng, [24, 16, 8], bits), mode, bits)
    assert any(n.op in ("batchnorm", "quant_act") for n in fin)

    engine = FusedEngine(fin)
    ops_left = [n.op for n in engine.graph]
    assert "batchnorm" not in ops_left and "quant_act" not in ops_left
    mvus = [n for n in engine.graph if n.op == "mvu"]
    # hidden MVUs carry fused thresholds; the head keeps its raw epilogue
    assert all(m.params["mvu"].thresholds is not None for m in mvus[:-1])
    assert all(m.attrs.get("fused") for m in mvus[:-1])
    assert mvus[-1].params["mvu"].thresholds is None


@pytest.mark.parametrize("mode", ["standard", "binary"])
def test_fused_engine_matches_interpreter_conv(mode):
    """Conv (SWU-lowered) graph: engine == interpreter, epilogues fused."""
    bits = 2
    rng = np.random.default_rng(11)
    g: Graph = [Node("input", "in", {"shape": (8, 8, 3), "bits": bits})]
    w = rng.normal(0, 0.5, (3, 3, 3, 6)).astype(np.float32)
    g.append(Node("conv", "c0", {"kernel": 3, "stride": 1, "pad": 0},
                  {"w": jnp.asarray(w)}))
    n = 6
    g.append(Node("batchnorm", "bn0", {}, {
        "gamma": jnp.asarray(rng.uniform(-1.5, 1.5, n).astype(np.float32)),
        "beta": jnp.asarray(rng.uniform(-0.5, 0.5, n).astype(np.float32)),
        "mean": jnp.asarray(rng.normal(0, 1, n).astype(np.float32)),
        "var": jnp.asarray(rng.uniform(0.5, 2, n).astype(np.float32)),
    }))
    g.append(Node("quant_act", "act0", {"bits": bits, "act_scale": 1.0}))
    fin = _finalized(g, mode, bits)
    x = jnp.asarray(rng.integers(0, 2**bits, (3, 8, 8, 3)), jnp.int32)

    want = np.asarray(dataflow.execute(fin, x))
    engine = FusedEngine(fin)
    got = np.asarray(engine(x))
    np.testing.assert_array_equal(got, want)
    # the swu+mvu pair collapses into the line-buffer conv kernel
    assert [node.op for node in engine.graph] == ["input", "conv_mvu"]


@pytest.mark.parametrize("mode", ["standard", "binary"])
@pytest.mark.parametrize("bits", [2, 8])
def test_engine_exact_at_act_bits(mode, bits):
    """8-bit activations (levels up to 255) do not fit the MXU's int8: the
    engine must stay bit-exact with the ``backend="xla"`` (ref.py) build,
    only streams proven to fit int8 are narrowed to it, and wider ones to
    the narrowest dtype that holds them (uint8 levels, int16 for an 8-bit
    input of either sign)."""
    rng = np.random.default_rng(17)
    dims = [48, 40, 24, 8]
    g = _mlp_graph(rng, dims, bits)
    graphs = {be: lowering.finalize(lowering.lower_to_mvu(
        g, mode=mode, weight_bits=4, act_bits=bits, backend=be))
        for be in ("pallas", "xla")}
    x = jnp.asarray(rng.integers(0, 2**bits, (9, dims[0])), jnp.int32)
    engine = FusedEngine(graphs["pallas"])
    want = np.asarray(FusedEngine(graphs["xla"])(x))
    np.testing.assert_array_equal(np.asarray(engine(x)), want)
    np.testing.assert_array_equal(
        np.asarray(dataflow.execute(graphs["pallas"], x)), want)
    mvus = {n.name for n in engine.graph if n.op == "mvu"}
    want_dtypes = ({n: jnp.int8 for n in mvus} if bits == 2 else
                   {"fc0.mvu": jnp.int16, "fc1.mvu": jnp.uint8,
                    "fc2.mvu": jnp.uint8})
    assert dataflow.narrow_inputs(engine.graph) == want_dtypes
    if bits == 8:  # the hidden activations really leave the int8 range
        env = dataflow.trace(engine.graph, x)
        assert max(int(np.asarray(env[n]).max()) for n in mvus
                   if n != "fc2.mvu") > 127


def test_microbatch_streaming_invariance():
    """Any microbatch split (including ragged last chunk) gives the same
    result as a single full-batch pass."""
    bits = 2
    rng = np.random.default_rng(5)
    fin = _finalized(_mlp_graph(rng, [32, 16, 8], bits), "standard", bits)
    x = jnp.asarray(rng.integers(0, 2**bits, (11, 32)), jnp.int32)
    base = np.asarray(FusedEngine(fin, microbatches=1)(x))
    for n_micro in (2, 3, 5, 11):
        got = np.asarray(FusedEngine(fin, microbatches=n_micro)(x))
        np.testing.assert_array_equal(got, base)


def test_stream_plan_from_schedule():
    bits = 2
    rng = np.random.default_rng(9)
    fin = _finalized(_mlp_graph(rng, [64, 32, 16, 8], bits), "standard", bits)
    engine = FusedEngine(fin)
    sched = engine.schedule
    plan = engine.plan(64)
    assert plan.interval_cycles == sched.steady_state_interval
    assert plan.fifo_bound == max(2, min(s.fifo_depth for s in sched.stages))
    # microbatch = the bottleneck stage's resident M tile (block_m): one
    # producer burst per microbatch, so 64 samples fit one burst ...
    tile = min(n.attrs["config"].block_m for n in engine.graph if n.op == "mvu")
    assert plan.n_micro == 1 and plan.microbatch == 64
    # ... and larger batches decompose into ceil(batch / tile) bursts.
    big = engine.plan(5 * tile + 1)
    assert big.n_micro == 6
    assert big.n_micro * big.microbatch >= 5 * tile + 1
    assert FusedEngine(fin).plan(1).n_micro == 1


def test_engine_server_coalesces_and_matches_direct():
    from repro.launch.serve import EngineServer

    bits = 2
    rng = np.random.default_rng(13)
    fin = _finalized(_mlp_graph(rng, [24, 16, 8], bits), "standard", bits)
    engine = FusedEngine(fin)
    server = EngineServer(engine, batch_buckets=(1, 4, 8))

    xs = rng.integers(0, 2**bits, (11, 24)).astype(np.int32)
    rids = [server.submit(x) for x in xs]
    done = {r.rid: r for r in server.flush()}
    assert sorted(done) == rids and not server._pending

    want = np.asarray(engine(jnp.asarray(xs)))
    for i, rid in enumerate(rids):
        np.testing.assert_array_equal(done[rid].out, want[i])
    # 11 requests over (1,4,8) buckets: one 8-chunk + one 4-bucket pad
    assert server.stats["flushes"] == 2
    assert server.stats["padded_samples"] == 1


def test_engine_server_splits_oversized_submissions():
    """Regression: a backlog larger than the biggest bucket must split across
    max-size bucket launches (not land in a non-existent bigger bucket)."""
    from repro.launch.serve import EngineServer

    bits = 2
    rng = np.random.default_rng(17)
    fin = _finalized(_mlp_graph(rng, [24, 16, 8], bits), "standard", bits)
    engine = FusedEngine(fin)
    server = EngineServer(engine, batch_buckets=(1, 4, 8))

    with pytest.raises(ValueError):
        server._bucket_for(9)  # no bucket holds 9 samples

    xs = rng.integers(0, 2**bits, (19, 24)).astype(np.int32)
    rids = server.submit_batch(xs)
    done = {r.rid: r for r in server.flush()}
    assert sorted(done) == rids and not server._pending
    # 19 = 8 + 8 + 3: two max-size launches, the tail padded up to 4
    assert server.stats["flushes"] == 3
    assert server.stats["padded_samples"] == 1
    want = np.asarray(engine(jnp.asarray(xs)))
    for i, rid in enumerate(rids):
        np.testing.assert_array_equal(done[rid].out, want[i])


def test_engine_server_validates_requests_at_submit():
    """Regression: a sample whose shape/dtype disagrees with the engine
    graph's input spec used to die inside np.stack at flush time with a
    cryptic error; it must fail at submit with a clear ValueError."""
    from repro.launch.serve import EngineServer

    bits = 2
    rng = np.random.default_rng(23)
    fin = _finalized(_mlp_graph(rng, [24, 16, 8], bits), "standard", bits)
    server = EngineServer(FusedEngine(fin), batch_buckets=(1, 4, 8))

    with pytest.raises(ValueError, match="input spec"):
        server.submit(np.zeros(25, np.int32))  # wrong feature width
    with pytest.raises(ValueError, match="integer"):
        server.submit(np.zeros(24, np.float32))  # wrong dtype
    with pytest.raises(ValueError, match="input spec"):
        server.submit_batch(np.zeros((3, 23), np.int32))
    # nothing leaked into the queue; a well-formed request still works
    assert not server._pending and server.stats["requests"] == 0
    rid = server.submit(np.zeros(24, np.int32))
    done = server.flush()
    assert [r.rid for r in done] == [rid] and done[0].out is not None


def test_engine_server_submit_batch_enqueues_one_block():
    """Regression: submit_batch looped Python-per-sample over the batch;
    it must enqueue one shared-buffer block while rids stay per-sample."""
    from repro.launch.serve import EngineServer

    bits = 2
    rng = np.random.default_rng(29)
    fin = _finalized(_mlp_graph(rng, [24, 16, 8], bits), "standard", bits)
    engine = FusedEngine(fin)
    server = EngineServer(engine, batch_buckets=(1, 4, 8))

    xs = rng.integers(0, 2**bits, (6, 24)).astype(np.int32)
    rids = server.submit_batch(xs)
    assert rids == list(range(6))  # one rid per sample
    blocks = server._batcher.queue._blocks
    assert len(blocks) == 1 and np.shares_memory(blocks[0].xs, xs)
    done = {r.rid: r for r in server.flush()}
    want = np.asarray(engine(jnp.asarray(xs)))
    for i, rid in enumerate(rids):
        np.testing.assert_array_equal(done[rid].out, want[i])


def test_engine_pipeline_multidevice_matches_single():
    """as_pipeline on a 4-stage host mesh == single-device fused engine
    (subprocess so XLA_FLAGS never leaks into this pytest process)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import lowering
        from repro.core.engine import FusedEngine
        from repro.core.ir import Node
        from repro.launch.mesh import make_mesh

        rng = np.random.default_rng(0)
        d, L, bits = 32, 4, 2
        g = [Node("input", "in", {"shape": (d,), "bits": bits})]
        for i in range(L):
            w = rng.normal(0, 0.5, (d, d)).astype(np.float32)
            g.append(Node("linear", f"fc{i}", {}, {"w": jnp.asarray(w)}))
            g.append(Node("batchnorm", f"bn{i}", {}, {
                "gamma": jnp.asarray(rng.uniform(0.5, 1.5, d).astype(np.float32)),
                "beta": jnp.asarray(rng.uniform(-0.5, 0.5, d).astype(np.float32)),
                "mean": jnp.asarray(rng.normal(0, 1, d).astype(np.float32)),
                "var": jnp.asarray(rng.uniform(0.5, 2, d).astype(np.float32)),
            }))
            g.append(Node("quant_act", f"act{i}", {"bits": bits, "act_scale": 1.0}))
        fin = lowering.finalize(
            lowering.lower_to_mvu(g, mode="standard", weight_bits=4, act_bits=bits))
        eng = FusedEngine(fin)
        x = jnp.asarray(rng.integers(0, 2**bits, (8, 4, d)), jnp.int32)
        run = eng.as_pipeline(make_mesh((4,), ("stage",)))
        got = np.asarray(run(x))
        want = np.asarray(eng(x.reshape(32, d))).reshape(8, 4, d)
        assert np.array_equal(got, want)
        print("OK")
    """)
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")},
    )
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    assert "OK" in proc.stdout
