"""The DAG graph algebra: topological iteration, branch labeling,
elementwise-binary broadcast semantics, the DAG interpreter, and the
illegal-graph diagnostics (cycle, dangling edge, multi-sink, arity)."""

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import dataflow, ir
from repro.core.ir import Graph, Node


def _input(shape=(16,), bits=2, name="in"):
    return Node("input", name, {"shape": shape, "bits": bits})


def _linear(name, n, k, src=None):
    w = jnp.asarray(np.arange(n * k).reshape(n, k) % 5 - 2, jnp.float32)
    return Node("linear", name, {}, {"w": w},
                inputs=(src,) if src else None)


# ------------------------------------------------------------ graph algebra
def test_as_graph_materializes_chain_edges():
    g = [_input(), _linear("fc0", 4, 16), Node("quant_act", "a", {"bits": 2})]
    eg = ir.as_graph(g)
    assert [n.inputs for n in eg] == [(), ("in",), ("fc0",)]
    # explicit edges pass through untouched; attrs/params dicts are shared
    assert eg[1].params is g[1].params
    g2 = ir.as_graph(eg)
    assert [n.inputs for n in g2] == [(), ("in",), ("fc0",)]


def test_toposort_is_stable_for_chains_and_orders_dags():
    chain = [_input(), _linear("fc0", 4, 16), _linear("fc1", 4, 4)]
    assert [n.name for n in ir.toposort(chain)] == ["in", "fc0", "fc1"]
    # authoring order scrambled; topo order must respect edges
    dag = Graph([
        Node("add", "res", {}, inputs=("a1", "a0")),
        Node("quant_act", "a1", {"bits": 2}, inputs=("fc1",)),
        _linear("head", 2, 4, "res"),
        _input(),
        Node("quant_act", "a0", {"bits": 2}, inputs=("fc0",)),
        _linear("fc0", 4, 16, "in"),
        _linear("fc1", 4, 4, "a0"),
    ])
    names = [n.name for n in ir.toposort(dag)]
    for src, dst in ir.edge_list(dag):
        assert names.index(src) < names.index(dst)


def test_cycle_diagnostic_names_the_nodes():
    g = Graph([
        _input(),
        _linear("fc0", 4, 16, "fc1"),
        _linear("fc1", 4, 4, "fc0"),
    ])
    with pytest.raises(ValueError, match=r"cycle through.*'fc0'.*'fc1'"):
        ir.validate_graph(g)


def test_dangling_edge_diagnostic():
    g = Graph([_input(), _linear("fc0", 4, 16, "ghost")])
    with pytest.raises(ValueError,
                       match=r"node 'fc0' \(linear\): dangling input edge "
                             r"from 'ghost'"):
        ir.validate_graph(g)


def test_dangling_branch_diagnostic():
    # fc1 forks off but nothing consumes it: two sinks
    g = Graph([_input(), _linear("fc0", 4, 16, "in"),
               _linear("fc1", 4, 4, "fc0"), _linear("fc2", 4, 4, "fc0")])
    with pytest.raises(ValueError, match=r"exactly one output \(sink\).*"
                                         r"dangling branch"):
        ir.validate_graph(g)


def test_eltwise_arity_diagnostic():
    g = Graph([_input(), _linear("fc0", 4, 16, "in"),
               Node("add", "res", {}, inputs=("fc0",))])
    with pytest.raises(ValueError,
                       match=r"node 'res' \(add\): 'add' takes exactly 2 "
                             r"inputs, got 1"):
        ir.validate_graph(g)


def test_branch_labels_name_fork_arms():
    g = Graph([
        _input(),
        _linear("fc0", 16, 16, "in"),
        _linear("fc1", 16, 16, "fc0"),   # arm A (through one more layer)
        _linear("fc2", 16, 16, "fc1"),
        Node("add", "res", {}, inputs=("fc2", "fc0")),  # arm B is direct
        _linear("head", 2, 16, "res"),
    ])
    labels = ir.branch_labels(g)
    assert labels["fc0"] == "main"
    assert labels["fc1"] == "fc0/fc1"
    assert labels["fc2"] == "fc0/fc1"      # inherited along the arm
    assert labels["res"] == "main"         # joins return to the trunk
    assert labels["head"] == "main"


def test_graph_output_and_edges():
    g = Graph([_input(), _linear("fc0", 4, 16, "in")])
    assert ir.graph_output(g).name == "fc0"
    assert ir.edge_list(g) == [["in", "fc0"]]


# ------------------------------------------------------- shape propagation
def test_broadcast_shapes():
    assert ir.broadcast_shapes((64,), (64,)) == (64,)
    assert ir.broadcast_shapes((8, 8, 4), (4,)) == (8, 8, 4)
    assert ir.broadcast_shapes((8, 8, 4), (1,)) == (8, 8, 4)
    assert ir.broadcast_shapes((1,), (8, 8, 4)) == (8, 8, 4)
    with pytest.raises(ValueError, match=r"cannot broadcast.*\(64,\).*\(32,\)"):
        ir.broadcast_shapes((64,), (32,))


def test_propagate_multi_input_and_infer_shapes():
    res = Node("add", "res", {}, inputs=("a", "b"))
    assert ir.propagate(res, (64,), (64,)) == (64,)
    assert ir.propagate(res, (8, 8, 4), (4,)) == (8, 8, 4)
    with pytest.raises(ValueError, match="exactly 2 input shapes"):
        ir.propagate(res, (64,))
    g = Graph([
        _input(),
        _linear("fc0", 8, 16, "in"),
        _linear("fc1", 8, 8, "fc0"),
        Node("add", "res", {}, inputs=("fc1", "fc0")),
    ])
    assert ir.infer_shapes(g) == {
        "in": (16,), "fc0": (8,), "fc1": (8,), "res": (8,)}
    rows = ir.io_shapes(g)
    assert [(n.name, ins, out) for n, ins, out in rows] == [
        ("in", (), (16,)), ("fc0", ((16,),), (8,)),
        ("fc1", ((8,),), (8,)), ("res", ((8,), (8,)), (8,))]


def test_eltwise_broadcast_fails_validation_when_illegal():
    g = Graph([
        _input((16,)),
        _linear("fc0", 8, 16, "in"),
        _linear("fc1", 4, 8, "fc0"),
        Node("add", "res", {}, inputs=("fc1", "fc0")),  # (4,) + (8,)
    ])
    with pytest.raises(ValueError,
                       match=r"node 'res' \(add\): cannot broadcast"):
        ir.validate_graph(g)


# --------------------------------------------------------- DAG interpreter
def test_eltwise_semantics_add_sub_mul_with_scales():
    a = jnp.asarray([[1, 2, 3]], jnp.int32)
    b = jnp.asarray([[10, 20, 30]], jnp.int32)
    for op, want in [("add", [[21, 42, 63]]),
                     ("sub", [[-19, -38, -57]]),
                     ("mul", [[20, 80, 180]])]:
        node = Node(op, "e", {"scales": (1, 2)}, inputs=("x", "y"))
        _, fn = dataflow.node_runner(node)
        np.testing.assert_array_equal(np.asarray(fn(None, a, b)), want)


def test_eltwise_broadcasts_trailing_dims_not_batch():
    # (B, H, W, C) + (B, C): the (C,) sample shape aligns to the trailing
    # channel dim, never to the batch axis
    x = jnp.asarray(np.arange(2 * 2 * 2 * 3).reshape(2, 2, 2, 3), jnp.int32)
    y = jnp.asarray([[1, 2, 3], [10, 20, 30]], jnp.int32)
    node = Node("add", "e", {}, inputs=("x", "y"))
    _, fn = dataflow.node_runner(node)
    got = np.asarray(fn(None, x, y))
    want = np.asarray(x) + np.asarray(y)[:, None, None, :]
    np.testing.assert_array_equal(got, want)


def test_trace_and_execute_run_branched_graphs():
    from repro.core import lowering

    g = Graph([
        _input((4,)),
        _linear("fc0", 4, 4, "in"),
        Node("add", "res", {}, inputs=("fc0", "in")),
    ])
    low = lowering.finalize(lowering.lower_to_mvu(g, mode="standard",
                                                  weight_bits=2, act_bits=2))
    x = jnp.asarray([[1, 0, 2, 1]], jnp.float32)
    env = dataflow.trace(low, x)
    assert set(env) == {"in", "fc0.mvu", "res"}
    np.testing.assert_array_equal(
        np.asarray(env["res"]), np.asarray(env["fc0.mvu"] + env["in"]))
    np.testing.assert_array_equal(np.asarray(dataflow.execute(low, x)),
                                  np.asarray(env["res"]))


def test_trace_multi_input_graph_takes_a_feed_dict():
    g = Graph([
        _input((4,), name="xa"),
        _input((4,), name="xb"),
        Node("add", "res", {}, inputs=("xa", "xb")),
    ])
    xa = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    xb = jnp.asarray([[10, 20, 30, 40]], jnp.int32)
    with pytest.raises(ValueError, match="2 input nodes"):
        dataflow.trace(g, xa)
    got = dataflow.execute(g, {"xa": xa, "xb": xb})
    np.testing.assert_array_equal(np.asarray(got), [[11, 22, 33, 44]])


def test_schedule_reports_branch_joins():
    from repro.core import lowering

    # both arms of the fork carry MVU stages: the long arm two, the short
    # arm one, so the critical path differs from the sum over all stages
    g = Graph([
        _input((16,)),
        _linear("fc0", 16, 16, "in"),
        _linear("fc1", 16, 16, "fc0"),
        _linear("fc2", 16, 16, "fc1"),
        _linear("fc3", 16, 16, "fc0"),
        Node("add", "res", {}, inputs=("fc2", "fc3")),
        _linear("head", 2, 16, "res"),
    ])
    low = lowering.finalize(lowering.lower_to_mvu(g, mode="standard",
                                                  weight_bits=2, act_bits=2))
    sched = dataflow.schedule(low)
    assert len(sched.joins) == 1
    j = sched.joins[0]
    assert j.name == "res"
    # the two-layer arm accumulates more latency than the direct edge, and
    # the skew FIFO must cover the difference (>= the floor of 2)
    assert j.branch_latency[0] != j.branch_latency[1]
    assert j.fifo_depth >= 2
    assert sched.summary()["joins"][0]["name"] == "res"
    # critical path: latency is the longest path, not the sum of all stages
    assert sched.latency_cycles < sum(s.cycles for s in sched.stages)


@pytest.mark.parametrize("bits,op,scales,narrow", [
    (2, "add", (1, 1), True),     # [0,3] + [-3,3]: fits int8
    (7, "add", (1, 1), False),    # [0,127] + [-127,127] reaches 254
    (6, "mul", (1, 1), False),    # 63 * 63 leaves int8
    (2, "add", (64, 1), False),   # an alignment scale of 64 leaves int8
    (7, "sub", (1, 1), False),    # 127 - (-127) leaves int8
])
def test_int8_inputs_proves_ranges_through_joins(bits, op, scales, narrow):
    """``dataflow.int8_inputs`` narrows an MVU's input only where the static
    range (input bits of either sign, quant_act grids, eltwise with integer
    scales) fits int8; ``head`` reads the join."""
    from repro.core.mvu import MVUConfig

    def mvu(name, src):
        return Node("mvu", name, {"config": MVUConfig(16, 16)}, {}, inputs=(src,))

    g = Graph([
        _input(bits=bits),
        mvu("fc0", "in"),
        Node("quant_act", "a0", {"bits": bits}, inputs=("fc0",)),
        Node(op, "join", {"scales": scales}, inputs=("a0", "in")),
        mvu("head", "join"),
    ])
    got = dataflow.int8_inputs(g)
    assert ("fc0" in got) == (bits <= 7)
    assert ("head" in got) == narrow
