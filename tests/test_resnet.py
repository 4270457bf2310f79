"""ResNet-50 W1A2's mechanisms against the plain reference, on the CPU.

The network is the benchmark's ResNet at test size: the published block
structure (7x7/2 8-bit stem, padded 3x3/2 max-pool, one bottleneck block
per stage with a projection shortcut, stride 2 on stages 2-4, global pool,
8-bit head) at 32x32 input and widths divided by 16.  The reference is the
benchmark's own (``bench/models/resnet.py``: exact integer accumulation,
batchnorm folded in float64), so the system is held bit-exact to the same
equations that decide ``correct`` on the chip.  Each new node kind is also
checked alone: strided and padded convs in both datapaths, 1x1 convs, the
padded max-pool, the add-and-threshold join at its ties, the global pool,
and the range pass that narrows 8-bit and 2-bit streams.
"""

from __future__ import annotations

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.models import resnet  # noqa: E402
from repro.build import build  # noqa: E402
from repro.core import dataflow  # noqa: E402
from repro.core.engine import FusedEngine  # noqa: E402
from repro.core.ir import Graph, Node  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402

QUICK = {
    "name": "resnet_quick", "family": "resnet",
    "input": {"shape": [32, 32, 3], "bits": 8},
    "stem": {"out": 4, "kernel": 7, "stride": 2, "pad": 3, "weight_bits": 8},
    "pool": {"size": 3, "stride": 2, "pad": 1},
    "stages": [{"blocks": 1, "mid": 4, "out": 16, "stride": 1},
               {"blocks": 1, "mid": 8, "out": 32, "stride": 2},
               {"blocks": 1, "mid": 16, "out": 64, "stride": 2},
               {"blocks": 1, "mid": 32, "out": 128, "stride": 2}],
    "head": {"out": 10, "weight_bits": 8},
    "mode": "binary", "weight_bits": 1, "act_bits": 2, "join_scale": 2,
    "build": {"target": "engine", "folding": "balance"},
    "init": {"bn_gamma": [0.5, 1.5], "bn_beta": [-0.5, 0.5],
             "calibration_images": 8},
}


@pytest.fixture(scope="module")
def network():
    params = resnet.init_params(QUICK, 11)
    acc = resnet.build(QUICK, params)
    x = np.asarray(resnet.make_inputs(QUICK, 5, 12))
    want = resnet.Reference(QUICK, jax.device_get(params)).forward(x)
    return acc, x, want


@pytest.mark.parametrize("path", ["engine", "interpreter"])
def test_resnet_bit_exact_with_the_plain_reference(network, path):
    """The whole network through ``Accelerator.__call__`` (the fused
    engine) and through its eager interpreter gives the reference's logits
    to the float32 rounding of the head's dequant scale."""
    acc, x, want = network
    got = np.asarray(acc(x) if path == "engine" else acc.interpret(x))
    assert got.shape == want.shape == (5, 10)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-5 * np.abs(want).max())
    if path == "engine":
        ops_run = [n.op for n in acc.engine.graph]
        assert ops_run.count("conv_mvu") == 17
        assert ops_run.count("add_threshold") == 4
        assert "global_pool" in ops_run and "maxpool" in ops_run


# ------------------------------------------------------------- one layer
def _centred_bn(rng, n, scale, mean, var):
    """Batchnorm parameters whose folded thresholds sit between integers
    (``resnet._centre``), so float32 and float64 folds agree."""
    p = {"gamma": rng.uniform(0.5, 1.5, n).astype(np.float32),
         "beta": rng.uniform(-0.5, 0.5, n).astype(np.float32),
         "mean": np.asarray(mean, np.float32), "var": np.asarray(var, np.float32)}
    resnet._centre(p, scale)
    return p


def _conv_case(mode, kernel, stride, pad, seed=0):
    """One conv + batchnorm + 2-bit quant_act, built; its input and the
    reference's levels."""
    rng = np.random.default_rng(seed)
    h, c, n = (7, 3, 8) if kernel == 7 else (13, 6, 8)
    bits, w_bits = (8, 8) if mode == "standard" else (2, 1)
    x = rng.integers(0, 2**bits, (3, h, h, c)).astype(np.int32)
    w = rng.normal(size=(kernel, kernel, c, n)).astype(np.float32)
    wm = w.reshape(-1, n).astype(np.float64)
    if w_bits == 1:
        q, scale = np.where(wm >= 0, 1.0, -1.0), np.mean(np.abs(wm), axis=0)
    else:
        scale = np.max(np.abs(wm), axis=0) / 127
        q = np.clip(np.round(wm / scale), -127, 127)
    acc = np.asarray(resnet.conv(jnp.asarray(x, jnp.float32),
                                 jnp.asarray(q.reshape(w.shape), jnp.float32),
                                 kernel=kernel, stride=stride, pad=pad))
    real = acc.reshape(-1, n) * scale
    bn = _centred_bn(rng, n, scale, real.mean(0), real.var(0) + 1e-3)
    j = np.arange(1, 4)[None]
    t = np.ceil(((j - 0.5 - bn["beta"][:, None].astype(np.float64))
                 * np.sqrt(bn["var"].astype(np.float64) + resnet.EPS)[:, None]
                 / bn["gamma"][:, None] + bn["mean"][:, None]) / scale[:, None])
    want = np.asarray(resnet.levels(jnp.asarray(acc), jnp.asarray(t, jnp.float32)))
    g = [Node("input", "in", {"shape": (h, h, c), "bits": bits, "signed": False}),
         Node("conv", "conv", {"kernel": kernel, "stride": stride, "pad": pad,
                               "mode": mode, "weight_bits": w_bits}, {"w": w}),
         Node("batchnorm", "bn", {}, {k: jnp.asarray(v) for k, v in bn.items()}),
         Node("quant_act", "act", {"bits": 2, "act_scale": 1.0})]
    return build(g, target="engine", mode="binary", weight_bits=1, act_bits=2,
                 folding="none"), x, want


@pytest.mark.parametrize("mode,kernel,stride,pad", [
    ("binary", 3, 2, 1),     # the strided 3x3 of a stage's first block
    ("standard", 3, 2, 1),   # 8-bit input and weights: two digit passes
    ("standard", 7, 2, 3),   # the stem's 7x7/2
    ("binary", 1, 1, 0),     # the bottleneck's 1x1 convs
    ("binary", 1, 2, 0),     # the strided 1x1 projection shortcut
])
def test_conv_layer_matches_the_reference(mode, kernel, stride, pad):
    acc, x, want = _conv_case(mode, kernel, stride, pad)
    got = np.asarray(acc(x))
    assert got.shape == want.shape
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(np.asarray(acc.interpret(x)), want)
    cfg = next(n for n in acc.engine.graph if n.op == "conv_mvu").attrs["config"]
    assert (cfg.mode, cfg.weight_bits) == (mode, 8 if mode == "standard" else 1)


@pytest.mark.parametrize("size,stride,pad,h", [(3, 2, 1, 16), (3, 2, 1, 15),
                                               (2, 2, 0, 8)])
def test_padded_maxpool_matches_the_reference(size, stride, pad, h):
    x = np.random.default_rng(1).integers(0, 4, (2, h, h, 5)).astype(np.int32)
    g = Graph([Node("input", "in", {"shape": (h, h, 5), "bits": 2}),
               Node("maxpool", "pool", {"size": size, "stride": stride,
                                        "pad": pad})])
    got = np.asarray(FusedEngine(g)(jnp.asarray(x)))
    want = np.asarray(resnet.max_pool(jnp.asarray(x, jnp.float32), size=size,
                                      stride=stride, pad=pad))
    assert got.shape == want.shape == (2, (h + 2 * pad - size) // stride + 1,
                                       (h + 2 * pad - size) // stride + 1, 5)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("m,n", [(49, 64), (3136, 256), (196, 1024)])
def test_add_threshold_kernel_at_ties(m, n):
    """Every sum the join can meet, the ties at 1, 3 and 5 among them:
    (x + 1) // 2 clipped to [0, 3], as the reference's floor(x/2 + 1/2)."""
    rng = np.random.default_rng(m)
    a = rng.integers(0, 4, (m, n)).astype(np.int32)
    b = rng.integers(0, 4, (m, n)).astype(np.int32)
    thr = jnp.broadcast_to(jnp.asarray([1, 3, 5], jnp.int32), (n, 3))
    got = np.asarray(ops.add_threshold(jnp.asarray(a), jnp.asarray(b), thr))
    assert set(np.unique(a + b)) >= {1, 3, 5}
    np.testing.assert_array_equal(got, np.minimum((a + b + 1) // 2, 3))
    np.testing.assert_array_equal(
        got, np.asarray(resnet.join(jnp.asarray(a, jnp.float32),
                                    jnp.asarray(b, jnp.float32),
                                    step=2.0, top=3)))
    np.testing.assert_array_equal(
        got, np.asarray(ref.add_threshold_ref(jnp.asarray(a), jnp.asarray(b),
                                              thr)))


def test_residual_join_fuses_into_one_node():
    """``add -> quant_act`` after a conv branch and the block's input
    becomes one ``add_threshold`` node, bit-exact with the unfused
    interpreter and the reference."""
    rng = np.random.default_rng(3)
    h, c = 6, 8
    x = rng.integers(0, 4, (4, h, h, c)).astype(np.int32)
    w = rng.normal(size=(1, 1, c, c)).astype(np.float32)
    g = [Node("input", "in", {"shape": (h, h, c), "bits": 2, "signed": False}),
         Node("conv", "c", {"kernel": 1, "stride": 1, "pad": 0}, {"w": w}),
         Node("quant_act", "c.act", {"bits": 2, "act_scale": 1.0}),
         Node("add", "add", {}, inputs=("c.act", "in")),
         Node("quant_act", "join", {"bits": 2, "act_scale": 2.0},
              inputs=("add",))]
    acc = build(g, target="engine", mode="binary", weight_bits=1, act_bits=2)
    assert [n.op for n in acc.engine.graph] == ["input", "conv_mvu",
                                                "add_threshold"]
    got = np.asarray(acc(x))
    env = dataflow.trace(acc.ref_graph, jnp.asarray(x))
    want = np.asarray(resnet.join(jnp.asarray(env["c.act"], jnp.float32),
                                  jnp.asarray(x, jnp.float32), step=2.0, top=3))
    np.testing.assert_array_equal(got, want)


def test_global_pool_sums_the_map():
    x = np.random.default_rng(4).integers(0, 4, (3, 7, 7, 16)).astype(np.int32)
    g = Graph([Node("input", "in", {"shape": (7, 7, 16), "bits": 2}),
               Node("global_pool", "gap", {})])
    got = np.asarray(FusedEngine(g)(jnp.asarray(x)))
    np.testing.assert_array_equal(
        got, np.asarray(resnet.sum_pool(jnp.asarray(x, jnp.float32))))
    assert got.shape == (3, 16)


@pytest.mark.parametrize("signed,stem", [(False, jnp.uint8), (None, jnp.int16)])
def test_narrow_inputs_mixes_8_bit_and_2_bit_nodes(signed, stem):
    """Each MVU's input is proved from its own producer: unsigned 8-bit
    pixels narrow to uint8 (two digit passes), pixels of either sign to
    int16, the 2-bit body streams to int8, and the head after a 7x7 sum
    of 2-bit levels (at most 3 * 64 here, 147 at 224x224) to uint8."""
    # stride 1 throughout: the pooled map stays 8x8, a sum of up to 192
    spec = dict(QUICK, stages=[dict(s, stride=1) for s in QUICK["stages"]])
    g = resnet.graph(spec, _draw_only(spec))
    if signed is None:
        del g[0].attrs["signed"]
    acc = build(g, target="engine", mode="binary", weight_bits=1, act_bits=2,
                verify="off")
    dtypes = dataflow.narrow_inputs(acc.engine.graph)
    mvus = {n.name: n for n in acc.engine.graph
            if n.op in ("mvu", "conv_mvu")}
    assert set(dtypes) == set(mvus)
    assert dtypes["stem.conv_mvu"] == stem
    assert dtypes["fc.mvu"] == jnp.uint8
    body = [k for k in mvus if k not in ("stem.conv_mvu", "fc.mvu")]
    assert len(body) == 16 and all(dtypes[k] == jnp.int8 for k in body)
    cfgs = {k: (v.attrs["config"].mode, v.attrs["config"].weight_bits)
            for k, v in mvus.items()}
    assert cfgs["stem.conv_mvu"] == cfgs["fc.mvu"] == ("standard", 8)
    assert all(cfgs[k] == ("binary", 1) for k in body)


def _draw_only(spec):
    """Uncalibrated parameters of the right shapes (ranges do not depend
    on the values)."""
    rng = np.random.default_rng(0)
    out = {}
    for st in resnet.walk(spec):
        if st.op == "conv":
            n = st.out
            out[st.name] = {
                "w": rng.normal(size=(st.kernel, st.kernel, st.in_shape[-1],
                                      n)).astype(np.float32),
                "gamma": np.ones(n, np.float32), "beta": np.zeros(n, np.float32),
                "mean": np.zeros(n, np.float32), "var": np.ones(n, np.float32)}
        elif st.op == "dense":
            out[st.name] = {"w": rng.normal(size=(st.out, st.k)).astype(
                np.float32)}
    return out


def test_build_counts_lowered_nodes_per_bit_width():
    """With telemetry on, the build records how many nodes it lowered at
    each (mode, weight bits, act bits) as tracer counters."""
    g = resnet.graph(QUICK, _draw_only(QUICK))
    acc = build(g, target="interpret", mode="binary", weight_bits=1,
                act_bits=2, verify="off", telemetry=True)
    counts = {ev["name"]: ev["args"]["value"] for ev in acc.tracer.events()
              if ev["ph"] == "C"}
    assert counts == {"lower.binary.w1a2": 16, "lower.standard.w8a2": 2}


def _published(name):
    from repro.configs import cnv_bnn, nid_mlp

    if name == "resnet50_w1a2":
        with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
            spec = json.load(f)
        return resnet.graph(spec, _draw_only(spec)), dict(
            mode="binary", weight_bits=1, act_bits=2)
    if name == "cnv_full":
        return cnv_bnn.build_graph(cnv_bnn.FULL), dict(
            mode="xnor", weight_bits=1, act_bits=1)
    return nid_mlp.build_graph(0), dict(
        mode="standard", weight_bits=nid_mlp.WEIGHT_BITS,
        act_bits=nid_mlp.INPUT_BITS)


@pytest.mark.parametrize("name,gathered", [
    ("resnet50_w1a2", 1), ("cnv_full", 1), ("nid_mlp", None),
])
def test_build_counts_gathered_conv_windows(name, gathered):
    """At published widths the build counts the convs whose whole window
    the line buffer hands the MXU in one dot: the ResNet-50 stem and CNV's
    ``conv0`` (C = 3); none in NID, which has no conv.  The build stops at
    ``fuse_swu``, where a conv's kernel and C are first known together."""
    g, bits = _published(name)
    steps = ["validate", "lower", "finalize", "fold", "fuse_epilogues",
             "fuse_swu"]
    acc = build(g, target="engine", steps=steps, verify="off",
                telemetry=True, **bits)
    counts = {ev["name"]: ev["args"]["value"] for ev in acc.tracer.events()
              if ev["ph"] == "C"}
    assert counts.get("lower.conv.gathered_taps") == gathered
