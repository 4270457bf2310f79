"""Streaming conv path tests: the SWU lowering and the fused SWU+MVU kernel
against ``jax.lax.conv_general_dilated`` over the full (kernel, stride, pad)
grid, plus graph-level fusion (``fuse_swu``) and the CNV topology end-to-end."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import cnv_bnn
from repro.core import dataflow, lowering, swu
from repro.core.engine import FusedEngine
from repro.core.folding import Folding
from repro.core.ir import Graph, Node
from repro.kernels import ops, packing, ref
from repro.kernels.swu_mvu import conv_mvu_pallas, gathers_taps

GRID = [(kd, st, pd) for kd in (1, 3, 5) for st in (1, 2) for pd in (0, 1, 2)]
MODES = ("standard", "binary", "xnor")


def _lax_conv(x, w, stride, pad):
    return jax.lax.conv_general_dilated(
        x.astype(jnp.float32), w.astype(jnp.float32),
        window_strides=(stride, stride),
        padding=[(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


@pytest.mark.parametrize("kd,stride,pad", GRID)
def test_sliding_window_matches_lax_conv(kd, stride, pad):
    """swu.sliding_window x packed weights == lax conv, non-square input."""
    rng = np.random.default_rng(kd * 100 + stride * 10 + pad)
    x = rng.normal(size=(2, 9, 13, 3)).astype(np.float32)
    w = rng.normal(size=(kd, kd, 3, 5)).astype(np.float32)
    got = swu.conv_via_swu_mvu(jnp.asarray(x), jnp.asarray(w), stride, pad)
    want = _lax_conv(jnp.asarray(x), jnp.asarray(w), stride, pad)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("i,kd,stride,pad", [(i, *g) for i, g in enumerate(GRID)])
def test_conv_mvu_kernel_matches_lax_conv(i, kd, stride, pad):
    """Fused line-buffer kernel == lax conv, exact integer equality.

    The weight coding rotates through the grid so every (mode, kernel,
    stride, pad) region is covered without tripling the compile count."""
    mode = MODES[i % len(MODES)]
    rng = np.random.default_rng(i)
    h, wdim, c, n = 8, 11, 3, 6  # non-square on purpose
    k = kd * kd * c
    if mode == "standard":
        x = rng.integers(0, 8, (2, h, wdim, c)).astype(np.int32)
        w_rows = rng.integers(-7, 8, (n, k)).astype(np.int8)
        w_arg, x_arg = jnp.asarray(w_rows), jnp.asarray(x)
        x_f, w_f = x, w_rows
    elif mode == "binary":
        x = rng.integers(0, 8, (2, h, wdim, c)).astype(np.int32)
        bits = rng.integers(0, 2, (n, k)).astype(np.int8)
        w_arg, x_arg = jnp.asarray(bits), jnp.asarray(x)
        x_f, w_f = x, 2 * bits - 1  # {0,1}-coded +/-1
    else:  # xnor: both operands bipolar
        x = rng.integers(0, 2, (2, h, wdim, c)).astype(np.int32)
        bits = rng.integers(0, 2, (n, k)).astype(np.int32)
        w_arg, x_arg = packing.pack_bits(jnp.asarray(bits)), jnp.asarray(x)
        x_f, w_f = 2 * x - 1, 2 * bits - 1
    got = np.asarray(ops.conv_mvu(
        x_arg, w_arg, kernel=kd, stride=stride, pad=pad, mode=mode,
        k_bits=k if mode == "xnor" else None))
    # reference: lax conv on the equivalent float weights, (ky, kx, c) order
    w_hwio = np.asarray(w_f).reshape(n, kd, kd, c).transpose(1, 2, 3, 0)
    want = np.asarray(_lax_conv(jnp.asarray(x_f), jnp.asarray(w_hwio),
                                stride, pad)).astype(np.int64)
    if mode == "xnor" and pad:
        # zero pad pixels contribute -1 per synapse in the bipolar view;
        # the line-buffer kernel treats pads as stored-bit 0 == -1, and so
        # does the reference once x is mapped to 2x-1 *before* padding, so
        # re-derive the reference with explicitly padded bipolar input.
        xp = np.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
        want = np.asarray(_lax_conv(jnp.asarray(2 * xp - 1),
                                    jnp.asarray(w_hwio), stride, 0))
    b = x.shape[0]
    assert got.shape[0] == b
    np.testing.assert_array_equal(got.reshape(want.shape), want)


@pytest.mark.parametrize("mode,n_thresh,table,n", [
    pytest.param("standard", 3, "spread", 5, id="3-standard"),
    pytest.param("xnor", 1, "spread", 5, id="1-xnor"),
    pytest.param("binary", 15, "spread", 5, id="15-binary"),
    pytest.param("standard", 255, "spread", 5, id="255-standard"),
    pytest.param("wide", 255, "spread", 5, id="255-wide"),
    pytest.param("xnor", 1, "ties", 130, id="1-xnor-ties-n130"),
    pytest.param("standard", 3, "ties", 5, id="3-standard-ties"),
    pytest.param("standard", 3, "below", 5, id="3-standard-below"),
    pytest.param("xnor", 1, "above", 5, id="1-xnor-above"),
])
def test_conv_mvu_kernel_threshold_epilogue(mode, n_thresh, table, n):
    """Fused kernel thresholds == materialized SWU + threshold reference.

    int8 activations run the epilogue inside the kernel: T = 15 and 255 its
    looped form, n = 130 two N blocks of 128, each with its own slice of
    the table.  ``wide`` (int16 activations) runs it in XLA after one kernel
    pass per base-256 digit."""
    rng = np.random.default_rng(3)
    kd, st, pd, c = 3, 1, 1, 4
    k = kd * kd * c
    hi = {"xnor": 2, "wide": 2**15}.get(mode, 4)
    x = jnp.asarray(rng.integers(0, hi, (2, 7, 9, c)),
                    jnp.int16 if mode == "wide" else jnp.int8)
    if mode in ("standard", "wide"):
        w_rows = jnp.asarray(rng.integers(-7, 8, (n, k)), jnp.int8)
    else:
        w_rows = jnp.asarray(rng.integers(0, 2, (n, k)), jnp.int8)
    kind = "standard" if mode == "wide" else mode
    kw = dict(kernel=kd, stride=st, pad=pd, mode=kind)
    acc = np.asarray(ref.conv_mvu_ref(x, w_rows, **kw))
    if table == "spread":
        t = rng.integers(acc.min(), acc.max() + 1, (n, n_thresh))
    elif table == "ties":  # levels drawn from each channel's own outputs
        t = acc.reshape(-1, n).T[np.arange(n)[:, None],
                                 rng.integers(0, acc.size // n, (n, n_thresh))]
    elif table == "below":
        t = np.full((n, n_thresh), acc.min() - 1)
    else:
        t = np.full((n, n_thresh), acc.max() + 1)
    t = jnp.asarray(np.sort(t, axis=1), jnp.int32)
    w = (packing.pack_bits(w_rows.astype(jnp.int32)) if mode == "xnor"
         else w_rows)
    got = ops.conv_mvu(x, w, k_bits=k, thresholds=t, **kw)
    want = ref.conv_mvu_ref(x, w_rows, thresholds=t, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(want), (acc[..., None] >= np.asarray(t)).sum(-1))
    want = np.asarray(want)
    if table == "below":
        assert (want == n_thresh).all()
    elif table == "above":
        assert (want == 0).all()
    else:
        assert want.max() <= n_thresh and want.min() < want.max()


@pytest.mark.parametrize("mode,kd,stride,pad,c,dtype,gathered", [
    pytest.param("standard", 7, 2, 3, 3, jnp.uint8, True, id="stem-uint8"),
    pytest.param("standard", 7, 1, 0, 3, jnp.int8, True, id="7x7-standard"),
    pytest.param("binary", 7, 2, 3, 3, jnp.int8, True, id="7x7s2-binary"),
    pytest.param("xnor", 7, 1, 3, 3, jnp.int8, True, id="7x7-xnor"),
    pytest.param("standard", 3, 2, 0, 3, jnp.int8, True, id="3x3s2-standard"),
    pytest.param("binary", 3, 1, 3, 3, jnp.uint8, True, id="3x3-binary-uint8"),
    pytest.param("xnor", 3, 1, 0, 3, jnp.int8, True, id="conv0-xnor"),
    pytest.param("xnor", 3, 2, 3, 3, jnp.uint8, True, id="3x3s2-xnor-uint8"),
    pytest.param("binary", 3, 1, 0, 27, jnp.int8, True, id="c27-binary"),
    pytest.param("standard", 3, 2, 3, 27, jnp.int8, True, id="c27s2-standard"),
    pytest.param("xnor", 3, 1, 0, 32, jnp.int8, False, id="c32-xnor"),
    pytest.param("standard", 3, 1, 3, 32, jnp.uint8, False, id="c32-uint8"),
])
def test_gathered_window_matches_reference(mode, kd, stride, pad, c, dtype,
                                           gathered):
    """Lane-narrow windows gathered into one dot, and the one-dot-per-tap
    path just past two lane tiles, == the materialized SWU reference,
    raw and through thresholds.  uint8 inputs run the base-256 digit path
    (two kernel passes, thresholds in XLA)."""
    assert gathers_taps(kd, c) == gathered
    rng = np.random.default_rng(kd * 1000 + c * 10 + stride + pad)
    n, k = 20, kd * kd * c
    hi = 2 if mode == "xnor" else np.iinfo(np.dtype(dtype)).max + 1
    lo = 0 if dtype == jnp.uint8 or mode == "xnor" else -128
    x = jnp.asarray(rng.integers(lo, hi, (2, 10, 16, c)), dtype)
    if mode == "standard":
        w = jnp.asarray(rng.integers(-128, 128, (n, k)), jnp.int8)
    else:
        w = jnp.asarray(rng.integers(0, 2, (n, k)), jnp.int8)
    kw = dict(kernel=kd, stride=stride, pad=pad, mode=mode)
    acc = np.asarray(ref.conv_mvu_ref(x, w, **kw))
    tiles = dict(block_m=32, interpret=True)  # several row tiles an image
    got = conv_mvu_pallas(x, w, **kw, **tiles)
    np.testing.assert_array_equal(np.asarray(got), acc)
    t = jnp.asarray(np.sort(rng.integers(acc.min(), acc.max() + 1, (n, 3)),
                            axis=1), jnp.int32)
    got = conv_mvu_pallas(x, w, t, **kw, **tiles)
    want = ref.conv_mvu_ref(x, w, thresholds=t, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("kd,c,gathered", [
    (7, 3, True), (3, 3, True), (3, 64, False), (1, 3, False), (1, 256, False),
])
def test_gathers_taps_only_lane_narrow_windows(kd, c, gathered):
    """A window of more than one tap that fits two lane tiles is gathered;
    C >= 64 3x3 convs and every 1x1 conv keep one dot per tap."""
    assert gathers_taps(kd, c) == gathered


def test_fuse_swu_collapses_pairs():
    g: Graph = [Node("input", "in", {"shape": (8, 8, 3), "bits": 2})]
    rng = np.random.default_rng(0)
    g.append(Node("conv", "c0", {"kernel": 3, "stride": 1, "pad": 1},
                  {"w": jnp.asarray(rng.normal(0, .5, (3, 3, 3, 4)).astype(np.float32))}))
    fin = lowering.finalize(lowering.lower_to_mvu(g, mode="standard"))
    assert [n.op for n in fin] == ["input", "swu", "mvu"]
    fused = lowering.fuse_swu(fin)
    assert [n.op for n in fused] == ["input", "conv_mvu"]
    node = fused[1]
    assert node.attrs["kernel"] == 3 and node.attrs["pad"] == 1
    assert node.name == "c0.conv_mvu" and "mvu" in node.params
    # un-finalized mvu nodes (still float) must NOT fuse
    raw = lowering.lower_to_mvu(g, mode="standard")
    assert [n.op for n in lowering.fuse_swu(raw)] == ["input", "swu", "mvu"]


@pytest.mark.parametrize("mode", MODES)
def test_cnv_engine_bit_exact_vs_interpreter(mode):
    """CNV-style graph (>=2 conv + pool + dense): FusedEngine == interpreter,
    all swu+mvu pairs collapsed into conv_mvu stages."""
    bits = 1 if mode == "xnor" else 2
    spec = cnv_bnn.CNVSpec(image=10, channels=(4, 4), pool_after=(1,),
                           fc=(8, 4), weight_bits=1 if mode != "standard" else 4,
                           act_bits=bits)
    g = cnv_bnn.build_graph(spec, seed=2)
    fin = lowering.finalize(lowering.lower_to_mvu(
        g, mode=mode, weight_bits=spec.weight_bits, act_bits=bits))
    fin = lowering.apply_folding(fin)
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.integers(0, 2**bits, (3, 10, 10, 3)), jnp.int32)

    want = np.asarray(dataflow.execute(fin, x))
    engine = FusedEngine(fin)
    got = np.asarray(engine(x))
    np.testing.assert_array_equal(got, want)
    ops_left = [n.op for n in engine.graph]
    assert "swu" not in ops_left and "batchnorm" not in ops_left
    assert ops_left.count("conv_mvu") == 2 and ops_left.count("mvu") == 2
    assert got.shape == (3, 4)


def test_conv_folding_pixel_cycles():
    """Conv folding counts the pixel dimension: cycles = OH*OW * NF * SF."""
    f = Folding(pe=4, simd=9)
    assert f.conv_cycles(8, 36, oh=6, ow=5) == 30 * (8 // 4) * (36 // 9)
    # apply_folding threads conv pixel counts into the schedule
    rng = np.random.default_rng(1)
    g: Graph = [Node("input", "in", {"shape": (8, 8, 3), "bits": 2}),
                Node("conv", "c0", {"kernel": 3, "stride": 1, "pad": 0},
                     {"w": jnp.asarray(rng.normal(0, .5, (3, 3, 3, 4)).astype(np.float32))})]
    fin = lowering.fuse_swu(lowering.finalize(lowering.lower_to_mvu(g)))
    fin = lowering.apply_folding(fin, max_pe=4, max_simd=9)
    sched = dataflow.schedule(fin)
    st = sched.stages[0]
    fold = fin[1].attrs["config"].resolved_folding()
    assert st.n_pixels == 36
    assert st.cycles == fold.conv_cycles(4, 27, oh=6, ow=6)


def test_sliding_window_property_random_shapes():
    """Hypothesis sweep: sliding_window + fused kernel == lax conv for
    arbitrary shapes/strides/pads (nightly CI installs hypothesis)."""
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=20, deadline=None)
    @given(
        h=st.integers(5, 12), w=st.integers(5, 12),
        c=st.integers(1, 4), kd=st.sampled_from([1, 3, 5]),
        stride=st.integers(1, 2), pad=st.integers(0, 2),
        seed=st.integers(0, 2**16),
    )
    def check(h, w, c, kd, stride, pad, seed):
        hypothesis.assume(h + 2 * pad >= kd and w + 2 * pad >= kd)
        rng = np.random.default_rng(seed)
        x = rng.normal(size=(1, h, w, c)).astype(np.float32)
        wt = rng.normal(size=(kd, kd, c, 3)).astype(np.float32)
        got = swu.conv_via_swu_mvu(jnp.asarray(x), jnp.asarray(wt), stride, pad)
        want = _lax_conv(jnp.asarray(x), jnp.asarray(wt), stride, pad)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-3, atol=1e-3)
        # and the fused line-buffer kernel, exact on the integer grid
        xi = jnp.asarray(rng.integers(0, 8, (1, h, w, c)), jnp.int32)
        wi = jnp.asarray(rng.integers(-7, 8, (3, kd * kd * c)), jnp.int8)
        kw = dict(kernel=kd, stride=stride, pad=pad, mode="standard")
        fused = ops.conv_mvu(xi, wi, **kw)
        ref = ops.conv_mvu(xi, wi, backend="xla", **kw)
        np.testing.assert_array_equal(np.asarray(fused), np.asarray(ref))

    check()
