"""Packed-datapath tests: the bit-packed kernel family vs the ref.py
oracles (all three weight codings x all epilogues x non-divisor K, both
backends), the pack_weights build step + report accounting, the packed
ScheduleCache key space, and the packed-vs-unpacked autotuner axis."""

import numpy as np
import pytest
import jax.numpy as jnp

from repro.build import build
from repro.core import autotune, lowering
from repro.core.ir import Graph, Node
from repro.core.mvu import KernelBlocks, MVUConfig
from repro.kernels import mvu_packed, packing, ref

SHAPES = [
    (4, 8, 32),     # one whole word
    (17, 9, 33),    # K one past a word boundary
    (33, 65, 127),  # nothing divides anything
    (65, 30, 600),  # NID layer-0-like K
]


def _rand(shape, lo, hi, seed, dtype=np.int8):
    return np.random.default_rng(seed).integers(lo, hi, shape).astype(dtype)


def _epilogue_args(n, epilogue, seed):
    if epilogue == "thresh":
        t = np.sort(_rand((n, 7), -200, 200, seed, np.int32), axis=1)
        return jnp.asarray(t), None
    if epilogue == "scale":
        s = np.random.default_rng(seed).uniform(0.1, 2.0, n).astype(np.float32)
        return None, jnp.asarray(s)
    return None, None


# ------------------------------------------------- bit-exactness matrix
@pytest.mark.parametrize("m,n,k", SHAPES)
@pytest.mark.parametrize("epilogue", ["raw", "thresh", "scale"])
@pytest.mark.parametrize("backend", ["pallas", "xla"])
def test_packed_binary_matches_oracle(m, n, k, epilogue, backend):
    a = _rand((m, k), -8, 8, 1)
    wb = _rand((n, k), 0, 2, 2)
    t, s = _epilogue_args(n, epilogue, 3)
    want = np.asarray(ref.mvu_binary_ref(jnp.asarray(a), jnp.asarray(wb), t, s))
    wp = mvu_packed.pack_mvu_weights(jnp.asarray(wb), "binary")
    assert wp.dtype == jnp.uint32 and wp.shape == (n, packing.num_words(k))
    got = mvu_packed.mvu_packed(jnp.asarray(a), wp, "binary", k, t, s,
                                backend=backend, block_m=32, block_n=16,
                                block_kw=2)
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("m,n,k", SHAPES)
@pytest.mark.parametrize("epilogue", ["raw", "thresh", "scale"])
@pytest.mark.parametrize("backend", ["pallas", "xla"])
def test_packed_int2_matches_oracle(m, n, k, epilogue, backend):
    a = _rand((m, k), -8, 8, 4)
    w = _rand((n, k), -2, 2, 5)  # signed 2-bit grid [-2, 1]
    t, s = _epilogue_args(n, epilogue, 6)
    want = np.asarray(ref.mvu_int_ref(jnp.asarray(a), jnp.asarray(w), t, s))
    wp = mvu_packed.pack_mvu_weights(jnp.asarray(w), "standard")
    assert wp.dtype == jnp.uint8 and wp.shape == (n, packing.num_int2_bytes(k))
    got = mvu_packed.mvu_packed(jnp.asarray(a), wp, "standard", k, t, s,
                                backend=backend, block_m=32, block_n=16,
                                block_k=32)
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("m,n,k", SHAPES)
@pytest.mark.parametrize("epilogue", ["raw", "thresh", "scale"])
def test_packed_xnor_xla_matches_oracle(m, n, k, epilogue):
    """The xnor *pallas* kernel is covered by test_kernels_mvu; here the
    packed-family XLA popcount path must agree on the same packed words."""
    ab = _rand((m, k), 0, 2, 7, np.int32)
    wb = _rand((n, k), 0, 2, 8, np.int32)
    ap, wp = packing.pack_bits(jnp.asarray(ab)), packing.pack_bits(jnp.asarray(wb))
    t, s = _epilogue_args(n, epilogue, 9)
    want = np.asarray(ref.mvu_xnor_ref(ap, wp, k, t, s))
    got = mvu_packed.mvu_packed(ap, wp, "xnor", k, t, s, backend="xla")
    np.testing.assert_array_equal(np.asarray(got), want)


def test_pack_mvu_weights_rejects_wide_standard():
    w = jnp.asarray(_rand((4, 8), -8, 8, 10))
    with pytest.raises(ValueError, match="2-bit"):
        mvu_packed.pack_mvu_weights(w, "standard")


def test_packed_weight_bytes_model():
    # binary/xnor: N * ceil(K/32) words * 4 B; standard: N * ceil(K/4) B
    assert mvu_packed.packed_weight_bytes(64, 600, "binary", 1) == 64 * 19 * 4
    assert mvu_packed.packed_weight_bytes(64, 600, "xnor", 1) == 64 * 19 * 4
    assert mvu_packed.packed_weight_bytes(64, 600, "standard", 2) == 64 * 150


# --------------------------------------------------- cache key space
def test_node_key_packed_never_aliases_canonical():
    cfg = MVUConfig(in_features=64, out_features=32, mode="binary")
    plain = autotune.node_key(cfg, epilogue="thresh", device="cpu")
    packed = autotune.node_key(
        MVUConfig(**{**cfg.__dict__, "packed": True}),
        epilogue="thresh", device="cpu")
    assert plain != packed
    assert packed == plain + "|packed"
    # a cache holding both resolves each config to its own entry
    cache = autotune.ScheduleCache({
        plain: {"backend": "pallas", "block_m": 32, "block_n": 8,
                "block_k": 32, "block_kw": 1},
        packed: {"backend": "pallas", "block_m": 64, "block_n": 16,
                 "block_k": 32, "block_kw": 2, "packed": True},
    })
    assert cache.get(plain)["block_m"] == 32
    assert cache.get(packed)["packed"] is True


def test_apply_entry_round_trips_packed_flag():
    cfg = MVUConfig(in_features=64, out_features=32, mode="binary")
    entry = {"backend": "pallas", "block_m": 64, "block_n": 16,
             "block_k": 32, "block_kw": 2, "packed": True}
    tuned = autotune.apply_entry(cfg, entry)
    assert tuned.packed is True
    assert tuned.blocks == KernelBlocks(block_m=64, block_n=16, block_k=32,
                                        block_kw=2)
    # an unpacked (legacy) entry must not flip the flag on
    plain = autotune.apply_entry(cfg, {**entry, "packed": False})
    assert plain.packed is False


# ----------------------------------------------------- build integration
def _mlp_graph(dims=(24, 16, 8), bits=2, seed=3):
    rng = np.random.default_rng(seed)
    g = [Node("input", "in", {"shape": (dims[0],), "bits": bits})]
    for i, (k, n) in enumerate(zip(dims[:-1], dims[1:])):
        w = rng.normal(0, 0.5, (n, k)).astype(np.float32)
        g.append(Node("linear", f"fc{i}", {}, {"w": jnp.asarray(w)}))
        if i < len(dims) - 2:
            g.append(Node("quant_act", f"act{i}", {"bits": bits,
                                                   "act_scale": 1.0}))
    return g


def _x(dims=(24,), bits=2, batch=9, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, 2**bits, (batch, *dims)), jnp.int32)


@pytest.mark.parametrize("mode", ["binary", "standard"])
def test_pack_always_build_bit_exact_and_reported(mode):
    wb = 1 if mode == "binary" else 2
    dims = (96, 64, 8)  # whole-word K: the byte ratio hits the ideal
    packed_acc = build(_mlp_graph(dims), target="engine", mode=mode,
                       weight_bits=wb, act_bits=2, tune="off", pack="always")
    plain_acc = build(_mlp_graph(dims), target="engine", mode=mode,
                      weight_bits=wb, act_bits=2, tune="off", pack="never")
    x = _x((96,))
    np.testing.assert_array_equal(np.asarray(packed_acc(x)),
                                  np.asarray(plain_acc(x)))
    nodes = packed_acc.report.nodes
    assert nodes and all(n.packed for n in nodes)
    for n in nodes:
        assert 0 < n.weight_bytes < n.canonical_weight_bytes
        want_ratio = 8.0 if mode == "binary" else 4.0
        assert n.canonical_weight_bytes / n.weight_bytes == want_ratio
    assert all(not n.packed for n in plain_acc.report.nodes)


def test_pack_never_ignores_cached_packed_entry():
    """pack="never" must not apply a committed packed-datapath schedule --
    the storage rewrite it needs is policy-forbidden."""
    fin = lowering.finalize(lowering.lower_to_mvu(
        _mlp_graph((16, 8)), mode="binary", weight_bits=1, act_bits=2))
    mvu_nodes = [n for n in fin if n.op == "mvu"]
    assert mvu_nodes
    key = autotune.node_key(mvu_nodes[0].attrs["config"], epilogue="scale")
    cache = autotune.ScheduleCache({key: {
        "backend": "pallas", "block_m": 32, "block_n": 8, "block_k": 16,
        "block_kw": 1, "packed": True}})
    tuned = autotune.tune_graph(fin, cache=cache, mode="cache",
                                allow_packed=False)
    cfgs = [n.attrs["config"] for n in tuned if n.op == "mvu"]
    assert all(not c.packed and c.blocks is None for c in cfgs)
    # the same entry applies when packing is allowed
    tuned = autotune.tune_graph(fin, cache=cache, mode="cache",
                                allow_packed=True)
    assert any(n.attrs["config"].packed for n in tuned if n.op == "mvu")


def test_tune_node_selects_packed_with_stub_timer():
    """With a timer that always reports the challenger 3x faster, the
    winning entry is a bit-exact packed candidate (the packed axis is in
    the searched space, not bolted on after).  At K = 4096 the packed
    word step covers all of K in one 128-word tile while the int8 K step
    stops at 512 lanes, so the cycle model measures the packed schedules
    first and the first challenger wins."""
    fin = lowering.finalize(lowering.lower_to_mvu(
        _mlp_graph((4096, 16)), mode="binary", weight_bits=1, act_bits=2))
    node = next(n for n in fin if n.op == "mvu")

    def fast_challenger(base_fn, fn, x, reps=3):
        return 1.0, 1.0 / 3.0, 3.0

    entry = autotune.tune_node(node, (4096,), timer=fast_challenger,
                               sample_m=16, max_measure=16)
    assert entry.get("packed") is True
    assert entry["speedup"] == pytest.approx(3.0)
    # and the policy switch removes packed candidates from the search
    entry = autotune.tune_node(node, (4096,), timer=fast_challenger,
                               sample_m=16, max_measure=16,
                               allow_packed=False)
    assert not entry.get("packed")


def test_tune_graph_skips_already_packed_nodes():
    """A node that already carries a tuned packed schedule (apply_entry ran
    in a prior pass) is passed through untouched -- re-tuning it under the
    |packed key would duplicate cache entries on every downstream pass."""
    fin = lowering.finalize(lowering.lower_to_mvu(
        _mlp_graph((16, 8)), mode="binary", weight_bits=1, act_bits=2))
    out: Graph = Graph()
    for n in fin:
        if n.op == "mvu":
            cfg = autotune.apply_entry(n.attrs["config"], {
                "backend": "pallas", "block_m": 32, "block_n": 8,
                "block_k": 16, "block_kw": 1, "packed": True})
            n = Node(n.op, n.name, {**n.attrs, "config": cfg}, n.params,
                     inputs=n.inputs)
        out.append(n)
    cache = autotune.ScheduleCache()

    def no_timer(*a, **kw):
        raise AssertionError("already-tuned packed node must not re-measure")

    tuned = autotune.tune_graph(out, cache=cache, mode="auto", timer=no_timer)
    assert len(cache) == 0
    assert [n.attrs["config"].packed for n in tuned if n.op == "mvu"] == [True]
