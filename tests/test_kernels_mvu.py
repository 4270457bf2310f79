"""Per-kernel allclose (exact integer) checks against the ref.py oracles.

Sweeps shapes (including non-multiples of every tile dim), all three SIMD
datapaths, both epilogues, and odd block shapes — interpret mode on CPU.
"""

import numpy as np
import pytest
import jax.numpy as jnp

from repro.kernels import ops, packing, ref

SHAPES = [
    (1, 1, 32),      # degenerate
    (4, 64, 64),     # PE/SIMD=small paper regime
    (33, 65, 127),   # nothing divides anything
    (128, 128, 256), # aligned
    (65, 130, 600),  # NID layer-0-like K
]
BLOCKS = [(32, 32, 64), (128, 128, 128)]


def _rand(shape, lo, hi, seed, dtype=np.int8):
    return np.random.default_rng(seed).integers(lo, hi, shape).astype(dtype)


@pytest.mark.parametrize("m,n,k", SHAPES)
@pytest.mark.parametrize("bm,bn,bk", BLOCKS)
def test_standard_matches_oracle(m, n, k, bm, bn, bk):
    a = _rand((m, k), -8, 8, 1)
    w = _rand((n, k), -8, 8, 2)
    want = np.asarray(ref.mvu_int_ref(jnp.asarray(a), jnp.asarray(w)))
    got = ops.mvu(jnp.asarray(a), jnp.asarray(w), "standard",
                  block_m=bm, block_n=bn, block_k=bk)
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("m,n,k", SHAPES)
def test_binary_matches_oracle(m, n, k):
    a = _rand((m, k), -8, 8, 3)
    wb = _rand((n, k), 0, 2, 4)
    want = np.asarray(ref.mvu_binary_ref(jnp.asarray(a), jnp.asarray(wb)))
    got = ops.mvu(jnp.asarray(a), jnp.asarray(wb), "binary",
                  block_m=32, block_n=32, block_k=64)
    np.testing.assert_array_equal(np.asarray(got), want)
    # exact bipolar semantics
    manual = a.astype(np.int64) @ (2 * wb.astype(np.int64) - 1).T
    np.testing.assert_array_equal(want, manual)


@pytest.mark.parametrize("m,n,k", SHAPES)
@pytest.mark.parametrize("bkw", [1, 4, 8])
def test_xnor_matches_oracle(m, n, k, bkw):
    ab = _rand((m, k), 0, 2, 5, np.int32)
    wb = _rand((n, k), 0, 2, 6, np.int32)
    ap = packing.pack_bits(jnp.asarray(ab))
    wp = packing.pack_bits(jnp.asarray(wb))
    want = np.asarray(ref.mvu_xnor_ref(ap, wp, k))
    got = ops.mvu(ap, wp, "xnor", k_bits=k, block_m=32, block_n=32, block_kw=bkw)
    np.testing.assert_array_equal(np.asarray(got), want)
    manual = (2 * ab - 1) @ (2 * wb - 1).T
    np.testing.assert_array_equal(want, manual)


@pytest.mark.parametrize("mode", ["standard", "binary", "xnor"])
@pytest.mark.parametrize("n_thresh", [1, 3, 15])
def test_threshold_epilogue(mode, n_thresh):
    m, n, k = 17, 29, 96
    if mode == "xnor":
        ab = _rand((m, k), 0, 2, 7, np.int32)
        wb = _rand((n, k), 0, 2, 8, np.int32)
        a = packing.pack_bits(jnp.asarray(ab))
        w = packing.pack_bits(jnp.asarray(wb))
        acc = (2 * ab - 1) @ (2 * wb - 1).T
    elif mode == "binary":
        a_ = _rand((m, k), -8, 8, 9)
        wb = _rand((n, k), 0, 2, 10)
        a, w = jnp.asarray(a_), jnp.asarray(wb)
        acc = a_.astype(np.int64) @ (2 * wb.astype(np.int64) - 1).T
    else:
        a_ = _rand((m, k), -8, 8, 11)
        w_ = _rand((n, k), -8, 8, 12)
        a, w = jnp.asarray(a_), jnp.asarray(w_)
        acc = a_.astype(np.int64) @ w_.astype(np.int64).T
    t = np.sort(_rand((n, n_thresh), -300, 300, 13, np.int32), axis=1)
    want = (acc[..., None] >= t[None]).sum(-1)
    got = ops.mvu(a, w, mode, k_bits=k, thresholds=jnp.asarray(t),
                  block_m=32, block_n=32, block_k=32, block_kw=2)
    np.testing.assert_array_equal(np.asarray(got), want)
    assert np.asarray(got).max() <= n_thresh and np.asarray(got).min() >= 0


@pytest.mark.parametrize("mode", ["standard", "binary"])
def test_scale_epilogue(mode):
    m, n, k = 19, 23, 80
    a_ = _rand((m, k), -8, 8, 14)
    if mode == "binary":
        w_ = _rand((n, k), 0, 2, 15)
        acc = a_.astype(np.int64) @ (2 * w_.astype(np.int64) - 1).T
    else:
        w_ = _rand((n, k), -8, 8, 15)
        acc = a_.astype(np.int64) @ w_.astype(np.int64).T
    s = np.random.default_rng(16).uniform(0.01, 2.0, (n,)).astype(np.float32)
    got = ops.mvu(jnp.asarray(a_), jnp.asarray(w_), mode,
                  out_scale=jnp.asarray(s), block_m=32, block_n=32, block_k=32)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), acc * s[None], rtol=1e-6)


def test_xla_backend_agrees_with_pallas():
    m, n, k = 40, 50, 160
    a = _rand((m, k), -8, 8, 17)
    w = _rand((n, k), -8, 8, 18)
    via_xla = ops.mvu(jnp.asarray(a), jnp.asarray(w), "standard", backend="xla")
    via_pl = ops.mvu(jnp.asarray(a), jnp.asarray(w), "standard", backend="pallas",
                     block_m=32, block_n=32, block_k=32)
    np.testing.assert_array_equal(np.asarray(via_xla), np.asarray(via_pl))


def test_xnor_mxu_variant_agrees():
    m, n, k = 30, 40, 222
    ab = _rand((m, k), 0, 2, 19, np.int32)
    wb = _rand((n, k), 0, 2, 20, np.int32)
    ap = packing.pack_bits(jnp.asarray(ab))
    wp = packing.pack_bits(jnp.asarray(wb))
    want = np.asarray(ref.mvu_xnor_ref(ap, wp, k))
    got = ops.xnor_mxu(ap, wp, k)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_accumulator_width_no_overflow():
    """int8 x int8 over K=8192 stays within int32 (FINN wide-accumulator claim)."""
    m, n, k = 8, 8, 8192
    a = np.full((m, k), 7, np.int8)
    w = np.full((n, k), 7, np.int8)
    got = ops.mvu(jnp.asarray(a), jnp.asarray(w), "standard",
                  block_m=8, block_n=8, block_k=256)
    assert int(np.asarray(got)[0, 0]) == 49 * k


# Activations wider than int8: an 8-bit unsigned grid (act_bits=8, values up
# to 255), full int16, and large int32.  The MXU multiplies int8, so these
# run once per base-256 digit and must still equal the oracle exactly.
WIDE = {
    "uint8": (0, 256, np.uint8),
    "int16": (-2**15, 2**15, np.int16),
    "int32": (-2**20, 2**20, np.int32),
}
WIDE_PATHS = ["standard", "binary", "binary_packed", "int2_packed",
              "conv_standard", "conv_binary", "conv_xnor"]
PACKED_MODE = {"binary_packed": "binary", "int2_packed": "standard"}


def _thresholds_for(acc, n_thresh, seed):
    """Ascending per-column thresholds spread over the accumulator's range,
    so every output level is reachable."""
    qs = np.quantile(acc.reshape(-1, acc.shape[-1]), np.linspace(0.1, 0.9, n_thresh),
                     axis=0).T.astype(np.int64)
    jitter = np.random.default_rng(seed).integers(-2, 3, qs.shape)
    return np.sort(qs + jitter, axis=1).astype(np.int32)


@pytest.mark.parametrize("dtype", sorted(WIDE))
@pytest.mark.parametrize("path", WIDE_PATHS)
def test_wide_activations_match_oracle(path, dtype):
    from repro.kernels import mvu_packed

    lo, hi, npdt = WIDE[dtype]
    if path.startswith("conv"):
        mode = path.split("_")[1]
        x = _rand((2, 6, 7, 5), lo, hi, 21, npdt)
        if mode == "xnor" and dtype == "uint8":
            x = _rand((2, 6, 7, 5), 0, 2, 21, npdt)  # the usual {0,1} bits
        n, kd = 11, 3
        w = (_rand((n, kd * kd * 5), -8, 8, 22) if mode == "standard"
             else _rand((n, kd * kd * 5), 0, 2, 22))
        kw = dict(kernel=kd, stride=1, pad=1, mode=mode)
        acc = np.asarray(ref.conv_mvu_ref(jnp.asarray(x), jnp.asarray(w), **kw))
        t = jnp.asarray(_thresholds_for(acc, 7, 23))
        want = ref.conv_mvu_ref(jnp.asarray(x), jnp.asarray(w), thresholds=t, **kw)
        wk = (packing.pack_bits(jnp.asarray(w, jnp.int32)) if mode == "xnor"
              else jnp.asarray(w))
        got = ops.conv_mvu(jnp.asarray(x), wk, thresholds=t, block_m=16,
                           block_n=8, k_bits=w.shape[1], **kw)
    else:
        m, n, k = 9, 13, 70
        a = jnp.asarray(_rand((m, k), lo, hi, 24, npdt))
        if path in ("standard", "int2_packed"):
            wlo, whi = (-2, 2) if path == "int2_packed" else (-8, 8)
            w = jnp.asarray(_rand((n, k), wlo, whi, 25))
            oracle = ref.mvu_int_ref
        else:
            w = jnp.asarray(_rand((n, k), 0, 2, 25))
            oracle = ref.mvu_binary_ref
        t = jnp.asarray(_thresholds_for(np.asarray(oracle(a, w)), 5, 26))
        want = oracle(a, w, t)
        blocks = dict(block_m=8, block_n=8, block_k=32, block_kw=1)
        if path in PACKED_MODE:
            mode = PACKED_MODE[path]
            got = ops.mvu(a, mvu_packed.pack_mvu_weights(w, mode), mode,
                          k_bits=k, thresholds=t, packed=True, **blocks)
        else:
            got = ops.mvu(a, w, path, thresholds=t, **blocks)
    want = np.asarray(want)
    assert want.min() < want.max()  # every case reaches several levels
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("epilogue", ["raw", "scale"])
@pytest.mark.parametrize("path", ["standard", "conv_standard"])
def test_wide_activations_raw_and_scale_epilogues(path, epilogue):
    s = np.random.default_rng(27).uniform(0.01, 2.0, (11,)).astype(np.float32)
    scale = jnp.asarray(s) if epilogue == "scale" else None
    if path == "standard":
        a = jnp.asarray(_rand((9, 70), 0, 256, 28, np.int32))
        w = jnp.asarray(_rand((11, 70), -8, 8, 29))
        want = ref.mvu_int_ref(a, w, out_scale=scale)
        got = ops.mvu(a, w, "standard", out_scale=scale,
                      block_m=8, block_n=8, block_k=32)
    else:
        x = jnp.asarray(_rand((2, 6, 6, 4), 0, 256, 30, np.int32))
        w = jnp.asarray(_rand((11, 36), -8, 8, 31))
        kw = dict(kernel=3, stride=2, pad=1, mode="standard", out_scale=scale)
        want = ref.conv_mvu_ref(x, w, **kw)
        got = ops.conv_mvu(x, w, block_m=8, block_n=8, **kw)
    assert np.asarray(got).dtype == np.asarray(want).dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
