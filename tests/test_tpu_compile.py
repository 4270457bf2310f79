"""Compile the main path's Pallas kernels for a described TPU v5e chip.

Nothing runs: the TPU compiler installed with JAX compiles for a chip that
is described, not attached, and refuses what the chip's compiler would
refuse -- block shapes off the (8, 128) layout, unaligned lane slices,
reshapes Mosaic cannot lay out, more VMEM than a kernel may use.  Interpret
mode (every other kernel test) cannot see any of that.  Shapes are the real
ones: the NID-MLP layers with the tiles its build picks, 512x512 dense
layers at CNV's width, the first two CNV FULL conv layers (and a strided
conv), and the whole NID engine step.

The topology is described inside a fixture, never on import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import nid_mlp
from repro.core.folding import Folding
from repro.core.mvu import MVUConfig
from repro.kernels import mvu_packed, ops, packing
from repro.kernels.mvu_binary import mvu_binary_pallas
from repro.kernels.mvu_int import mvu_int_pallas
from repro.kernels.mvu_xnor import mvu_xnor_pallas
from repro.kernels.swu_mvu import conv_mvu_pallas


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _kernels(fn, one_chip, *shapes) -> list[str]:
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return ops.tpu_kernel_names(jax.jit(fn).lower(*args).compile().as_text())


NID_SHAPES = sorted({(k, n, pe, simd) for k, n, pe, simd in nid_mlp.LAYERS})


@pytest.mark.parametrize("k,n,pe,simd", NID_SHAPES)
def test_nid_mvu_int_compiles(one_chip, k, n, pe, simd):
    blocks = MVUConfig(in_features=k, out_features=n,
                       weight_bits=nid_mlp.WEIGHT_BITS,
                       folding=Folding(pe, simd)).kernel_blocks()
    if n > 1:  # hidden layers: 2-bit activations through 3 thresholds
        epi = ((n, 2**nid_mlp.INPUT_BITS - 1), jnp.int32)
        fn = lambda a, w, t: mvu_int_pallas(a, w, t, interpret=False, **blocks)
    else:  # the classifier head dequantizes
        epi = ((n,), jnp.float32)
        fn = lambda a, w, s: mvu_int_pallas(a, w, None, s, interpret=False,
                                            **blocks)
    names = _kernels(fn, one_chip, ((1024, k), jnp.int8), ((n, k), jnp.int8),
                     epi)
    assert names == ["mvu_int"]


def _dense(mode):
    n = k = 512
    words = packing.num_words(k)
    thr = ((n, 1), jnp.int32)
    if mode == "binary":
        return (lambda a, w, t: mvu_binary_pallas(a, w, t, interpret=False),
                [((256, k), jnp.int8), ((n, k), jnp.int8), thr])
    if mode == "xnor":
        return (lambda a, w, t: mvu_xnor_pallas(a, w, k, t, interpret=False),
                [((256, words), jnp.uint32), ((n, words), jnp.uint32), thr])
    if mode == "binary_packed":
        return (lambda a, w, t: mvu_packed.mvu_binary_packed_pallas(
                    a, w, k, t, interpret=False),
                [((256, k), jnp.int8), ((n, words), jnp.uint32), thr])
    return (lambda a, w, t: mvu_packed.mvu_int2_packed_pallas(
                a, w, k, t, interpret=False),
            [((256, k), jnp.int8), ((n, packing.num_int2_bytes(k)), jnp.uint8),
             thr])


@pytest.mark.parametrize("mode,name", [
    ("binary", "mvu_binary"), ("xnor", "mvu_xnor"),
    ("binary_packed", "mvu_binary_packed"), ("int2_packed", "mvu_int2_packed"),
])
def test_dense_kernels_compile_at_512(one_chip, mode, name):
    fn, shapes = _dense(mode)
    assert _kernels(fn, one_chip, *shapes) == [name]


@pytest.mark.parametrize("h,c,n,mode,stride,pad", [
    (32, 3, 64, "xnor", 1, 0),  # CNV FULL conv0
    (30, 64, 64, "xnor", 1, 0),  # CNV FULL conv1
    (32, 64, 128, "standard", 2, 1),  # strided taps load 32-bit rows
])
def test_conv_layers_compile(one_chip, h, c, n, mode, stride, pad):
    k = 9 * c
    fn = lambda x, w, t: conv_mvu_pallas(x, w, t, kernel=3, stride=stride,
                                         pad=pad, mode=mode, block_n=n,
                                         interpret=False)
    names = _kernels(fn, one_chip, ((8, h, h, c), jnp.int8),
                     ((n, k), jnp.int8), ((n, 1), jnp.int32))
    assert names == [f"conv_mvu_{mode}"]


def test_nid_engine_step_compiles(one_chip, monkeypatch):
    from repro.build import build

    acc = build(nid_mlp.build_graph(0), target="engine", mode="standard",
                weight_bits=nid_mlp.WEIGHT_BITS, act_bits=nid_mlp.INPUT_BITS,
                folding=nid_mlp.foldings(), verify="off")
    engine = acc.engine
    # the host is a CPU, so the kernels would pick interpret mode; compile
    # them as the chip runs them
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    batch = 1024
    params = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype, sharding=one_chip),
        engine.params)
    x = jax.ShapeDtypeStruct((batch, 600), jnp.int32, sharding=one_chip)
    compiled = engine._jit.lower(params, x, engine.plan(batch).n_micro).compile()
    text = compiled.as_text()
    assert ops.tpu_kernel_names(text) == ["mvu_int"] * 4
    # each kernel carries its node's name scope, which a device trace shows
    kernel_scopes = re.findall(
        r'custom_call_target="tpu_custom_call".*?op_name="[^"]*?/'
        r'closed_call/([^/"]+)/', text)
    assert kernel_scopes == ["fc0.mvu", "fc1.mvu", "fc2.mvu", "fc3.mvu"]


@pytest.mark.parametrize("kind,dtype,passes", [
    ("dense", jnp.uint8, 2),  # 8-bit unsigned activations: two digits
    ("conv", jnp.int32, 4),
])
def test_wide_activations_compile(one_chip, kind, dtype, passes):
    """Activations wider than int8 compile as one int8 kernel per base-256
    digit, with the threshold epilogue outside the kernels."""
    n, k = 128, 512
    if kind == "dense":
        fn = lambda a, w, t: mvu_int_pallas(a, w, t, interpret=False)
        shapes = [((256, k), dtype), ((n, k), jnp.int8), ((n, 255), jnp.int32)]
        name = "mvu_int"
    else:
        fn = lambda x, w, t: conv_mvu_pallas(x, w, t, kernel=3, mode="standard",
                                             block_n=n, interpret=False)
        shapes = [((2, 16, 16, 64), dtype), ((n, 9 * 64), jnp.int8),
                  ((n, 255), jnp.int32)]
        name = "conv_mvu_standard"
    assert _kernels(fn, one_chip, *shapes) == [name] * passes


@pytest.mark.parametrize("kind", ["dense", "conv"])
def test_looped_threshold_epilogue_compiles(one_chip, kind):
    """A 255-row threshold table (8-bit activations) inside the kernel: the
    epilogue loops over aligned 8-row groups of the (T, bn) table."""
    n, k, thr = 128, 512, ((128, 255), jnp.int32)
    if kind == "dense":
        fn = lambda a, w, t: mvu_int_pallas(a, w, t, interpret=False)
        shapes = [((256, k), jnp.int8), ((n, k), jnp.int8), thr]
        name = "mvu_int"
    else:
        fn = lambda x, w, t: conv_mvu_pallas(x, w, t, kernel=3, mode="standard",
                                             block_n=n, interpret=False)
        shapes = [((2, 16, 16, 64), jnp.int8), ((n, 9 * 64), jnp.int8), thr]
        name = "conv_mvu_standard"
    assert _kernels(fn, one_chip, *shapes) == [name]
