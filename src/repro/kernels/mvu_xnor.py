"""XNOR-popcount MVU (paper Fig. 4a): 1-bit weights x 1-bit activations.

Faithful TPU port of the bit-serial FPGA datapath: 32 synapses are packed
per uint32 "wire bundle" and each grid step computes, on the VPU,

    acc[m, n] += sum_w popcount(~(a[m, w] ^ w[n, w]))

with the bipolar dot product recovered in the epilogue as

    dot = 2*acc - Kp - n_pad      (Kp = padded bits, n_pad = Kp - K)

since every zero pad bit in *both* operands contributes one spurious
popcount.  SIMD = 32 * block_kw synapses per step.

A beyond-paper MXU alternative (unpack to +/-1 int8 and matmul) lives in
ops.py as ``xnor_mxu`` -- benchmarked against this one in EXPERIMENTS.md.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._common import (
    LANE,
    SUBLANE,
    compiler_params,
    epilogue_operands,
    epilogue_write,
    fit_block,
    pad_to,
    split_refs,
    std_grid,
    swar_popcount,
)
from repro.kernels.packing import WORD_BITS, num_words, pad_correction


def fit_blocks(n: int, k: int, *, block_m: int, block_n: int, block_kw: int,
               m: int | None = None) -> dict[str, int]:
    """The tile a kernel stepping K in 32-bit words runs for a requested
    schedule over ``k`` synapses (``mvu_xnor`` and the packed binary
    kernel); ``m=None`` leaves block_m uncapped by the batch."""
    return {"block_m": fit_block(block_m, m, SUBLANE),
            "block_n": fit_block(block_n, n, LANE),
            "block_kw": fit_block(block_kw, num_words(k), LANE)}


def _kernel(*refs, correction: int, has_thresh: bool, has_scale: bool):
    (a_ref, w_ref), t_ref, s_ref, (o_ref, acc_ref) = split_refs(
        refs, 2, has_thresh, has_scale)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a_blk = a_ref[...]  # (bm, bkw) uint32
    w_blk = w_ref[...]  # (bn, bkw) uint32
    # (bm, bn, bkw) xnor + popcount, reduced over the word axis on the VPU.
    xnor = ~(a_blk[:, None, :] ^ w_blk[None, :, :])
    acc_ref[...] += jnp.sum(swar_popcount(xnor), axis=-1, dtype=jnp.int32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _done():
        # bipolar dot over the true K bits (packing.pad_correction)
        dot = 2 * acc_ref[...] - correction
        epilogue_write(o_ref, dot, t_ref, s_ref)


@functools.partial(
    jax.jit,
    static_argnames=("k_bits", "block_m", "block_n", "block_kw", "interpret"),
)
def mvu_xnor_pallas(
    a_packed: jax.Array,
    w_packed: jax.Array,
    k_bits: int,
    thresholds: jax.Array | None = None,
    out_scale: jax.Array | None = None,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_kw: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """Bipolar out[M,N] from packed a (M, Wd) uint32 and w (N, Wd) uint32."""
    m, wd = a_packed.shape
    n, wd2 = w_packed.shape
    assert wd == wd2
    bm, bn, bkw = fit_blocks(n, wd * WORD_BITS, m=m, block_m=block_m,
                             block_n=block_n, block_kw=block_kw).values()

    a_p = pad_to(pad_to(a_packed, 0, bm), 1, bkw)
    w_p = pad_to(pad_to(w_packed, 0, bn), 1, bkw)
    mp, wdp = a_p.shape
    np_, _ = w_p.shape
    epi_specs, epi_ops, out_dtype = epilogue_operands(
        thresholds, out_scale, bn, lambda mi, ni, ki: ni)
    has_thresh, has_scale = thresholds is not None, out_scale is not None

    out = pl.pallas_call(
        functools.partial(
            _kernel,
            correction=pad_correction(k_bits, wdp * WORD_BITS),
            has_thresh=has_thresh,
            has_scale=has_scale,
        ),
        grid=std_grid(mp, np_, wdp, bm, bn, bkw),
        in_specs=[
            pl.BlockSpec((bm, bkw), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((bn, bkw), lambda mi, ni, ki: (ni, ki)),
            *epi_specs,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=compiler_params("parallel", "arbitrary", "arbitrary"),
        interpret=interpret,
        name="mvu_xnor",
    )(a_p, w_p, *epi_ops)
    return out[:m, :n]
