"""Standard-SIMD MVU (paper Fig. 4c): arbitrary-precision integer lanes.

TPU adaptation: the per-lane multipliers + adder tree of the FPGA datapath
map onto the MXU systolic array -- an int8 x int8 -> int32 matmul per grid
step.  4-bit operands are carried in int8 (the MXU's native integer width);
the int32 accumulator matches FINN's wide accumulator.  The multi-threshold
unit (or a dequant scale) is fused as the output epilogue.
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
    by_int8_digits,
    compiler_params,
    epilogue_operands,
    epilogue_write,
    fit_block,
    fits_int8,
    pad_to,
    split_refs,
    std_grid,
)


def fit_blocks(n: int, k: int, *, block_m: int, block_n: int, block_k: int,
               m: int | None = None) -> dict[str, int]:
    """The tile an (M, N, K) int8-synapse kernel runs for a requested
    schedule (``mvu_int`` and ``mvu_binary``); ``m=None`` leaves block_m
    uncapped by the batch."""
    return {"block_m": fit_block(block_m, m, SUBLANE),
            "block_n": fit_block(block_n, n, LANE),
            "block_k": fit_block(block_k, k, LANE)}


def _kernel(*refs, has_thresh: bool, has_scale: bool):
    (a_ref, w_ref), t_ref, s_ref, (o_ref, acc_ref) = split_refs(
        refs, 2, has_thresh, has_scale)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        a_ref[...],  # (bm, bk) int8, the SF-th input chunk
        w_ref[...],  # (bn, bk) int8
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(k == pl.num_programs(2) - 1)
    def _done():
        epilogue_write(o_ref, acc_ref[...], t_ref, s_ref)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "interpret"),
)
def mvu_int_pallas(
    a: jax.Array,
    w: jax.Array,
    thresholds: jax.Array | None = None,
    out_scale: jax.Array | None = None,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """out[M,N] = epilogue(A[M,K] . W[N,K]^T); integer datapath.

    a: (M, K) integer activations; int8 (int4 values carried in int8) runs
       one MXU pass, a wider dtype one pass per base-256 digit
    w: (N, K) int8
    thresholds: optional (N, T) int32  -> int32 activations in [0, T]
    out_scale: optional (N,) float32   -> float32 dequantized output
    """
    m, k = a.shape
    n, k2 = w.shape
    assert k == k2, (a.shape, w.shape)
    if not fits_int8(a.dtype):
        return by_int8_digits(
            lambda d: mvu_int_pallas(d, w, block_m=block_m, block_n=block_n,
                                     block_k=block_k, interpret=interpret),
            a, thresholds, out_scale)
    bm, bn, bk = fit_blocks(n, k, m=m, block_m=block_m, block_n=block_n,
                            block_k=block_k).values()

    a_p = pad_to(pad_to(a.astype(jnp.int8), 0, bm), 1, bk)
    w_p = pad_to(pad_to(w.astype(jnp.int8), 0, bn), 1, bk)
    mp, kp = a_p.shape
    np_, _ = w_p.shape
    epi_specs, epi_ops, out_dtype = epilogue_operands(
        thresholds, out_scale, bn, lambda mi, ni, ki: (ni, 0))
    has_thresh, has_scale = thresholds is not None, out_scale is not None

    out = pl.pallas_call(
        functools.partial(_kernel, has_thresh=has_thresh, has_scale=has_scale),
        grid=std_grid(mp, np_, kp, bm, bn, bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((bn, bk), lambda mi, ni, ki: (ni, ki)),
            *epi_specs,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=compiler_params("parallel", "arbitrary", "arbitrary"),
        interpret=interpret,
        name="mvu_int",
    )(a_p, w_p, *epi_ops)
    return out[:m, :n]
