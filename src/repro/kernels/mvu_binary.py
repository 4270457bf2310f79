"""Binary-weight MVU (paper Fig. 4b): {0,1}-coded +/-1 weights, n-bit inputs.

The FPGA datapath selects +x or -x per synapse and feeds an adder tree.  On
TPU we use the algebraic identity

    sum_k x_k * (2 w_k - 1)  =  2 * (x . w01) - sum_k x_k

so the select/add tree becomes one 0/1 int8 MXU matmul plus a per-row input
sum correction -- the MXU *is* the compressor tree (cf. Preusser [36]).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._common import (
    by_int8_digits,
    compiler_params,
    epilogue_operands,
    epilogue_write,
    fits_int8,
    pad_to,
    split_refs,
    std_grid,
)
from repro.kernels.mvu_int import fit_blocks


def _kernel(*refs, has_thresh: bool, has_scale: bool):
    (a_ref, w_ref), t_ref, s_ref, (o_ref, acc_ref) = split_refs(
        refs, 2, has_thresh, has_scale)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a_blk = a_ref[...]  # (bm, bk) int8
    w_blk = w_ref[...]  # (bn, bk) int8 in {0,1}
    dot = jax.lax.dot_general(
        a_blk, w_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.int32
    )
    # per-block correction: 2*(x.w01) - sum(x); zero-padded K chunks add 0.
    rowsum = jnp.sum(a_blk.astype(jnp.int32), axis=1, keepdims=True)
    acc_ref[...] += 2 * dot - rowsum

    @pl.when(k == pl.num_programs(2) - 1)
    def _done():
        epilogue_write(o_ref, acc_ref[...], t_ref, s_ref)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "interpret"),
)
def mvu_binary_pallas(
    a: jax.Array,
    w_bits: jax.Array,
    thresholds: jax.Array | None = None,
    out_scale: jax.Array | None = None,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """out[M,N] = epilogue(A[M,K] . (2*W01[N,K]-1)^T).

    a: (M, K) integer activations (int8 runs one MXU pass, a wider dtype
    one per base-256 digit); w_bits: (N, K) int8 in {0,1}.
    """
    m, k = a.shape
    n, k2 = w_bits.shape
    assert k == k2
    if not fits_int8(a.dtype):
        return by_int8_digits(
            lambda d: mvu_binary_pallas(d, w_bits, block_m=block_m,
                                        block_n=block_n, block_k=block_k,
                                        interpret=interpret),
            a, thresholds, out_scale)
    bm, bn, bk = fit_blocks(n, k, m=m, block_m=block_m, block_n=block_n,
                            block_k=block_k).values()

    a_p = pad_to(pad_to(a.astype(jnp.int8), 0, bm), 1, bk)
    w_p = pad_to(pad_to(w_bits.astype(jnp.int8), 0, bn), 1, bk)
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
        name="mvu_binary",
    )(a_p, w_p, *epi_ops)
    return out[:m, :n]
