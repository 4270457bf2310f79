"""Packed-datapath MVU kernel family (paper Fig. 4a economics in storage).

The RTL MVU wins on resources because synapses live bit-packed in the PE
weight memories and the datapath consumes them without ever widening to
canonical operands.  This module is that datapath on TPU: every kernel takes
*packed* weight storage -- uint32 bitplanes for 1-bit codings
(:func:`packing.pack_bits`), 4x 2-bit two's-complement lanes per byte for
2-bit weights (:func:`packing.pack_int2`) -- and computes the exact same
integers as ``kernels/ref.py`` via the pack-domain identities:

    xnor    dot = 2 * popcount(~(a ^ w)) - pad_correction(K)   (Fig. 4a)
    binary  dot = 2 * (x . w01) - rowsum(x)                    (Fig. 4b)
    2-bit   dot = x . sign_extend(w2)                          (Fig. 4c)

Pallas kernels unpack one weight tile at a time inside VMEM, so HBM traffic
and the weight-resident footprint shrink by the packing factor (32x bits,
4x lanes) while the MXU/VPU still sees full-rate operands.  The XLA paths
are the compiled fallbacks the autotuner races against them; the blocked
XNOR popcount path in particular is memory-bandwidth-bound and beats the
unpack-then-matmul reference by a wide margin on large N*K layers.
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
    default_interpret,
    epilogue_operands,
    epilogue_write,
    fit_block,
    fits_int8,
    pad_to,
    split_refs,
    std_grid,
)
from repro.kernels import mvu_xnor, packing, ref
from repro.kernels.packing import INT2_PER_BYTE, WORD_BITS, pad_correction


# --------------------------------------------------------------- xnor / xla
@functools.partial(jax.jit, static_argnames=("k_bits", "block_n"))
def mvu_xnor_popcount_xla(
    a_packed: jax.Array,
    w_packed: jax.Array,
    k_bits: int,
    thresholds: jax.Array | None = None,
    out_scale: jax.Array | None = None,
    *,
    block_n: int | None = None,
) -> jax.Array:
    """Blocked XNOR+popcount entirely in the packed domain (no unpack).

    a_packed: (M, Wd) uint32, w_packed: (N, Wd) uint32.  The (M, bn, Wd)
    xnor intermediate is tiled over N (``block_n`` words of output columns
    per step, default sized so the tile stays ~4 MiB) and reduced with the
    hardware popcount -- the compiled analog of the paper's LUT popcount
    tree, and the memory-bandwidth-bound fast path on large N*K layers.
    """
    if thresholds is not None and out_scale is not None:
        raise ValueError("thresholds and out_scale are mutually exclusive")
    m, wd = a_packed.shape
    n, wd2 = w_packed.shape
    assert wd == wd2
    nb = block_n or max(1, (1 << 22) // max(1, m * max(wd, 1)))
    nb = min(n, nb)
    w_p = pad_to(w_packed, 0, nb)

    def chunk(wc):  # (nb, Wd) -> (M, nb) popcounts
        x = ~(a_packed[:, None, :] ^ wc[None, :, :])
        return jnp.sum(packing.popcount(x), axis=-1, dtype=jnp.int32)

    pcs = jax.lax.map(chunk, w_p.reshape(-1, nb, wd))  # (n/nb, M, nb)
    pc = jnp.moveaxis(pcs, 0, 1).reshape(m, -1)[:, :n]
    dot = 2 * pc - pad_correction(k_bits, wd * WORD_BITS)
    return ref._epilogue(dot, thresholds, out_scale)


# ----------------------------------------------------------- binary / pallas
def _binary_kernel(*refs, has_thresh: bool, has_scale: bool):
    (a_ref, w_ref), t_ref, s_ref, (o_ref, acc_ref) = split_refs(
        refs, 2, has_thresh, has_scale)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a_blk = a_ref[...]  # (bm, bkw*32) int8
    w_blk = w_ref[...]  # (bn, bkw) uint32 bitplanes
    # in-VMEM unpack of one weight tile: (bn, bkw, 32) bits -> (bn, bkw*32)
    shifts = jax.lax.broadcasted_iota(jnp.uint32, (1, 1, WORD_BITS), 2)
    w01 = ((w_blk[:, :, None] >> shifts) & jnp.uint32(1)).astype(jnp.int8)
    w01 = w01.reshape(w_blk.shape[0], -1)
    dot = jax.lax.dot_general(
        a_blk, w01, (((1,), (1,)), ((), ())), preferred_element_type=jnp.int32
    )
    # 2*(x.w01) - sum(x): zero-padded activation columns contribute 0 to both
    # terms, so garbage pad bits in the weight words are harmless.
    rowsum = jnp.sum(a_blk.astype(jnp.int32), axis=1, keepdims=True)
    acc_ref[...] += 2 * dot - rowsum

    @pl.when(k == pl.num_programs(2) - 1)
    def _done():
        epilogue_write(o_ref, acc_ref[...], t_ref, s_ref)


@functools.partial(
    jax.jit,
    static_argnames=("k_bits", "block_m", "block_n", "block_kw", "interpret"),
)
def mvu_binary_packed_pallas(
    a: jax.Array,
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
    """out[M,N] = epilogue(A[M,K] . (2*W01[N,K]-1)^T) from bitplane weights.

    a: (M, K) integer activations (int8 runs one MXU pass, a wider dtype
    one per base-256 digit); w_packed: (N, ceil(K/32)) uint32 bitplanes
    of the {0,1} weight coding (:func:`packing.pack_bits`).  The weight
    tile is unpacked inside the kernel, so the HBM-resident weights stay
    32x smaller than the canonical int8 form.  Tiles as ``mvu_xnor``.
    """
    m, k = a.shape
    n, wd = w_packed.shape
    assert k == k_bits and wd * WORD_BITS >= k
    if not fits_int8(a.dtype):
        return by_int8_digits(
            lambda d: mvu_binary_packed_pallas(
                d, w_packed, k_bits, block_m=block_m, block_n=block_n,
                block_kw=block_kw, interpret=interpret),
            a, thresholds, out_scale)
    bm, bn, bkw = mvu_xnor.fit_blocks(
        n, wd * WORD_BITS, m=m, block_m=block_m, block_n=block_n,
        block_kw=block_kw).values()

    w_p = pad_to(pad_to(w_packed, 0, bn), 1, bkw)
    np_, wdp = w_p.shape
    # activations padded out to the full unpacked span of the padded words
    a_p = pad_to(pad_to(a.astype(jnp.int8), 0, bm), 1, wdp * WORD_BITS)
    mp, _ = a_p.shape
    epi_specs, epi_ops, out_dtype = epilogue_operands(
        thresholds, out_scale, bn, lambda mi, ni, ki: ni)
    has_thresh, has_scale = thresholds is not None, out_scale is not None

    out = pl.pallas_call(
        functools.partial(
            _binary_kernel, has_thresh=has_thresh, has_scale=has_scale),
        grid=std_grid(mp, np_, wdp, bm, bn, bkw),
        in_specs=[
            pl.BlockSpec((bm, bkw * WORD_BITS), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((bn, bkw), lambda mi, ni, ki: (ni, ki)),
            *epi_specs,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=compiler_params("parallel", "arbitrary", "arbitrary"),
        interpret=interpret,
        name="mvu_binary_packed",
    )(a_p, w_p, *epi_ops)
    return out[:m, :n]


@functools.partial(jax.jit, static_argnames=("k_bits",))
def mvu_binary_packed_xla(
    a: jax.Array,
    w_packed: jax.Array,
    k_bits: int,
    thresholds: jax.Array | None = None,
    out_scale: jax.Array | None = None,
) -> jax.Array:
    """Compiled fallback: unpack the bitplanes once, then the Fig. 4b oracle."""
    w_bits = packing.unpack_bits(w_packed, k_bits)
    return ref.mvu_binary_ref(a, w_bits, thresholds, out_scale)


# ------------------------------------------------------------- 2-bit / pallas
def fit_int2_blocks(n: int, k: int, *, block_m: int, block_n: int,
                    block_k: int, m: int | None = None) -> dict[str, int]:
    """The tile the 2-bit-lane kernel runs for a requested schedule over
    ``k`` synapses: ``block_k`` counts synapse lanes and rounds up to whole
    legal byte tiles (4 lanes per byte); ``m=None`` leaves block_m
    uncapped by the batch."""
    bkb = fit_block(-(-block_k // INT2_PER_BYTE), packing.num_int2_bytes(k),
                    LANE)
    return {"block_m": fit_block(block_m, m, SUBLANE),
            "block_n": fit_block(block_n, n, LANE),
            "block_k": bkb * INT2_PER_BYTE}


def _int2_kernel(*refs, has_thresh: bool, has_scale: bool):
    (a_ref, w_ref), t_ref, s_ref, (o_ref, acc_ref) = split_refs(
        refs, 2, has_thresh, has_scale)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # in-VMEM sign-extending unpack: (bn, bkb, 4) fields -> (bn, bkb*4),
    # widened to int32 first (the chip has no 3-D int8 layout for it)
    w_blk = w_ref[...].astype(jnp.int32)  # (bn, bkb) 2-bit lanes
    shifts = 2 * jax.lax.broadcasted_iota(jnp.int32, (1, 1, INT2_PER_BYTE), 2)
    fields = (w_blk[:, :, None] >> shifts) & 0x3
    w2 = jnp.where(fields >= 2, fields - 4, fields)
    w2 = w2.reshape(w_blk.shape[0], -1).astype(jnp.int8)
    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], w2, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(k == pl.num_programs(2) - 1)
    def _done():
        epilogue_write(o_ref, acc_ref[...], t_ref, s_ref)


@functools.partial(
    jax.jit,
    static_argnames=("k_bits", "block_m", "block_n", "block_k", "interpret"),
)
def mvu_int2_packed_pallas(
    a: jax.Array,
    w_packed: jax.Array,
    k_bits: int,
    thresholds: jax.Array | None = None,
    out_scale: jax.Array | None = None,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """out[M,N] = epilogue(A[M,K] . W2[N,K]^T) from 2-bit lane weights.

    a: (M, K) integer activations (int8 runs one MXU pass, a wider dtype
    one per base-256 digit); w_packed: (N, ceil(K/4)) uint8 holding four
    signed 2-bit two's-complement lanes per byte (:func:`packing.pack_int2`).
    Tiles by :func:`fit_int2_blocks`; padded lanes decode to weight 0 and
    contribute nothing.
    """
    m, k = a.shape
    n, bd = w_packed.shape
    assert k == k_bits and bd * INT2_PER_BYTE >= k
    if not fits_int8(a.dtype):
        return by_int8_digits(
            lambda d: mvu_int2_packed_pallas(
                d, w_packed, k_bits, block_m=block_m, block_n=block_n,
                block_k=block_k, interpret=interpret),
            a, thresholds, out_scale)
    bm, bn, bk = fit_int2_blocks(
        n, bd * INT2_PER_BYTE, m=m, block_m=block_m, block_n=block_n,
        block_k=block_k).values()
    bkb = bk // INT2_PER_BYTE

    w_p = pad_to(pad_to(w_packed, 0, bn), 1, bkb)
    np_, bdp = w_p.shape
    a_p = pad_to(pad_to(a.astype(jnp.int8), 0, bm), 1, bdp * INT2_PER_BYTE)
    mp, _ = a_p.shape
    epi_specs, epi_ops, out_dtype = epilogue_operands(
        thresholds, out_scale, bn, lambda mi, ni, ki: ni)
    has_thresh, has_scale = thresholds is not None, out_scale is not None

    out = pl.pallas_call(
        functools.partial(
            _int2_kernel, has_thresh=has_thresh, has_scale=has_scale),
        grid=std_grid(mp, np_, bdp, bm, bn, bkb),
        in_specs=[
            pl.BlockSpec((bm, bkb * INT2_PER_BYTE),
                         lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((bn, bkb), lambda mi, ni, ki: (ni, ki)),
            *epi_specs,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=compiler_params("parallel", "arbitrary", "arbitrary"),
        interpret=interpret,
        name="mvu_int2_packed",
    )(a_p, w_p, *epi_ops)
    return out[:m, :n]


@functools.partial(jax.jit, static_argnames=("k_bits",))
def mvu_int2_packed_xla(
    a: jax.Array,
    w_packed: jax.Array,
    k_bits: int,
    thresholds: jax.Array | None = None,
    out_scale: jax.Array | None = None,
) -> jax.Array:
    """Compiled fallback: sign-extend the 2-bit lanes, then the int oracle."""
    w = packing.unpack_int2(w_packed, k_bits)
    return ref.mvu_int_ref(a, w, thresholds, out_scale)


def pack_mvu_weights(w: jax.Array, mode: str) -> jax.Array:
    """Canonical (N, K) weights -> the mode's packed storage form.

    xnor weights arrive already bit-packed (the pack is a no-op); binary
    {0,1} rows become uint32 bitplanes; standard rows (must fit signed
    2-bit, i.e. values in [-2, 1]) become uint8 2-bit lanes.
    """
    if mode == "xnor":
        return w
    if mode == "binary":
        return packing.pack_bits(w.astype(jnp.int32))
    lo, hi = int(jnp.min(w)), int(jnp.max(w))
    if lo < -2 or hi > 1:
        raise ValueError(
            f"standard-mode packing needs signed 2-bit weights in [-2, 1]; "
            f"got range [{lo}, {hi}]")
    return packing.pack_int2(w.astype(jnp.int32))


def packed_weight_bytes(n: int, k: int, mode: str, weight_bits: int) -> int:
    """HBM-resident bytes of the packed (N, K) weight matrix for ``mode``."""
    if mode in ("xnor", "binary"):
        return n * packing.num_words(k) * 4
    del weight_bits  # standard packing is the 2-bit lane format
    return n * packing.num_int2_bytes(k)


def mvu_packed(
    a: jax.Array,
    w_packed: jax.Array,
    mode: str,
    k_bits: int,
    thresholds: jax.Array | None = None,
    out_scale: jax.Array | None = None,
    *,
    backend: str = "pallas",
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    block_kw: int = 8,
    interpret: bool | None = None,
) -> jax.Array:
    """Dispatch over the packed kernel family (mirror of ``ops.mvu``)."""
    if interpret is None:
        interpret = default_interpret()
    if mode == "xnor":
        if backend == "xla":
            return mvu_xnor_popcount_xla(
                a, w_packed, k_bits, thresholds, out_scale)
        from repro.kernels.mvu_xnor import mvu_xnor_pallas

        # the Fig. 4a Pallas kernel is natively packed -- same datapath
        return mvu_xnor_pallas(
            a, w_packed, k_bits, thresholds, out_scale,
            block_m=block_m, block_n=block_n, block_kw=block_kw,
            interpret=interpret,
        )
    if mode == "binary":
        if backend == "xla":
            return mvu_binary_packed_xla(a, w_packed, k_bits, thresholds, out_scale)
        return mvu_binary_packed_pallas(
            a, w_packed, k_bits, thresholds, out_scale,
            block_m=block_m, block_n=block_n, block_kw=block_kw,
            interpret=interpret,
        )
    if backend == "xla":
        return mvu_int2_packed_xla(a, w_packed, k_bits, thresholds, out_scale)
    return mvu_int2_packed_pallas(
        a, w_packed, k_bits, thresholds, out_scale,
        block_m=block_m, block_n=block_n, block_k=block_k,
        interpret=interpret,
    )
