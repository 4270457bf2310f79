"""Shared pieces of the MVU Pallas kernels.

The folded schedule (DESIGN.md §4) is identical for all three SIMD
datapaths; only the inner dot-product step differs:

    grid = (M/bm, N/bn, K/bk)            # (pixel tiles, NF, SF)
    A block (bm, bk) @ index (m, k)      # input buffer, one SF chunk per step
    W block (bn, bk) @ index (n, k)      # weight stream (PE memories)
    acc scratch (bm, bn) int32 in VMEM   # PE accumulators
    T block (T, bn) @ index (0, n)       # threshold table, one row per level
    epilogue at k == SF-1                # thresholds / scale / raw acc

The multi-threshold epilogue broadcasts each (1, bn) threshold row down the
sublanes of the (bm, bn) accumulator tile and sums the hits, so channels
stay on lanes and each vector compare covers a whole (8, 128) tile of
outputs.

PE = bn rows in parallel, SIMD = bk synapses per grid step (x32 for the
bit-packed datapath). II = 1 grid step.

Every block edge goes through :func:`fit_block`, which rounds the FINN
(PE, SIMD) request to the TPU layout: the last dim of a block is a multiple
of 128 lanes and the second-to-last a multiple of 8 sublanes, or the block
spans the whole (8-padded) array dim.  The chip's compiler refuses any other
block, so the rounding happens here, once, for every kernel and every mode
(interpret mode included, so CPU tests run the tiles the chip runs).  Each
kernel module exports the ``fit_blocks`` that maps its schedule arguments
onto those edges; ``core.folding.legal_blocks`` calls it.

The MXU multiplies int8.  int8 (or bool) activations go straight in;
anything wider runs once per signed base-256 digit (:func:`by_int8_digits`),
so every kernel stays exact on any integer input.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
SUBLANE = 8
# Scoped VMEM each kernel may use; the autotuner prunes schedules against
# the same number (``resource_model.VMEM_BYTES``).  v5e has 128 MiB.
VMEM_LIMIT_BYTES = 64 * 2**20


def round_up(x: int, multiple: int) -> int:
    return -(-x // multiple) * multiple


def fit_block(want: int, dim: int | None, align: int) -> int:
    """Chip-legal block edge for an array edge of ``dim`` elements.

    ``want`` (a PE/SIMD-derived tile) rounds up to a multiple of ``align``
    (``LANE`` for a block's last dim, ``SUBLANE`` for the one before); a
    block that would reach past the array instead spans the whole dim,
    padded to ``SUBLANE``.  The array is then padded to a multiple of the
    result.  ``dim=None`` (the batch, unknown at schedule time) caps
    nothing.  Idempotent: ``fit_block(fit_block(w, d, a), d, a)`` is
    ``fit_block(w, d, a)``.
    """
    b = round_up(max(want, 1), align)
    if dim is None:
        return b
    full = round_up(max(dim, 1), SUBLANE)
    return full if b >= full else b


def fits_int8(dtype) -> bool:
    """Whether every value of ``dtype`` is an int8, the MXU's operand."""
    dtype = jnp.dtype(dtype)
    if dtype == jnp.bool_:
        return True
    if not jnp.issubdtype(dtype, jnp.integer):
        return False
    info = jnp.iinfo(dtype)
    return info.min >= -128 and info.max <= 127


def int8_digits(a: jax.Array) -> list[jax.Array]:
    """Signed base-256 digits of an integer array, each int8.

    ``a == sum_p d_p * 256**p``, with as many digits as ``a.dtype``'s range
    needs (2 for uint8, 3 for int16) and at most 4, where the identity holds
    mod 2**32 -- the int32 accumulator's own wrap.  Non-integer inputs are
    first cast to int32, as ``ref.py`` does.
    """
    if not jnp.issubdtype(a.dtype, jnp.integer):
        a = a.astype(jnp.int32)
    info = jnp.iinfo(a.dtype)
    n = 1
    while n < 4 and not (-128 * ((256**n - 1) // 255) <= info.min
                         and info.max <= 127 * ((256**n - 1) // 255)):
        n += 1
    x = a.astype(jnp.int32)
    digits = []
    for _ in range(n):
        d = ((x + 128) & 255) - 128
        digits.append(d.astype(jnp.int8))
        x = (x - d) >> 8
    return digits


def by_int8_digits(raw, a: jax.Array, thresholds, out_scale, offset=None):
    """``epilogue(raw(a))`` for activations wider than int8, exactly.

    ``raw(d)`` is a kernel's int32 accumulator (no epilogue) on int8
    activations ``d``, affine in ``d``: ``raw(d) = L(d) + offset`` with ``L``
    linear and ``offset`` (per output column, None = 0) independent of
    ``d``.  It runs once per digit of :func:`int8_digits`; the sum scaled
    by ``256**p`` is ``raw(a)``.  The epilogue then runs once, in XLA.
    """
    if thresholds is not None and out_scale is not None:
        raise ValueError("thresholds and out_scale are mutually exclusive")
    digits = int8_digits(a)
    acc = sum(raw(d) * (256**p) for p, d in enumerate(digits))
    if offset is not None:
        acc = acc - offset * (sum(256**p for p in range(len(digits))) - 1)
    t = None if thresholds is None else thresholds.astype(jnp.int32).T
    s = None if out_scale is None else out_scale.astype(jnp.float32)
    return epilogue_value(acc, t, s)


def compiler_params(*dimension_semantics: str):
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics,
                                vmem_limit_bytes=VMEM_LIMIT_BYTES)


def swar_popcount(x: jax.Array) -> jax.Array:
    """Branch-free SWAR popcount on uint32 (the LUT-fabric popcount analog)."""
    x = x.astype(jnp.uint32)
    x = x - ((x >> 1) & jnp.uint32(0x55555555))
    x = (x & jnp.uint32(0x33333333)) + ((x >> 2) & jnp.uint32(0x33333333))
    x = (x + (x >> 4)) & jnp.uint32(0x0F0F0F0F)
    return ((x * jnp.uint32(0x01010101)) >> 24).astype(jnp.int32)


def epilogue_value(acc, t_ref, s_ref):
    """MVTU epilogue as a value: thresholds > scale > raw accumulator.

    ``t_ref`` is the (T, bn) threshold table: a kernel's VMEM block, or the
    whole (T, N) array where the epilogue runs in XLA.
    """
    if t_ref is not None:
        return _multi_threshold(acc, t_ref)
    if s_ref is not None:
        return acc.astype(jnp.float32) * s_ref[...].reshape(1, -1)
    return acc


def _multi_threshold(acc, t_ref):
    """act = sum_t (acc >= T[t, :]) -- the multi-threshold unit.

    Threshold rows compare as a (rows, M, bn) value whose row axis leads:
    each row is broadcast down the sublanes of the whole (M, bn) tile, the
    channels stay on lanes, and the sum over rows is plain vector adds.  A
    table taller than one sublane group (8-bit activations have 255 rows)
    loops over 8-row groups, so the kernel's live values, body and compile
    time stay bounded.  One unrolled ``acc >= T[t:t+1, :]`` per row gives
    the chip the same code but compiles far slower on XLA's CPU backend,
    where interpret mode runs the kernels.
    """
    def count(rows):
        return jnp.sum(acc[None] >= rows[:, None, :], axis=0, dtype=jnp.int32)

    n_thresh = t_ref.shape[0]
    if n_thresh <= SUBLANE:
        return count(t_ref[...])
    groups = n_thresh // SUBLANE
    act = jax.lax.fori_loop(
        0, groups,
        lambda g, act: act + count(_row_group(t_ref, g)),
        jnp.zeros(acc.shape, jnp.int32))
    if n_thresh % SUBLANE:
        act = act + count(t_ref[groups * SUBLANE:, :])
    return act


def _row_group(t_ref, g):
    """Rows ``[8g, 8g + 8)`` (traced ``g``) of a kernel's table ref or of
    an XLA table."""
    start = g * SUBLANE
    if isinstance(jax.typeof(t_ref), jax.ref.AbstractRef):
        return t_ref[pl.ds(pl.multiple_of(start, SUBLANE), SUBLANE), :]
    return jax.lax.dynamic_slice_in_dim(t_ref, start, SUBLANE)


def epilogue_write(o_ref, acc, t_ref, s_ref) -> None:
    """Write the MVTU epilogue: thresholds > scale > raw accumulator."""
    o_ref[...] = epilogue_value(acc, t_ref, s_ref)


def epilogue_operands(thresholds, out_scale, block_n: int, n_block):
    """``(in_specs, operands, out_dtype)`` for the MVTU epilogue tables.

    Thresholds (N, T) int32 give int32 activations; they go in transposed,
    a (T, N) table in (T, block_n) blocks, one threshold row per sublane.
    A scale (N,) gives the float32 dequantized output, in (block_n, 1)
    blocks; neither leaves the raw int32 accumulator.  ``n_block`` maps the
    kernel's grid indices to its N-block index.  N pads to ``block_n``
    (pad scale rows are 1, never read back).
    """
    if thresholds is not None and out_scale is not None:
        raise ValueError("thresholds and out_scale are mutually exclusive")
    if thresholds is not None:
        t = pad_to(thresholds.astype(jnp.int32).T, 1, block_n)
        return ([pl.BlockSpec((t.shape[0], block_n),
                              lambda *g: (0, n_block(*g)))], [t], jnp.int32)
    if out_scale is not None:
        s = pad_to(out_scale.reshape(-1, 1).astype(jnp.float32), 0, block_n,
                   value=1)
        return ([pl.BlockSpec((block_n, 1), lambda *g: (n_block(*g), 0))],
                [s], jnp.float32)
    return [], [], jnp.int32


def split_refs(refs, n_in: int, has_thresh: bool, has_scale: bool):
    """``(input refs, t_ref, s_ref, remaining refs)`` of a kernel call."""
    ins, rest = refs[:n_in], refs[n_in:]
    t_ref = rest[0] if has_thresh else None
    s_ref = rest[0] if has_scale else None
    if has_thresh or has_scale:
        rest = rest[1:]
    return ins, t_ref, s_ref, rest


def pad_to(x: jax.Array, axis: int, multiple: int, value=0) -> jax.Array:
    size = x.shape[axis]
    rem = (-size) % multiple
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad, constant_values=value)


def default_interpret() -> bool:
    """Pallas kernels target TPU; everywhere else we validate via interpret."""
    return jax.default_backend() != "tpu"


def std_grid(m: int, n: int, k: int, bm: int, bn: int, bk: int):
    return (pl.cdiv(m, bm), pl.cdiv(n, bn), pl.cdiv(k, bk))
