"""Fused SWU+MVU conv kernel (FINN Fig. 1 without the im2col matrix).

In FINN the Sliding Window Unit lowers convolution to an interleaved GEMM
*stream*: a line buffer holds the last ``Kd`` input rows and feeds the MVU
one K = Kd^2*C window per output pixel, so the (P, K) im2col matrix never
exists in memory.  ``repro.core.swu.sliding_window`` is the host-side analog
that *does* materialize it -- exactly the (B, OH*OW, Kd^2*C) HBM blow-up the
RTL avoids.

This kernel family restores the line-buffer discipline on TPU: the input
image stays in its natural (B, H, W, C) layout in HBM, and each grid step
accumulates one tile of output rows *inside the kernel*, one (ky, kx) tap
at a time: the tap's (rt*OW, C) activation rows are static slices of the
``Kd`` resident kernel rows (the line buffer), multiplied against the tap's
(C, PE) slice of the weight matrix and summed in int32, before the fused
multi-threshold epilogue.  The weights keep the (ky, kx, c) feature order
of :func:`repro.core.swu.pack_conv_weights`, so the same packed weights
serve both paths.

Grid = (B, row tiles, NF); every step is independent (full-K dot per step),
mirroring one pass of the FINN SWU/MVU pair over ``rt`` output rows:

    A tap    (rt*OW, C) per (ky, kx), sliced from the Kd-row line buffer
    W block  (Kd^2, C, PE=bn) weight stream, one NF row group per step
    epilogue thresholds / scale / raw int32 accumulator (shared MVTU code)

All three weight codings run through the MXU via the usual identities
(cf. ``mvu_binary``/``ops.xnor_mxu``):

    standard  acc = A . W^T                          (int8 x int8 -> int32)
    binary    acc = 2*(A . W01^T) - sum_k A          ({0,1}-coded +/-1 rows)
    xnor      acc = 4*(A01 . W01^T) - 2*sum_k A01
                    - 2*sum_k W01 + K                (1-bit x 1-bit, bipolar)

The xnor identity needs no pad-bit correction: the taps carry exactly K
true synapses, unlike the packed-word datapath.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.swu import out_dim
from repro.kernels._common import (
    LANE,
    SUBLANE,
    by_int8_digits,
    compiler_params,
    epilogue_operands,
    epilogue_value,
    fit_block,
    fits_int8,
    pad_to,
    split_refs,
)

MODES = ("standard", "binary", "xnor")


def conv_rows_per_tile(oh: int, ow: int, block_m: int,
                       rows: int | None = None) -> int:
    """Output rows per grid step: the MXU sees ~block_m pixels per tile.

    ``rows`` (a tuned schedule's request) or ``ceil(block_m / OW)`` rounds
    up so that a tile of rt*OW pixels is a whole number of 8-row sublane
    tiles, the output block the chip accepts; a tile that would cover the
    image takes all OH rows instead.
    """
    q = SUBLANE // math.gcd(ow, SUBLANE)
    rt = rows if rows is not None else -(-block_m // ow)
    rt = -(-max(1, rt) // q) * q
    return oh if rt >= oh else rt


def fit_conv_blocks(oh: int, ow: int, n: int, *, block_m: int, block_n: int,
                    rows_per_tile: int | None = None) -> dict[str, int]:
    """The (rows_per_tile, block_n) tile the conv kernel runs for a
    requested schedule on an OH x OW output with N channels."""
    return {"rows_per_tile": conv_rows_per_tile(oh, ow, block_m,
                                                rows_per_tile),
            "block_n": fit_block(block_n, n, LANE)}


def _padded_rows(h: int, oh: int, rt: int, *, kernel: int, stride: int,
                 pad: int) -> tuple[int, int]:
    """(top, bottom) row padding: OH pads up to whole row tiles."""
    need_h = (-(-oh // rt) * rt - 1) * stride + kernel
    return pad, max(pad, need_h - h - pad)


def conv_vmem_bytes(
    h: int, w: int, c: int, n: int, k: int,
    *,
    kernel: int, stride: int, pad: int,
    block_m: int, block_n: int, n_thresh: int = 0,
    rows_per_tile: int | None = None,
) -> int:
    """VMEM working set of one ``conv_mvu_pallas`` grid step, in bytes.

    Mirrors the kernel's actual residency: the whole padded image (the
    line-buffer source), one tap's (rt*OW, C) activation rows, one PE block
    of the weight matrix, the int32 accumulator and output tiles, and the
    threshold table.  The autotuner prunes candidate schedules against this
    before timing.
    """
    oh = out_dim(h, kernel, stride, pad)
    ow = out_dim(w, kernel, stride, pad)
    rt, bn = fit_conv_blocks(oh, ow, n, block_m=block_m, block_n=block_n,
                             rows_per_tile=rows_per_tile).values()
    top, bottom = _padded_rows(h, oh, rt, kernel=kernel, stride=stride,
                               pad=pad)
    # line-buffer source: int8, or int32 where the taps are strided loads
    image = (h + top + bottom) * (w + 2 * pad) * c * _image_bytes(stride)
    a_tap = rt * ow * c  # int8 rows of one (ky, kx) tap
    w_tile = bn * k  # int8 PE block, full K
    acc_tile = rt * ow * bn * 4
    out_tile = rt * ow * bn * 4
    thr = bn * n_thresh * 4
    return int(image + a_tap + w_tile + acc_tile + out_tile + thr)


def _image_bytes(stride: int) -> int:
    """Bytes per resident image element: the chip's strided loads take
    32-bit data only, so a strided conv keeps its image in int32."""
    return 1 if stride == 1 else 4


def _kernel(*refs, kernel: int, stride: int, ow: int, rt: int, k: int,
            mode: str, has_thresh: bool, has_scale: bool):
    (x_ref, w_ref), t_ref, s_ref, (o_ref,) = split_refs(
        refs, 2, has_thresh, has_scale)
    t = pl.program_id(1)

    # Line-buffer taps: for each (ky, kx) only the rt output rows' kernel
    # rows are touched, each a static slice of the resident image, so no
    # im2col matrix ever exists outside this kernel.
    acc = rowsum = colsum = None
    for ky in range(kernel):
        for kx in range(kernel):
            a = jnp.concatenate(
                [x_ref[0, (t * rt + r) * stride + ky, pl.ds(kx, ow, stride), :]
                 for r in range(rt)], axis=0).astype(jnp.int8)  # (rt*OW, C)
            w_tap = w_ref[ky * kernel + kx]  # (C, bn) int8
            dot = jax.lax.dot_general(
                a, w_tap, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
            acc = dot if acc is None else acc + dot
            if mode != "standard":
                rs = jnp.sum(a.astype(jnp.int32), axis=1, keepdims=True)
                rowsum = rs if rowsum is None else rowsum + rs
            if mode == "xnor":
                cs = jnp.sum(w_tap.astype(jnp.int32), axis=0, keepdims=True)
                colsum = cs if colsum is None else colsum + cs
    if mode == "binary":
        acc = 2 * acc - rowsum
    elif mode == "xnor":  # both operands {0,1}-coded +/-1
        acc = 4 * acc - 2 * rowsum - 2 * colsum + k

    o_ref[...] = epilogue_value(acc, t_ref, s_ref)[None]


@functools.partial(
    jax.jit,
    static_argnames=(
        "kernel", "stride", "pad", "mode", "block_n", "rows_per_tile",
        "block_m", "interpret",
    ),
)
def conv_mvu_pallas(
    x: jax.Array,
    w: jax.Array,
    thresholds: jax.Array | None = None,
    out_scale: jax.Array | None = None,
    *,
    kernel: int,
    stride: int = 1,
    pad: int = 0,
    mode: str = "standard",
    block_n: int = 128,
    block_m: int = 128,
    rows_per_tile: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """out[B, OH*OW, N] = epilogue(SWU(x) . W^T), without materializing SWU(x).

    x: (B, H, W, C) integer activations (standard/binary) or {0,1} bits
       (xnor); int8 runs one MXU pass per tap, a wider dtype one per
       base-256 digit
    w: (N, K = Kd^2*C) int8 packed in (ky, kx, c) order; binary/xnor rows are
       {0,1}-coded +/-1 (``packing.bipolar_to_bits``)
    thresholds: optional (N, T) int32  -> int32 activations in [0, T]
    out_scale: optional (N,) float32   -> float32 dequantized output
    """
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    b, h, wdim, c = x.shape
    n, k = w.shape
    assert k == kernel * kernel * c, (w.shape, kernel, c)
    oh = out_dim(h, kernel, stride, pad)
    ow = out_dim(wdim, kernel, stride, pad)
    if not fits_int8(x.dtype):
        # xnor's raw accumulator is affine in x: 4*A.W - 2*sum(A) plus the
        # per-column K - 2*sum(W), which the digit sum must count once
        offset = (k - 2 * jnp.sum(w.astype(jnp.int32), axis=1)
                  if mode == "xnor" else None)
        out = by_int8_digits(
            lambda d: conv_mvu_pallas(
                d, w, kernel=kernel, stride=stride, pad=pad, mode=mode,
                block_n=block_n, block_m=block_m, rows_per_tile=rows_per_tile,
                interpret=interpret).reshape(b * oh * ow, n),
            x, thresholds, out_scale, offset=offset)
        return out.reshape(b, oh * ow, n)

    # Output-row tiling: rt rows per grid step so the MXU sees M ~ block_m
    # pixels; OH pads up to a whole number of tiles (garbage rows sliced off).
    rt, bn = fit_conv_blocks(oh, ow, n, block_m=block_m, block_n=block_n,
                             rows_per_tile=rows_per_tile).values()
    n_tiles = -(-oh // rt)
    x_p = jnp.pad(
        x.astype(jnp.int8 if _image_bytes(stride) == 1 else jnp.int32),
        ((0, 0), _padded_rows(h, oh, rt, kernel=kernel, stride=stride,
                              pad=pad), (pad, pad), (0, 0)),
    )
    hp, wp = x_p.shape[1], x_p.shape[2]
    # (N, Kd^2*C) -> (Kd^2, C, N): one (C, PE) weight slice per tap
    w_t = pad_to(w.astype(jnp.int8), 0, bn).reshape(-1, kernel * kernel, c)
    w_t = jnp.transpose(w_t, (1, 2, 0))
    np_ = w_t.shape[2]
    epi_specs, epi_ops, out_dtype = epilogue_operands(
        thresholds, out_scale, bn, lambda bi, ti, ni: ni)
    has_thresh, has_scale = thresholds is not None, out_scale is not None

    out = pl.pallas_call(
        functools.partial(
            _kernel, kernel=kernel, stride=stride, ow=ow, rt=rt, k=k,
            mode=mode, has_thresh=has_thresh, has_scale=has_scale,
        ),
        grid=(b, n_tiles, np_ // bn),
        in_specs=[
            pl.BlockSpec((1, hp, wp, c), lambda bi, ti, ni: (bi, 0, 0, 0)),
            pl.BlockSpec((kernel * kernel, c, bn),
                         lambda bi, ti, ni: (0, 0, ni)),
            *epi_specs,
        ],
        out_specs=pl.BlockSpec((1, rt * ow, bn), lambda bi, ti, ni: (bi, ti, ni)),
        out_shape=jax.ShapeDtypeStruct((b, n_tiles * rt * ow, np_), out_dtype),
        compiler_params=compiler_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name=f"conv_mvu_{mode}",
    )(x_p, w_t, *epi_ops)
    return out[:, : oh * ow, :n]
