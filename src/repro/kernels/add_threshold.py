"""Residual join with re-quantization: FINN's AddStreams followed by
Thresholding, as one kernel.

    out[m, n] = sum_t (sa * a[m, n] + sb * b[m, n] >= T[t, n])

The two integer streams of a residual block meet here and leave as
activation levels again, so the sum never exists in HBM.  The threshold
table and its epilogue are the MVU kernels' own (``_common``): one (T, bn)
block of rows, each broadcast down the (bm, bn) tile's sublanes.

Grid = (M/bm, N/bn), every step independent.  Each edge is cut into the
fewest equal blocks under its cap, so the pixel and channel counts of a
feature map (3136 x 256, 196 x 1024, ...) need no padding.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels._common import (
    LANE,
    SUBLANE,
    compiler_params,
    epilogue_operands,
    epilogue_write,
    pad_to,
    round_up,
)


def even_block(dim: int, cap: int, align: int) -> int:
    """The whole ``dim`` when it fits ``cap``, else the fewest equal
    ``align``-aligned blocks covering it."""
    if dim <= cap:
        return dim
    return round_up(-(-dim // -(-dim // cap)), align)


def _kernel(a_ref, b_ref, t_ref, o_ref, *, scales: tuple[int, int]):
    sa, sb = scales
    acc = (a_ref[...].astype(jnp.int32) * sa
           + b_ref[...].astype(jnp.int32) * sb)
    epilogue_write(o_ref, acc, t_ref, None)


@functools.partial(
    jax.jit, static_argnames=("scales", "block_m", "block_n", "interpret"))
def add_threshold_pallas(
    a: jax.Array,
    b: jax.Array,
    thresholds: jax.Array,
    *,
    scales: tuple[int, int] = (1, 1),
    block_m: int = 2048,
    block_n: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """out[M, N] = thresholds(sa * a + sb * b), int32 levels in [0, T].

    a, b: (M, N) integer streams; thresholds: (N, T) int32, ascending.
    """
    assert a.shape == b.shape, (a.shape, b.shape)
    m, n = a.shape
    bm = even_block(m, block_m, SUBLANE)
    bn = even_block(n, block_n, LANE)
    a_p = pad_to(pad_to(a, 0, bm), 1, bn)
    b_p = pad_to(pad_to(b, 0, bm), 1, bn)
    mp, np_ = a_p.shape
    epi_specs, epi_ops, _ = epilogue_operands(thresholds, None, bn,
                                              lambda mi, ni: ni)
    tile = pl.BlockSpec((bm, bn), lambda mi, ni: (mi, ni))
    out = pl.pallas_call(
        functools.partial(_kernel, scales=tuple(scales)),
        grid=(mp // bm, np_ // bn),
        in_specs=[tile, tile, *epi_specs],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.int32),
        compiler_params=compiler_params("parallel", "parallel"),
        interpret=interpret,
        name="add_threshold",
    )(a_p, b_p, *epi_ops)
    return out[:m, :n]
