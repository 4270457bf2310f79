"""Pure-jnp oracles for every MVU kernel (the "golden model").

All reference paths are written in the mathematically transparent form
(unpack -> integer matmul -> epilogue) so the Pallas kernels can be checked
for *exact* integer equality.

Shapes follow the paper's GEMM view (Fig. 1):
  activations A: (M, K)   -- M output pixels, K = Kd^2 * I_c synapses
  weights     W: (N, K)   -- N = O_c output channels (one row per neuron)
  output        : (M, N)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.thresholds import apply_thresholds
from repro.kernels import packing


def _epilogue(
    acc: jax.Array,
    thresholds: jax.Array | None,
    out_scale: jax.Array | None,
) -> jax.Array:
    if thresholds is not None:
        return apply_thresholds(acc, thresholds)
    if out_scale is not None:
        return acc.astype(jnp.float32) * out_scale
    return acc


def mvu_xnor_ref(
    a_packed: jax.Array,
    w_packed: jax.Array,
    k_bits: int,
    thresholds: jax.Array | None = None,
    out_scale: jax.Array | None = None,
) -> jax.Array:
    """XNOR-popcount MVU oracle on *packed* operands.

    a_packed: (M, Wd) uint32, w_packed: (N, Wd) uint32; both packed with
    :func:`packing.pack_bits` (zero pad bits).  Implements the bipolar dot
    product over the true K = ``k_bits`` synapses.
    """
    m, wd = a_packed.shape
    a_bits = packing.unpack_bits(a_packed, k_bits)  # (M, K) {0,1}
    w_bits = packing.unpack_bits(w_packed, k_bits)  # (N, K)
    a = packing.bits_to_bipolar(a_bits)
    w = packing.bits_to_bipolar(w_bits)
    acc = jax.lax.dot_general(
        a, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.int32
    )
    return _epilogue(acc, thresholds, out_scale)


def mvu_binary_ref(
    a: jax.Array,
    w_bits: jax.Array,
    thresholds: jax.Array | None = None,
    out_scale: jax.Array | None = None,
) -> jax.Array:
    """Binary-weight MVU oracle: a (M, K) int, w_bits (N, K) in {0,1} ~ {-1,+1}."""
    w = packing.bits_to_bipolar(w_bits.astype(jnp.int32))
    acc = jax.lax.dot_general(
        a.astype(jnp.int32), w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    return _epilogue(acc, thresholds, out_scale)


def conv_mvu_ref(
    x: jax.Array,
    w: jax.Array,
    *,
    kernel: int,
    stride: int = 1,
    pad: int = 0,
    mode: str = "standard",
    thresholds: jax.Array | None = None,
    out_scale: jax.Array | None = None,
) -> jax.Array:
    """Fused-conv oracle: materialized SWU + the mode's MVU reference.

    x: (B, H, W, C) ints ({0,1} bits for binary/xnor weights' activations as
    appropriate); w: (N, Kd^2*C) in (ky, kx, c) order.  This is the "HLS"
    path -- it pays the im2col blow-up the Pallas kernel avoids.
    """
    from repro.core import swu as swu_mod

    b = x.shape[0]
    cols = swu_mod.sliding_window(x, kernel, stride, pad)  # (B, P, K)
    a = cols.reshape(-1, cols.shape[-1])
    if mode == "xnor":
        acc = jax.lax.dot_general(
            packing.bits_to_bipolar(a.astype(jnp.int32)),
            packing.bits_to_bipolar(w.astype(jnp.int32)),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.int32,
        )
        out = _epilogue(acc, thresholds, out_scale)
    elif mode == "binary":
        out = mvu_binary_ref(a, w, thresholds, out_scale)
    else:
        out = mvu_int_ref(a, w, thresholds, out_scale)
    return out.reshape(b, cols.shape[1], -1)


def mvu_int_ref(
    a: jax.Array,
    w: jax.Array,
    thresholds: jax.Array | None = None,
    out_scale: jax.Array | None = None,
) -> jax.Array:
    """Standard (arbitrary-precision) MVU oracle: int x int -> int32 matmul."""
    acc = jax.lax.dot_general(
        a.astype(jnp.int32), w.astype(jnp.int32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    return _epilogue(acc, thresholds, out_scale)


def add_threshold_ref(
    a: jax.Array,
    b: jax.Array,
    thresholds: jax.Array,
    scales: tuple[int, int] = (1, 1),
) -> jax.Array:
    """Residual-join oracle: thresholds(sa * a + sb * b), (..., N) int32."""
    sa, sb = scales
    acc = a.astype(jnp.int32) * sa + b.astype(jnp.int32) * sb
    return apply_thresholds(acc, thresholds)
