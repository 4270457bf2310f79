"""The algorithm's operations and bytes for one kernel call.

Counted from the layer's shapes and bit widths, never from the datapath
that runs it: 2 operations per multiply-accumulate whether the kernel
pops bits, feeds int8 to the MXU or unpacks 2-bit lanes; weights, input
activations and outputs each read or written once, at their bit widths.
A later change of datapath or tile therefore leaves these counts fixed.
"""

from __future__ import annotations

import json
import math
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def _nbytes(elems: int, bits: int) -> int:
    return math.ceil(elems * bits / 8)


def dense(m: int, n: int, k: int, *, w_bits: int, a_bits: int,
          o_bits: int) -> tuple[int, int]:
    """(M, K) x (K, N) for ``m`` samples: ``(ops, bytes)``."""
    ops = 2 * m * n * k
    nbytes = (_nbytes(n * k, w_bits) + _nbytes(m * k, a_bits)
              + _nbytes(m * n, o_bits))
    return ops, nbytes


def conv(batch: int, h: int, w: int, c: int, n: int, kernel: int, *,
         w_bits: int, a_bits: int, o_bits: int) -> tuple[int, int]:
    """Valid ``kernel`` x ``kernel`` stride-1 conv of ``batch`` (H, W, C)
    images into N channels: ``(ops, bytes)``.  The input feature map is
    read once (the line buffer's job), not once per window."""
    oh, ow = h - kernel + 1, w - kernel + 1
    ops = 2 * batch * oh * ow * n * kernel * kernel * c
    nbytes = (_nbytes(n * kernel * kernel * c, w_bits)
              + _nbytes(batch * h * w * c, a_bits)
              + _nbytes(batch * oh * ow * n, o_bits))
    return ops, nbytes


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown device is an error."""
    with open(PEAKS) as f:
        table = json.load(f)
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS}; add the chip's published figures") from None


def least_time(ops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """Seconds the chip needs at best, and which bound sets it."""
    t_ops = ops / peak["int8_ops_per_s"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "bandwidth")
