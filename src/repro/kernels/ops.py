"""Public jit'd entry points for the MVU kernels.

``mvu(...)`` dispatches on the SIMD-lane datapath (paper Fig. 4):

    mode="xnor"     1-bit x 1-bit, bit-packed XNOR+popcount   (Fig. 4a)
    mode="binary"   {+-1} weights x n-bit inputs               (Fig. 4b)
    mode="standard" arbitrary-precision integer lanes          (Fig. 4c)

Each mode has two backends:
    backend="pallas"  hand-scheduled kernel (the paper's RTL analog)
    backend="xla"     pure-jnp reference compiled by XLA (the HLS analog)

On non-TPU hosts the Pallas backend runs in interpret mode (CPU validation);
the TPU is the deployment target.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp

from repro.kernels import mvu_packed as packed_kernels
from repro.kernels import packing, ref
from repro.kernels._common import default_interpret
from repro.kernels.add_threshold import add_threshold_pallas
from repro.kernels.mvu_binary import mvu_binary_pallas
from repro.kernels.mvu_int import mvu_int_pallas
from repro.kernels.mvu_xnor import mvu_xnor_pallas
from repro.kernels.swu_mvu import conv_mvu_pallas

MODES = ("xnor", "binary", "standard")
BACKENDS = ("pallas", "xla")


def tpu_kernel_names(compiled_text: str) -> list[str]:
    """Names of the Pallas kernels (``tpu_custom_call`` ops) in the text of
    a program compiled for the TPU, e.g. ``["mvu_int", "mvu_int"]``.  An
    interpret-mode or XLA substitute leaves none."""
    return [m.group(1) for m in re.finditer(
        r"%([A-Za-z_]\w*?)(?:\.\d+)? = [^\n]*custom_call_target=\"tpu_custom_call\"",
        compiled_text)]


def xnor_mxu(
    a_packed: jax.Array,
    w_packed: jax.Array,
    k_bits: int,
    thresholds: jax.Array | None = None,
    out_scale: jax.Array | None = None,
) -> jax.Array:
    """Beyond-paper XNOR variant: unpack to +/-1 int8 and use the MXU.

    On FPGA the bit-serial datapath wins because LUTs are the scarce
    resource; on TPU the MXU's int8 path delivers 394 TOP/s vs the VPU's
    ~4 TOP/s, so paying an 8x unpack blow-up in VMEM traffic can still win
    by >10x on compute.  Benchmarked against the faithful datapath in
    EXPERIMENTS.md section Perf.
    """
    a = packing.bits_to_bipolar(packing.unpack_bits(a_packed, k_bits)).astype(jnp.int8)
    w = packing.bits_to_bipolar(packing.unpack_bits(w_packed, k_bits)).astype(jnp.int8)
    return mvu_int_pallas(a, w, thresholds, out_scale, interpret=default_interpret())


def mvu_layer_fn(mode: str = "standard", *, backend: str = "pallas",
                 int8_input: bool = False, **blocks):
    """Stage callable for the streaming executors: ``fn(params, x) -> y``.

    ``params`` is a dict with ``"w"`` (N, K) plus optionally ``"t"``
    (thresholds) or ``"s"`` (out_scale) — the stackable form used by
    ``repro.core.engine.FusedEngine.as_pipeline`` to run one MVU per
    pipeline stage through ``repro.distributed.pipeline.pipeline_apply``.
    ``int8_input`` narrows ``x`` to int8 first: only where the caller has
    proved every value fits (``dataflow.narrow_inputs``).
    """

    def fn(params, x):
        if int8_input:
            x = x.astype(jnp.int8)
        return mvu(
            x,
            params["w"],
            mode,
            thresholds=params.get("t"),
            out_scale=params.get("s"),
            backend=backend,
            **blocks,
        )

    return fn


def add_threshold(
    a: jax.Array,
    b: jax.Array,
    thresholds: jax.Array,
    *,
    scales: tuple[int, int] = (1, 1),
    interpret: bool | None = None,
) -> jax.Array:
    """Residual join re-quantized: thresholds(sa * a + sb * b) over the
    last axis' channels -> int32 levels of ``a``'s shape.

    a, b: integer streams of one shape (..., N); thresholds: (N, T) int32.
    ``ref.add_threshold_ref`` is its oracle.
    """
    if a.shape != b.shape:
        raise ValueError(f"add_threshold joins streams of one shape, got "
                         f"{a.shape} and {b.shape}")
    if interpret is None:
        interpret = default_interpret()
    n = a.shape[-1]
    out = add_threshold_pallas(a.reshape(-1, n), b.reshape(-1, n), thresholds,
                               scales=tuple(scales), interpret=interpret)
    return out.reshape(a.shape)


def conv_mvu(
    x: jax.Array,
    w: jax.Array,
    *,
    kernel: int,
    stride: int = 1,
    pad: int = 0,
    mode: str = "standard",
    k_bits: int | None = None,
    thresholds: jax.Array | None = None,
    out_scale: jax.Array | None = None,
    backend: str = "pallas",
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    block_kw: int = 8,
    rows_per_tile: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused SWU+MVU convolution: epilogue(SWU(x) . W^T) -> (B, OH*OW, N).

    x: (B, H, W, C) integer activations ({0,1} bits for xnor); w: (N, Kd^2*C)
    in (ky, kx, c) order -- ``standard`` integer rows, ``binary`` {0,1}-coded
    +/-1 rows, ``xnor`` bit-packed (N, Wd) uint32 rows (``k_bits`` = Kd^2*C,
    unpacked on the fly; the fused gather needs the true synapse axis).

    backend="pallas" streams sliding windows through the line-buffer kernel
    (no im2col in HBM); backend="xla" is the materializing reference.
    """
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    if backend not in BACKENDS:
        raise ValueError(f"backend must be one of {BACKENDS}, got {backend!r}")
    if interpret is None:
        interpret = default_interpret()
    if mode == "xnor":
        assert k_bits is not None, "xnor mode requires k_bits"
        w = packing.unpack_bits(w, k_bits).astype(jnp.int8)  # (N, K) {0,1}

    if backend == "xla":
        return ref.conv_mvu_ref(
            x, w, kernel=kernel, stride=stride, pad=pad, mode=mode,
            thresholds=thresholds, out_scale=out_scale,
        )
    del block_k, block_kw  # each conv grid step takes every tap's full C
    return conv_mvu_pallas(
        x, w, thresholds, out_scale,
        kernel=kernel, stride=stride, pad=pad, mode=mode,
        block_m=block_m, block_n=block_n, rows_per_tile=rows_per_tile,
        interpret=interpret,
    )


def mvu(
    a: jax.Array,
    w: jax.Array,
    mode: str = "standard",
    *,
    k_bits: int | None = None,
    thresholds: jax.Array | None = None,
    out_scale: jax.Array | None = None,
    backend: str = "pallas",
    packed: bool = False,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    block_kw: int = 8,
    rows_per_tile: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Matrix-vector(-batch) compute: epilogue(A . W^T).

    Shapes: standard/binary: a (M, K), w (N, K). xnor: packed a (M, Wd)
    uint32, w (N, Wd) uint32 with ``k_bits`` true synapses.

    ``packed=True`` selects the bit-packed datapath (kernels/mvu_packed.py):
    ``w`` is then the mode's packed storage form -- uint32 bitplanes for
    binary, uint8 2-bit lanes for standard, the usual packed words for xnor
    -- and ``k_bits`` carries the true K for every mode.

    ``rows_per_tile`` is accepted for uniform block plumbing with
    :func:`conv_mvu` (tuned schedules pass one kwargs set to either entry
    point); the dense kernels have no row tiling and ignore it, just as the
    conv path ignores ``block_k``/``block_kw``.
    """
    del rows_per_tile
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    if backend not in BACKENDS:
        raise ValueError(f"backend must be one of {BACKENDS}, got {backend!r}")
    if interpret is None:
        interpret = default_interpret()

    if packed:
        assert k_bits is not None, "packed mvu requires k_bits"
        return packed_kernels.mvu_packed(
            a, w, mode, k_bits, thresholds, out_scale,
            backend=backend, block_m=block_m, block_n=block_n,
            block_k=block_k, block_kw=block_kw, interpret=interpret,
        )

    if backend == "xla":
        if mode == "xnor":
            assert k_bits is not None
            return ref.mvu_xnor_ref(a, w, k_bits, thresholds, out_scale)
        if mode == "binary":
            return ref.mvu_binary_ref(a, w, thresholds, out_scale)
        return ref.mvu_int_ref(a, w, thresholds, out_scale)

    if mode == "xnor":
        assert k_bits is not None, "xnor mode requires k_bits"
        return mvu_xnor_pallas(
            a, w, k_bits, thresholds, out_scale,
            block_m=block_m, block_n=block_n, block_kw=block_kw,
            interpret=interpret,
        )
    if mode == "binary":
        return mvu_binary_pallas(
            a, w, thresholds, out_scale,
            block_m=block_m, block_n=block_n, block_k=block_k,
            interpret=interpret,
        )
    return mvu_int_pallas(
        a, w, thresholds, out_scale,
        block_m=block_m, block_n=block_n, block_k=block_k,
        interpret=interpret,
    )
