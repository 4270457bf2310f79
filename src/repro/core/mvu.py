"""The MVU layer: FINN's Matrix-Vector-Threshold Unit as a JAX module.

Two facings:

* :class:`MVULayer` -- the faithful FINN unit. Integer/bit tensors in,
  integer activations out through the fused multi-threshold epilogue.
  This is what the NID example and the paper-sweep benchmarks instantiate.

* :func:`quantized_linear` -- the LM-framework facing: float activations
  are dynamically quantized, pushed through the integer MVU datapath, and
  dequantized.  This is how the paper's engine becomes a first-class
  ``Linear`` backend for the ten assigned architectures (W8A8 / W4A4 /
  binary / xnor projections).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.folding import (
    Folding,
    choose_folding,
    legal_blocks,
    steps_in_words,
    to_tpu_blocks,
)
from repro.core.quantize import QTensor, int_bounds, quantize_weights
from repro.core.resource_model import MVUResources, mvu_resources
from repro.core.thresholds import integerize_thresholds
from repro.kernels import ops, packing


@dataclasses.dataclass(frozen=True)
class KernelBlocks:
    """An explicit Pallas tile schedule for one MVU instance.

    ``to_tpu_blocks`` derives one of these from a (PE, SIMD) folding; the
    autotuner (``repro.core.autotune``) instead searches the legal schedule
    space and pins the winner here.  Hashable so tuned configs stay usable
    as set/dict members like untuned ones.
    """

    block_m: int = 128
    block_n: int = 128
    block_k: int = 128
    block_kw: int = 8  # packed-word K step (xnor datapath only)
    rows_per_tile: int | None = None  # conv line-buffer rows per grid step

    def as_kwargs(self, mode: str, packed: bool = False) -> dict[str, int]:
        """The kwargs the kernel entry points take (uniform plumbing: the
        dense path ignores ``rows_per_tile``, the conv path ignores the K
        blocks -- both accept the full set).  The packed binary datapath
        steps K in 32-bit words like xnor, so it takes ``block_kw``."""
        if steps_in_words(mode, packed):
            out = {"block_m": self.block_m, "block_n": self.block_n,
                   "block_kw": self.block_kw}
        else:
            out = {"block_m": self.block_m, "block_n": self.block_n,
                   "block_k": self.block_k}
        if self.rows_per_tile is not None:
            out["rows_per_tile"] = self.rows_per_tile
        return out

    @classmethod
    def from_blocks(cls, blocks: dict) -> "KernelBlocks":
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: int(v) for k, v in blocks.items()
                      if k in known and v is not None})


@dataclasses.dataclass(frozen=True)
class MVUConfig:
    in_features: int  # K = Kd^2 * I_c
    out_features: int  # N = O_c
    mode: str = "standard"  # xnor | binary | standard
    weight_bits: int = 4
    act_bits: int = 4  # output activation precision when thresholds are used
    folding: Folding | None = None  # None = fully parallel tile defaults
    backend: str = "pallas"
    packed: bool = False  # bit-packed weight storage + packed datapath
    block_m: int = 128
    blocks: KernelBlocks | None = None  # explicit (tuned) schedule wins

    def resolved_folding(self) -> Folding:
        if self.folding is not None:
            # An explicit folding is a schedule claim: PE | N and SIMD | K
            # (FINN's legality condition).  Reject illegal choices here, at
            # config time, instead of letting them silently mis-tile.
            self.folding.validate(self.out_features, self.in_features)
            return self.folding
        return choose_folding(self.out_features, self.in_features)

    def kernel_blocks(self) -> dict[str, int]:
        """The chip-legal tile the kernels run (``folding.legal_blocks``)."""
        n, k = self.out_features, self.in_features
        if self.blocks is not None:
            return legal_blocks(self.blocks.as_kwargs(self.mode, self.packed),
                                n, k, self.mode, packed=self.packed)
        return to_tpu_blocks(self.resolved_folding(), self.mode, self.block_m,
                             n=n, k=k, packed=self.packed)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class MVUParams:
    """Deployed (post-streamlining) parameters of one MVU instance."""

    weights: jax.Array  # xnor: packed (N, Wd) uint32; else (N, K) int8
    thresholds: jax.Array | None  # (N, T) int32, ascending
    out_scale: jax.Array | None  # (N,) float32 dequant scale

    def tree_flatten(self):
        return (self.weights, self.thresholds, self.out_scale), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


class MVULayer:
    def __init__(self, config: MVUConfig):
        self.config = config

    def init_params(self, key: jax.Array) -> MVUParams:
        """Random integer weights on the mode's grid (tests/benchmarks)."""
        cfg = self.config
        n, k = cfg.out_features, cfg.in_features
        if cfg.mode == "xnor":
            bits = jax.random.bernoulli(key, 0.5, (n, k)).astype(jnp.int32)
            w = packing.pack_bits(bits)
        elif cfg.mode == "binary":
            w = jax.random.bernoulli(key, 0.5, (n, k)).astype(jnp.int8)
        else:
            lo, hi = int_bounds(cfg.weight_bits, signed=True)
            w = jax.random.randint(key, (n, k), lo, hi + 1, jnp.int8)
        if cfg.packed:
            from repro.kernels.mvu_packed import pack_mvu_weights

            w = pack_mvu_weights(w, cfg.mode)
        return MVUParams(weights=w, thresholds=None, out_scale=None)

    @staticmethod
    def from_float(
        config: MVUConfig,
        w_float: jax.Array,
        thresholds: jax.Array | None = None,
    ) -> tuple[MVUParams, QTensor]:
        """Quantize trained float weights (N, K) onto the MVU grid."""
        qt = quantize_weights(w_float, 1 if config.mode in ("xnor", "binary") else config.weight_bits)
        if config.mode == "xnor":
            w = packing.pack_bits(packing.bipolar_to_bits(qt.values))
        elif config.mode == "binary":
            w = packing.bipolar_to_bits(qt.values).astype(jnp.int8)
        else:
            w = qt.values
        if config.packed:
            from repro.kernels.mvu_packed import pack_mvu_weights

            w = pack_mvu_weights(w, config.mode)
        t = None if thresholds is None else integerize_thresholds(thresholds)
        scale = None if t is not None else qt.scale.reshape(-1).astype(jnp.float32)
        return MVUParams(weights=w, thresholds=t, out_scale=scale), qt

    def __call__(self, params: MVUParams, x: jax.Array) -> jax.Array:
        """x: (..., K) ints (standard/binary) or (..., Wd) packed (xnor)."""
        cfg = self.config
        w = params.weights
        if cfg.packed and cfg.mode != "xnor" and w.dtype == jnp.int8:
            # packed datapath selected but storage not yet rewritten --
            # the window between the tune step (apply_entry flips the
            # flag) and the pack_weights step (rewrites storage).  Pack on
            # the fly so the graph stays runnable/verifiable throughout.
            from repro.kernels.mvu_packed import pack_mvu_weights

            w = pack_mvu_weights(w, cfg.mode)
        lead = x.shape[:-1]
        xm = x.reshape(-1, x.shape[-1])
        out = ops.mvu(
            xm,
            w,
            cfg.mode,
            k_bits=(cfg.in_features
                    if cfg.mode == "xnor" or cfg.packed else None),
            thresholds=params.thresholds,
            out_scale=params.out_scale,
            backend=cfg.backend,
            packed=cfg.packed,
            **self.config.kernel_blocks(),
        )
        return out.reshape(*lead, cfg.out_features)

    def resources(self, n_pixels: int = 1) -> MVUResources:
        cfg = self.config
        t = 2**cfg.act_bits - 1
        return mvu_resources(
            cfg.out_features,
            cfg.in_features,
            cfg.resolved_folding(),
            mode=cfg.mode,
            weight_bits=cfg.weight_bits,
            act_bits=cfg.act_bits,
            n_pixels=n_pixels,
            block_m=cfg.block_m,
            n_thresh=t,
            blocks=cfg.kernel_blocks(),  # tuned schedules model what they run
            packed=cfg.packed,
        )


def quantized_linear(
    x: jax.Array,
    w_q: QTensor,
    *,
    act_bits: int = 8,
    backend: str = "xla",
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
) -> jax.Array:
    """Float-facing MVU linear: y = x @ W_q^T with dynamic act quantization.

    x: (..., K) float; w_q: symmetric-int QTensor (N, K) with per-channel
    scale.  Activations get one dynamic per-tensor scale (abs-max), the
    integer MVU kernel runs the dot product, and the epilogue dequantizes.
    backend="xla" is the GSPMD-friendly path used by the sharded models;
    backend="pallas" runs the hand-scheduled kernel.
    """
    lead = x.shape[:-1]
    k = x.shape[-1]
    xm = x.reshape(-1, k)
    lo, hi = int_bounds(act_bits, signed=True)
    a_scale = jnp.maximum(jnp.max(jnp.abs(xm)), 1e-6) / hi
    a_int = jnp.clip(jnp.round(xm / a_scale), lo, hi).astype(jnp.int8)

    if w_q.bits == 1:
        w_bits = packing.bipolar_to_bits(w_q.values).astype(jnp.int8)
        out = ops.mvu(
            a_int, w_bits, "binary",
            out_scale=w_q.scale.reshape(-1).astype(jnp.float32),
            backend=backend, block_m=block_m, block_n=block_n, block_k=block_k,
        )
    else:
        out = ops.mvu(
            a_int, w_q.values, "standard",
            out_scale=w_q.scale.reshape(-1).astype(jnp.float32),
            backend=backend, block_m=block_m, block_n=block_n, block_k=block_k,
        )
    y = out * a_scale
    return y.reshape(*lead, -1).astype(x.dtype)
