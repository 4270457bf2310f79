"""Shared model layers: norms, linears (dense | MVU-quantized), rotary
embeddings (RoPE / partial / M-RoPE), activations.

Everything is functional: params are plain dict pytrees, layers are pure
functions.  ``linear`` is the integration point for the paper's technique:
with ``backend="mvu_*"`` the projection runs through the quantized MVU
datapath (fake-quant STE during training, integer kernels at serving).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.mvu import quantized_linear
from repro.core.quantize import QTensor, fake_quant_weights

Params = dict[str, Any]


# ---------------------------------------------------------------- init utils
def _dense_init(key, shape, dtype, scale: float | None = None):
    fan_in = shape[0]
    if scale is None:
        scale = 1.0 / jnp.sqrt(fan_in)
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def linear_init(key, d_in: int, d_out: int, dtype=jnp.bfloat16) -> Params:
    return {"w": _dense_init(key, (d_in, d_out), dtype)}


# ---------------------------------------------------------------- linear
MVU_BACKENDS = {
    "mvu_w8a8": (8, 8),
    "mvu_w4a8": (4, 8),
    "mvu_w4a4": (4, 4),
    "mvu_binary": (1, 8),
}


def linear(p: Params, x: jax.Array, *, backend: str = "dense") -> jax.Array:
    """y = x @ w  (+ quantized datapaths).

    dense:     w stored (d_in, d_out), plain matmul.
    mvu_* fake-quant (training): weights STE-quantized, float matmul.
    mvu_* integer (serving): p holds {"values" (out,in) int8, "scale"} and
    the MVU kernel (xla backend for GSPMD-sharded graphs) runs the dot.
    """
    if "values" in p:  # integer-deployed MVU weights
        w_bits, a_bits = MVU_BACKENDS[backend] if backend in MVU_BACKENDS else (8, 8)
        vals = p["values"]
        if "int4" in str(vals.dtype):  # unpack for the int8-carried datapath
            vals = vals.astype(jnp.int8)
        qt = QTensor(vals, p["scale"], w_bits, True)
        return quantized_linear(x, qt, act_bits=a_bits, backend="xla")
    w = p["w"]
    if backend in MVU_BACKENDS:
        w_bits, _ = MVU_BACKENDS[backend]
        w = fake_quant_weights(w, w_bits, axis=1)
    return x @ w


def quantize_linear_params(p: Params, backend: str) -> Params:
    """dense params -> integer MVU deployment params (out,in int8 + scale)."""
    from repro.core.quantize import quantize_weights

    w_bits, _ = MVU_BACKENDS[backend]
    qt = quantize_weights(p["w"].T.astype(jnp.float32), w_bits, axis=0)
    vals = qt.values.astype(jnp.int4) if w_bits <= 4 else qt.values
    return {"values": vals, "scale": qt.scale.reshape(-1)}


PROJ_NAMES = ("wq", "wk", "wv", "wo", "w_up", "w_gate", "w_down")


def quantize_model_params(params: Params, backend: str) -> Params:
    """Post-training quantization of every projection in a model tree onto
    the MVU integer grid (handles layer-stacked (L, in, out) weights)."""

    def one(node):
        w = node["w"]
        if w.ndim == 2:
            return quantize_linear_params(node, backend)
        flat = w.reshape(-1, *w.shape[-2:])
        outs = [quantize_linear_params({"w": flat[i]}, backend) for i in range(flat.shape[0])]
        vals = jnp.stack([o["values"] for o in outs]).reshape(
            *w.shape[:-2], w.shape[-1], w.shape[-2])
        scales = jnp.stack([o["scale"] for o in outs]).reshape(*w.shape[:-2], w.shape[-1])
        return {"values": vals, "scale": scales}

    def walk(node, name):
        if isinstance(node, dict):
            if name in PROJ_NAMES and set(node) == {"w"} and node["w"].ndim >= 2:
                return one(node)
            return {k: walk(v, k) for k, v in node.items()}
        return node

    return walk(params, "")


# ---------------------------------------------------------------- norms
def rmsnorm_init(d: int, dtype=jnp.bfloat16) -> Params:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * p["scale"].astype(jnp.float32)).astype(dt)


def layernorm_init(d: int, dtype=jnp.bfloat16) -> Params:
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(p: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------- activations
def activation(name: str, gate: jax.Array, up: jax.Array | None = None) -> jax.Array:
    if name == "swiglu":
        assert up is not None
        return jax.nn.silu(gate) * up
    if name == "geglu":
        assert up is not None
        return jax.nn.gelu(gate) * up
    if name == "squared_relu":  # Nemotron-4 (Primer)
        return jnp.square(jax.nn.relu(gate))
    if name == "gelu":
        return jax.nn.gelu(gate)
    raise ValueError(f"unknown activation {name!r}")


def is_gated(name: str) -> bool:
    return name in ("swiglu", "geglu")


# ---------------------------------------------------------------- rotary
def rope_freqs(head_dim: int, theta: float, rot_dim: int | None = None) -> jax.Array:
    rd = rot_dim or head_dim
    return 1.0 / (theta ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd))


def apply_rope(
    x: jax.Array,  # (B, S, H, hd)
    positions: jax.Array,  # (B, S)
    theta: float = 1e4,
    rot_dim: int | None = None,
) -> jax.Array:
    hd = x.shape[-1]
    rd = rot_dim or hd
    freqs = rope_freqs(hd, theta, rd)  # (rd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, rd/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    xr, xp = x[..., :rd], x[..., rd:]
    x1, x2 = xr[..., : rd // 2], xr[..., rd // 2 :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), xp], axis=-1)


def apply_mrope(
    x: jax.Array,  # (B, S, H, hd)
    positions: jax.Array,  # (3, B, S): temporal, height, width ids
    theta: float = 1e6,
    sections: tuple[int, int, int] = (16, 24, 24),  # half-dims per axis
) -> jax.Array:
    """Qwen2-VL multimodal RoPE: the rotary half-dims are split into
    (temporal, height, width) sections, each rotated by its own position id.
    Text tokens carry identical t/h/w ids, which degenerates to 1-D RoPE.
    """
    hd = x.shape[-1]
    half = hd // 2
    assert sum(sections) == half, (sections, half)
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))  # (half,)
    # per-frequency position id chosen by section
    sec_id = jnp.concatenate(
        [jnp.full((s,), i, jnp.int32) for i, s in enumerate(sections)]
    )  # (half,)
    pos = positions.astype(jnp.float32)  # (3, B, S)
    pos_per_freq = jnp.take(pos, sec_id, axis=0)  # (half, B, S) -> gather axis0
    ang = jnp.moveaxis(pos_per_freq, 0, -1) * inv  # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------- embeddings
def embed_init(key, vocab: int, d: int, dtype=jnp.bfloat16) -> Params:
    return {"table": (jax.random.normal(key, (vocab, d)) * 0.02).astype(dtype)}


def embed(p: Params, tokens: jax.Array) -> jax.Array:
    return jnp.take(p["table"], tokens, axis=0)


def unembed(p: Params, x: jax.Array) -> jax.Array:
    return x @ p["table"].T


def seq_shard(x: jax.Array) -> jax.Array:
    """Megatron-style sequence parallelism: shard the residual stream's
    sequence dim over "model".  Cuts the remat-saved activation footprint by
    the TP degree; GSPMD inserts the all-gather before attention/MLP matmuls
    and the reduce-scatter after (see EXPERIMENTS.md section Perf).

    No-op when no mesh context is active, when "model" is absent, or when
    the sequence does not divide evenly (e.g. decode steps).
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or not getattr(mesh, "axis_names", None):
        return x
    if "model" not in mesh.axis_names or x.ndim < 3:
        return x
    size = dict(mesh.shape)["model"]
    if x.shape[1] <= 1 or x.shape[1] % size:
        return x
    from jax.sharding import PartitionSpec as P

    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    spec = P(dp if dp else None, "model", None)
    return jax.lax.with_sharding_constraint(x, spec)
