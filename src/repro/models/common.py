"""Shared model primitives: norms, RoPE, glu mlps, initializers.

Params are plain nested dicts of jnp arrays; every init function returns
(params, specs) where specs is a parallel tree of
``jax.sharding.PartitionSpec`` used by the launcher for pjit in_shardings.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

# ---------------------------------------------------------------------------
# sharding helpers
# ---------------------------------------------------------------------------

# mesh axis sizes assumed by `maybe_axis`; the launcher guarantees the
# production mesh has model axis 16.  For smoke tests (1 device) everything
# is replicated anyway because the mesh has a single device.
MODEL_AXIS = "model"
DATA_AXIS = "data"
MODEL_AXIS_SIZE = 16


def maybe_axis(dim_size: int, axis: str = MODEL_AXIS, size: int = MODEL_AXIS_SIZE):
    """Shard a dim over `axis` only if divisible; else replicate."""
    return axis if dim_size % size == 0 else None


def dense_spec(shape: tuple, shard_dim: Optional[int], axis: str = MODEL_AXIS) -> P:
    parts = [None] * len(shape)
    if shard_dim is not None and shape[shard_dim] % MODEL_AXIS_SIZE == 0:
        parts[shard_dim] = axis
    return P(*parts)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def trunc_normal(key, shape, scale: float, dtype) -> jax.Array:
    std = math.sqrt(scale)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std).astype(dtype)


def dense_init(key, d_in: int, d_out: int, dtype, scale: Optional[float] = None):
    scale = scale if scale is not None else 1.0 / d_in
    return trunc_normal(key, (d_in, d_out), scale, dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + weight.astype(jnp.float32))).astype(dtype)


def init_rms_norm(d: int, dtype=jnp.float32):
    # zero-centered weight (gemma-style "1 + w") so init is identity
    return jnp.zeros((d,), dtype), P(None)


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: broadcastable to (..., seq)."""
    head_dim = x.shape[-1]
    freqs = rope_frequencies(head_dim, theta)                       # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs    # (..., seq, hd/2)
    angles = angles[..., :, None, :]                                # (..., seq, 1, hd/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoid_positions(seq: int, d_model: int) -> jax.Array:
    """Whisper-style fixed sinusoidal position embeddings."""
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d_model // 2, dtype=jnp.float32)[None, :]
    inv = jnp.exp(-math.log(10000.0) * dim / max(d_model // 2 - 1, 1))
    ang = pos * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# gated mlp (SwiGLU / GeGLU)
# ---------------------------------------------------------------------------


def init_mlp(key, d_model: int, d_ff: int, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    params = {
        "w_gate": dense_init(k1, d_model, d_ff, dtype),
        "w_up": dense_init(k2, d_model, d_ff, dtype),
        "w_down": dense_init(k3, d_ff, d_model, dtype, scale=1.0 / d_ff),
    }
    specs = {
        "w_gate": dense_spec((d_model, d_ff), 1),
        "w_up": dense_spec((d_model, d_ff), 1),
        "w_down": dense_spec((d_ff, d_model), 0),
    }
    return params, specs


def glu_act(gate: jax.Array, act: str) -> jax.Array:
    if act == "silu":
        return jax.nn.silu(gate)
    if act == "gelu":
        return jax.nn.gelu(gate, approximate=True)
    if act == "gelu_exact":
        return jax.nn.gelu(gate, approximate=False)
    raise ValueError(f"unknown act {act}")


def apply_mlp(params, x: jax.Array, act: str) -> jax.Array:
    gate = glu_act(x @ params["w_gate"], act)
    up = x @ params["w_up"]
    return (gate * up) @ params["w_down"]


# ---------------------------------------------------------------------------
# embedding / unembedding
# ---------------------------------------------------------------------------


def init_embedding(key, vocab: int, d_model: int, dtype):
    params = trunc_normal(key, (vocab, d_model), 1.0 / d_model, dtype)
    spec = dense_spec((vocab, d_model), 0)
    return params, spec


def embed(table: jax.Array, tokens: jax.Array, scale: bool = False) -> jax.Array:
    out = jnp.take(table, tokens, axis=0)
    if scale:
        out = out * jnp.asarray(math.sqrt(table.shape[-1]), out.dtype)
    return out


def unembed(table: jax.Array, x: jax.Array, softcap: Optional[float] = None) -> jax.Array:
    logits = jnp.einsum("...d,vd->...v", x, table).astype(jnp.float32)
    if softcap is not None:
        logits = jnp.tanh(logits / softcap) * softcap
    return logits
