"""Wire quantization for the butterfly boundary.

The paper quantizes the FP16 reduced feature tensor to 8 bits *only for the
uplink* (Section III-A); compute stays full precision.  We implement the
same: symmetric absmax int8 per token row (per (batch, position), over the
d_r channel axis), an f32 scale vector rides along (its bytes are counted in
the wire-size accounting — see core/profiler.py).

A straight-through estimator makes the codec differentiable so the butterfly
+ codec train end-to-end, which is the paper's key difference from bolting
JPEG onto a frozen model.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def quantize(x: jax.Array, bits: int = 8) -> Tuple[jax.Array, jax.Array]:
    """x: (..., d_r) -> (codes int8/int16, scales f32 (..., 1))."""
    assert bits in (4, 8, 16), bits
    qmax = 2 ** (bits - 1) - 1
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(absmax, 1e-8) / qmax
    codes = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -qmax - 1, qmax)
    dtype = jnp.int8 if bits <= 8 else jnp.int16
    return codes.astype(dtype), scale


def dequantize(codes: jax.Array, scale: jax.Array, dtype=jnp.float32) -> jax.Array:
    return (codes.astype(jnp.float32) * scale).astype(dtype)


from functools import partial


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def fake_quant(x: jax.Array, bits: int = 8) -> jax.Array:
    """Quantize-dequantize with a straight-through gradient."""
    codes, scale = quantize(x, bits)
    return dequantize(codes, scale, x.dtype)


def _fq_fwd(x, bits):
    return fake_quant(x, bits), None


def _fq_bwd(bits, _, g):
    return (g,)


fake_quant.defvjp(_fq_fwd, _fq_bwd)


def pack_int4(codes: jax.Array) -> jax.Array:
    """Pack int8 codes in [-8, 7] two-per-byte along the last axis.

    Layout: byte b holds code 2b in its low nibble and code 2b+1 in its
    high nibble, so a ``(..., d)`` tensor packs to ``(..., d // 2)`` int8
    (``d`` must be even).  The nibbles are two's-complement; sign recovery
    happens in :func:`unpack_int4`."""
    assert codes.shape[-1] % 2 == 0, \
        f"int4 packing needs an even last axis, got {codes.shape}"
    lo = codes[..., ::2] & 0x0F
    hi = codes[..., 1::2] & 0x0F
    return (lo | (hi << 4)).astype(jnp.int8)


def unpack_int4(packed: jax.Array) -> jax.Array:
    """Invert :func:`pack_int4`: ``(..., d // 2)`` int8 -> ``(..., d)`` int8
    codes in [-8, 7].  Sign-extend each nibble via an arithmetic shift of
    the nibble parked in the high bits."""
    lo = (packed.astype(jnp.int8) << 4) >> 4            # low nibble, signed
    hi = packed.astype(jnp.int8) >> 4                   # high nibble, signed
    out = jnp.stack([lo, hi], axis=-1)
    return out.reshape(packed.shape[:-1] + (packed.shape[-1] * 2,))


def scale_dtype_bytes(dtype=jnp.float32) -> int:
    """Wire width of one per-row scale at its real dtype."""
    return jnp.dtype(dtype).itemsize


def wire_bytes(shape: tuple, bits: int, scale_dtype=jnp.float32) -> int:
    """Bytes on the wire for bit-packed codes + per-row scales.

    Codes pack to ``ceil(n * bits / 8)`` bytes (two int4 codes per byte,
    no silent floor-to-zero for sub-byte wires); scales are counted at
    their real dtype width, one per row."""
    import math
    n = math.prod(shape)
    rows = n // shape[-1]
    return (n * bits + 7) // 8 + rows * scale_dtype_bytes(scale_dtype)


@jax.tree_util.register_pytree_node_class
class CodedRows:
    """The edge's wire payload for a model whose layers past the split read
    the token embeddings beside the residual (zamba2's hybrid layers): the
    butterfly's codes and, beside them, the id of the token at each row.

    A pytree, so it passes into and out of jitted programs as it is.  Slices
    and ``shape`` follow the codes' leading (batch, position) axes, and
    ``nbytes`` counts the codes and the ids, so a link charged by ``nbytes``
    carries the ids too."""

    def __init__(self, codes, ids):
        self.codes, self.ids = codes, ids

    def tree_flatten(self):
        return (self.codes, self.ids), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def shape(self):
        return self.codes.shape

    @property
    def nbytes(self) -> int:
        return self.codes.nbytes + self.ids.nbytes

    def __getitem__(self, index):
        """``index`` selects over the leading (batch, position) axes."""
        return CodedRows(self.codes[index], self.ids[index])
