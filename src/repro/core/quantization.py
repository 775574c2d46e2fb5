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

from dataclasses import dataclass
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


WIRE_MODES = ("raw", "reduced", "int8", "int4", "entropy")


@dataclass(frozen=True)
class Wire:
    """What crosses the butterfly's boundary, in one of five modes:

      "raw"     the boundary rows in the model dtype (prior-work offload)
      "reduced" the reduction unit's rows, unquantized, in the model dtype
      "int8"    the paper: reduction + ``bits``-bit codes + per-row f32 scales
      "int4"    reduction + 4-bit codes packed two per byte + scales
      "entropy" the int8 codes, rANS-coded on the link: numerically int8 (the
                coding is lossless), only the bytes differ

    The one place a mode string is interpreted.  Options, configs and the
    CLI name the mode; each entry point parses it once (:meth:`parse`).
    ``encode`` is the edge's side (reduce, quantize, pack) and ``decode`` the
    cloud's (unpack, dequantize, restore).  With ``kernel`` a quantized wire
    of at most 8 bits runs through the fused Pallas kernels
    (kernels/ops.py), else through the eager codec above."""
    mode: str
    bits: int
    d_r: int

    def __post_init__(self):
        if self.mode not in WIRE_MODES:
            raise ValueError(f"unknown wire mode {self.mode!r}; "
                             f"known: {WIRE_MODES}")
        if self.quantized and self.bits not in (4, 8, 16):
            raise ValueError(f"{self.mode} wire of {self.bits} bits")
        if self.mode == "int4" and (self.bits != 4 or self.d_r % 2):
            raise ValueError(f"int4 wire packs two 4-bit codes per byte: "
                             f"bits {self.bits}, d_r {self.d_r}")

    @classmethod
    def parse(cls, mode: str, wire_bits: int, d_r: int) -> "Wire":
        """The wire of a configured mode: int4 is 4-bit whatever
        ``wire_bits`` says."""
        return cls(mode, 4 if mode == "int4" else wire_bits, d_r)

    @property
    def quantized(self) -> bool:
        return self.mode in ("int8", "int4", "entropy")

    def _kernel(self, kernel: bool) -> bool:
        # the fused codec emits int8 codes, which covers 4-bit ones too
        return kernel and self.bits <= 8

    def encode(self, x, w_reduce, *, kernel: bool = True):
        """Boundary rows ``x`` (..., d) -> (payload, scales (..., 1) f32);
        the unquantized modes send zero scales."""
        if self.mode == "raw":
            return x, jnp.zeros((*x.shape[:-1], 1), jnp.float32)
        if self.mode == "reduced":
            r = x @ w_reduce
            return r, jnp.zeros((*r.shape[:-1], 1), jnp.float32)
        if self._kernel(kernel):
            from repro.kernels import ops
            codes, scales = ops.butterfly_reduce_quant(x, w_reduce,
                                                       bits=self.bits)
        else:
            codes, scales = quantize(x @ w_reduce, self.bits)
        return (pack_int4(codes) if self.mode == "int4" else codes), scales

    def unpack(self, payload):
        """A quantized payload's codes, one per channel."""
        return unpack_int4(payload) if self.mode == "int4" else payload

    def decode(self, payload, scales, w_restore, dtype, *,
               kernel: bool = True):
        """Invert :meth:`encode`: the restored rows (..., d) in ``dtype``
        (the unquantized modes keep the payload's)."""
        if self.mode == "raw":
            return payload
        if self.mode == "reduced":
            return payload @ w_restore
        codes = self.unpack(payload)
        if self._kernel(kernel):
            from repro.kernels import ops
            return ops.butterfly_dequant_restore(codes, scales, w_restore,
                                                 out_dtype=dtype)
        return dequantize(codes, scales, dtype) @ w_restore

    def through(self, x, w_reduce, w_restore, *, kernel: bool = True):
        """``x`` as the far side of the wire sees it, in one graph.  The
        weights are the butterfly's two leaves by name, so
        ``through(x, **params["butterfly"])`` passes them."""
        return self.decode(*self.encode(x, w_reduce, kernel=kernel),
                           w_restore, x.dtype, kernel=kernel)

    def payload_shape_dtype(self, batch: int, seq: int, d_model: int, dtype):
        """ShapeDtypeStructs of :meth:`encode`'s (payload, scales) for
        ``(batch, seq, d_model)`` rows of ``dtype``."""
        return jax.eval_shape(
            lambda x, w: self.encode(x, w, kernel=False),
            jax.ShapeDtypeStruct((batch, seq, d_model), dtype),
            jax.ShapeDtypeStruct((d_model, self.d_r), dtype))

    def nbytes(self, cfg, seq: int, batch: int = 1) -> float:
        """Uplink bytes of ``batch`` x ``seq`` boundary rows of model
        ``cfg``: codes packed to ``bits`` plus per-row f32 scales for the
        quantized modes.  The entropy wire is predicted at the trained
        prior's nominal rate (the runtime charges the actual coded bytes
        when real codes exist) plus the per-payload stream overhead, and
        never worse than int8: the edge ships the raw codes when coding
        would expand them, which is why single decode rows stay int8."""
        act_bytes = 2 if cfg.dtype == "bfloat16" else 4
        rows = batch * seq
        if self.mode == "raw":
            return float(rows * cfg.d_model * act_bytes)
        if self.mode == "reduced":
            return float(rows * self.d_r * act_bytes)
        if self.mode == "entropy":
            from repro.core import wire_codec
            n = rows * self.d_r
            coded = wire_codec.predicted_code_bytes(n) \
                + wire_codec.payload_overhead_bytes(self.d_r)
            return float(min(coded, n) + rows * 4)
        return float(wire_bytes((batch, seq, self.d_r), self.bits))
