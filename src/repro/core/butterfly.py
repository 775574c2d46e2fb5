"""The butterfly unit (the paper's Section II-A), adapted per DESIGN.md:

  reduction unit  : learned projection  d -> d_r   (edge side)
  wire            : int8 symmetric quantization (+ f32 scales)
  restoration unit: learned projection  d_r -> d   (cloud side)

For the transformer architectures ``d`` is d_model and the unit acts on the
residual stream at a layer boundary; a 1x1 conv over NHWC (the paper's
ResNet form, models/resnet.py) is exactly the same per-position linear map.

The unit is trained end-to-end inside the host model (``fake_quant`` is a
straight-through estimator), and at serving time the reduce+quantize half
runs on the edge stage while dequantize+restore runs on the cloud stage
(``quantization.Wire``), with only (codes, scales) crossing the boundary.
"""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ButterflyConfig
from repro.core.quantization import Wire, fake_quant, wire_bytes
from repro.models.common import dense_init


def init_butterfly(key, d: int, bf: ButterflyConfig, dtype):
    k1, k2 = jax.random.split(key)
    params = {
        "w_reduce": dense_init(k1, d, bf.d_r, dtype),
        "w_restore": dense_init(k2, bf.d_r, d, dtype, scale=1.0 / bf.d_r),
    }
    specs = {"w_reduce": P(None, None), "w_restore": P(None, None)}
    return params, specs


def apply_butterfly(params, x: jax.Array, *, wire_bits: int = 8,
                    train: bool = True, use_kernel: bool = False) -> jax.Array:
    """In-graph form (training / single-mesh inference): the wire is a
    fake-quant so gradients flow straight through (paper: trained
    end-to-end).  With ``train=False`` the quantized wire runs through
    :class:`Wire`, with ``use_kernel`` on the fused Pallas reduce+quant /
    dequant+restore kernels (the serving hot path)."""
    if not train:
        wire = Wire("int8", wire_bits, params["w_reduce"].shape[1])
        return wire.through(x, **params, kernel=use_kernel)
    r = fake_quant(x @ params["w_reduce"], wire_bits)
    return r @ params["w_restore"]


def butterfly_wire_bytes(batch: int, seq: int, d_r: int, wire_bits: int = 8) -> int:
    return wire_bytes((batch, seq, d_r), wire_bits)


def compression_ratio(d: int, d_r: int, act_bits: int, wire_bits: int = 8) -> float:
    """Feature-volume compression vs. shipping the raw boundary tensor."""
    return (d * act_bits) / (d_r * wire_bits)
