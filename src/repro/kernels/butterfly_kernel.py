"""Pallas TPU kernel for the butterfly hot path: fused reduction projection +
int8 wire quantization (and the mirror dequant + restoration).

Why fuse: on the edge stage the reduced tensor (T, d_r) would otherwise make
an HBM round trip between the matmul and the quantizer; fusing keeps it in
VMEM, and the only HBM writes are the int8 codes + f32 scales — exactly the
bytes that cross the pod boundary.  Token-tiled: each grid step loads a
(TM, d) x-tile and the full (d, d_r) weight (d_r << d, so the weight tile is
small), runs the MXU matmul at f32 accumulation, then the absmax/scale/round
epilogue in-register.

TM defaults to 256 rows; d and d_r are padded to the 128-lane boundary by
the ops.py wrapper so MXU dims stay hardware-aligned.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _reduce_quant_kernel(x_ref, w_ref, codes_ref, scales_ref, *, qmax: int):
    x = x_ref[...]
    w = w_ref[...]
    r = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)               # (TM, d_r) f32, MXU
    absmax = jnp.max(jnp.abs(r), axis=-1, keepdims=True)
    scale = jnp.maximum(absmax, 1e-8) / qmax
    codes = jnp.clip(jnp.round(r / scale), -qmax - 1, qmax)
    codes_ref[...] = codes.astype(jnp.int8)
    scales_ref[...] = scale


def butterfly_reduce_quant_kernel(x, w_reduce, *, bits: int = 8,
                                  block_t: int = 256,
                                  interpret: bool = False):
    """x: (T, d), w_reduce: (d, d_r); T % block_t == 0, dims 128-aligned."""
    T, d = x.shape
    d_r = w_reduce.shape[1]
    assert T % block_t == 0, (T, block_t)
    qmax = 2 ** (bits - 1) - 1
    grid = (T // block_t,)
    return pl.pallas_call(
        functools.partial(_reduce_quant_kernel, qmax=qmax),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_t, d), lambda i: (i, 0)),
            pl.BlockSpec((d, d_r), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_t, d_r), lambda i: (i, 0)),
            pl.BlockSpec((block_t, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, d_r), jnp.int8),
            jax.ShapeDtypeStruct((T, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x, w_reduce)


def _reduce_quant_bincount_kernel(x_ref, w_ref, codes_ref, scales_ref,
                                  counts_ref, *, qmax: int, nsym: int):
    """Reduce+quant epilogue plus a per-channel symbol histogram, accumulated
    across the token grid into a single fixed-index (nsym, d_r) output — the
    codes never leave VMEM between quantization and counting, so the edge
    gets its entropy estimate for free in the same pass.

    Counting runs one symbol at a time: a (TM, d_r) compare and a column sum
    per symbol.  A (TM, d_r, nsym) one-hot would do the same work but holds
    nsym times the codes in VMEM, which overflows the scoped limit at real
    widths (TM=256, d_r=128)."""
    x = x_ref[...]
    w = w_ref[...]
    r = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)               # (TM, d_r) f32, MXU
    absmax = jnp.max(jnp.abs(r), axis=-1, keepdims=True)
    scale = jnp.maximum(absmax, 1e-8) / qmax
    codes = jnp.clip(jnp.round(r / scale), -qmax - 1, qmax)
    codes_ref[...] = codes.astype(jnp.int8)
    scales_ref[...] = scale

    @pl.when(pl.program_id(0) == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)

    sym = codes.astype(jnp.int32) + (qmax + 1)            # (TM, d_r) in [0, nsym)

    def count(k, carry):
        hits = jnp.sum((sym == k).astype(jnp.int32), axis=0, keepdims=True)
        counts_ref[pl.ds(k, 1), :] += hits                # (1, d_r)
        return carry

    jax.lax.fori_loop(0, nsym, count, 0)


def butterfly_reduce_quant_bincount_kernel(x, w_reduce, *, bits: int = 8,
                                           block_t: int = 256,
                                           interpret: bool = False):
    """x: (T, d), w_reduce: (d, d_r); T % block_t == 0.  Returns
    (codes (T, d_r) int8, scales (T, 1) f32, counts (d_r, 2**bits) int32)."""
    T, d = x.shape
    d_r = w_reduce.shape[1]
    assert T % block_t == 0, (T, block_t)
    qmax = 2 ** (bits - 1) - 1
    nsym = 1 << bits
    grid = (T // block_t,)
    codes, scales, counts = pl.pallas_call(
        functools.partial(_reduce_quant_bincount_kernel, qmax=qmax, nsym=nsym),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_t, d), lambda i: (i, 0)),
            pl.BlockSpec((d, d_r), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_t, d_r), lambda i: (i, 0)),
            pl.BlockSpec((block_t, 1), lambda i: (i, 0)),
            pl.BlockSpec((nsym, d_r), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, d_r), jnp.int8),
            jax.ShapeDtypeStruct((T, 1), jnp.float32),
            jax.ShapeDtypeStruct((nsym, d_r), jnp.int32),
        ],
        interpret=interpret,
    )(x, w_reduce)
    return codes, scales, counts.T


def _dequant_restore_norm_kernel(codes_ref, scales_ref, w_ref, nw_ref,
                                 x_ref, h_ref, *, eps: float):
    """Dequant + restore matmul + the first cloud layer's input RMSNorm in
    one VMEM residency: the restored activation never round-trips HBM
    before the layer consumes its normed copy.  The norm mirrors
    models.common.rms_norm bitwise — including the round-trip through the
    output dtype between restore and norm, so fused == unfused exactly."""
    r = codes_ref[...].astype(jnp.float32) * scales_ref[...]
    w = w_ref[...]
    out = jax.lax.dot_general(
        r, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    x = out.astype(x_ref.dtype)
    x_ref[...] = x
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    normed = xf * jax.lax.rsqrt(var + eps)
    h_ref[...] = (normed * (1.0 + nw_ref[...].astype(jnp.float32))
                  ).astype(h_ref.dtype)


def butterfly_dequant_restore_norm_kernel(codes, scales, w_restore, norm_w, *,
                                          eps: float = 1e-6,
                                          out_dtype=jnp.float32,
                                          block_t: int = 256,
                                          interpret: bool = False):
    """codes: (T, d_r) int8, scales: (T, 1), w_restore: (d_r, d),
    norm_w: (1, d) -> (x (T, d), h (T, d)): the restored activation and its
    RMSNormed copy (the first cloud layer's norm1 input)."""
    T, d_r = codes.shape
    d = w_restore.shape[1]
    assert T % block_t == 0, (T, block_t)
    grid = (T // block_t,)
    return pl.pallas_call(
        functools.partial(_dequant_restore_norm_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_t, d_r), lambda i: (i, 0)),
            pl.BlockSpec((block_t, 1), lambda i: (i, 0)),
            pl.BlockSpec((d_r, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_t, d), lambda i: (i, 0)),
            pl.BlockSpec((block_t, d), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, d), out_dtype),
            jax.ShapeDtypeStruct((T, d), out_dtype),
        ],
        interpret=interpret,
    )(codes, scales, w_restore, norm_w)


def _dequant_restore_kernel(codes_ref, scales_ref, w_ref, out_ref):
    r = codes_ref[...].astype(jnp.float32) * scales_ref[...]
    w = w_ref[...]
    out = jax.lax.dot_general(
        r, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    out_ref[...] = out.astype(out_ref.dtype)


def butterfly_dequant_restore_kernel(codes, scales, w_restore, *,
                                     out_dtype=jnp.float32,
                                     block_t: int = 256,
                                     interpret: bool = False):
    """codes: (T, d_r) int8, scales: (T, 1), w_restore: (d_r, d) -> (T, d)."""
    T, d_r = codes.shape
    d = w_restore.shape[1]
    assert T % block_t == 0, (T, block_t)
    grid = (T // block_t,)
    return pl.pallas_call(
        _dequant_restore_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_t, d_r), lambda i: (i, 0)),
            pl.BlockSpec((block_t, 1), lambda i: (i, 0)),
            pl.BlockSpec((d_r, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_t, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((T, d), out_dtype),
        interpret=interpret,
    )(codes, scales, w_restore)
