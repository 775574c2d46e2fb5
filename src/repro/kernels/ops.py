"""jit'd public wrappers for the Pallas kernels.

On a TPU backend every call compiles through Mosaic.  On the CPU backend the
kernels run in interpret mode (the kernel body executes with jnp semantics),
which is how the tests check them against the ``ref`` oracles.  Any other
backend raises: a measurement must never fall back to the interpreter.
The wrappers pick each kernel's row tile (:func:`decode_row_block`, capped
by a VMEM budget for wide rows) and handle padding and the decode-row fast
path.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.butterfly_kernel import (
    butterfly_dequant_restore_kernel,
    butterfly_dequant_restore_norm_kernel,
    butterfly_reduce_quant_bincount_kernel,
    butterfly_reduce_quant_kernel,
)
from repro.kernels.flash_attention import flash_attention_kernel
from repro.kernels.rmsnorm import rmsnorm_kernel


def interpret_mode() -> bool:
    """True on the CPU backend, False on TPU; any other backend raises."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"Pallas kernels need a TPU (or the CPU interpreter); "
                       f"backend is {backend!r}")


def _pad_to(x, multiple: int, axis: int):
    pad = (-x.shape[axis]) % multiple
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


# Row counts at or below this skip the Pallas grid entirely: a decode step's
# (B, 1, d) residual row would otherwise pad to an 8-row tile and pay the
# pallas_call dispatch for a single MXU-tile of work.  The fast path runs the
# identical math (f32-accumulated dot + absmax quant), so kernel and fast
# path are bitwise-equal in interpret mode.
_FAST_PATH_ROWS = 8


def decode_row_block(n_rows: int = 1, block_t: int = 256) -> int:
    """The kernel block size the wrappers below pick for an ``n_rows``-row
    call — exposed so hot-path callers (the split bank's compile cache) can
    derive it once and fold it into their cache keys instead of re-deriving
    it per call."""
    return min(block_t, max(_FAST_PATH_ROWS, n_rows))


# Mosaic's scoped-VMEM limit on a TPU v5e is 16 MiB per kernel.  A row tile
# holds its double-buffered row blocks plus an f32 working row; keeping
# those under 12 MiB leaves room for the weight block and compiler scratch.
# (At d=4096 the fused restore+norm with two f32 outputs needs 17 MiB at
# 256 rows and is refused; it fits at 128.)
_VMEM_ROW_BUDGET = 12 * 2 ** 20


def _row_block(n_rows: int, block_t: int, row_bytes: int) -> int:
    """:func:`decode_row_block`, capped at the largest power of two of rows
    of ``row_bytes`` VMEM each that fits ``_VMEM_ROW_BUDGET``."""
    fit = max(_FAST_PATH_ROWS, _VMEM_ROW_BUDGET // row_bytes)
    return min(decode_row_block(n_rows, block_t), 1 << (fit.bit_length() - 1))


def _reduce_quant_rows(xf, w_reduce, qmax: int):
    r = jax.lax.dot_general(xf, w_reduce, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    absmax = jnp.max(jnp.abs(r), axis=-1, keepdims=True)
    scale = jnp.maximum(absmax, 1e-8) / qmax
    codes = jnp.clip(jnp.round(r / scale), -qmax - 1, qmax)
    return codes.astype(jnp.int8), scale


@functools.partial(jax.jit, static_argnames=("bits", "block_t"))
def butterfly_reduce_quant(x, w_reduce, *, bits: int = 8,
                           block_t: int = 256) -> Tuple[jax.Array, jax.Array]:
    """x: (..., d) -> (codes (..., d_r) int8, scales (..., 1) f32)."""
    assert bits <= 8, "fused codec emits int8 codes; wider wires go eager"
    shape = x.shape
    d = shape[-1]
    d_r = w_reduce.shape[1]
    xf = x.reshape(-1, d)
    T = xf.shape[0]
    if T <= _FAST_PATH_ROWS:                   # (B, 1, d) decode-row fast path
        codes, scales = _reduce_quant_rows(xf, w_reduce,
                                           2 ** (bits - 1) - 1)
        return (codes.reshape(*shape[:-1], d_r),
                scales.reshape(*shape[:-1], 1))
    block = _row_block(T, block_t, d * (2 * x.dtype.itemsize + 4))
    xf, pad_t = _pad_to(xf, block, 0)
    codes, scales = butterfly_reduce_quant_kernel(
        xf, w_reduce, bits=bits, block_t=block, interpret=interpret_mode())
    if pad_t:
        codes, scales = codes[:T], scales[:T]
    return codes.reshape(*shape[:-1], d_r), scales.reshape(*shape[:-1], 1)


def _channel_bincount(codes, qmax: int, nsym: int):
    sym = codes.astype(jnp.int32) + (qmax + 1)
    ks = jnp.arange(nsym, dtype=jnp.int32)[None, None, :]
    return jnp.sum((sym[:, :, None] == ks).astype(jnp.int32), axis=0)


@functools.partial(jax.jit, static_argnames=("bits", "block_t"))
def butterfly_reduce_quant_bincount(x, w_reduce, *, bits: int = 8,
                                    block_t: int = 256):
    """Fused reduce+quant+entropy-histogram: x (..., d) ->
    (codes (..., d_r) int8, scales (..., 1) f32, counts (d_r, 2**bits) i32).

    ``counts`` is the per-channel symbol histogram of the emitted codes —
    the input ``wire_codec.estimate_coded_bytes`` needs to predict the
    entropy-coded payload size on-device, produced in the same VMEM
    residency as the codes themselves.  Codes/scales are bitwise identical
    to ``butterfly_reduce_quant``."""
    assert bits <= 8, "fused codec emits int8 codes; wider wires go eager"
    shape = x.shape
    d = shape[-1]
    d_r = w_reduce.shape[1]
    qmax = 2 ** (bits - 1) - 1
    nsym = 1 << bits
    xf = x.reshape(-1, d)
    T = xf.shape[0]
    if T <= _FAST_PATH_ROWS:                   # (B, 1, d) decode-row fast path
        codes, scales = _reduce_quant_rows(xf, w_reduce, qmax)
        counts = _channel_bincount(codes, qmax, nsym)
        return (codes.reshape(*shape[:-1], d_r),
                scales.reshape(*shape[:-1], 1), counts)
    block = _row_block(T, block_t, d * (2 * x.dtype.itemsize + 4))
    xf, pad_t = _pad_to(xf, block, 0)
    codes, scales, counts = butterfly_reduce_quant_bincount_kernel(
        xf, w_reduce, bits=bits, block_t=block, interpret=interpret_mode())
    if pad_t:
        codes, scales = codes[:T], scales[:T]
        # pad rows are all-zero -> they quantize to code 0 (symbol qmax+1)
        # in every channel; remove exactly those counts.
        counts = counts.at[:, qmax + 1].add(-pad_t)
    return codes.reshape(*shape[:-1], d_r), scales.reshape(*shape[:-1], 1), counts


@functools.partial(jax.jit, static_argnames=("out_dtype", "block_t"))
def butterfly_dequant_restore(codes, scales, w_restore, *,
                              out_dtype=None, block_t: int = 256):
    """codes (..., d_r) int8, scales (..., 1) -> restored (..., d).
    ``out_dtype`` defaults to the model dtype (``w_restore``'s)."""
    out_dtype = w_restore.dtype if out_dtype is None else out_dtype
    shape = codes.shape
    d_r = shape[-1]
    d = w_restore.shape[1]
    cf = codes.reshape(-1, d_r)
    sf = scales.reshape(-1, 1)
    T = cf.shape[0]
    if T <= _FAST_PATH_ROWS:                   # (B, 1, d_r) decode-row fast path
        r = cf.astype(jnp.float32) * sf
        out = jax.lax.dot_general(r, w_restore, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        return out.astype(out_dtype).reshape(*shape[:-1], d)
    block = _row_block(T, block_t,
                       d * (2 * jnp.dtype(out_dtype).itemsize + 4))
    cf, pad_t = _pad_to(cf, block, 0)
    sf, _ = _pad_to(sf, block, 0)
    out = butterfly_dequant_restore_kernel(
        cf, sf, w_restore, out_dtype=out_dtype, block_t=block,
        interpret=interpret_mode())
    if pad_t:
        out = out[:T]
    return out.reshape(*shape[:-1], d)


@functools.partial(jax.jit, static_argnames=("eps", "out_dtype", "block_t"))
def butterfly_restore_norm(codes, scales, w_restore, norm_w, *,
                           eps: float = 1e-6, out_dtype=None,
                           block_t: int = 256):
    """Fused dequant + restore + first-cloud-layer RMSNorm.

    codes: (..., d_r) int8, scales: (..., 1) -> (x (..., d), h (..., d))
    where ``x`` is the restored boundary activation (the residual-stream
    input) and ``h = rms_norm(x, norm_w)`` (the layer's norm1 output).
    Bitwise equal to butterfly_dequant_restore followed by rms_norm.
    ``out_dtype`` defaults to the model dtype (``w_restore``'s)."""
    out_dtype = w_restore.dtype if out_dtype is None else out_dtype
    shape = codes.shape
    d_r = shape[-1]
    d = w_restore.shape[1]
    cf = codes.reshape(-1, d_r)
    sf = scales.reshape(-1, 1)
    T = cf.shape[0]
    if T <= _FAST_PATH_ROWS:                   # decode-row fast path
        r = cf.astype(jnp.float32) * sf
        out = jax.lax.dot_general(r, w_restore, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        x = out.astype(out_dtype)
        h = ref.rms_norm_ref(x, norm_w, eps)
        return (x.reshape(*shape[:-1], d), h.reshape(*shape[:-1], d))
    block = _row_block(T, block_t,
                       d * (4 * jnp.dtype(out_dtype).itemsize + 4))
    cf, pad_t = _pad_to(cf, block, 0)
    sf, _ = _pad_to(sf, block, 0)
    x, h = butterfly_dequant_restore_norm_kernel(
        cf, sf, w_restore, norm_w.reshape(1, d), eps=eps,
        out_dtype=out_dtype, block_t=block, interpret=interpret_mode())
    if pad_t:
        x, h = x[:T], h[:T]
    return x.reshape(*shape[:-1], d), h.reshape(*shape[:-1], d)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None, block_q: int = 128,
                    block_k: int = 128):
    return flash_attention_kernel(q, k, v, causal=causal, window=window,
                                  block_q=block_q, block_k=block_k,
                                  interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("eps", "block_t"))
def rmsnorm(x, w, *, eps: float = 1e-6, block_t: int = 256):
    """x: (..., d) -> fused RMSNorm (gemma-style 1+w weight)."""
    shape = x.shape
    xf = x.reshape(-1, shape[-1])
    T = xf.shape[0]
    block = decode_row_block(T, block_t)
    xf, pad_t = _pad_to(xf, block, 0)
    out = rmsnorm_kernel(xf, w, eps=eps, block_t=block,
                         interpret=interpret_mode())
    if pad_t:
        out = out[:T]
    return out.reshape(shape)


def rmsnorm_ref(x, w, eps: float = 1e-6):
    from repro.models.common import rms_norm
    return rms_norm(x, w, eps)


# reference aliases (oracles)
butterfly_reduce_quant_ref = ref.butterfly_reduce_quant_ref
butterfly_reduce_quant_bincount_ref = ref.butterfly_reduce_quant_bincount_ref
butterfly_dequant_restore_ref = ref.butterfly_dequant_restore_ref
butterfly_restore_norm_ref = ref.butterfly_restore_norm_ref
flash_attention_ref = ref.flash_attention_ref
