"""Property-based tests (hypothesis) for the int8 wire codec — the invariants
the paper's wire format relies on — and the wire format (``Wire``) pinned
to the codec's formulas and byte counts."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property suites need hypothesis "
                    "(pip install -r requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.core.quantization import (WIRE_MODES, Wire, dequantize, fake_quant,
                                     pack_int4, quantize, unpack_int4,
                                     wire_bytes)
from repro.kernels import ops

arrays = st.integers(1, 7).flatmap(
    lambda rows: st.integers(2, 33).flatmap(
        lambda cols: st.lists(
            st.floats(-1e4, 1e4, allow_nan=False, width=32),
            min_size=rows * cols, max_size=rows * cols,
        ).map(lambda v: np.asarray(v, np.float32).reshape(rows, cols))))


@settings(max_examples=40, deadline=None)
@given(arrays, st.sampled_from([4, 8, 16]))
def test_roundtrip_error_bounded(x, bits):
    codes, scale = quantize(jnp.asarray(x), bits)
    back = np.asarray(dequantize(codes, scale))
    # absolute error per row bounded by scale/2 (+eps for f32 rounding)
    err = np.abs(back - x)
    bound = np.asarray(scale) * 0.5 + 1e-5 * np.abs(x) + 1e-6
    assert np.all(err <= bound + 1e-6)


@settings(max_examples=40, deadline=None)
@given(arrays)
def test_codes_range_int8(x):
    codes, _ = quantize(jnp.asarray(x), 8)
    c = np.asarray(codes, np.int32)
    assert c.min() >= -128 and c.max() <= 127
    assert codes.dtype == jnp.int8


@settings(max_examples=25, deadline=None)
@given(arrays)
def test_scale_invariance(x):
    """quantize(a*x) has codes equal to quantize(x) for power-of-two a
    (symmetric absmax; rows below the 1e-8 scale floor are excluded)."""
    from hypothesis import assume
    assume(np.all(np.max(np.abs(x), axis=-1) > 1e-3))
    c1, s1 = quantize(jnp.asarray(x), 8)
    c2, s2 = quantize(jnp.asarray(x * 4.0), 8)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s1) * 4.0, rtol=1e-5)


def test_ste_gradient_is_identity():
    x = jax.random.normal(jax.random.key(0), (8, 16))
    g = jax.grad(lambda a: jnp.sum(fake_quant(a, 8) * 3.0))(x)
    np.testing.assert_allclose(np.asarray(g), 3.0 * np.ones_like(x))


def test_wire_bytes_accounting():
    # codes (int8) + f32 scale per row
    assert wire_bytes((4, 16, 32), 8) == 4 * 16 * 32 + 4 * 16 * 4
    assert wire_bytes((2, 8), 4) == 2 * 8 // 2 + 2 * 4


@settings(max_examples=40, deadline=None)
@given(st.integers(1, 7).flatmap(
    lambda rows: st.integers(1, 16).flatmap(
        lambda half: st.lists(st.integers(-8, 7),
                              min_size=rows * half * 2,
                              max_size=rows * half * 2)
        .map(lambda v: np.asarray(v, np.int8).reshape(rows, half * 2)))))
def test_pack_int4_roundtrip_exact(codes):
    packed = pack_int4(jnp.asarray(codes))
    assert packed.dtype == jnp.int8
    assert packed.shape == (codes.shape[0], codes.shape[1] // 2)
    back = unpack_int4(packed)
    np.testing.assert_array_equal(np.asarray(back), codes)


def test_int4_wire_halves_code_bytes():
    shape = (4, 16, 32)
    scales = 4 * 16 * 4                      # f32 scale per row either way
    assert wire_bytes(shape, 8) - scales == 2 * (wire_bytes(shape, 4) - scales)


def test_fake_quant_equals_quant_dequant():
    x = jax.random.normal(jax.random.key(1), (16, 32))
    a = fake_quant(x, 8)
    c, s = quantize(x, 8)
    b = dequantize(c, s)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def _reference_wire(mode, kernel, x, w_reduce, w_restore):
    """The wire's (payload, restored rows) by the codec's own formulas."""
    if mode == "raw":
        return x, x
    if mode == "reduced":
        return x @ w_reduce, (x @ w_reduce) @ w_restore
    bits = 4 if mode == "int4" else 8
    if kernel:
        codes, scales = ops.butterfly_reduce_quant(x, w_reduce, bits=bits)
        out = ops.butterfly_dequant_restore(codes, scales, w_restore,
                                            out_dtype=x.dtype)
    else:
        codes, scales = quantize(x @ w_reduce, bits)
        out = dequantize(codes, scales, x.dtype) @ w_restore
    return (pack_int4(codes) if mode == "int4" else codes), out


@pytest.mark.parametrize("kernel", [True, False])
@pytest.mark.parametrize("mode", WIRE_MODES)
def test_wire_roundtrip_matches_codec(mode, kernel):
    """Encode then decode is the codec, bit for bit: quantize (fused or
    eager), int4 packing, dequantize and the two projections; the
    unquantized modes send zero scales."""
    x = jax.random.normal(jax.random.key(0), (2, 24, 64))
    w_reduce = jax.random.normal(jax.random.key(1), (64, 16)) * 0.1
    w_restore = jax.random.normal(jax.random.key(2), (16, 64)) * 0.1
    wire = Wire.parse(mode, 8, 16)
    payload, scales = wire.encode(x, w_reduce, kernel=kernel)
    out = wire.decode(payload, scales, w_restore, x.dtype, kernel=kernel)
    want_payload, want_out = _reference_wire(mode, kernel, x, w_reduce,
                                             w_restore)
    np.testing.assert_array_equal(np.asarray(payload),
                                  np.asarray(want_payload))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want_out))
    np.testing.assert_array_equal(
        np.asarray(wire.through(x, w_reduce, w_restore, kernel=kernel)),
        np.asarray(out))
    assert scales.shape == (2, 24, 1) and scales.dtype == jnp.float32
    if not wire.quantized:
        assert not np.asarray(scales).any()
    want = wire.payload_shape_dtype(2, 24, 64, x.dtype)
    assert (want[0].shape, want[0].dtype) == (payload.shape, payload.dtype)


# bytes of one and of 128 rows (d_r 16, bf16 activations of width 256)
WIRE_NBYTES = {"raw": (512, 65536), "reduced": (32, 4096),
               "int8": (20, 2560), "int4": (12, 1536),
               "entropy": (20, 1540)}


@pytest.mark.parametrize("mode", WIRE_MODES)
def test_wire_nbytes(mode):
    import dataclasses
    cfg = dataclasses.replace(get_config("qwen3-8b").reduced(),
                              dtype="bfloat16")
    wire = Wire.parse(mode, 8, 16)
    assert (wire.nbytes(cfg, 1), wire.nbytes(cfg, 128)) == WIRE_NBYTES[mode]
    assert wire.nbytes(cfg, 64, batch=2) == wire.nbytes(cfg, 128)


def test_wire_refuses_unknown_mode_and_odd_int4():
    with pytest.raises(ValueError, match="unknown wire mode"):
        Wire.parse("int2", 8, 16)
    with pytest.raises(ValueError, match="int4"):
        Wire.parse("int4", 8, 15)
    assert Wire.parse("int4", 8, 16).bits == 4
