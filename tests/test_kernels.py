"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU): shape/dtype
sweeps + allclose, per assignment deliverable c."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize("T,d,d_r", [(32, 128, 8), (64, 256, 32), (100, 128, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_butterfly_reduce_quant(T, d, d_r, dtype):
    k1, k2 = jax.random.split(jax.random.key(0))
    x = jax.random.normal(k1, (T, d), dtype)
    w = (jax.random.normal(k2, (d, d_r), jnp.float32) * 0.05).astype(dtype)
    codes, scales = ops.butterfly_reduce_quant(x, w, block_t=32)
    codes_r, scales_r = ref.butterfly_reduce_quant_ref(x, w)
    assert codes.dtype == jnp.int8
    # int8 codes may differ by 1 ULP at rounding boundaries in bf16
    diff = np.abs(np.asarray(codes, np.int32) - np.asarray(codes_r, np.int32))
    assert diff.max() <= (0 if dtype == jnp.float32 else 1)
    np.testing.assert_allclose(np.asarray(scales), np.asarray(scales_r),
                               rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("T,d,d_r", [(32, 128, 8), (48, 256, 16)])
def test_butterfly_dequant_restore(T, d, d_r):
    k1, k2, k3 = jax.random.split(jax.random.key(1), 3)
    x = jax.random.normal(k1, (T, d), jnp.float32)
    w = jax.random.normal(k2, (d, d_r), jnp.float32) * 0.05
    wr = jax.random.normal(k3, (d_r, d), jnp.float32) * 0.05
    codes, scales = ref.butterfly_reduce_quant_ref(x, w)
    out = ops.butterfly_dequant_restore(codes, scales, wr, block_t=16)
    out_r = ref.butterfly_dequant_restore_ref(codes, scales, wr)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_r),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("T,d,d_r", [(32, 128, 8),     # kernel grid path
                                     (4, 128, 16)])    # decode-row fast path
def test_butterfly_restore_norm_vs_ref(T, d, d_r):
    """Fused dequant+restore+norm1 against the oracle AND against the
    unfused composition it replaces (restore, then rms_norm)."""
    k1, k2, k3, k4 = jax.random.split(jax.random.key(5), 4)
    x = jax.random.normal(k1, (T, d), jnp.float32)
    w = jax.random.normal(k2, (d, d_r), jnp.float32) * 0.05
    wr = jax.random.normal(k3, (d_r, d), jnp.float32) * 0.05
    nw = jax.random.normal(k4, (d,), jnp.float32) * 0.1
    codes, scales = ref.butterfly_reduce_quant_ref(x, w)
    xr, h = ops.butterfly_restore_norm(codes, scales, wr, nw, block_t=16)
    xr_r, h_r = ref.butterfly_restore_norm_ref(codes, scales, wr, nw)
    np.testing.assert_allclose(np.asarray(xr), np.asarray(xr_r),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_r),
                               rtol=1e-5, atol=1e-5)
    unfused_x = ops.butterfly_dequant_restore(codes, scales, wr, block_t=16)
    unfused_h = ops.rmsnorm_ref(unfused_x, nw)
    np.testing.assert_allclose(np.asarray(xr), np.asarray(unfused_x),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(h), np.asarray(unfused_h),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("T", [32, 4])     # kernel grid path, fast path
def test_restore_out_dtype_follows_model_dtype(T):
    """Without an explicit out_dtype the restore kernels emit the model
    dtype (w_restore's), bitwise what an explicit out_dtype gives."""
    k1, k2, k3 = jax.random.split(jax.random.key(3), 3)
    x = jax.random.normal(k1, (T, 128), jnp.float32)
    w = jax.random.normal(k2, (128, 16), jnp.float32) * 0.05
    codes, scales = ref.butterfly_reduce_quant_ref(x, w)
    for dt in (jnp.float32, jnp.bfloat16):
        wr = (jax.random.normal(k3, (16, 128), jnp.float32) * 0.05).astype(dt)
        nw = jnp.zeros((128,), dt)
        out = ops.butterfly_dequant_restore(codes, scales, wr, block_t=16)
        xr, h = ops.butterfly_restore_norm(codes, scales, wr, nw, block_t=16)
        assert out.dtype == xr.dtype == h.dtype == dt
        exp = ops.butterfly_dequant_restore(codes, scales, wr, block_t=16,
                                            out_dtype=dt)
        exp_x, exp_h = ops.butterfly_restore_norm(codes, scales, wr, nw,
                                                  block_t=16, out_dtype=dt)
        for got, want in ((out, exp), (xr, exp_x), (h, exp_h)):
            np.testing.assert_array_equal(np.asarray(got, np.float32),
                                          np.asarray(want, np.float32))


@pytest.mark.parametrize("row_bytes,rows", [
    (4096 * (4 * 4 + 4), 128),   # restore+norm, two f32 outputs at d=4096
    (4096 * (4 * 2 + 4), 256),   # restore+norm, two bf16 outputs at d=4096
    (128 * (2 * 4 + 4), 256),    # narrow rows keep the requested tile
    (10 ** 9, 8),                # never below the 8-row tile
])
def test_row_tile_capped_by_vmem_budget(row_bytes, rows):
    assert ops._row_block(512, 256, row_bytes) == rows
    assert ops._row_block(4, 256, row_bytes) == min(rows, 8)


@pytest.mark.parametrize("backend,interpret", [("cpu", True),
                                               ("tpu", False),
                                               ("gpu", None)])
def test_interpret_mode_only_on_cpu(monkeypatch, backend, interpret):
    """The Pallas interpreter runs on the CPU only: a TPU compiles through
    Mosaic, and any other backend fails instead of falling back."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match="gpu"):
            ops.interpret_mode()
    else:
        assert ops.interpret_mode() is interpret


@pytest.mark.parametrize("T,d,d_r", [(32, 128, 8),     # kernel grid path
                                     (100, 128, 16),   # padded grid (count fix)
                                     (4, 128, 16)])    # decode-row fast path
@pytest.mark.parametrize("bits", [8, 4])
def test_butterfly_reduce_quant_bincount(T, d, d_r, bits):
    """Fused quantize+per-channel-bincount: codes/scales bitwise-identical
    to the plain fused quantize, counts bitwise vs the host histogram
    oracle (including the padded-grid correction), eager ref within the
    repo's usual quant tolerance."""
    from repro.core import wire_codec
    k1, k2 = jax.random.split(jax.random.key(9))
    x = jax.random.normal(k1, (T, d), jnp.float32)
    w = jax.random.normal(k2, (d, d_r), jnp.float32) * 0.05
    codes, scales, counts = ops.butterfly_reduce_quant_bincount(
        x, w, bits=bits, block_t=32)
    codes_p, scales_p = ops.butterfly_reduce_quant(x, w, bits=bits,
                                                   block_t=32)
    assert np.array_equal(np.asarray(codes), np.asarray(codes_p))
    assert np.array_equal(np.asarray(scales), np.asarray(scales_p))
    assert np.array_equal(np.asarray(counts),
                          wire_codec.channel_counts(np.asarray(codes), bits))
    assert int(np.asarray(counts).sum()) == T * d_r
    codes_r, scales_r, counts_r = ref.butterfly_reduce_quant_bincount_ref(
        x, w, bits=bits)
    assert np.array_equal(np.asarray(codes), np.asarray(codes_r))
    np.testing.assert_allclose(np.asarray(scales), np.asarray(scales_r),
                               rtol=1e-5, atol=1e-7)
    assert np.array_equal(np.asarray(counts), np.asarray(counts_r))


def test_butterfly_roundtrip_error_bound():
    """|x - deq(quant(x))| <= scale/2 per element (symmetric rounding)."""
    x = jax.random.normal(jax.random.key(2), (64, 128), jnp.float32)
    w = jnp.eye(128)
    codes, scales = ops.butterfly_reduce_quant(x, w, block_t=32)
    back = codes.astype(jnp.float32) * scales
    assert float(jnp.max(jnp.abs(back - x))) <= float(jnp.max(scales)) * 0.5 + 1e-6


ATTN_CASES = [
    # B, Sq, Skv, N, K, hd, causal, window
    (2, 128, 128, 4, 2, 64, True, None),
    (2, 128, 128, 4, 2, 64, True, 32),
    (1, 128, 128, 8, 8, 32, False, None),
    (2, 64, 128, 4, 4, 64, True, None),       # continuation (q aligned to end)
    (1, 1, 128, 4, 2, 64, True, None),        # decode-like
]


@pytest.mark.parametrize("B,Sq,Skv,N,K,hd,causal,window", ATTN_CASES)
def test_flash_attention_vs_ref(B, Sq, Skv, N, K, hd, causal, window):
    ks = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(ks[0], (B, Sq, N, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, Skv, K, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, Skv, K, hd), jnp.float32)
    o = ops.flash_attention(q, k, v, causal=causal, window=window,
                            block_q=min(64, Sq), block_k=64)
    o_r = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_r),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    ks = jax.random.split(jax.random.key(4), 3)
    q = jax.random.normal(ks[0], (1, 64, 2, 64), dtype)
    k = jax.random.normal(ks[1], (1, 64, 2, 64), dtype)
    v = jax.random.normal(ks[2], (1, 64, 2, 64), dtype)
    o = ops.flash_attention(q, k, v, block_q=32, block_k=32)
    o_r = ref.flash_attention_ref(q, k, v)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_r, np.float32), rtol=tol, atol=tol)


def test_model_attention_uses_kernel_consistently():
    """Model attention with use_kernel=True equals the jnp path."""
    import dataclasses
    from repro.configs import get_config
    from repro.models import model as M
    cfg = get_config("qwen3-8b").reduced()
    built = M.build(cfg)
    params, _ = M.init_model(jax.random.key(0), built)
    toks = jax.random.randint(jax.random.key(1), (2, 64), 0, cfg.vocab_size)
    a, _ = M.forward_train(params, built, {"tokens": toks}, use_kernel=False)
    b, _ = M.forward_train(params, built, {"tokens": toks}, use_kernel=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("T,d", [(32, 128), (100, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_kernel_vs_ref(T, d, dtype):
    x = jax.random.normal(jax.random.key(7), (T, d), dtype)
    w = (jax.random.normal(jax.random.key(8), (d,), jnp.float32) * 0.1).astype(dtype)
    got = ops.rmsnorm(x, w, block_t=32)
    want = ops.rmsnorm_ref(x, w)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)
