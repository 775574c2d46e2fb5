"""GQA attention with qk-norm, RoPE, sliding windows and ring-buffer KV caches.

Three modes share one code path:
  * ``train``   — full sequence, causal (+ optional window), no cache
  * ``prefill`` — like train but also returns the populated KV cache
  * ``decode``  — one new token per sequence against an existing cache

Caches are ring buffers when a window is set (cache length == window), so the
``long_500k`` shape holds only O(window) keys for windowed layers.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.common import apply_rope, dense_init, dense_spec, rms_norm

MASK_VALUE = -1e30


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------


def _padded_heads(cfg: ModelConfig) -> int:
    """Perf iteration pair 2 / iter 3 (EXPERIMENTS.md section Perf): when the
    q-head count does not divide the 16-way model axis, pad to the next
    multiple of 16 that the kv-head count divides.  The padded heads are
    functionally dead (their wo rows init to zero and stay exactly zero under
    weight decay-free norms... they train, but the *initial* function is
    identical and sharding is clean: whole heads per shard, no GSPMD
    reshape all-reduces).  Enabled with REPRO_ATTN_PAD_HEADS=1."""
    import os as _os
    if _os.environ.get("REPRO_ATTN_PAD_HEADS", "0") != "1":
        return cfg.num_heads
    n = cfg.num_heads
    if n % 16 == 0:
        return n
    p = ((n + 15) // 16) * 16
    while p % cfg.num_kv_heads:
        p += 16
    return p


def init_attention(key, cfg: ModelConfig, dtype, cross: bool = False,
                   d_in: Optional[int] = None):
    """``d_in``: width of the projections' input, when it is not the
    residual's (zamba2's shared block reads the residual concatenated with
    the token embeddings); the output is ``d_model`` wide either way."""
    hd = cfg.resolved_head_dim
    d_in = cfg.d_model if d_in is None else d_in
    kq, kk, kv, ko = jax.random.split(key, 4)
    n_pad = _padded_heads(cfg)
    wq = dense_init(kq, d_in, n_pad * hd, dtype)
    wo = dense_init(ko, cfg.num_heads * hd, cfg.d_model, dtype,
                    scale=1.0 / (cfg.num_heads * hd))
    if n_pad != cfg.num_heads:
        # dead padded heads: zero wo rows, inserted PER KV GROUP so the
        # (B,S,K,G_pad,hd) grouping keeps each q head with its kv head
        K = cfg.num_kv_heads
        G, G_pad = cfg.num_heads // K, n_pad // K
        wo = wo.reshape(K, G, hd, cfg.d_model)
        pad = jnp.zeros((K, G_pad - G, hd, cfg.d_model), dtype)
        wo = jnp.concatenate([wo, pad], axis=1).reshape(n_pad * hd, cfg.d_model)
    params = {
        "wq": wq,
        "wk": dense_init(kk, d_in, cfg.num_kv_heads * hd, dtype),
        "wv": dense_init(kv, d_in, cfg.num_kv_heads * hd, dtype),
        "wo": wo,
    }
    # Perf iteration (EXPERIMENTS.md section Perf pair 2): sharding the fused
    # (heads*hd) dim when heads % mesh != 0 leaves 2.5 heads per shard; GSPMD
    # then resolves the (B,S,N,hd) reshape with per-layer all-reduces of
    # f32 score-sized tensors (~1.8 TB/device for qwen3-14b prefill).  Shard
    # head dims only when the *head count* divides the axis; otherwise
    # replicate the attention weights and let batch parallelism carry.
    import os as _os
    head_aware = _os.environ.get("REPRO_ATTN_HEAD_AWARE", "0") == "1"
    q_ok = (not head_aware) or n_pad % 16 == 0
    kv_ok = (not head_aware) or cfg.num_kv_heads % 16 == 0
    specs = {
        "wq": dense_spec((d_in, n_pad * hd), 1 if q_ok else None),
        "wk": dense_spec((d_in, cfg.num_kv_heads * hd), 1 if kv_ok else None),
        "wv": dense_spec((d_in, cfg.num_kv_heads * hd), 1 if kv_ok else None),
        "wo": dense_spec((n_pad * hd, cfg.d_model), 0 if q_ok else None),
    }
    if cfg.qk_norm:
        params["q_norm"] = jnp.zeros((hd,), dtype)
        params["k_norm"] = jnp.zeros((hd,), dtype)
        specs["q_norm"] = P(None)
        specs["k_norm"] = P(None)
    return params, specs


def init_kv_cache(cfg: ModelConfig, batch: int, length: int, dtype) -> dict:
    hd = cfg.resolved_head_dim
    shape = (batch, length, cfg.num_kv_heads, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def kv_cache_spec(batch_axis, length_axis=None, head_axis=None) -> dict:
    """``head_axis`` shards the kv-head dim (tensor-parallel stages keep each
    rank's cache slice resident with its attention-head shard)."""
    return {"k": P(batch_axis, length_axis, head_axis, None),
            "v": P(batch_axis, length_axis, head_axis, None)}


def tp_attention_specs(cfg: ModelConfig, axis: str = "model") -> dict:
    """Megatron-style specs for one attention param set sharded over a model
    axis: fused q/k/v projections column-parallel (whole heads per shard),
    the out projection row-parallel — its partial outputs are psum'd by
    ``apply_layer``.  Requires whole-head divisibility, asserted by
    :func:`check_tp_divisibility` at spec-build time."""
    specs = {"wq": P(None, axis), "wk": P(None, axis), "wv": P(None, axis),
             "wo": P(axis, None)}
    if cfg.qk_norm:
        specs["q_norm"] = P(None)          # per-head-dim, replicated
        specs["k_norm"] = P(None)
    return specs


# ---------------------------------------------------------------------------
# core math
# ---------------------------------------------------------------------------


def _project_qkv(params, x, cfg: ModelConfig, positions, rope: bool = True):
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    # head counts come from the param shapes, not cfg: inside a shard_map
    # body each model rank holds a whole-head slice of wq/wk/wv (and the
    # padded-head variant widens wq), so cfg.num_heads is the *global* count
    n_q = params["wq"].shape[1] // hd
    n_kv = params["wk"].shape[1] // hd
    q = (x @ params["wq"]).reshape(B, S, n_q, hd)
    k = (x @ params["wk"]).reshape(B, S, n_kv, hd)
    v = (x @ params["wv"]).reshape(B, S, n_kv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.rms_eps)
        k = rms_norm(k, params["k_norm"], cfg.rms_eps)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _gqa_scores(q, k, cfg: ModelConfig, scale: Optional[float] = None):
    """q: (B,S,N,hd) -> grouped (B,S,K,G,hd); scores (B,K,G,S,T), times
    ``scale`` (default 1/sqrt(hd))."""
    B, S, N, hd = q.shape
    K = k.shape[2]
    G = N // K
    qg = q.reshape(B, S, K, G, hd)
    scores = jnp.einsum("bskgh,btkh->bkgst", qg.astype(jnp.float32),
                        k.astype(jnp.float32))
    return scores * (1.0 / math.sqrt(hd) if scale is None else scale)


def _attend(scores, v, mask, dtype):
    scores = jnp.where(mask, scores, MASK_VALUE)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v.astype(jnp.float32))
    B, S, K, G, hd = out.shape
    return out.reshape(B, S, K * G, hd).astype(dtype)


def causal_mask(S: int, T: int, offset: int = 0, window: Optional[int] = None):
    """(S, T) boolean mask; query i at absolute position offset+i."""
    qpos = jnp.arange(S)[:, None] + offset
    kpos = jnp.arange(T)[None, :]
    mask = kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    return mask


# ---------------------------------------------------------------------------
# full-sequence attention (train / prefill)
# ---------------------------------------------------------------------------


def attention_fullseq(params, x, *, cfg: ModelConfig, window: Optional[int],
                      positions=None, use_kernel: bool = False,
                      causal: bool = True, rope: bool = True,
                      scale: Optional[float] = None):
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)[None, :]
    q, k, v = _project_qkv(params, x, cfg, positions, rope=rope)
    if use_kernel:
        assert scale is None, "the flash kernel scales by 1/sqrt(hd)"
        from repro.kernels import ops as kops
        out = kops.flash_attention(q, k, v, causal=causal, window=window)
    else:
        scores = _gqa_scores(q, k, cfg, scale)
        if causal:
            mask = causal_mask(S, S, window=window)[None, None, None]
        else:
            mask = jnp.ones((S, S), bool)[None, None, None]
        out = _attend(scores, v, mask, x.dtype)
    out = out.reshape(B, S, -1) @ params["wo"]
    return out, {"k": k, "v": v}


# ---------------------------------------------------------------------------
# decode attention (one token, ring-buffer-aware cache)
# ---------------------------------------------------------------------------


def attention_decode(params, x, cache, cache_pos, *, cfg: ModelConfig,
                     window: Optional[int], rope: bool = True,
                     scale: Optional[float] = None, layer):
    """x: (B,1,d). ``cache_pos`` — absolute position of the new token, either
    an int32 scalar (all sequences aligned: dry-run / batch decode) or an
    (B,) vector (ragged serving engine).  When ``window`` is set the cache
    length equals the window and is used as a ring buffer (slot = p % W).

    ``cache`` is a stack of layers' caches (a leading layer axis): the call
    writes each sequence's new row into layer ``layer`` of it and returns
    the whole stack; the scores read that layer from the updated stack.  A
    layer loop that carries the stack so updates it in place, where handing
    one layer's cache back would copy it out and in."""
    B, _, _ = x.shape
    T = cache["k"].shape[2]
    pos = jnp.asarray(cache_pos, jnp.int32)
    scalar_pos = pos.ndim == 0
    positions = (jnp.full((B, 1), pos, jnp.int32) if scalar_pos
                 else pos[:, None])
    q, k_new, v_new = _project_qkv(params, x, cfg, positions, rope=rope)
    if scalar_pos:
        # aligned path: dynamic_update_slice shards cleanly under GSPMD
        slot = pos % T if window is not None else pos

        def write(stack, new):
            return jax.lax.dynamic_update_slice(
                stack, new[None].astype(stack.dtype), (layer, 0, slot, 0, 0))
        slots = jnp.broadcast_to(slot, (B,))
    else:
        slots = pos % T if window is not None else jnp.minimum(pos, T - 1)
        # Which write keeps the pool in place depends on the TPU's default
        # layout of the cache.  With a head_dim of whole 128-lane tiles,
        # one scatter of all rows does; one dynamic_update_slice per slot
        # made XLA relayout the pool (AOT, qwen3-8b's 16 x 4224 step:
        # temporaries 2.77 GB against the scatter's 0.48 GB).  Otherwise
        # the layout puts the positions minor and the scatter relayouts
        # every layer's whole cache (zamba2's head_dim 224: 7.75 GB), so
        # each slot's row is written on its own.
        if k_new.shape[-1] % 128:
            def write(stack, new):
                for b in range(B):
                    stack = jax.lax.dynamic_update_slice(
                        stack, new[b][None, None].astype(stack.dtype),
                        (layer, b, slots[b], 0, 0))
                return stack
        else:
            b_idx = jnp.arange(B)

            def write(stack, new):
                return stack.at[layer, b_idx, slots].set(
                    new[:, 0].astype(stack.dtype))
    kv = {"k": write(cache["k"], k_new), "v": write(cache["v"], v_new)}
    k, v = (jax.lax.dynamic_index_in_dim(kv[n], layer, keepdims=False)
            for n in ("k", "v"))
    scores = _gqa_scores(q, k, cfg, scale)               # (B,K,G,1,T)
    idx = jnp.arange(T)[None, :]
    posb = positions                                      # (B,1)
    if window is not None:
        # ring buffer: slot s holds absolute position p iff p % T == s and
        # p <= cache_pos and p > cache_pos - window
        age = (slots[:, None] - idx) % T                  # 0 = newest
        valid = age < jnp.minimum(posb + 1, window)
    else:
        valid = idx <= posb
    out = _attend(scores, v, valid[:, None, None, None, :], x.dtype)
    out = out.reshape(B, 1, -1) @ params["wo"]
    return out, kv


# ---------------------------------------------------------------------------
# cross attention (whisper decoder -> encoder states)
# ---------------------------------------------------------------------------


def cross_attention(params, x, enc_kv, *, cfg: ModelConfig):
    """enc_kv: dict(k=(B,F,K,hd), v=...) precomputed from encoder output."""
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    q = (x @ params["wq"]).reshape(B, S, cfg.num_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.rms_eps)
    scores = _gqa_scores(q, enc_kv["k"], cfg)
    F = enc_kv["k"].shape[1]
    mask = jnp.ones((1, 1, 1, S, F), bool)
    out = _attend(scores, enc_kv["v"], mask, x.dtype)
    out = out.reshape(B, S, -1) @ params["wo"]
    return out


def encoder_kv(params, enc_out, *, cfg: ModelConfig):
    B, F, _ = enc_out.shape
    hd = cfg.resolved_head_dim
    k = (enc_out @ params["wk"]).reshape(B, F, cfg.num_kv_heads, hd)
    v = (enc_out @ params["wv"]).reshape(B, F, cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        k = rms_norm(k, params["k_norm"], cfg.rms_eps)
    return {"k": k, "v": v}
