"""Plain float32 ``jax.numpy`` reference of a configuration, with the
butterfly wire, and its lower-precision control.

It imports nothing of the program.  Its weights are the benchmark's own
(``weights.py``), drawn again from the seed one layer at a time and held in
float32, so that it fits on the chip after the program has been freed.  The
layer equations are the architecture's (``forward`` in
``arch/<model_type>.py``), built from the numerics here: matrix products
that the control rounds to float8, RMSNorm with a gain ``1 + w``, split-half
rotary embeddings, blocked causal attention, the wire and the head.  After
layer ``split`` the butterfly wire reduces the residual to ``d_r`` channels,
quantizes each row to int8 by its absolute maximum, and restores it.

:func:`token_gaps` runs one teacher-forced pass over each prompt and its
served tokens and returns, for each served token, how far its logit lies
below the reference's best at that position.  With ``control=True`` every
linear layer runs on float8 (e4m3) operands, the precision below the bf16
that the configuration states, and the gap is that of the token the control
ranks first.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

import spec
import weights as W

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512            # query rows per attention block
PAD_TO = 1024            # sequence lengths pad to a multiple of this
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _fq(x, axis):
    """Round ``x`` to float8 e4m3 with an absmax scale along ``axis``."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30)
    s = s / F8_MAX
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(x, w, control: bool):
    """x (..., k) @ w (k, n) in float32, or on float8 operands: weights by
    their tensor's absmax, activations by each row's."""
    if control:
        x = _fq(x, -1)
        w = _fq(w, None)
    return jnp.matmul(x, w, precision=HI)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
        (1.0 + w)


def rope(x, theta):
    """x (S, heads, hd), rotating the first half against the second."""
    S, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None, None] * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], -1)


def attention(q, k, v):
    """Causal GQA, queries in blocks: q (S, H, hd), k/v (S, K, hd); query
    head h reads kv head h // (H // K)."""
    S, H, hd = q.shape
    G = H // k.shape[1]
    k = jnp.repeat(k, G, axis=1)
    v = jnp.repeat(v, G, axis=1)
    out = []
    for lo in range(0, S, Q_BLOCK):
        qb = q[lo:lo + Q_BLOCK]
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) / math.sqrt(hd)
        qpos = jnp.arange(lo, lo + qb.shape[0])[:, None]
        s = jnp.where(jnp.arange(S)[None, :] <= qpos, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        out.append(jnp.einsum("hqk,khd->qhd", p, v, precision=HI))
    return jnp.concatenate(out, 0)


def wire(x, w_reduce, w_restore, bits: int, control: bool):
    """Reduce, quantize each row to ``bits`` by its absmax, restore."""
    qmax = 2 ** (bits - 1) - 1
    r = _mm(x, w_reduce, control)
    scale = jnp.maximum(jnp.max(jnp.abs(r), -1, keepdims=True), 1e-8) / qmax
    codes = jnp.clip(jnp.round(r / scale), -qmax - 1, qmax)
    return _mm(codes * scale, w_restore, control)


def head_logits(x, final_norm, head, eps, control: bool):
    return _mm(rms_norm(x, final_norm, eps), head.T, control)


def f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def wire_rows(seed: int, m, xs, control: bool) -> list:
    """The butterfly wire over each sequence's residual rows."""
    fwire = jax.jit(lambda x, a, b: wire(x, a, b, m.wire_bits, control))
    a = W.draw_global(seed, m, "w_reduce").astype(jnp.float32)
    b = W.draw_global(seed, m, "w_restore").astype(jnp.float32)
    return [fwire(x, a, b) for x in xs]


def _served_rows(seed: int, m, seqs, control: bool) -> list:
    """The residual rows that predict each served token, one (T, d) array
    per sequence (T padded to a multiple of 128), after the last layer."""
    P = max(len(p) + len(t) - 1 for p, t in seqs)
    P = -(-P // PAD_TO) * PAD_TO
    T = -(-max(len(t) for _, t in seqs) // 128) * 128
    embed = W.draw_global(seed, m, "embed")
    xs = []
    for p, t in seqs:
        s = np.zeros((P,), np.int32)
        s[:len(p) + len(t) - 1] = np.concatenate([p, t[:-1]])
        xs.append(jnp.take(embed, jnp.asarray(s), axis=0).astype(jnp.float32))
    del embed
    xs = spec.arch_module(m.arch).forward(seed, m, xs, control)
    rows = []
    for x, (p, t) in zip(xs, seqs):
        idx = np.full((T,), len(p) - 1, np.int32)
        idx[:len(t)] += np.arange(len(t), dtype=np.int32)
        rows.append(jnp.take(x, jnp.asarray(idx), axis=0))
    return rows


def token_gaps(seed: int, m, seqs, *, control: bool = False) -> list:
    """``seqs``: list of (prompt ids, served ids).  Returns, per sequence,
    a float64 array with one gap per served token: the reference's best
    logit minus its logit of the served token (``control=False``), or of
    the token that the float8 control ranks first at that position, on the
    same prompt and served tokens (``control=True``)."""
    tied = "head" not in W.global_leaves(m)
    head = W.draw_global(seed, m, "embed" if tied else "head").astype(
        jnp.float32)
    fnorm = W.draw_global(seed, m, "final_norm").astype(jnp.float32)
    ref_fn = jax.jit(head_logits, static_argnums=(3, 4))
    ref = [np.asarray(ref_fn(r, fnorm, head, m.rms_eps, False),
                      np.float64)[:len(t)]
           for r, (_, t) in zip(_served_rows(seed, m, seqs, False), seqs)]
    if control:
        picks = [np.asarray(jnp.argmax(ref_fn(r, fnorm, head, m.rms_eps,
                                              True), -1))[:len(t)]
                 for r, (_, t) in zip(_served_rows(seed, m, seqs, True),
                                      seqs)]
    else:
        picks = [np.asarray(t) for _, t in seqs]
    return [r.max(-1) - r[np.arange(len(k)), k] for r, k in zip(ref, picks)]
