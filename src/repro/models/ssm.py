"""Mamba2 (SSD) block: chunked state-space dual form for train/prefill and a
recurrent step for decode.

The chunked algorithm follows Dao & Gu 2024 (SSD): within a chunk the output
is a masked-decay attention-like matmul (MXU-friendly); across chunks a
single lax.scan carries the (B, H, P, N) state.  All decay exponents are
differences of a *decreasing* cumulative sum (A<0, dt>0) so every exp() is
<= 1 and bf16-safe.

State for decode: {"ssm": (B, H, P, N), "conv": (B, W-1, d_conv_channels)}.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, SSMConfig
from repro.models.common import dense_init, dense_spec, rms_norm
from repro.models.parallel import ParallelContext


def _dims(cfg: ModelConfig):
    s = cfg.ssm
    d_inner = s.num_heads * s.head_dim
    conv_ch = d_inner + 2 * s.n_groups * s.state_dim
    return s, d_inner, conv_ch


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------


def init_mamba(key, cfg: ModelConfig, dtype):
    s, d_inner, conv_ch = _dims(cfg)
    d = cfg.d_model
    proj_out = d_inner + conv_ch + s.num_heads               # z, x, B, C, dt
    k1, k2, k3, k4 = jax.random.split(key, 4)
    params = {
        "in_proj": dense_init(k1, d, proj_out, dtype),
        "conv_w": (jax.random.normal(k2, (s.conv_width, conv_ch), jnp.float32)
                   * (1.0 / math.sqrt(s.conv_width))).astype(dtype),
        "conv_b": jnp.zeros((conv_ch,), dtype),
        "dt_bias": jnp.zeros((s.num_heads,), jnp.float32),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, s.num_heads, dtype=jnp.float32)),
        "D": jnp.ones((s.num_heads,), jnp.float32),
        "norm_w": jnp.zeros((d_inner,), dtype),
        "out_proj": dense_init(k3, d_inner, d, dtype, scale=1.0 / d_inner),
    }
    specs = {
        "in_proj": dense_spec((d, proj_out), 1),
        "conv_w": P(None, None),
        "conv_b": P(None),
        "dt_bias": P(None),
        "A_log": P(None),
        "D": P(None),
        "norm_w": P(None),
        "out_proj": dense_spec((d_inner, d), 0),
    }
    return params, specs


def init_ssm_state(cfg: ModelConfig, batch: int, dtype) -> dict:
    s, d_inner, conv_ch = _dims(cfg)
    return {
        "ssm": jnp.zeros((batch, s.num_heads, s.head_dim, s.state_dim), jnp.float32),
        "conv": jnp.zeros((batch, s.conv_width - 1, conv_ch), dtype),
    }


def ssm_state_spec(batch_axis) -> dict:
    return {"ssm": P(batch_axis, None, None, None),
            "conv": P(batch_axis, None, None)}


# ---------------------------------------------------------------------------
# pieces
# ---------------------------------------------------------------------------


def _split_proj(params, x, cfg: ModelConfig):
    s, d_inner, conv_ch = _dims(cfg)
    zxbcdt = x @ params["in_proj"]
    z = zxbcdt[..., :d_inner]
    xbc = zxbcdt[..., d_inner:d_inner + conv_ch]
    dt = zxbcdt[..., d_inner + conv_ch:].astype(jnp.float32)     # (..., H)
    return z, xbc, dt


def _causal_conv(xbc, conv_w, conv_b, width: int):
    """Depthwise causal conv over (B, S, C) via width-shifted adds."""
    out = xbc * conv_w[-1]
    for i in range(1, width):
        shifted = jnp.pad(xbc, ((0, 0), (i, 0), (0, 0)))[:, :-i or None][:, :xbc.shape[1]]
        out = out + shifted * conv_w[-1 - i]
    return jax.nn.silu(out + conv_b)


def _time_step(params, dt, s: SSMConfig):
    """softplus(dt + bias), floored at ``dt_min`` where the model has one."""
    dt = jax.nn.softplus(dt + params["dt_bias"])
    return jnp.maximum(dt, s.dt_min) if s.dt_min else dt


def _gated_out(params, y, z, cfg: ModelConfig):
    """RMSNorm of ``y * silu(z)`` over each of the ``n_groups`` groups of
    channels, in float32 from the scan's float32 ``y``, then the out
    projection in the model's dtype."""
    G = cfg.ssm.n_groups
    g = y * jax.nn.silu(z.astype(jnp.float32))
    shape = g.shape
    g = rms_norm(g.reshape(*shape[:-1], G, shape[-1] // G),
                 params["norm_w"].reshape(G, -1), cfg.rms_eps)
    return g.reshape(shape).astype(z.dtype) @ params["out_proj"]


def _per_head(m, heads: int):
    """(..., G, N) group rows -> (..., H, N): head h reads group h // (H/G)."""
    return jnp.repeat(m, heads // m.shape[-2], axis=-2)


# ---------------------------------------------------------------------------
# full-sequence SSD (train / prefill)
# ---------------------------------------------------------------------------


def mamba_fullseq(params, x, *, cfg: ModelConfig, return_state: bool = False):
    """Chunked SSD over any length: the sequence is padded to whole chunks
    with time step 0 (decay 1, no input), so the padded rows leave the state
    as the last real row left it."""
    s, d_inner, conv_ch = _dims(cfg)
    Bsz, S, _ = x.shape
    H, Pd, N, G = s.num_heads, s.head_dim, s.state_dim, s.n_groups
    L = min(s.chunk_size, S)
    C = -(-S // L)
    pad = C * L - S

    z, xbc_in, dt = _split_proj(params, x, cfg)
    # the conv, the scan and the gated norm run in float32, as decode does
    xbc = _causal_conv(xbc_in.astype(jnp.float32),
                       params["conv_w"].astype(jnp.float32),
                       params["conv_b"].astype(jnp.float32), s.conv_width)
    xs = xbc[..., :d_inner].reshape(Bsz, S, H, Pd)
    Bm = xbc[..., d_inner:d_inner + G * N].reshape(Bsz, S, G, N)
    Cm = xbc[..., d_inner + G * N:].reshape(Bsz, S, G, N)

    dt = _time_step(params, dt, s)                                # (B,S,H) f32
    A = -jnp.exp(params["A_log"])                                 # (H,) < 0
    if pad:
        def tail(a):
            return jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        xs_c, dt, Bm, Cm = tail(xs), tail(dt), tail(Bm), tail(Cm)
    else:
        xs_c = xs

    # chunked views; heads split as (group, head in group), before positions
    R = H // G
    xc = xs_c.reshape(Bsz, C, L, G, R, Pd).transpose(0, 1, 3, 4, 2, 5)
    dtc = dt.reshape(Bsz, C, L, G, R).transpose(0, 1, 3, 4, 2)    # (B,C,G,R,L)
    Bc = Bm.reshape(Bsz, C, L, G, N).astype(jnp.float32)
    Cc = Cm.reshape(Bsz, C, L, G, N).astype(jnp.float32)
    cum = jnp.cumsum(dtc * A.reshape(G, R)[:, :, None], axis=-1)  # (B,C,G,R,L)

    # ---- intra-chunk (decay-masked attention) -----------------------------
    seg = cum[..., :, None] - cum[..., None, :]                   # (..,L,L) i-j
    mask = jnp.arange(L)[:, None] >= jnp.arange(L)[None, :]
    # mask BEFORE exp: masked (i<j) entries are positive and can overflow;
    # where(mask, exp(seg), 0) would make the backward 0 * inf = NaN
    decay = jnp.exp(jnp.where(mask, seg, -1e9))                   # <= 1
    scores = jnp.einsum("bcign,bcjgn->bcgij", Cc, Bc)             # per group
    Gm = scores[:, :, :, None] * decay                            # (B,C,G,R,L,L)
    y_intra = jnp.einsum("bcgrij,bcgrjp->bcgrip", Gm, xc * dtc[..., None])

    # ---- chunk summary states ---------------------------------------------
    last = cum[..., -1:]                                          # (B,C,G,R,1)
    w = jnp.exp(last - cum) * dtc                                 # (B,C,G,R,L)
    S_chunk = jnp.einsum("bclgn,bcgrl,bcgrlp->bcgrpn",
                         Bc, w, xc)                               # (B,C,G,R,P,N)

    # ---- inter-chunk scan --------------------------------------------------
    chunk_decay = jnp.exp(last[..., 0])                           # (B,C,G,R)

    def step(state, inputs):
        s_c, dec_c, C_c, cum_c = inputs
        # y from previous state, decayed to each position in the chunk
        y = jnp.einsum("blgn,bgrpn->bgrlp", C_c, state) * \
            jnp.exp(cum_c)[..., None]
        new = state * dec_c[..., None, None] + s_c
        return new, y

    init = jnp.zeros((Bsz, G, R, Pd, N), jnp.float32)
    # scan over chunk axis: move C to leading
    lead = lambda a: jnp.moveaxis(a, 1, 0)                        # noqa: E731
    final_state, y_inter = jax.lax.scan(
        step, init, (lead(S_chunk), lead(chunk_decay), lead(Cc), lead(cum)))
    final_state = final_state.reshape(Bsz, H, Pd, N)

    y = y_intra + lead(y_inter)                                   # (B,C,G,R,L,P)
    y = y.transpose(0, 1, 4, 2, 3, 5).reshape(Bsz, C * L, H, Pd)[:, :S]
    y = y + params["D"][None, None, :, None] * xs
    out = _gated_out(params, y.reshape(Bsz, S, d_inner), z, cfg)
    if return_state:
        # conv state comes from the *pre-activation* conv input stream
        return out, {"ssm": final_state,
                     "conv": _conv_tail(xbc_in, s.conv_width)}
    return out, None


def _conv_tail(xbc_in, width: int):
    """Last (W-1) pre-conv channel rows, for seeding decode; zeros before the
    first row of a sequence shorter than that."""
    short = width - 1 - xbc_in.shape[1]
    if short > 0:
        xbc_in = jnp.pad(xbc_in, ((0, 0), (short, 0), (0, 0)))
    return xbc_in[:, -(width - 1):, :]


# ---------------------------------------------------------------------------
# decode step
# ---------------------------------------------------------------------------


def mamba_decode(params, x, state, *, cfg: ModelConfig):
    """x: (B, 1, d); state: {"ssm": (B,H,P,N) f32, "conv": (B,W-1,Cc)}."""
    s, d_inner, conv_ch = _dims(cfg)
    Bsz = x.shape[0]
    H, Pd, N, G = s.num_heads, s.head_dim, s.state_dim, s.n_groups

    z, xbc_new, dt = _split_proj(params, x, cfg)                  # (B,1,*)
    window = jnp.concatenate([state["conv"], xbc_new], axis=1)    # (B,W,Cc)
    conv = jnp.einsum("bwc,wc->bc", window.astype(jnp.float32),
                      params["conv_w"].astype(jnp.float32))
    xbc = jax.nn.silu(conv + params["conv_b"].astype(jnp.float32))[:, None, :]
    new_conv_state = window[:, 1:, :]

    xs = xbc[..., :d_inner].reshape(Bsz, H, Pd)
    Bm = _per_head(xbc[..., d_inner:d_inner + G * N].reshape(Bsz, G, N), H)
    Cm = _per_head(xbc[..., d_inner + G * N:].reshape(Bsz, G, N), H)

    dt = _time_step(params, dt[:, 0], s)                          # (B,H)
    A = -jnp.exp(params["A_log"])
    decay = jnp.exp(dt * A)                                       # (B,H)

    upd = jnp.einsum("bh,bhp,bhn->bhpn", dt, xs.astype(jnp.float32), Bm)
    ssm = state["ssm"] * decay[:, :, None, None] + upd
    y = jnp.einsum("bhpn,bhn->bhp", ssm, Cm)
    y = y + params["D"][None, :, None] * xs.astype(jnp.float32)
    out = _gated_out(params, y.reshape(Bsz, 1, d_inner), z, cfg)
    return out, {"ssm": ssm, "conv": new_conv_state.astype(state["conv"].dtype)}
