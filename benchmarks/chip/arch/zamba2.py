"""Zamba2 (https://huggingface.co/Zyphra/Zamba2-7B-Instruct): Mamba2 layers,
some of which first invoke one of two shared attention+MLP blocks.

Every layer has its own Mamba2 mixer.  A hybrid layer ``i``
(``hybrid_layer_ids``; invocation ``k``, block ``b = k % num_mem_blocks``)
first runs shared block ``b`` over the residual ``h`` concatenated with the
token embeddings ``x_emb``, and adds what it gives to the input of its own
mixer, not to the residual::

    a     = rms(attn_b(rms(concat(h, x_emb), in_norm_b)), ff_norm_b)
    g, up = split(a @ gate_up_b + (a @ A_k) @ B_k)     # adapter k, rank r
    t     = ((gelu(g) * up) @ down_b) @ linear_i
    h     = h + mamba_i(rms(h + t, norm1_i))           # t = 0 in a mamba layer

``attn_b`` is causal attention over 2d-wide inputs, 32 heads of 224 (no GQA),
split-half rotary embeddings (theta 1e4) on queries and keys, softmax scale
``(hd / 2)^-0.5``, out projection to d.  ``mamba_i``: in_proj to
``[z, x, B, C, dt]``; a depthwise causal conv (width 4, bias) and SiLU over
``[x, B, C]``; ``dt = max(softplus(dt + dt_bias), time_step_min)``;
``A = -exp(A_log)``; per head h, with B and C of group ``h // (H / G)``, the
recurrence ``s_t = exp(dt_t A) s_{t-1} + dt_t B_t x_t``,
``y_t = C_t s_t + D x_t``; RMSNorm of ``y * silu(z)`` over each of the G
groups of channels; out_proj.  The head is tied to ``embed``.

The reference (``forward``) runs that recurrence token by token
(``lax.scan`` over positions), not the chunked SSD the program runs, so the
two share no algorithm.  Its departures from ``transformers`` 4.57.6
(``models/zamba2/modeling_zamba2.py``):

- every RMSNorm gain is ``1 + w`` (the benchmark's weights hold the offset
  ``w``; ``transformers`` holds the gain);
- the weights are random (``weights.py``), not Zyphra's; the mixer's
  ``dt_bias``, ``A_log`` and ``D`` are placed from their drawn leaves as
  ``Zamba2PreTrainedModel._init_weights`` places them (:func:`mixer_init`);
- the butterfly wire (``reference.wire_rows``) reduces, quantizes and
  restores the residual after layer ``split - 1``; ``x_emb`` stays exact;
- the attention adapters (``use_shared_attention_adapter`` is false), the
  long-context RoPE rescaling (``use_long_context`` false) and
  ``time_step_limit`` (null) are not implemented; a configuration that sets
  them is refused;
- sequences are computed alone, with no attention mask or padding tokens.

The interface this module provides is written in ``spec.py``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

import reference as R
import weights as W

HI = jax.lax.Precision.HIGHEST


@dataclass(frozen=True)
class Dims:
    """The dims the harness, the reference and the work counts use."""
    name: str
    num_layers: int
    d_model: int
    num_heads: int          # shared attention heads (no GQA)
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    rms_eps: float
    mamba_heads: int
    mamba_head_dim: int
    d_state: int
    n_groups: int
    d_conv: int
    chunk: int
    dt_min: float
    dt_max: float
    dt_floor: float
    adapter_rank: int
    num_mem_blocks: int
    hybrid_layer_ids: tuple
    split: int              # butterfly after this many layers
    d_r: int
    wire_bits: int
    arch: str = "zamba2"

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_ch(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.d_state


def dims(name: str, c: dict) -> Dims:
    """Dims from a configuration file (published ``config.json`` keys)."""
    d = c["hidden_size"]
    ids = tuple(c["hybrid_layer_ids"])
    kinds = ["hybrid" if i in ids else "mamba"
             for i in range(c["num_hidden_layers"])]
    unsupported = {
        "add_bias_linear": False, "use_conv_bias": True,
        "use_shared_mlp_adapter": True, "use_shared_attention_adapter": False,
        "use_mem_rope": True, "use_long_context": False,
        "time_step_limit": None, "hidden_act": "gelu"}
    bad = {k: c.get(k) for k, v in unsupported.items() if c.get(k) != v}
    if bad or not c.get("tie_word_embeddings", True) or \
            c["num_key_value_heads"] != c["num_attention_heads"] or \
            c["attention_hidden_size"] != 2 * d or \
            c["attention_head_dim"] * c["num_attention_heads"] != 2 * d or \
            c["mamba_expand"] * d != c["n_mamba_heads"] * c["mamba_headdim"] \
            or c["layers_block_type"] != kinds:
        raise ValueError(f"{name}: only tied, bias-free zamba2 stacks with the "
                         f"shared MLP adapter and mem RoPE ({bad or 'shapes'})")
    bf = c["butterfly"]
    return Dims(name=name, num_layers=c["num_hidden_layers"], d_model=d,
                num_heads=c["num_attention_heads"],
                head_dim=c["attention_head_dim"], d_ff=c["intermediate_size"],
                vocab=c["vocab_size"], rope_theta=float(c["rope_theta"]),
                rms_eps=float(c["rms_norm_eps"]),
                mamba_heads=c["n_mamba_heads"],
                mamba_head_dim=c["mamba_headdim"], d_state=c["mamba_d_state"],
                n_groups=c["mamba_ngroups"], d_conv=c["mamba_d_conv"],
                chunk=c["chunk_size"], dt_min=float(c["time_step_min"]),
                dt_max=float(c["time_step_max"]),
                dt_floor=float(c["time_step_floor"]),
                adapter_rank=c["adapter_rank"],
                num_mem_blocks=c["num_mem_blocks"], hybrid_layer_ids=ids,
                split=bf["after_layer"], d_r=bf["d_r"],
                wire_bits=bf["wire_bits"])


# -------------------------------------------------------------------- leaves
MAMBA = ("norm1", "in_proj", "conv_w", "conv_b", "dt_bias", "A_log", "D",
         "mamba_norm", "out_proj")
BLOCK = ("in_norm", "wq", "wk", "wv", "wo", "ff_norm", "w_gate", "w_up",
         "w_down")
MAX_BLOCKS = 2          # shared blocks with key indices below

LEAF_IDS = {
    **{n: i for i, n in enumerate(MAMBA)},                      # 0-8
    "embed": 9, "final_norm": 10, "linear": 11, "adapter_a": 12,
    "adapter_b": 13,                                            # 14, 15: wire
    **{f"mem{b}_{n}": 16 + len(BLOCK) * b + i
       for b in range(MAX_BLOCKS) for i, n in enumerate(BLOCK)},  # 16-33
}


def layer_leaves(m, layer: int) -> dict:
    d, din = m.d_model, m.d_inner
    out = {"norm1": (d,),
           "in_proj": (d, din + m.conv_ch + m.mamba_heads),
           "conv_w": (m.d_conv, m.conv_ch), "conv_b": (m.conv_ch,),
           "dt_bias": (m.mamba_heads,), "A_log": (m.mamba_heads,),
           "D": (m.mamba_heads,), "mamba_norm": (din,),
           "out_proj": (din, d)}
    if layer in m.hybrid_layer_ids:
        # the layer's projection of the shared block into its mixer, and
        # its invocation's adapter on the block's MLP gate/up projection
        out.update(linear=(d, d), adapter_a=(d, m.adapter_rank),
                   adapter_b=(m.adapter_rank, 2 * m.d_ff))
    return out


def _block_leaves(m) -> dict:
    d, q = m.d_model, m.num_heads * m.head_dim
    return {"in_norm": (2 * d,), "wq": (2 * d, q), "wk": (2 * d, q),
            "wv": (2 * d, q), "wo": (q, d), "ff_norm": (d,),
            "w_gate": (d, m.d_ff), "w_up": (d, m.d_ff), "w_down": (m.d_ff, d)}


def global_leaves(m) -> dict:
    if m.num_mem_blocks > MAX_BLOCKS:
        raise ValueError(f"{m.name}: {m.num_mem_blocks} shared blocks; the "
                         f"leaf table has keys for {MAX_BLOCKS}")
    out = {"embed": (m.vocab, m.d_model), "final_norm": (m.d_model,)}
    for b in range(m.num_mem_blocks):
        out.update({f"mem{b}_{n}": s for n, s in _block_leaves(m).items()})
    return out


def mixer_init(lw: dict, m) -> dict:
    """The mixer's ``dt_bias``, ``A_log`` and ``D`` (float32, one layer's or
    a stack's) from their drawn leaves, each ``N(0, 1/H)`` over the H heads,
    as ``transformers`` initializes them: a time step log-uniform over
    ``[time_step_min, time_step_max]`` (floored at ``time_step_floor``),
    through the inverse softplus, at the quantile its leaf's value has;
    ``A = arange(1, H + 1)``, and ``D = 1``, each offset by its leaf.  With
    every leaf drawn at ``N(0, 1/fan_in)`` instead, the time step is about
    0.7 and A about -1, and each mixer amplifies rounding more."""
    std = m.mamba_heads ** -0.5
    z = lw["dt_bias"].astype(jnp.float32) / std
    u = 0.5 * (1.0 + jax.lax.erf(z / math.sqrt(2.0)))   # its quantile
    lo, hi = math.log(m.dt_min), math.log(m.dt_max)
    dt = jnp.maximum(jnp.exp(lo + u * (hi - lo)), m.dt_floor)
    a = jnp.log(jnp.arange(1, m.mamba_heads + 1, dtype=jnp.float32))
    return {**lw, "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": a + lw["A_log"].astype(jnp.float32),
            "D": 1.0 + lw["D"].astype(jnp.float32)}


# ------------------------------------------------------------------- program
def program_config(m):
    from repro.configs.base import ModelConfig, SSMConfig
    return ModelConfig(
        name=m.name, arch_type="hybrid", num_layers=m.num_layers,
        d_model=m.d_model, num_heads=m.num_heads, num_kv_heads=m.num_heads,
        head_dim=m.head_dim, d_ff=m.d_ff, vocab_size=m.vocab,
        act="gelu_exact", rope_theta=m.rope_theta, rms_eps=m.rms_eps,
        tie_embeddings=True, hybrid_layer_ids=m.hybrid_layer_ids,
        num_mem_blocks=m.num_mem_blocks, adapter_rank=m.adapter_rank,
        ssm=SSMConfig(state_dim=m.d_state, num_heads=m.mamba_heads,
                      head_dim=m.mamba_head_dim, conv_width=m.d_conv,
                      chunk_size=m.chunk, n_groups=m.n_groups,
                      dt_min=m.dt_min),
        dtype="bfloat16")


def program_tree(w: dict, m) -> dict:
    """``repro.models.model.init_model``'s layout: the program's segments of
    one-layer units, each stacked over its run of layers (copies of slices of
    ``w``'s stacks), the shared blocks and the per-invocation adapters."""
    from repro.models import model as M
    lw, ids = w["layers"], list(m.hybrid_layer_ids)
    segments, base = [], 0
    for seg in M.build(program_config(m)).stages[0]:
        assert len(seg.unit) == 1, "zamba2 segments are one-layer runs"
        lo, hi = base, base + seg.repeats
        # the program holds the time step's bias, A and D in float32
        f32 = mixer_init({n: lw[n][lo:hi] for n in ("dt_bias", "A_log",
                                                      "D")}, m)
        layer = {"norm1": lw["norm1"][lo:hi],
                 "mixer": {"in_proj": lw["in_proj"][lo:hi],
                           "conv_w": lw["conv_w"][lo:hi],
                           "conv_b": lw["conv_b"][lo:hi], **f32,
                           "norm_w": lw["mamba_norm"][lo:hi],
                           "out_proj": lw["out_proj"][lo:hi]}}
        if seg.unit[0].hybrid is not None:
            k = ids.index(lo)
            layer["linear"] = lw["linear"][k:k + 1]
        segments.append([layer])
        base = hi
    blocks = [{"norm_in": w[f"mem{b}_in_norm"],
               "mixer": {n: w[f"mem{b}_{n}"] for n in ("wq", "wk", "wv",
                                                       "wo")},
               "norm_ff": w[f"mem{b}_ff_norm"],
               "ffn": {n: w[f"mem{b}_{n}"] for n in ("w_gate", "w_up",
                                                     "w_down")}}
              for b in range(m.num_mem_blocks)]
    adapters = [{"a": lw["adapter_a"][k], "b": lw["adapter_b"][k]}
                for k in range(len(ids))]
    return {"embed": w["embed"], "final_norm": w["final_norm"],
            "stages": [segments],
            "shared_attn": {"blocks": blocks, "adapters": adapters}}


# ----------------------------------------------------------------- reference
def mamba(x, lw, m, control: bool):
    """One Mamba2 mixer over one sequence (S, d), as a token-by-token
    recurrence."""
    S = x.shape[0]
    H, P, N, G = m.mamba_heads, m.mamba_head_dim, m.d_state, m.n_groups
    din = m.d_inner
    zxbcdt = R._mm(x, lw["in_proj"], control)
    z, xbc, dt = (zxbcdt[:, :din], zxbcdt[:, din:din + m.conv_ch],
                  zxbcdt[:, din + m.conv_ch:])
    pad = jnp.pad(xbc, ((m.d_conv - 1, 0), (0, 0)))
    conv = sum(lw["conv_w"][k] * pad[k:k + S] for k in range(m.d_conv))
    xbc = jax.nn.silu(conv + lw["conv_b"])
    xs = xbc[:, :din].reshape(S, H, P)
    group = jnp.arange(H) // (H // G)                  # head -> its group
    Bh = xbc[:, din:din + G * N].reshape(S, G, N)[:, group]
    Ch = xbc[:, din + G * N:].reshape(S, G, N)[:, group]
    dt = jnp.maximum(jax.nn.softplus(dt + lw["dt_bias"]), m.dt_min)
    A = -jnp.exp(lw["A_log"])

    def step(s, inp):
        dt_t, x_t, b_t, c_t = inp
        s = jnp.exp(dt_t * A)[:, None, None] * s + \
            (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return s, jnp.einsum("hpn,hn->hp", s, c_t, precision=HI)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                        (dt, xs, Bh, Ch))
    y = (y + lw["D"][:, None] * xs).reshape(S, din) * jax.nn.silu(z)
    y = R.rms_norm(y.reshape(S, G, din // G),
                   lw["mamba_norm"].reshape(G, -1), m.rms_eps)
    return R._mm(y.reshape(S, din), lw["out_proj"], control)


def shared_block(x, x_emb, blk, lw, m, control: bool):
    """The shared block's invocation at a hybrid layer over one sequence:
    what it adds to the input of the layer's mixer."""
    S, hd = x.shape[0], m.head_dim
    h = R.rms_norm(jnp.concatenate([x, x_emb], -1), blk["in_norm"], m.rms_eps)
    q, k, v = (R._mm(h, blk[n], control).reshape(S, m.num_heads, hd)
               for n in ("wq", "wk", "wv"))
    q, k = R.rope(q, m.rope_theta), R.rope(k, m.rope_theta)
    # R.attention scales by 1/sqrt(hd); zamba2 scales by 1/sqrt(hd / 2)
    o = R.attention(q * math.sqrt(2.0), k, v).reshape(S, -1)
    a = R.rms_norm(R._mm(o, blk["wo"], control), blk["ff_norm"], m.rms_eps)
    gate_up = R._mm(R._mm(a, lw["adapter_a"], control), lw["adapter_b"],
                    control)
    g = R._mm(a, blk["w_gate"], control) + gate_up[:, :m.d_ff]
    u = R._mm(a, blk["w_up"], control) + gate_up[:, m.d_ff:]
    t = R._mm(jax.nn.gelu(g, approximate=False) * u, blk["w_down"], control)
    return R._mm(t, lw["linear"], control)


def layer(x, x_emb, lw, blk, m, control: bool):
    h = x
    if blk is not None:
        h = x + shared_block(x, x_emb, blk, lw, m, control)
    return x + mamba(R.rms_norm(h, lw["norm1"], m.rms_eps), lw, m, control)


def forward(seed: int, m, xs, control: bool) -> list:
    """Every layer in turn, each drawn alone, over all sequences at once
    (they share one padded length); the shared blocks are drawn once; the
    wire after ``split``; the token embeddings ``xs`` carried beside the
    residual, exact."""
    with jax.default_matmul_precision("highest"):
        x_emb = jnp.stack(xs)
        x = x_emb
        blocks = [R.f32({n: W.draw_global(seed, m, f"mem{b}_{n}")
                         for n in _block_leaves(m)})
                  for b in range(m.num_mem_blocks)]
        plain = jax.jit(jax.vmap(
            lambda x, e, lw: layer(x, e, lw, None, m, control),
            in_axes=(0, 0, None)))
        hybrid = jax.jit(jax.vmap(
            lambda x, e, lw, blk: layer(x, e, lw, blk, m, control),
            in_axes=(0, 0, None, None)))
        for li in range(m.num_layers):
            lw = mixer_init(R.f32(W.draw_layer(seed, m, li)), m)
            if li in m.hybrid_layer_ids:
                k = m.hybrid_layer_ids.index(li)
                x = hybrid(x, x_emb, lw, blocks[k % m.num_mem_blocks])
            else:
                x = plain(x, x_emb, lw)
            if li == m.split - 1:
                x = jnp.stack(R.wire_rows(seed, m, list(x), control))
            del lw
        return list(x)


# -------------------------------------------------------------------- counts
def _pairs(n: int) -> int:
    return n * (n + 1) // 2


def mamba_params(m) -> int:
    """Weights of one Mamba2 layer's matrix multiplications."""
    return m.d_model * (m.d_inner + m.conv_ch + m.mamba_heads) + \
        m.d_inner * m.d_model


def hybrid_params(m) -> int:
    """Weights of one shared-block invocation's matrix multiplications: the
    block's attention and MLP, the adapter and the layer's linear."""
    d, q = m.d_model, m.num_heads * m.head_dim
    return (3 * 2 * d * q + q * d + 3 * d * m.d_ff
            + m.adapter_rank * (d + 2 * m.d_ff) + d * d)


def ssd(m, seq: int) -> int:
    """The chunked SSD of one Mamba2 layer over ``seq`` tokens in chunks of
    ``chunk`` (the last one ragged): intra-chunk C.B scores per group and
    their application to the values per head over each chunk's causal pairs,
    each chunk's summary state, and the state's contribution to every
    output."""
    H, P, N, G, L = (m.mamba_heads, m.mamba_head_dim, m.d_state, m.n_groups,
                     m.chunk)
    pairs = sum(_pairs(min(L, seq - lo)) for lo in range(0, seq, L))
    intra = 2 * G * N * pairs + 2 * H * P * pairs
    chunk_state = 2 * seq * H * P * N
    inter = 2 * seq * H * P * N
    return intra + chunk_state + inter


def _layers(m, lo: int, hi: int, seq: int, keys: int) -> int:
    """Layers [lo, hi) over ``seq`` new tokens, the attention of hybrid
    layers over ``keys`` (query, key) pairs."""
    f = 0
    for i in range(lo, hi):
        f += 2 * seq * mamba_params(m)
        if i in m.hybrid_layer_ids:
            f += 2 * seq * hybrid_params(m) + \
                4 * m.num_heads * m.head_dim * keys
    return f


def _ssd_layers(m, lo: int, hi: int, seq: int) -> int:
    return (hi - lo) * ssd(m, seq)


def _step_state(m, layers: int) -> int:
    """One token's recurrence over ``layers`` Mamba2 layers: the state's
    decay and update, and its read-out."""
    return layers * 4 * m.mamba_heads * m.mamba_head_dim * m.d_state


def edge_prefill(m, seq: int) -> int:
    """Layers [0, split) over ``seq`` tokens and the reduction."""
    return (_layers(m, 0, m.split, seq, _pairs(seq))
            + _ssd_layers(m, 0, m.split, seq) + 2 * seq * m.d_model * m.d_r)


def cloud_prefill(m, seq: int) -> int:
    """Restoration, layers [split, L) over ``seq`` tokens and the head at the
    last position."""
    n = m.num_layers
    return (2 * seq * m.d_r * m.d_model
            + _layers(m, m.split, n, seq, _pairs(seq))
            + _ssd_layers(m, m.split, n, seq) + 2 * m.d_model * m.vocab)


def edge_step(m, pos: int) -> int:
    """One token on the edge at position ``pos``: layers [0, split) and the
    reduction."""
    return (_layers(m, 0, m.split, 1, pos + 1) + _step_state(m, m.split)
            + 2 * m.d_model * m.d_r)


def cloud_step(m, pos: int) -> int:
    """One token on the cloud: restoration, layers [split, L), head."""
    n = m.num_layers
    return (2 * m.d_r * m.d_model + _layers(m, m.split, n, 1, pos + 1)
            + _step_state(m, n - m.split) + 2 * m.d_model * m.vocab)


def decode_token(m, pos: int) -> int:
    """One token of the batched decode step: the whole model with the wire."""
    return edge_step(m, pos) + cloud_step(m, pos)


def param_count(m) -> int:
    """Every leaf's elements: the layers, the shared blocks, the table."""
    n = sum(math.prod(s) for i in range(m.num_layers)
            for s in layer_leaves(m, i).values())
    return n + sum(math.prod(s) for s in global_leaves(m).values())


def decode_step_bytes(m, positions, weight_bytes: int = 2) -> int:
    """Least HBM bytes of one batched decode step with active slots at
    ``positions`` (each the position its new token is written at): every
    weight read once (the butterfly's too; the tied head is the embedding
    table), each active slot's SSM state (float32) and conv state read and
    written in every layer, and each hybrid invocation's KV read up to the
    slot's position with one row written."""
    weights = (param_count(m) + 2 * m.d_model * m.d_r) * weight_bytes
    state = m.num_layers * (4 * m.mamba_heads * m.mamba_head_dim * m.d_state
                            + weight_bytes * (m.d_conv - 1) * m.conv_ch)
    kv_row = 2 * m.num_heads * m.head_dim * weight_bytes        # K and V
    hybrid = len(m.hybrid_layer_ids)
    return weights + sum(2 * state + hybrid * kv_row * (p + 1)
                         for p in positions)
