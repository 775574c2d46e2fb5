"""Qwen3 (https://huggingface.co/Qwen/Qwen3-8B): a dense stack of one
repeated layer.

Pre-norm RMSNorm with a gain ``1 + w``, GQA attention with per-head RMSNorm
on queries and keys and split-half rotary embeddings, a SwiGLU MLP, an
untied head.  The program serves it as its ``dense`` stack with
``qk_norm``.  The interface this module provides is written in ``spec.py``.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax

import reference as R
import weights as W


@dataclass(frozen=True)
class Dims:
    """The dims the harness, the reference and the work counts use."""
    name: str
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    rms_eps: float
    split: int          # butterfly after this many layers
    d_r: int
    wire_bits: int
    arch: str = "qwen3"


def dims(name: str, c: dict) -> Dims:
    """Dims from a configuration file (published ``config.json`` keys)."""
    if c.get("tie_word_embeddings", False) or c.get("hidden_act") != "silu" \
            or c.get("attention_bias", False):
        raise ValueError(f"{name}: only untied, silu, bias-free qwen3 stacks")
    bf = c["butterfly"]
    return Dims(name=name, num_layers=c["num_hidden_layers"],
                d_model=c["hidden_size"],
                num_heads=c["num_attention_heads"],
                num_kv_heads=c["num_key_value_heads"],
                head_dim=c["head_dim"], d_ff=c["intermediate_size"],
                vocab=c["vocab_size"], rope_theta=float(c["rope_theta"]),
                rms_eps=float(c["rms_norm_eps"]), split=bf["after_layer"],
                d_r=bf["d_r"], wire_bits=bf["wire_bits"])


# -------------------------------------------------------------------- leaves
LEAF_IDS = {"wq": 0, "wk": 1, "wv": 2, "wo": 3, "q_norm": 4, "k_norm": 5,
            "norm1": 6, "norm2": 7, "w_gate": 8, "w_up": 9, "w_down": 10,
            "embed": 11, "head": 12, "final_norm": 13}


def layer_leaves(m, layer: int) -> dict:
    """Every layer has the same leaves."""
    d, hd = m.d_model, m.head_dim
    return {"wq": (d, m.num_heads * hd), "wk": (d, m.num_kv_heads * hd),
            "wv": (d, m.num_kv_heads * hd), "wo": (m.num_heads * hd, d),
            "q_norm": (hd,), "k_norm": (hd,), "norm1": (d,), "norm2": (d,),
            "w_gate": (d, m.d_ff), "w_up": (d, m.d_ff),
            "w_down": (m.d_ff, d)}


def global_leaves(m) -> dict:
    return {"embed": (m.vocab, m.d_model), "head": (m.vocab, m.d_model),
            "final_norm": (m.d_model,)}


# ------------------------------------------------------------------- program
def program_config(m):
    from repro.configs.base import ModelConfig
    return ModelConfig(name=m.name, arch_type="dense",
                       num_layers=m.num_layers, d_model=m.d_model,
                       num_heads=m.num_heads, num_kv_heads=m.num_kv_heads,
                       head_dim=m.head_dim, d_ff=m.d_ff, vocab_size=m.vocab,
                       qk_norm=True, act="silu", rope_theta=m.rope_theta,
                       rms_eps=m.rms_eps, tie_embeddings=False,
                       dtype="bfloat16")


def program_tree(w: dict, m) -> dict:
    """``repro.models.model.init_model``'s layout for a dense stack of one
    repeated layer."""
    lw = w["layers"]
    layer = {"norm1": lw["norm1"], "norm2": lw["norm2"],
             "mixer": {k: lw[k] for k in ("wq", "wk", "wv", "wo", "q_norm",
                                          "k_norm")},
             "ffn": {k: lw[k] for k in ("w_gate", "w_up", "w_down")}}
    return {"embed": w["embed"], "final_norm": w["final_norm"],
            "head": w["head"], "stages": [[[layer]]]}


# ----------------------------------------------------------------- reference
def layer(x, lw, m, control: bool):
    S = x.shape[0]
    hd = m.head_dim
    h = R.rms_norm(x, lw["norm1"], m.rms_eps)
    q = R._mm(h, lw["wq"], control).reshape(S, m.num_heads, hd)
    k = R._mm(h, lw["wk"], control).reshape(S, m.num_kv_heads, hd)
    v = R._mm(h, lw["wv"], control).reshape(S, m.num_kv_heads, hd)
    q = R.rope(R.rms_norm(q, lw["q_norm"], m.rms_eps), m.rope_theta)
    k = R.rope(R.rms_norm(k, lw["k_norm"], m.rms_eps), m.rope_theta)
    x = x + R._mm(R.attention(q, k, v).reshape(S, -1), lw["wo"], control)
    h = R.rms_norm(x, lw["norm2"], m.rms_eps)
    g = R._mm(h, lw["w_gate"], control)
    u = R._mm(h, lw["w_up"], control)
    return x + R._mm(jax.nn.silu(g) * u, lw["w_down"], control)


def forward(seed: int, m, xs, control: bool) -> list:
    """Every layer in turn, each drawn alone, the wire after ``split``."""
    step = jax.jit(lambda x, lw: layer(x, lw, m, control))
    for li in range(m.num_layers):
        lw = R.f32(W.draw_layer(seed, m, li))
        xs = [step(x, lw) for x in xs]
        if li == m.split - 1:
            xs = R.wire_rows(seed, m, xs, control)
        del lw
    return xs


# -------------------------------------------------------------------- counts
def layer_params(m) -> int:
    """Weights of one layer's matrix multiplications."""
    d, q, kv = m.d_model, m.num_heads * m.head_dim, m.num_kv_heads * \
        m.head_dim
    return d * q + 2 * d * kv + q * d + 3 * d * m.d_ff


def _attn(m, layers: int, queries_keys: int) -> int:
    """QK^T and PV over ``queries_keys`` (query, key) pairs per layer."""
    return 4 * m.num_heads * m.head_dim * queries_keys * layers


def _pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def edge_prefill(m, seq: int) -> int:
    """Layers [0, split) over ``seq`` tokens and the reduction."""
    return (2 * seq * layer_params(m) * m.split + _attn(m, m.split, _pairs(seq))
            + 2 * seq * m.d_model * m.d_r)


def cloud_prefill(m, seq: int) -> int:
    """Restoration, layers [split, L) over ``seq`` tokens and the head at the
    last position."""
    n = m.num_layers - m.split
    return (2 * seq * m.d_r * m.d_model + 2 * seq * layer_params(m) * n
            + _attn(m, n, _pairs(seq)) + 2 * m.d_model * m.vocab)


def edge_step(m, pos: int) -> int:
    """One streamed token on the edge: layers [0, split) and the reduction;
    the token at position ``pos`` attends to ``pos + 1`` keys."""
    return (2 * layer_params(m) * m.split + _attn(m, m.split, pos + 1)
            + 2 * m.d_model * m.d_r)


def cloud_step(m, pos: int) -> int:
    """One streamed token on the cloud: restoration, layers [split, L), head."""
    n = m.num_layers - m.split
    return (2 * m.d_r * m.d_model + 2 * layer_params(m) * n
            + _attn(m, n, pos + 1) + 2 * m.d_model * m.vocab)


def decode_token(m, pos: int) -> int:
    """One token of the batched decode step: the whole model with the wire."""
    return edge_step(m, pos) + cloud_step(m, pos)
