"""Top-level model build/init/apply for every assigned architecture.

Public API:
  build(cfg, long_mode)        -> BuiltModel (segmentation, metadata)
  init_model(key, built)       -> (params, param_specs)
  forward_train(params, built, batch, pctx)   -> (logits, aux)
  forward_prefill(params, built, batch, pctx) -> (logits, caches)
  forward_decode(params, built, tokens, caches, pos, pctx) -> (logits, caches)
  input_specs(built, shape, pctx) -> (batch tree of ShapeDtypeStruct, PartitionSpec tree)
  decode_state_specs(built, shape, pctx) -> (cache SDS tree, cache spec tree)

Modality frontends are stubs per the assignment carve-out: pixtral gets
precomputed patch embeddings, whisper gets precomputed frame embeddings —
both already at d_model.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import InputShape, ModelConfig
from repro.core import butterfly as bf_lib
from repro.models import attention as attn_lib
from repro.models import transformer as tfm
from repro.models.common import embed, init_embedding, init_rms_norm, rms_norm, \
    sinusoid_positions, trunc_normal, unembed
from repro.models.parallel import LOCAL, ParallelContext

# ---------------------------------------------------------------------------
# build
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class BuiltModel:
    cfg: ModelConfig
    stages: tuple                     # tuple of tuple[Segment]
    enc_segments: tuple               # whisper encoder segments (or ())
    long_mode: bool = False

    @property
    def has_butterfly(self) -> bool:
        return self.cfg.butterfly is not None


def build(cfg: ModelConfig, long_mode: bool = False) -> BuiltModel:
    defs = tfm.build_layer_defs(cfg, long_mode=long_mode)
    boundary = cfg.butterfly.layer if cfg.butterfly is not None else None
    stages = tuple(tuple(s) for s in tfm.split_defs(defs, boundary))
    enc_segments = ()
    if cfg.is_encdec:
        enc_defs = [tfm.LayerDef(mixer="attn", ffn="mlp")] * cfg.encoder_layers
        enc_segments = tuple(tfm.segmentize(enc_defs))
    return BuiltModel(cfg=cfg, stages=stages, enc_segments=enc_segments,
                      long_mode=long_mode)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def init_model(key, built: BuiltModel):
    cfg = built.cfg
    dtype = _dtype(cfg)
    keys = iter(jax.random.split(key, 64))
    params: dict = {}
    specs: dict = {}

    params["embed"], specs["embed"] = init_embedding(next(keys), cfg.vocab_size,
                                                     cfg.d_model, dtype)
    params["final_norm"], specs["final_norm"] = init_rms_norm(cfg.d_model, dtype)
    if not cfg.tie_embeddings:
        params["head"], specs["head"] = init_embedding(next(keys), cfg.vocab_size,
                                                       cfg.d_model, dtype)

    stage_params, stage_specs = [], []
    for segs in built.stages:
        seg_params, seg_specs = [], []
        for seg in segs:
            p, s = tfm.init_segment(next(keys), seg, cfg, dtype)
            seg_params.append(p)
            seg_specs.append(s)
        stage_params.append(seg_params)
        stage_specs.append(seg_specs)
    params["stages"], specs["stages"] = stage_params, stage_specs

    if cfg.butterfly is not None:
        params["butterfly"], specs["butterfly"] = bf_lib.init_butterfly(
            next(keys), cfg.d_model, cfg.butterfly, dtype)

    if cfg.hybrid_layer_ids is not None:
        params["shared_attn"], specs["shared_attn"] = _init_shared_blocks(
            keys, cfg, dtype)

    if cfg.is_encdec:
        enc_p, enc_s = [], []
        for seg in built.enc_segments:
            p, s = tfm.init_segment(next(keys), seg, cfg, dtype)
            enc_p.append(p)
            enc_s.append(s)
        nw, ns = init_rms_norm(cfg.d_model, dtype)
        params["encoder"] = {"segments": enc_p, "final_norm": nw}
        specs["encoder"] = {"segments": enc_s, "final_norm": ns}

    return params, specs


def _init_shared_blocks(keys, cfg: ModelConfig, dtype):
    """zamba2: ``num_mem_blocks`` shared attention+MLP blocks (attention over
    the residual concatenated with the token embeddings, 2 * d_model wide)
    and one LoRA adapter on the MLP's gate/up projection per invocation."""
    from repro.models.common import dense_init, init_mlp
    d, r = cfg.d_model, cfg.adapter_rank
    blocks, block_specs = [], []
    for _ in range(cfg.num_mem_blocks):
        pa, sa = attn_lib.init_attention(next(keys), cfg, dtype, d_in=2 * d)
        pm, sm = init_mlp(next(keys), d, cfg.d_ff, dtype)
        n_in, s_in = init_rms_norm(2 * d, dtype)
        n_ff, s_ff = init_rms_norm(d, dtype)
        blocks.append({"norm_in": n_in, "mixer": pa, "norm_ff": n_ff,
                       "ffn": pm})
        block_specs.append({"norm_in": s_in, "mixer": sa, "norm_ff": s_ff,
                            "ffn": sm})
    adapters, adapter_specs = [], []
    for _ in cfg.hybrid_layer_ids:
        ka, kb = jax.random.split(next(keys))
        adapters.append({"a": dense_init(ka, d, r, dtype),
                         "b": dense_init(kb, r, 2 * cfg.d_ff, dtype)})
        adapter_specs.append({"a": P(None, None), "b": P(None, None)})
    return ({"blocks": blocks, "adapters": adapters},
            {"blocks": block_specs, "adapters": adapter_specs})


def tp_param_specs(built: BuiltModel, *, with_butterfly: Optional[bool] = None):
    """PartitionSpec pytree matching :func:`init_model`'s params with every
    stage layer sharded tensor-parallel over the ``model`` axis (attention
    heads / d_ff columns / experts; see ``transformer.tp_layer_specs``) and
    everything else — embeddings, norms, LM head, butterfly — replicated.
    This is the in_specs tree manual shard_map stages feed params through
    (serving/pipeline.py, runtime/split_exec.py)."""
    from jax.sharding import PartitionSpec as P  # noqa: F811 (local alias)
    cfg = built.cfg
    assert not cfg.is_encdec, "enc-dec archs have no tensor-parallel stages"
    dt = _dtype(cfg)
    if with_butterfly is None:
        with_butterfly = built.has_butterfly
    specs: dict = {
        "embed": P(),
        "final_norm": P(),
        "stages": [tfm.tp_stage_specs(list(segs), cfg, dt)
                   for segs in built.stages],
    }
    if not cfg.tie_embeddings:
        specs["head"] = P()
    if with_butterfly:
        specs["butterfly"] = {"w_reduce": P(), "w_restore": P()}
    if cfg.hybrid_layer_ids is not None:
        # the shared blocks have no tensor-parallel decomposition here: every
        # rank holds them whole and runs them redundantly, as the mixers
        shapes = jax.eval_shape(lambda k: _init_shared_blocks(
            iter(jax.random.split(k, 64)), cfg, dt)[0], jax.random.key(0))
        specs["shared_attn"] = jax.tree.map(lambda _: P(), shapes)
    return specs


# ---------------------------------------------------------------------------
# embedding frontends
# ---------------------------------------------------------------------------


def _embed_inputs(params, built: BuiltModel, batch: dict, pos0: int = 0):
    """Token (+stub modality) embeddings -> (B, S, d) residual stream input."""
    cfg = built.cfg
    scale = cfg.arch_type == "dense" and cfg.act == "gelu"   # gemma family
    x = embed(params["embed"], batch["tokens"], scale=scale)
    if cfg.is_encdec:
        S = x.shape[1]
        sin = sinusoid_positions(pos0 + S, cfg.d_model)[pos0:pos0 + S]
        x = x + sin[None].astype(x.dtype)
    if cfg.num_patches and "patches" in batch:
        x = jnp.concatenate([batch["patches"].astype(x.dtype), x], axis=1)
    return x


def _encode(params, built: BuiltModel, frames, pctx, use_kernel=False):
    cfg = built.cfg
    sin = sinusoid_positions(frames.shape[1], cfg.d_model)
    x = frames.astype(_dtype(cfg)) + sin[None].astype(_dtype(cfg))
    for si, seg in enumerate(built.enc_segments):
        x, _, _, _ = tfm.apply_segment(
            seg, params["encoder"]["segments"][si], x, cfg=cfg, pctx=pctx,
            mode="train", seg_cache=None, pos=None, causal=False,
            use_kernel=use_kernel)
    return rms_norm(x, params["encoder"]["final_norm"], cfg.rms_eps)


def _logits(params, built: BuiltModel, x):
    cfg = built.cfg
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    table = params["embed"] if cfg.tie_embeddings else params["head"]
    return unembed(table, x, cfg.logit_softcap)


def _run_stages(params, built: BuiltModel, x, *, mode, pctx, caches, pos,
                enc_out, use_kernel, train: bool):
    cfg = built.cfg
    shared = params.get("shared_attn")
    # hybrid layers read the token embeddings beside the residual, exact
    # on either side of the butterfly
    x_emb = x if cfg.hybrid_layer_ids is not None else None
    aux_total = jnp.zeros((2,), jnp.float32)
    rate = jnp.float32(0.0)
    new_caches = []
    for stage_idx, segs in enumerate(built.stages):
        if stage_idx == 1:
            if train and cfg.butterfly.rate_weight > 0:
                # entropy-rate of the wire codes under the codec prior;
                # recomputes the (cheap, d_r-wide) reduce matmul so the
                # serving-path apply_butterfly signature stays untouched
                from repro.core.wire_codec import rate_bits
                rate = rate_bits(x @ params["butterfly"]["w_reduce"],
                                 bits=cfg.butterfly.wire_bits)
            x = bf_lib.apply_butterfly(params["butterfly"], x,
                                       wire_bits=cfg.butterfly.wire_bits,
                                       train=train, use_kernel=use_kernel)
        stage_cache = None if caches is None else caches[stage_idx]
        x, nc, aux = tfm.apply_stage(
            list(segs), params["stages"][stage_idx], x, cfg=cfg, pctx=pctx,
            mode=mode, stage_cache=stage_cache, pos=pos, enc_out=enc_out,
            shared_params=shared, use_kernel=use_kernel, x_emb=x_emb)
        new_caches.append(nc)
        aux_total = aux_total + aux
    return x, new_caches, aux_total, rate


# ---------------------------------------------------------------------------
# forward passes
# ---------------------------------------------------------------------------


def forward_train(params, built: BuiltModel, batch: dict,
                  pctx: ParallelContext = LOCAL, use_kernel: bool = False):
    cfg = built.cfg
    enc_out = None
    if cfg.is_encdec:
        enc_out = _encode(params, built, batch["frames"], pctx, use_kernel)
    x = _embed_inputs(params, built, batch)
    x, _, aux, rate = _run_stages(params, built, x, mode="train", pctx=pctx,
                                  caches=None, pos=None, enc_out=enc_out,
                                  use_kernel=use_kernel, train=True)
    logits = _logits(params, built, x)
    return logits, {"load_balance": aux[0], "router_z": aux[1],
                    "wire_rate_bits": rate}


def forward_prefill(params, built: BuiltModel, batch: dict,
                    pctx: ParallelContext = LOCAL, use_kernel: bool = False):
    cfg = built.cfg
    enc_out = None
    if cfg.is_encdec:
        enc_out = _encode(params, built, batch["frames"], pctx, use_kernel)
    x = _embed_inputs(params, built, batch)
    x, caches, _, _ = _run_stages(params, built, x, mode="prefill", pctx=pctx,
                                  caches=None, pos=None, enc_out=enc_out,
                                  use_kernel=use_kernel, train=False)
    logits = _logits(params, built, x[:, -1:])
    return logits, caches


def forward_decode(params, built: BuiltModel, tokens, caches, pos,
                   pctx: ParallelContext = LOCAL, use_kernel: bool = False):
    """tokens: (B, 1); pos: int32 scalar (absolute position of this token)."""
    cfg = built.cfg
    if cfg.is_encdec:
        # sinusoid position embedding at the (dynamic) absolute position
        import math as _math
        x = embed(params["embed"], tokens)
        dim = jnp.arange(cfg.d_model // 2, dtype=jnp.float32)[None, :]
        inv = jnp.exp(-_math.log(10000.0) * dim / max(cfg.d_model // 2 - 1, 1))
        a = jnp.asarray(pos, jnp.float32) * inv
        sin = jnp.concatenate([jnp.sin(a), jnp.cos(a)], axis=-1)
        x = x + sin[None].astype(x.dtype)
    else:
        scale = cfg.arch_type == "dense" and cfg.act == "gelu"
        x = embed(params["embed"], tokens, scale=scale)
    x, new_caches, _, _ = _run_stages(params, built, x, mode="decode",
                                      pctx=pctx, caches=caches, pos=pos,
                                      enc_out=None, use_kernel=use_kernel,
                                      train=False)
    logits = _logits(params, built, x)
    return logits, new_caches


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------


def lm_loss(logits, targets, ignore: int = -1):
    """Cross entropy; targets == ignore are masked (vlm patch positions)."""
    mask = (targets != ignore)
    tgt = jnp.where(mask, targets, 0)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStructs for AOT lowering) + shardings
# ---------------------------------------------------------------------------


def input_specs(built: BuiltModel, shape: InputShape, pctx: ParallelContext):
    """Batch pytree of ShapeDtypeStruct + matching PartitionSpec tree."""
    cfg = built.cfg
    B, S = shape.global_batch, shape.seq_len
    dp = pctx.batch_spec_axes()
    bx = dp if (pctx.enabled and B % max(pctx.dp_size, 1) == 0 and B >= pctx.dp_size) else None
    sds, spec = {}, {}
    i32 = jnp.int32
    dt = _dtype(cfg)

    if shape.kind == "train":
        if cfg.num_patches:
            n_text = S - cfg.num_patches
            sds["tokens"] = jax.ShapeDtypeStruct((B, n_text), i32)
            sds["patches"] = jax.ShapeDtypeStruct((B, cfg.num_patches, cfg.d_model), dt)
            spec["tokens"] = P(bx, None)
            spec["patches"] = P(bx, None, None)
        else:
            sds["tokens"] = jax.ShapeDtypeStruct((B, S), i32)
            spec["tokens"] = P(bx, None)
        if cfg.is_encdec:
            sds["frames"] = jax.ShapeDtypeStruct((B, cfg.encoder_frames, cfg.d_model), dt)
            spec["frames"] = P(bx, None, None)
        sds["targets"] = jax.ShapeDtypeStruct((B, S), i32)
        spec["targets"] = P(bx, None)
    elif shape.kind == "prefill":
        if cfg.num_patches:
            sds["tokens"] = jax.ShapeDtypeStruct((B, S - cfg.num_patches), i32)
            sds["patches"] = jax.ShapeDtypeStruct((B, cfg.num_patches, cfg.d_model), dt)
            spec["tokens"] = P(bx, None)
            spec["patches"] = P(bx, None, None)
        else:
            sds["tokens"] = jax.ShapeDtypeStruct((B, S), i32)
            spec["tokens"] = P(bx, None)
        if cfg.is_encdec:
            sds["frames"] = jax.ShapeDtypeStruct((B, cfg.encoder_frames, cfg.d_model), dt)
            spec["frames"] = P(bx, None, None)
    else:  # decode
        sds["tokens"] = jax.ShapeDtypeStruct((B, 1), i32)
        spec["tokens"] = P(bx, None)
    return sds, spec


def decode_state_specs(built: BuiltModel, shape: InputShape,
                       pctx: ParallelContext, seq_axis=None):
    """Cache ShapeDtypeStructs + PartitionSpecs for a decode serve_step."""
    cfg = built.cfg
    B, S = shape.global_batch, shape.seq_len
    dp = pctx.batch_spec_axes()
    bx = dp if (pctx.enabled and B % max(pctx.dp_size, 1) == 0 and B >= pctx.dp_size) else None
    dt = _dtype(cfg)

    def mk():
        return [tfm.init_stage_cache(list(segs), cfg, B, S, dt)
                for segs in built.stages]

    sds = jax.eval_shape(mk)
    specs = [tfm.stage_cache_spec(list(segs), bx, seq_axis)
             for segs in built.stages]
    return sds, specs
