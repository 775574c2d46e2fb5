"""Segment/scan-based layer stack shared by all assigned architectures.

A model is a flat list of ``LayerDef``s compressed into ``Segment``s (a
repeating unit scanned with stacked params) so the lowered HLO is O(#segment
kinds), not O(depth) — this keeps 94-layer compiles fast.  The butterfly
split cuts the flat list at the configured boundary, producing two stages;
the butterfly unit (the paper's contribution) runs between them.

Layer kinds: mixer in {attn, mamba, mlstm, slstm} x ffn in {mlp, moe, None};
``hybrid=k`` marks a zamba2 layer that runs invocation ``k`` of the
shared-parameter attention+MLP blocks before its own mixer, over the
residual and the token embeddings (``x_emb``, carried beside the residual);
``cross`` adds whisper-style cross attention.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models import xlstm as xlstm_lib
from repro.models.common import (apply_mlp, dense_init, dense_spec, glu_act,
                                 init_mlp, init_rms_norm, rms_norm)
from repro.models.parallel import LOCAL, ParallelContext, model_psum

# Dry-run knob: when True, segment scans fully unroll so XLA's cost analysis
# (which counts while-loop bodies once) reports exact per-step FLOPs/bytes.
# An int k unrolls k iterations per while step (the two-point scan-correction
# probe in launch/dryrun.py). Training/serving keep scans rolled.
SCAN_UNROLL = False


def _scan_unroll(repeats: int) -> int:
    if SCAN_UNROLL is True:
        return repeats
    if SCAN_UNROLL:
        return min(int(SCAN_UNROLL), repeats)
    return 1

# ---------------------------------------------------------------------------
# layer defs and segmentation
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class LayerDef:
    mixer: str                      # attn | mamba | mlstm | slstm
    ffn: Optional[str] = "mlp"      # mlp | moe | None
    window: Optional[int] = None
    hybrid: Optional[int] = None    # zamba2: shared-block invocation index
    cross: bool = False             # whisper decoder cross-attention


@dataclass(frozen=True)
class Segment:
    unit: Tuple[LayerDef, ...]
    repeats: int

    @property
    def num_layers(self) -> int:
        return len(self.unit) * self.repeats


def build_layer_defs(cfg: ModelConfig, long_mode: bool = False) -> List[LayerDef]:
    """The flat per-layer spec for an architecture.

    ``long_mode`` — the long_500k sub-quadratic variant: every attention layer
    runs with a bounded window (cfg.long_context_window)."""
    defs: List[LayerDef] = []
    for i in range(cfg.num_layers):
        if cfg.xlstm is not None:
            every = cfg.xlstm.slstm_every
            mixer = "slstm" if (i % every == every - 1) else "mlstm"
            defs.append(LayerDef(mixer=mixer, ffn=None))
            continue
        if cfg.hybrid_layer_ids is not None:
            # every layer has its own Mamba2 mixer; a hybrid layer runs a
            # shared block (windowed in long mode) before it
            ids = list(cfg.hybrid_layer_ids)
            if i in ids:
                window = cfg.long_context_window if long_mode else None
                defs.append(LayerDef(mixer="mamba", ffn=None,
                                     hybrid=ids.index(i), window=window))
            else:
                defs.append(LayerDef(mixer="mamba", ffn=None))
            continue
        if cfg.arch_type == "ssm" and cfg.ssm is not None:
            defs.append(LayerDef(mixer="mamba", ffn=None))
            continue
        # attention archs
        window = None
        if cfg.sliding_window is not None:
            if cfg.global_every is None or (i % cfg.global_every != cfg.global_every - 1):
                window = cfg.sliding_window
            elif long_mode:
                window = cfg.long_context_window
        elif long_mode and cfg.long_context_window is not None:
            window = cfg.long_context_window
        ffn = "mlp"
        if cfg.moe is not None and (i % cfg.moe.every == cfg.moe.every - 1):
            ffn = "moe"
        defs.append(LayerDef(mixer="attn", ffn=ffn, window=window,
                             cross=cfg.is_encdec))
    return defs


def segmentize(defs: Sequence[LayerDef]) -> List[Segment]:
    """Compress a flat def list into repeated-unit segments (greedy)."""
    defs = list(defs)
    if not defs:
        return []
    best = None
    for u in range(1, min(len(defs), 8) + 1):
        unit = tuple(defs[:u])
        if u > 1 and any(d.hybrid is not None for d in unit):
            # a hybrid layer's invocation is its own: a unit holding one
            # never repeats, so it would only unroll its neighbours
            break
        reps = 1
        while (reps + 1) * u <= len(defs) and tuple(defs[reps * u:(reps + 1) * u]) == unit:
            reps += 1
        covered = reps * u
        # prefer covering more layers with fewer scans; tie-break small unit
        score = (covered, -u)
        if best is None or score > best[0]:
            best = (score, unit, reps)
    _, unit, reps = best
    head = [Segment(unit=unit, repeats=reps)]
    return head + segmentize(defs[len(unit) * reps:])


def split_defs(defs: Sequence[LayerDef], boundary: Optional[int]) -> List[List[Segment]]:
    """Stage list for a butterfly at ``boundary`` (layers [0,b) | [b,N))."""
    if boundary is None:
        return [segmentize(defs)]
    assert 0 < boundary < len(defs), boundary
    return [segmentize(defs[:boundary]), segmentize(defs[boundary:])]


# ---------------------------------------------------------------------------
# layer-range views over a full stacked stage (shared-weight split bank)
# ---------------------------------------------------------------------------


def _range_spans(segments: Sequence[Segment], lo: int, hi: int):
    """Walk a stage's segmentation and yield, for flat layers [lo, hi), either
    aligned repeat-slices or per-layer peels:

      ("slice", seg_index, rep_lo, rep_hi)    — whole repeats [rep_lo, rep_hi)
      ("peel",  seg_index, rep, pos_in_unit)  — one layer of one repeat

    A boundary that lands inside a repeat unit peels individual layers so any
    0 < boundary < N is representable (gemma3-style multi-layer units)."""
    base = 0
    for si, seg in enumerate(segments):
        u = len(seg.unit)
        span = u * seg.repeats
        s, e = max(lo, base) - base, min(hi, base + span) - base
        if s < e:
            # peel only the unaligned head/tail remainders; the aligned
            # middle keeps its stacked-repeat scan
            head = min(e, (s + u - 1) // u * u)
            tail = max(head, e // u * u)
            for li in range(s, head):
                yield ("peel", si, li // u, li % u)
            if head < tail:
                yield ("slice", si, head // u, tail // u)
            for li in range(tail, e):
                yield ("peel", si, li // u, li % u)
        base += span


def range_segments(segments: Sequence[Segment], lo: int, hi: int) -> List[Segment]:
    """Segmentation of the flat layer range [lo, hi) of a full stage, one
    segment per span of :func:`_range_spans`: the structure of
    :func:`apply_layer_range`'s range cache (and of what
    :func:`slice_stage_params` produces), so cache templates built from it
    line up with both."""
    out: List[Segment] = []
    for span in _range_spans(segments, lo, hi):
        if span[0] == "slice":
            _, si, r0, r1 = span
            out.append(Segment(unit=segments[si].unit, repeats=r1 - r0))
        else:
            _, si, _, pos = span
            out.append(Segment(unit=(segments[si].unit[pos],), repeats=1))
    return out


def slice_stage_params(segments: Sequence[Segment], stage_params, lo: int,
                       hi: int):
    """Restrict a stage's stacked params to flat layers [lo, hi).

    Returns ``(segments', params')`` where every leaf of ``params'`` is a
    static slice ``a[r0:r1]`` of the corresponding full stacked leaf.  Each
    slice is a real copy of its layers' weights, eagerly or under jit (XLA
    materialises a slice that feeds a scan); it is kept for the decode
    pipeline, which slices once, eagerly, outside its program.  Programs
    that run a range of a shared stack use :func:`apply_layer_range`, which
    reads the stacked leaves in place."""
    out_segs: List[Segment] = []
    out_params = []
    for span in _range_spans(segments, lo, hi):
        if span[0] == "slice":
            _, si, r0, r1 = span
            out_segs.append(Segment(unit=segments[si].unit, repeats=r1 - r0))
            out_params.append([jax.tree.map(lambda a: a[r0:r1], up)
                               for up in stage_params[si]])
        else:
            _, si, rep, pos = span
            out_segs.append(Segment(unit=(segments[si].unit[pos],), repeats=1))
            out_params.append([jax.tree.map(lambda a: a[rep:rep + 1],
                                            stage_params[si][pos])])
    return out_segs, out_params


def apply_layer_range(segments: Sequence[Segment], stage_params, x, lo: int,
                      hi: int, *, cfg, pctx, mode, range_cache, pos,
                      enc_out=None, shared_params=None, use_kernel=False,
                      causal=True, x_emb=None):
    """Run flat layers [lo, hi) of a full stacked stage, reading each
    layer's weights in place.  Every span of :func:`_range_spans` scans its
    repeat indices over the full stacked leaves of its segment (closed over,
    so they are loop-invariant operands of the while loop) and the body
    takes repeat ``i`` by dynamic index: no range of the stack is copied
    out.  A peel scans its one index over the one layer's leaves the same
    way.  ``range_cache`` must be structured per :func:`range_segments`
    (see init_stage_cache).  In decode each span's stacked cache is carried
    through its scan and every layer writes its new rows and state into it
    at its index, so a step updates its cache in place (the caller donates
    it); prefill scans no cache in and emits each layer's as ``ys``.
    ``x_emb`` (the token embeddings, for hybrid layers) is carried beside
    the residual.  Same layers, weights and order as
    :func:`slice_stage_params` followed by :func:`apply_stage`."""
    kw = dict(cfg=cfg, pctx=pctx, mode=mode, pos=pos, enc_out=enc_out,
              shared_params=shared_params, use_kernel=use_kernel,
              causal=causal, x_emb=x_emb)
    decode = mode == "decode"
    aux_total = jnp.zeros((2,), jnp.float32)
    new_caches = []
    for k, span in enumerate(_range_spans(segments, lo, hi)):
        if span[0] == "slice":
            _, si, r0, r1 = span
            seg, unit_params = segments[si], stage_params[si]
        else:
            _, si, r0, upos = span
            r1 = r0 + 1
            seg = Segment(unit=(segments[si].unit[upos],), repeats=1)
            unit_params = [stage_params[si][upos]]

        def body(carry, i, seg=seg, unit_params=unit_params, r0=r0):
            xc, aux_sum, stack = carry
            layer = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False),
                unit_params)
            xc, aux_sum, _, nc = _apply_unit(
                seg, layer, stack, xc, aux_sum, None,
                layer=i - r0 if decode else None, **kw)
            return (xc, aux_sum, nc if decode else None), \
                None if decode else nc

        stack = range_cache[k] if decode else None
        (x, aux, stack), nc = jax.lax.scan(
            body, (x, jnp.zeros((2,), jnp.float32), stack),
            jnp.arange(r0, r1), unroll=_scan_unroll(r1 - r0))
        new_caches.append(stack if decode else nc)
        aux_total = aux_total + aux
    return x, new_caches, aux_total


def first_layer_norm1(segments: Sequence[Segment], stage_params, lo: int = 0):
    """The norm1 weight of flat layer ``lo`` of a stacked stage — what the
    fused dequant+restore+norm kernel needs to pre-compute that layer's
    input norm at the butterfly boundary."""
    for span in _range_spans(segments, lo, lo + 1):
        if span[0] == "peel":
            _, si, rep, pos = span
            return stage_params[si][pos]["norm1"][rep]
        _, si, r0, _ = span
        return stage_params[si][0]["norm1"][r0]
    raise ValueError(f"layer {lo} out of range")


# ---------------------------------------------------------------------------
# tensor-parallel (model-axis) sharding specs for manual shard_map stages
# ---------------------------------------------------------------------------


def check_tp_divisibility(defs: Sequence[LayerDef], cfg: ModelConfig,
                          mp: int) -> None:
    """Model-parallel stages shard whole attention heads, whole d_ff columns
    and whole experts — fail loudly when ``mp`` can't divide them.  Mixers
    without a tensor-parallel decomposition here (mamba/xlstm, and the
    shared blocks of hybrid layers) replicate and run redundantly per rank,
    so they impose no constraint."""
    if mp <= 1:
        return
    for ldef in defs:
        if ldef.mixer != "attn":
            continue
        if ldef.cross:
            raise ValueError("tensor-parallel stages do not support "
                             "cross-attention layers")
        if attn._padded_heads(cfg) % mp or cfg.num_kv_heads % mp:
            raise ValueError(
                f"model axis {mp} must divide heads "
                f"({attn._padded_heads(cfg)}) and kv heads "
                f"({cfg.num_kv_heads})")
        if ldef.ffn == "mlp" and cfg.d_ff % mp:
            raise ValueError(f"model axis {mp} must divide d_ff ({cfg.d_ff})")
        if ldef.ffn == "moe" and cfg.moe.num_experts % mp:
            raise ValueError(f"model axis {mp} must divide num_experts "
                             f"({cfg.moe.num_experts})")


def tp_layer_specs(ldef: LayerDef, cfg: ModelConfig, dtype,
                   axis: str = "model"):
    """PartitionSpec tree for one layer's params with attention heads, d_ff
    and experts sharded over ``axis`` (everything else replicated) — the
    in_specs a manual shard_map stage feeds params through.  Structure
    mirrors :func:`init_layer` exactly (built by replicating the init spec
    tree, then overriding the shardable projections)."""
    specs = jax.tree.map(lambda _: P(), layer_specs(ldef, cfg, dtype),
                         is_leaf=lambda s: isinstance(s, P))
    if ldef.mixer == "attn":
        specs["mixer"] = attn.tp_attention_specs(cfg, axis)
    if ldef.mixer == "attn" and ldef.ffn == "mlp":
        specs["ffn"] = tp_mlp_specs(axis)
    elif ldef.mixer == "attn" and ldef.ffn == "moe":
        specs["ffn"]["wg"] = P(axis, None, None)   # expert dim -> model axis
        specs["ffn"]["wu"] = P(axis, None, None)
        specs["ffn"]["wd"] = P(axis, None, None)
        # router (and the shared expert, when present) stay replicated: their
        # outputs are full, so only the routed-expert partials get psum'd
    return specs


def tp_mlp_specs(axis: str = "model") -> dict:
    return {"w_gate": P(None, axis), "w_up": P(None, axis),
            "w_down": P(axis, None)}


def tp_stage_specs(segments: Sequence[Segment], cfg: ModelConfig, dtype,
                   axis: str = "model"):
    """Spec tree matching :func:`init_segment` stacking for a whole stage
    (leading repeats dim unsharded)."""
    out = []
    for seg in segments:
        out.append([_prepend_none(tp_layer_specs(ldef, cfg, dtype, axis))
                    for ldef in seg.unit])
    return out


# ---------------------------------------------------------------------------
# per-layer init
# ---------------------------------------------------------------------------


def _prepend_none(spec_tree):
    return jax.tree.map(
        lambda s: P(*((None,) + tuple(s))), spec_tree,
        is_leaf=lambda s: isinstance(s, P))


def init_layer(key, ldef: LayerDef, cfg: ModelConfig, dtype):
    params: dict = {}
    specs: dict = {}
    ks = iter(jax.random.split(key, 8))
    params["norm1"], specs["norm1"] = init_rms_norm(cfg.d_model, dtype)
    if ldef.mixer == "attn":
        params["mixer"], specs["mixer"] = attn.init_attention(next(ks), cfg, dtype)
        if ldef.cross:
            params["norm_cross"], specs["norm_cross"] = init_rms_norm(cfg.d_model, dtype)
            params["cross"], specs["cross"] = attn.init_attention(next(ks), cfg, dtype)
    elif ldef.mixer == "mamba":
        params["mixer"], specs["mixer"] = ssm_lib.init_mamba(next(ks), cfg, dtype)
        if ldef.hybrid is not None:
            # the shared blocks live at the top level; the layer owns the
            # projection of their output into its mixer's input
            params["linear"] = dense_init(next(ks), cfg.d_model, cfg.d_model,
                                          dtype)
            specs["linear"] = dense_spec((cfg.d_model, cfg.d_model), None)
    elif ldef.mixer == "mlstm":
        params["mixer"], specs["mixer"] = xlstm_lib.init_mlstm(next(ks), cfg, dtype)
    elif ldef.mixer == "slstm":
        params["mixer"], specs["mixer"] = xlstm_lib.init_slstm(next(ks), cfg, dtype)
    else:
        raise ValueError(ldef.mixer)
    if ldef.ffn is not None and ldef.mixer == "attn":
        params["norm2"], specs["norm2"] = init_rms_norm(cfg.d_model, dtype)
        if ldef.ffn == "mlp":
            params["ffn"], specs["ffn"] = init_mlp(next(ks), cfg.d_model, cfg.d_ff, dtype)
        elif ldef.ffn == "moe":
            params["ffn"], specs["ffn"] = moe_lib.init_moe(next(ks), cfg, dtype)
    return params, specs


def layer_specs(ldef: LayerDef, cfg: ModelConfig, dtype):
    """Sharding specs for one layer, computed without allocating params."""
    captured = {}

    def fn(k):
        p, s = init_layer(k, ldef, cfg, dtype)
        captured["s"] = s
        return p

    jax.eval_shape(fn, jax.random.key(0))
    return captured["s"]


def init_segment(key, seg: Segment, cfg: ModelConfig, dtype):
    """Returns ([params per unit pos, stacked over repeats], matching specs)."""
    unit_params, unit_specs = [], []
    keys = jax.random.split(key, len(seg.unit))
    for ldef, k in zip(seg.unit, keys):
        rep_keys = jax.random.split(k, seg.repeats)
        p = jax.vmap(lambda kk: init_layer(kk, ldef, cfg, dtype)[0])(rep_keys)
        unit_params.append(p)
        unit_specs.append(_prepend_none(layer_specs(ldef, cfg, dtype)))
    return unit_params, unit_specs


# ---------------------------------------------------------------------------
# per-layer apply
# ---------------------------------------------------------------------------


def init_layer_cache(ldef: LayerDef, cfg: ModelConfig, batch: int, length: int,
                     dtype):
    """Cache template (zeros) for one layer in decode mode."""
    if ldef.mixer == "attn":
        cache_len = min(length, ldef.window) if ldef.window else length
        c = {"kv": attn.init_kv_cache(cfg, batch, cache_len, dtype)}
        if ldef.cross:
            hd = cfg.resolved_head_dim
            c["cross_kv"] = {
                "k": jnp.zeros((batch, cfg.encoder_frames, cfg.num_kv_heads, hd), dtype),
                "v": jnp.zeros((batch, cfg.encoder_frames, cfg.num_kv_heads, hd), dtype),
            }
        return c
    if ldef.mixer == "mamba":
        c = ssm_lib.init_ssm_state(cfg, batch, dtype)
        if ldef.hybrid is not None:       # the shared block's invocation KV
            cache_len = min(length, ldef.window) if ldef.window else length
            c["kv"] = attn.init_kv_cache(cfg, batch, cache_len, dtype)
        return c
    if ldef.mixer == "mlstm":
        return xlstm_lib.init_mlstm_state(cfg, batch, dtype)
    if ldef.mixer == "slstm":
        return xlstm_lib.init_slstm_state(cfg, batch, dtype)
    raise ValueError(ldef.mixer)


def layer_cache_spec(ldef: LayerDef, batch_axis, seq_axis, head_axis=None):
    if ldef.mixer == "attn":
        c = {"kv": attn.kv_cache_spec(batch_axis, seq_axis, head_axis)}
        if ldef.cross:
            c["cross_kv"] = attn.kv_cache_spec(batch_axis, None)
        return c
    if ldef.mixer == "mamba":
        c = ssm_lib.ssm_state_spec(batch_axis)
        if ldef.hybrid is not None:       # shared blocks replicate: whole heads
            c["kv"] = attn.kv_cache_spec(batch_axis, seq_axis, None)
        return c
    if ldef.mixer == "mlstm":
        return {"C": P(batch_axis, None, None, None), "n": P(batch_axis, None, None),
                "conv": P(batch_axis, None, None)}
    if ldef.mixer == "slstm":
        return {k: P(batch_axis, None, None) for k in ("c", "n", "h", "m")}
    raise ValueError(ldef.mixer)


def to_ring(kv: dict, window: int) -> dict:
    """Arrange the last ``window`` positions of a full-seq KV into ring order."""
    S = kv["k"].shape[1]
    if S <= window:
        return kv
    tail = {k: v[:, -window:] for k, v in kv.items()}
    slots = (jnp.arange(S - window, S)) % window
    return {k: jnp.zeros_like(v).at[:, slots].set(v) for k, v in tail.items()}


def _hybrid_block(ldef: LayerDef, lparams, x, x_emb, *, cfg: ModelConfig,
                  mode: str, cache, pos, shared_params, layer=None):
    """Invocation ``ldef.hybrid`` of zamba2's shared blocks: block
    ``k % num_mem_blocks`` attends over ``concat(x, x_emb)`` (softmax scale
    ``(hd/2)^-0.5``), its MLP's gate/up projection gets invocation k's
    LoRA adapter, and the layer's own ``linear`` projects the result.
    Returns (t, kv): what is added to the mixer's input, and the
    invocation's KV cache (in decode, the stack ``layer`` indexes, with the
    new rows written in place)."""
    k = ldef.hybrid
    blk = shared_params["blocks"][k % cfg.num_mem_blocks]
    adapter = shared_params["adapters"][k]
    scale = (cfg.resolved_head_dim / 2) ** -0.5
    h = rms_norm(jnp.concatenate([x, x_emb], axis=-1), blk["norm_in"],
                 cfg.rms_eps)
    if mode == "decode":
        out, kv = attn.attention_decode(blk["mixer"], h, cache["kv"], pos,
                                        cfg=cfg, window=ldef.window,
                                        scale=scale, layer=layer)
    else:
        out, kv = attn.attention_fullseq(blk["mixer"], h, cfg=cfg,
                                         window=ldef.window, scale=scale)
        if ldef.window:
            kv = to_ring(kv, ldef.window)
    a = rms_norm(out, blk["norm_ff"], cfg.rms_eps)
    gate_up = (a @ adapter["a"]) @ adapter["b"]
    gate, up = jnp.split(gate_up, 2, axis=-1)
    ffn = blk["ffn"]
    t = (glu_act(a @ ffn["w_gate"] + gate, cfg.act) *
         (a @ ffn["w_up"] + up)) @ ffn["w_down"]
    return t @ lparams["linear"], kv


def _layer_of(stack, layer):
    """Layer ``layer`` of a stacked cache tree (a leading layer axis)."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, layer, keepdims=False),
        stack)


def _state_step(decode_fn, params, h, stack, layer, cfg):
    """A recurrent mixer's decode step against layer ``layer`` of the stack
    of its states: the layer's new state is written back into the stack at
    its index.  Returns (out, new stack)."""
    state = {k: a for k, a in stack.items() if k != "kv"}
    out, st = decode_fn(params, h, _layer_of(state, layer), cfg=cfg)
    return out, {**stack, **{
        k: jax.lax.dynamic_update_index_in_dim(stack[k], a, layer, 0)
        for k, a in st.items()}}


def apply_layer(ldef: LayerDef, lparams, x, *, cfg: ModelConfig,
                pctx: ParallelContext, mode: str, cache, pos,
                enc_out=None, shared_params=None, use_kernel: bool = False,
                causal: bool = True, h_pre=None, pending=None,
                defer_psum: bool = False, x_emb=None, layer=None):
    """Returns (x, new_cache, aux_vec[2], pending_out).

    In decode mode ``cache`` is the stack of this layer's caches over the
    layer loop that runs it (a leading layer axis) and ``layer`` its index
    in that stack: the layer writes its new KV rows and its recurrent state
    into the stack in place, and ``new_cache`` is the whole stack.

    ``h_pre`` short-circuits the input RMSNorm: a caller that already holds
    ``rms_norm(x, norm1)`` (the fused dequant+restore+norm kernel at the
    butterfly boundary) passes it here so the norm never runs twice.

    ``pending``/``defer_psum`` implement psum overlap (opt-in): a dense
    attn+mlp layer returns its MLP output as an *unreduced* per-rank
    partial (``pending_out``) instead of psumming it in place; the next
    layer folds ``x + model_psum(pending)`` in at its top, before norm1 —
    the same value added one layer later, which frees the compiler to
    overlap the model-axis collective with the boundary's independent work
    (weight loads, cache indexing) instead of serializing on it.  Layers
    with in-place reductions (MoE) or no model-axis partials return a zero
    pending, so the carried structure is stable under scan."""
    aux = jnp.zeros((2,), jnp.float32)
    new_cache = None
    p = lparams

    if pending is not None:
        x = x + model_psum(pending, pctx)
    pending_out = jnp.zeros_like(x) if defer_psum else None

    assert (mode == "decode") == (layer is not None), \
        "decode, and only decode, runs against a stacked cache"
    t = kv = None
    if ldef.hybrid is not None:
        assert h_pre is None, "a hybrid layer's norm reads its shared block"
        t, kv = _hybrid_block(ldef, p, x, x_emb, cfg=cfg, mode=mode,
                              cache=cache, pos=pos,
                              shared_params=shared_params, layer=layer)
    h = h_pre if h_pre is not None else \
        rms_norm(x if t is None else x + t, p["norm1"], cfg.rms_eps)
    rope = not cfg.is_encdec          # whisper uses sinusoid embeds, no RoPE
    if ldef.mixer == "attn":
        if mode == "decode":
            out, kv = attn.attention_decode(
                p["mixer"], h, cache["kv"], pos, cfg=cfg, window=ldef.window,
                rope=rope, layer=layer)
            new_cache = {"kv": kv}
        else:
            out, kv = attn.attention_fullseq(
                p["mixer"], h, cfg=cfg, window=ldef.window,
                use_kernel=use_kernel, causal=causal, rope=rope)
            if mode == "prefill":
                new_cache = {"kv": to_ring(kv, ldef.window) if ldef.window else kv}
        # tensor-parallel stages: wo is row-sharded, so `out` is this model
        # rank's partial sum (the kv cache stays a local whole-head slice)
        x = x + model_psum(out, pctx)
        if ldef.cross:
            hc = rms_norm(x, p["norm_cross"], cfg.rms_eps)
            if mode == "decode":
                ckv = _layer_of(cache["cross_kv"], layer)
            else:
                ckv = attn.encoder_kv(p["cross"], enc_out, cfg=cfg)
            x = x + attn.cross_attention(p["cross"], hc, ckv, cfg=cfg)
            if mode == "prefill":
                new_cache["cross_kv"] = ckv
            elif mode == "decode":
                new_cache["cross_kv"] = cache["cross_kv"]
        if ldef.ffn is not None:
            h2 = rms_norm(x, p["norm2"], cfg.rms_eps)
            if ldef.ffn == "mlp":
                # w_down row-sharded under tensor parallelism -> partial out
                part = apply_mlp(p["ffn"], h2, cfg.act)
                if defer_psum:
                    pending_out = part
                else:
                    x = x + model_psum(part, pctx)
            else:
                out, moe_aux = moe_lib.apply_moe(p["ffn"], h2, cfg=cfg,
                                                 pctx=pctx, act=cfg.act)
                x = x + out
                aux = aux + jnp.stack([moe_aux["load_balance"],
                                       moe_aux["router_z"]])
    elif ldef.mixer == "mamba":
        if mode == "decode":
            out, new_cache = _state_step(ssm_lib.mamba_decode, p["mixer"], h,
                                         cache, layer, cfg)
        else:
            out, st = ssm_lib.mamba_fullseq(p["mixer"], h, cfg=cfg,
                                            return_state=(mode == "prefill"))
            new_cache = st
        if kv is not None and new_cache is not None:
            new_cache = {**new_cache, "kv": kv}
        x = x + out
    elif ldef.mixer == "mlstm":
        if mode == "decode":
            out, new_cache = _state_step(xlstm_lib.mlstm_decode, p["mixer"],
                                         h, cache, layer, cfg)
        else:
            out, new_cache = xlstm_lib.mlstm_fullseq(
                p["mixer"], h, cfg=cfg, return_state=(mode == "prefill"))
        x = x + out
    elif ldef.mixer == "slstm":
        if mode == "decode":
            out, new_cache = _state_step(xlstm_lib.slstm_decode, p["mixer"],
                                         h, cache, layer, cfg)
        else:
            out, new_cache = xlstm_lib.slstm_fullseq(
                p["mixer"], h, cfg=cfg, return_state=(mode == "prefill"))
        x = x + out
    else:
        raise ValueError(ldef.mixer)
    return x, new_cache, aux, pending_out


# ---------------------------------------------------------------------------
# segment / stage apply (scan over repeats)
# ---------------------------------------------------------------------------


def _apply_unit(seg: Segment, unit_params, unit_cache, x, aux_sum, pending, *,
                cfg, pctx, mode, pos, enc_out, shared_params, use_kernel,
                causal, first_h=None, defer_psum=False, x_emb=None,
                layer=None):
    """One pass over a segment's repeat unit; shared by the scan body and
    the peeled first repeat.  In decode ``unit_cache`` holds the stacked
    caches of the loop's repeats and ``layer`` is this repeat's index in
    them (see :func:`apply_layer`)."""
    new_caches = []
    for i, ldef in enumerate(seg.unit):
        c = None if unit_cache is None else unit_cache[i]
        x, nc, aux, pending = apply_layer(
            ldef, unit_params[i], x, cfg=cfg, pctx=pctx, mode=mode,
            cache=c, pos=pos, enc_out=enc_out, shared_params=shared_params,
            use_kernel=use_kernel, causal=causal,
            h_pre=first_h if i == 0 else None, pending=pending,
            defer_psum=defer_psum, x_emb=x_emb, layer=layer)
        aux_sum = aux_sum + aux
        new_caches.append(nc)
    return x, aux_sum, pending, new_caches


def apply_segment(seg: Segment, seg_params, x, *, cfg, pctx, mode, seg_cache,
                  pos, enc_out=None, shared_params=None, use_kernel=False,
                  causal=True, first_h=None, overlap_psum=False,
                  pending=None, x_emb=None):
    """seg_params: list per unit pos of stacked params; seg_cache likewise.

    ``first_h`` is the fused restore+norm kernel's pre-normed input for the
    segment's FIRST layer; when given, the first repeat is peeled out of the
    scan (a scan body takes one trace for all repeats, so the norm skip
    cannot live inside it) and the remaining repeats scan as usual.
    ``overlap_psum`` threads a deferred MLP partial (``pending``) through
    the repeats — see :func:`apply_layer`; the caller flushes the returned
    pending.  In decode the segment's stacked cache is carried through the
    repeats and written in place, as :func:`apply_layer_range` does."""
    kw = dict(cfg=cfg, pctx=pctx, mode=mode, pos=pos, enc_out=enc_out,
              shared_params=shared_params, use_kernel=use_kernel,
              causal=causal, defer_psum=overlap_psum, x_emb=x_emb)
    decode = mode == "decode"
    if overlap_psum and pending is None:
        pending = jnp.zeros_like(x)
    aux0 = jnp.zeros((2,), jnp.float32)
    peel = first_h is not None
    if peel:
        p0 = jax.tree.map(lambda a: a[0], seg_params)
        c0 = seg_cache if decode or seg_cache is None else \
            jax.tree.map(lambda a: a[0], seg_cache)
        x, aux0, pending, first_caches = _apply_unit(
            seg, p0, c0, x, aux0, pending, first_h=first_h,
            layer=0 if decode else None, **kw)
        if decode:
            seg_cache = first_caches
        if seg.repeats == 1:
            new_cache = first_caches if decode else \
                jax.tree.map(lambda a: a[None], first_caches)
            return x, new_cache, aux0, pending
        seg_params = jax.tree.map(lambda a: a[1:], seg_params)
        if not decode and seg_cache is not None:
            seg_cache = jax.tree.map(lambda a: a[1:], seg_cache)

    def body(carry, xs):
        xc, pend, aux_sum, stack = carry
        unit_params, li, unit_cache = xs
        xc, aux_sum, pend, nc = _apply_unit(
            seg, unit_params, stack if decode else unit_cache, xc, aux_sum,
            pend, layer=li if decode else None, **kw)
        return (xc, pend, aux_sum, nc if decode else None), \
            None if decode else nc

    start = 1 if peel else 0
    init = (x, pending, aux0, seg_cache if decode else None)
    (x, pending, aux, stack), new_cache = jax.lax.scan(
        body, init, (seg_params, jnp.arange(start, seg.repeats),
                     None if decode else seg_cache),
        unroll=_scan_unroll(seg.repeats - start))
    if decode:
        new_cache = stack
    elif peel:
        new_cache = jax.tree.map(
            lambda f, r: jnp.concatenate([f[None], r], axis=0),
            first_caches, new_cache)
    return x, new_cache, aux, pending


def apply_stage(segments: List[Segment], stage_params, x, *, cfg, pctx, mode,
                stage_cache, pos, enc_out=None, shared_params=None,
                use_kernel=False, causal=True, first_h=None,
                overlap_psum=False, x_emb=None):
    aux_total = jnp.zeros((2,), jnp.float32)
    new_caches = []
    pending = None
    for si, seg in enumerate(segments):
        cache = None if stage_cache is None else stage_cache[si]
        x, nc, aux, pending = apply_segment(
            seg, stage_params[si], x, cfg=cfg, pctx=pctx, mode=mode,
            seg_cache=cache, pos=pos, enc_out=enc_out,
            shared_params=shared_params, use_kernel=use_kernel, causal=causal,
            first_h=first_h if si == 0 else None,
            overlap_psum=overlap_psum, pending=pending, x_emb=x_emb)
        new_caches.append(nc)
        aux_total = aux_total + aux
    if pending is not None:
        x = x + model_psum(pending, pctx)      # stage-end flush
    return x, new_caches, aux_total


# ---------------------------------------------------------------------------
# stacked cache init/specs for a stage
# ---------------------------------------------------------------------------


def init_stage_cache(segments: List[Segment], cfg, batch, length, dtype):
    out = []
    for seg in segments:
        unit = []
        for ldef in seg.unit:
            c = init_layer_cache(ldef, cfg, batch, length, dtype)
            unit.append(jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (seg.repeats,) + a.shape), c))
        out.append(unit)
    return out


def stage_cache_spec(segments: List[Segment], batch_axis, seq_axis,
                     head_axis=None):
    """``head_axis`` shards attention kv-head dims (tensor-parallel stages);
    recurrent mixer state has no head-sharded decomposition here and stays
    replicated."""
    out = []
    for seg in segments:
        unit = []
        for ldef in seg.unit:
            s = layer_cache_spec(ldef, batch_axis, seq_axis, head_axis)
            unit.append(_prepend_none(s))
        out.append(unit)
    return out
