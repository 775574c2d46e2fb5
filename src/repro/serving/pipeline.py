"""The paper's deployment, TPU-native: a 2-stage microbatched pipeline over
the ``pod`` mesh axis with the butterfly unit at the stage boundary.

Pod 0 ("edge") computes layers [0, j) + the reduction unit + int8 wire
quantization; a single ``lax.ppermute`` per tick carries ONLY the quantized
codes + f32 scales across the pod boundary (this is the paper's compressed
uplink, visible in the HLO as a collective-permute of an int8 tensor);
pod 1 ("cloud") dequantizes, restores, runs layers [j, N) and the LM head,
and the last-token logits ride the same ppermute back ("the inference
outcome is sent back to the mobile device").

Within a pod, stages are model-parallel (DESIGN.md section 11): when the
mesh carries a ``model`` axis, attention heads / d_ff columns / MoE experts
shard over it Megatron-style and each layer's partial outputs psum over
``model`` — so the "significant computational load on the cloud server"
spreads across the pod's devices while the *only* tensor crossing the pod
axis is still the compressed ``(mb, S, d_r)`` wire.  MoE configs run
expert-parallel inside the 2-pod split (each model rank owns E/mp experts,
``models/moe.py`` manual path).  With no ``model`` axis (or size 1) the
stage params replicate exactly as before.

Decode pipelining (:func:`make_decode_pipeline`): with >= 2 in-flight
microbatches rotating through the 2-pod mesh, pod 0 runs the edge decode
step for microbatch k+1 while pod 1 runs the cloud step for microbatch k —
one ppermute of int8 (or nibble-packed int4) codes per tick instead of the
serial ping-pong that idles one pod every token.  ``pipelined=False`` runs
the same per-step math one microbatch at a time (the serial reference), so
the two schedules are greedy-bitwise comparable.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.quantization import Wire
from repro.kernels import ops
from repro.models import model as M
from repro.models import transformer as tfm
from repro.models.common import embed, rms_norm, unembed
from repro.models.parallel import LOCAL, manual_context


def wire_stats(cfg, microbatch: int, seq: int,
               wire_bits: Optional[int] = None) -> dict:
    """Bytes crossing the pod boundary per microbatch tick: ceil-packed
    codes (two int4 codes per byte — sub-byte wires no longer floor to 0)
    plus per-row scales at their real dtype width (f32)."""
    bits = cfg.butterfly.wire_bits if wire_bits is None else wire_bits
    d_r = cfg.butterfly.d_r
    wire = int(Wire("int8", bits, d_r).nbytes(cfg, seq, microbatch))
    raw = int(Wire("raw", bits, d_r).nbytes(cfg, seq, microbatch))
    return {"wire_bytes": wire, "raw_boundary_bytes": raw,
            "compression": raw / wire}


def _zero_wire(wire: Wire, batch: int, seq: int, cfg):
    """Zeros in the shape of ``wire``'s (payload, scales): what a pod that
    sends nothing on a tick puts on the ppermute."""
    return tuple(jnp.zeros(a.shape, a.dtype) for a in wire.payload_shape_dtype(
        batch, seq, cfg.d_model, jnp.dtype(cfg.dtype)))


def pipeline_param_specs(built: M.BuiltModel, mp: int):
    """PartitionSpec pytree (a prefix of the params tree) for the pipeline's
    shard_map: stage layers shard over the ``model`` axis per the tensor-
    parallel rules, everything else (embeddings, norms, butterfly, LM head)
    replicates.  ``mp == 1`` returns a bare ``P()`` — the fully replicated
    prefix, bit-identical to the pre-model-parallel pipeline."""
    if mp <= 1:
        return P()
    return M.tp_param_specs(built)


def make_split_pipeline(built: M.BuiltModel, mesh, num_microbatches: int,
                        seq_len: int, microbatch: int,
                        wire_mode: str = "int8", use_kernel: bool = False):
    """Returns jit-able ``pipeline_fn(params, tokens) -> last-token logits``.

    tokens: (num_microbatches * microbatch, seq_len) int32, sharded over the
    'data' axis on the batch dim; requires a 'pod' axis of size 2.  An
    optional 'model' axis makes each stage tensor-parallel within its pod
    (heads/d_ff/experts must divide the axis — see
    ``transformer.check_tp_divisibility``).

    wire_mode — what crosses the pod boundary: one of
    ``quantization.Wire``'s modes ("raw", "reduced", "int8", "int4",
    "entropy"; the last is numerically int8).

    use_kernel — quantized wires run through the fused Pallas reduce+quant
    (edge) and dequant+restore (cloud) kernels instead of the eager codec.
    """
    cfg = built.cfg
    assert built.has_butterfly and len(built.stages) == 2, \
        "pipeline needs a butterfly split (cfg.with_butterfly(...))"
    assert not cfg.is_encdec, "enc-dec archs are out of pipeline scope"
    assert cfg.hybrid_layer_ids is None, \
        "hybrid layers read the token embeddings, which no pod ships"
    n_pods = mesh.shape["pod"]
    assert n_pods == 2, "2-stage pipeline: edge pod + cloud pod"
    axes = mesh.axis_names
    mp = int(mesh.shape["model"]) if "model" in axes else 1
    tfm.check_tp_divisibility(tfm.build_layer_defs(cfg, built.long_mode),
                              cfg, mp)
    pctx = manual_context(mesh) if mp > 1 else LOCAL
    V = cfg.vocab_size
    Mmb = num_microbatches
    dt = jnp.dtype(cfg.dtype)
    wire = Wire.parse(wire_mode, cfg.butterfly.wire_bits, cfg.butterfly.d_r)

    def stage_edge(params, toks):
        scale = cfg.arch_type == "dense" and cfg.act == "gelu"
        x = embed(params["embed"], toks, scale=scale)
        x, _, _ = tfm.apply_stage(
            list(built.stages[0]), params["stages"][0], x, cfg=cfg,
            pctx=pctx, mode="train", stage_cache=None, pos=None,
            shared_params=params.get("shared_attn"))
        return wire.encode(x, params["butterfly"]["w_reduce"],
                           kernel=use_kernel)

    def stage_cloud(params, payload, scales):
        x = wire.decode(payload, scales, params["butterfly"]["w_restore"], dt,
                        kernel=use_kernel)
        x, _, _ = tfm.apply_stage(
            list(built.stages[1]), params["stages"][1], x, cfg=cfg,
            pctx=pctx, mode="train", stage_cache=None, pos=None,
            shared_params=params.get("shared_attn"))
        x = rms_norm(x[:, -1:], params["final_norm"], cfg.rms_eps)
        table = params["embed"] if cfg.tie_embeddings else params["head"]
        return unembed(table, x)[:, 0]                      # (mb, V)

    def shard_body(params, tokens):
        pod = jax.lax.axis_index("pod")
        mb_toks = tokens.reshape(Mmb, -1, seq_len)
        mb = mb_toks.shape[1]

        zero_wire = _zero_wire(wire, mb, seq_len, cfg)
        zero_logits = jnp.zeros((mb, V), jnp.float32)

        def tick(t, carry):
            recv_codes, recv_scales, out, back = carry

            # each branch runs only on its pod's ranks; the model-axis psums
            # inside the stages reduce within the pod (disjoint replica
            # groups per pod), so neither branch communicates across pods
            def edge(_):
                i = jnp.clip(t, 0, Mmb - 1)
                toks = jax.lax.dynamic_index_in_dim(mb_toks, i, 0, False)
                codes, scales = stage_edge(params, toks)
                return codes, scales, zero_logits

            def cloud(_):
                logits = stage_cloud(params, recv_codes, recv_scales)
                return zero_wire[0], zero_wire[1], logits

            codes, scales, logits = jax.lax.cond(pod == 0, edge, cloud, None)
            # the wire: int8 codes + scales cross 0 -> 1; logits cross 1 -> 0
            codes = jax.lax.ppermute(codes, "pod", [(0, 1), (1, 0)])
            scales = jax.lax.ppermute(scales, "pod", [(0, 1), (1, 0)])
            logits_back = jax.lax.ppermute(logits, "pod", [(0, 1), (1, 0)])
            out = jnp.where(t >= 1, out.at[jnp.maximum(t - 1, 0)].set(logits),
                            out)
            back = jnp.where(t >= 1, back.at[jnp.maximum(t - 1, 0)].set(logits_back),
                             back)
            return codes, scales, out, back

        out0 = jnp.zeros((Mmb, mb, V), jnp.float32)
        carry = (*zero_wire, out0, out0)
        *_, out, back = jax.lax.fori_loop(0, Mmb + 1, tick, carry)
        # pod 1 filled `out` locally; pod 0 received `back`, a bitwise copy
        # of it. Select the live copy so the result is pod-invariant.
        return jnp.where(pod == 0, back, out).reshape(-1, V)

    return _pod_invariant_shard_map(shard_body, built, mesh, mp)


def _pod_invariant_shard_map(shard_body, built, mesh, mp: int):
    """shard_map a pipeline body whose per-pod results are bitwise equal.
    The output spec leaves ``pod`` unmapped, so the caller gets one copy
    without indexing a pod-sharded dim, and the body returns its rows in
    input order, so no reshape merges a sharded dim.  Sharding-in-types
    rejects both on a mesh with explicit axes."""
    data_ax = "data" if "data" in mesh.axis_names else None
    return jax.shard_map(
        shard_body, mesh=mesh,
        in_specs=(pipeline_param_specs(built, mp), P(data_ax, None)),
        out_specs=P(data_ax, None),
        check_vma=False,
    )


def _grow_cache(small, template):
    """Zero-pad a prefill-time stage cache into a decode-capacity template
    (seq axis grows from prompt_len to prompt_len + new_tokens; ring-window
    and state caches already match).  Padding is safe because decode masks
    cache slots beyond the current position."""
    def copy(big, sm):
        pads = [(0, b - s) for b, s in zip(big.shape, sm.shape)]
        if any(p for _, p in pads):
            sm = jnp.pad(sm, pads)
        return sm.astype(big.dtype)
    return jax.tree.map(copy, template, small)


def make_decode_pipeline(built: M.BuiltModel, mesh, num_microbatches: int,
                         prompt_len: int, microbatch: int, new_tokens: int,
                         wire_mode: str = "int8", pipelined: bool = True,
                         use_kernel: bool = False,
                         overlap_psum: bool = False):
    """Returns ``decode_fn(params, tokens) -> greedy token ids``.

    tokens: (num_microbatches * microbatch, prompt_len) int32 prompts; the
    result is (num_microbatches * microbatch, new_tokens) int32 — column 0
    is the token greedily decoded from the prefill logits, the rest come
    from per-token decode steps through the split.

    Schedule (``pipelined=True``, needs >= 2 microbatches): decode runs one
    fori_loop over ticks t.  At tick t pod 0 (edge) runs the embed+stage-0
    decode step for microbatch ``t % M`` round ``t // M`` and emits its
    quantized boundary row; pod 1 (cloud) *concurrently* runs stage-1 +
    LM head on the row it received at the end of tick t-1 (microbatch
    ``(t-1) % M``).  One ppermute carries the fresh codes 0 -> 1 and the
    decoded token 1 -> 0 per tick, so both pods stay busy every tick.  The
    M-1 tick gap between a token's decode and its reuse by the edge is what
    makes >= 2 in-flight microbatches mandatory.

    ``pipelined=False`` is the serial reference: each tick runs edge ->
    ppermute -> cloud -> ppermute-back for a single microbatch, so one pod
    always idles.  Both modes share the same per-step closures and visit
    the same (microbatch, position) pairs in the same order, so greedy
    outputs are bitwise identical.

    ``wire_mode``: "int8" or nibble-packed "int4" (halves uplink bytes).
    ``use_kernel``: fused reduce+quant on the edge and fused
    dequant+restore+norm1 (``ops.butterfly_restore_norm``) on the cloud.
    ``overlap_psum``: defer each dense layer's MLP psum into the next layer
    (see ``transformer.apply_layer``).
    """
    cfg = built.cfg
    assert built.has_butterfly and len(built.stages) == 2, \
        "decode pipeline needs a butterfly split (cfg.with_butterfly(...))"
    assert not cfg.is_encdec, "enc-dec archs are out of pipeline scope"
    assert cfg.hybrid_layer_ids is None, \
        "hybrid layers read the token embeddings, which no pod ships"
    assert mesh.shape["pod"] == 2, "2-stage pipeline: edge pod + cloud pod"
    axes = mesh.axis_names
    mp = int(mesh.shape["model"]) if "model" in axes else 1
    tfm.check_tp_divisibility(tfm.build_layer_defs(cfg, built.long_mode),
                              cfg, mp)
    pctx = manual_context(mesh) if mp > 1 else LOCAL
    S = int(prompt_len)
    T = int(new_tokens)
    Mmb = int(num_microbatches)
    dt = jnp.dtype(cfg.dtype)
    # the codes are int8, or int4 packed two per byte
    wire = Wire.parse(wire_mode, 8, cfg.butterfly.d_r)
    assert wire.quantized, wire_mode
    assert T >= 2, "need at least one decode tick"
    if pipelined:
        assert Mmb >= 2, "pipelined decode needs >= 2 in-flight microbatches"
    stages0 = list(built.stages[0])
    stages1 = list(built.stages[1])
    embed_scale = cfg.arch_type == "dense" and cfg.act == "gelu"

    def edge_wire(params, x):
        return wire.encode(x, params["butterfly"]["w_reduce"],
                           kernel=use_kernel)

    def cloud_restore(params, codes, scales):
        """The restored rows and, on the kernel path, the first cloud
        layer's norm of them from the same fused kernel (else None)."""
        w_restore = params["butterfly"]["w_restore"]
        if use_kernel:
            nw = tfm.first_layer_norm1(stages1, params["stages"][1])
            return ops.butterfly_restore_norm(
                wire.unpack(codes), scales, w_restore, nw, eps=cfg.rms_eps,
                out_dtype=dt)
        return wire.decode(codes, scales, w_restore, dt, kernel=False), None

    def greedy(params, x):
        x = rms_norm(x[:, -1:], params["final_norm"], cfg.rms_eps)
        table = params["embed"] if cfg.tie_embeddings else params["head"]
        logits = unembed(table, x, cfg.logit_softcap)[:, 0]
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def edge_prefill(params, toks):
        x = embed(params["embed"], toks, scale=embed_scale)
        x, caches, _ = tfm.apply_stage(
            stages0, params["stages"][0], x, cfg=cfg, pctx=pctx,
            mode="prefill", stage_cache=None, pos=None,
            shared_params=params.get("shared_attn"))
        codes, scales = edge_wire(params, x)
        return codes, scales, caches

    def cloud_prefill(params, codes, scales):
        x, h = cloud_restore(params, codes, scales)
        x, caches, _ = tfm.apply_stage(
            stages1, params["stages"][1], x, cfg=cfg, pctx=pctx,
            mode="prefill", stage_cache=None, pos=None,
            shared_params=params.get("shared_attn"), first_h=h,
            overlap_psum=overlap_psum)
        return greedy(params, x), caches

    def edge_step(params, tok, cache, pos):
        x = embed(params["embed"], tok[:, None], scale=embed_scale)
        x, cache, _ = tfm.apply_stage(
            stages0, params["stages"][0], x, cfg=cfg, pctx=pctx,
            mode="decode", stage_cache=cache, pos=pos,
            shared_params=params.get("shared_attn"))
        codes, scales = edge_wire(params, x)
        return codes, scales, cache

    def cloud_step(params, codes, scales, cache, pos):
        x, h = cloud_restore(params, codes, scales)
        x, cache, _ = tfm.apply_stage(
            stages1, params["stages"][1], x, cfg=cfg, pctx=pctx,
            mode="decode", stage_cache=cache, pos=pos,
            shared_params=params.get("shared_attn"), first_h=h,
            overlap_psum=overlap_psum)
        return greedy(params, x), cache

    def _at(tree, i):
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, False), tree)

    def _put(tree, upd, i, keep):
        def one(a, u):
            new = jax.lax.dynamic_update_index_in_dim(a, u, i, 0)
            return jnp.where(keep, new, a)
        return jax.tree.map(one, tree, upd)

    n_ticks = Mmb * (T - 1)

    def shard_body(params, tokens):
        pod = jax.lax.axis_index("pod")
        mb_toks = tokens.reshape(Mmb, -1, S)
        mb = mb_toks.shape[1]
        zero_prefill_wire = _zero_wire(wire, mb, S, cfg)
        zero_row_wire = _zero_wire(wire, mb, 1, cfg)
        zero_tok = jnp.zeros((mb,), jnp.int32)
        # Each model rank caches only its own KV-head slice, so size the
        # decode templates with per-rank head counts (recurrent-mixer states
        # replicate per rank and keep their global shapes).
        cfg_rank = (dataclasses.replace(cfg, num_kv_heads=cfg.num_kv_heads // mp)
                    if mp > 1 else cfg)
        tmpl0 = tfm.init_stage_cache(stages0, cfg_rank, mb, S + T, dt)
        tmpl1 = tfm.init_stage_cache(stages1, cfg_rank, mb, S + T, dt)

        # ---- prefill: build both pods' decode caches + token_0 per mb ----
        toks0, c0_list, c1_list = [], [], []
        for k in range(Mmb):
            toks = mb_toks[k]

            def p_edge(_):
                codes, scales, caches = edge_prefill(params, toks)
                return codes, scales, _grow_cache(caches, tmpl0)

            def p_skip_e(_):
                return (*zero_prefill_wire, tmpl0)

            codes, scales, c0k = jax.lax.cond(pod == 0, p_edge, p_skip_e, None)
            codes = jax.lax.ppermute(codes, "pod", [(0, 1), (1, 0)])
            scales = jax.lax.ppermute(scales, "pod", [(0, 1), (1, 0)])

            def p_cloud(_):
                tok0, caches = cloud_prefill(params, codes, scales)
                return tok0, _grow_cache(caches, tmpl1)

            def p_skip_c(_):
                return zero_tok, tmpl1

            tok0, c1k = jax.lax.cond(pod == 1, p_cloud, p_skip_c, None)
            tok_back = jax.lax.ppermute(tok0, "pod", [(0, 1), (1, 0)])
            toks0.append(jnp.where(pod == 0, tok_back, tok0))
            c0_list.append(c0k)
            c1_list.append(c1k)

        c0 = jax.tree.map(lambda *xs: jnp.stack(xs), *c0_list)
        c1 = jax.tree.map(lambda *xs: jnp.stack(xs), *c1_list)
        tok = jnp.stack(toks0)                               # (Mmb, mb)
        out = jnp.zeros((Mmb, T, mb), jnp.int32).at[:, 0].set(tok)

        # ---- decode ticks ----
        def run_edge(t, tok, c0):
            k = jnp.mod(t, Mmb)
            pos = S + jnp.clip(t // Mmb, 0, T - 2)           # scalar, aligned
            codes, scales, cache = edge_step(params, _at(tok, k), _at(c0, k),
                                             pos)
            return codes, scales, _put(c0, cache, k, t < n_ticks)

        def run_cloud(t, codes, scales, c1, active):
            # `active` gates the cache write: a warm-up tick fed zero codes
            # must not advance recurrent (ssm/xlstm) states
            k = jnp.mod(t, Mmb)
            pos = S + jnp.clip(t // Mmb, 0, T - 2)
            tok_next, cache = cloud_step(params, codes, scales, _at(c1, k),
                                         pos)
            return tok_next, _put(c1, cache, k, active)

        def commit(t, tok_next, tok, out, active):
            # both pods fold the decoded token into their (identical) copy
            k, j = jnp.mod(t, Mmb), t // Mmb
            tok = jnp.where(active, tok.at[k].set(tok_next), tok)
            out = jnp.where(active, out.at[k, j + 1].set(tok_next), out)
            return tok, out

        def tick_pipelined(t, carry):
            codes_in, scales_in, tok, out, c0, c1 = carry
            tc = jnp.maximum(t - 1, 0)                       # cloud serves t-1

            def edge(_):
                codes, scales, new_c0 = run_edge(t, tok, c0)
                return codes, scales, zero_tok, new_c0, c1

            def cloud(_):
                tok_next, new_c1 = run_cloud(tc, codes_in, scales_in, c1,
                                             t >= 1)
                return (*zero_row_wire, tok_next, c0, new_c1)

            codes, scales, tok_next, c0n, c1n = jax.lax.cond(
                pod == 0, edge, cloud, None)
            codes = jax.lax.ppermute(codes, "pod", [(0, 1), (1, 0)])
            scales = jax.lax.ppermute(scales, "pod", [(0, 1), (1, 0)])
            tok_back = jax.lax.ppermute(tok_next, "pod", [(0, 1), (1, 0)])
            tok_val = jnp.where(pod == 0, tok_back, tok_next)
            tok, out = commit(tc, tok_val, tok, out, t >= 1)
            return codes, scales, tok, out, c0n, c1n

        def tick_serial(t, carry):
            _, _, tok, out, c0, c1 = carry

            def edge(_):
                codes, scales, new_c0 = run_edge(t, tok, c0)
                return codes, scales, new_c0

            def skip_e(_):
                return (*zero_row_wire, c0)

            codes, scales, c0 = jax.lax.cond(pod == 0, edge, skip_e, None)
            codes = jax.lax.ppermute(codes, "pod", [(0, 1), (1, 0)])
            scales = jax.lax.ppermute(scales, "pod", [(0, 1), (1, 0)])

            def cloud(_):
                return run_cloud(t, codes, scales, c1, True)

            def skip_c(_):
                return zero_tok, c1

            tok_next, c1 = jax.lax.cond(pod == 1, cloud, skip_c, None)
            tok_back = jax.lax.ppermute(tok_next, "pod", [(0, 1), (1, 0)])
            tok_val = jnp.where(pod == 0, tok_back, tok_next)
            tok, out = commit(t, tok_val, tok, out, True)
            return codes, scales, tok, out, c0, c1

        carry = (*zero_row_wire, tok, out, c0, c1)
        tick = tick_pipelined if pipelined else tick_serial
        # pipelined: one extra drain tick so the cloud finishes the last row
        carry = jax.lax.fori_loop(0, n_ticks + (1 if pipelined else 0),
                                  tick, carry)
        # both pods commit every decoded token, so `out` is pod-invariant
        return jnp.transpose(carry[3], (0, 2, 1)).reshape(-1, T)

    return _pod_invariant_shard_map(shard_body, built, mesh, mp)
