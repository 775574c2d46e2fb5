"""Real numerics + analytic timing for partitioned execution.

Numerics and time are decoupled on purpose: the jax computation produces the
actual logits/tokens/caches (so split serving is verifiable against the
single-mesh forward), while durations come from the roofline
cost model (core/profiler) driven by the deterministic virtual clock — a
CPU-only container can therefore simulate a Jetson-class edge talking to a
GPU-class cloud over 3G with reproducible traces.

The cloud hosts the paper's "M partitioned models" (Sec. III-C) as ONE
shared backbone parameter tree: :class:`SplitModelBank` initialises the
model once and every candidate split's edge/cloud halves read their layer
range of the stacked params in place, by index inside the layer scan
(``models/transformer.apply_layer_range``), so bank memory stays O(1) in
the number of hosted splits, no program copies its range out of the stack,
and only the tiny per-split butterfly projections are materialised per
candidate.
:class:`SplitRunner` is a thin facade over the bank's compile cache: jitted
edge/cloud/prefill/decode functions are keyed on ``(kind, split)`` with
bucket-padded ``(B, S)`` shapes, so a candidate sweep re-uses executables
instead of recompiling per prompt length.  Every program is composed of the
same edge front, the wire (``quantization.Wire``: the int8 wire runs through
the fused Pallas reduce+quant / dequant+restore kernels) and the same head.

Multi-token requests pick a decode transport (runtime/transports.py):
``cache_handoff`` ships the edge stage-0 KV cache to the cloud alongside the
codes (prefill/decode-disaggregation style cache transfer) so decode runs
entirely cloud-side; ``streamed`` keeps the stage-0 cache on the edge and
streams one fused-quantized ``(1, d_r)`` row per generated token through the
butterfly (DESIGN.md section 8.6) — the bank's compile cache grows per-token
``edge_step``/``cloud_step`` entries for it.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import numpy as np

from repro.core import costs
from repro.core.profiler import HardwareProfile
from repro.core.quantization import CodedRows, Wire
from repro.runtime.tracing import span


# ---------------------------------------------------------------------------
# analytic timing (virtual-clock durations)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class CostModel:
    """``edge_mp``/``cloud_mp`` — model-axis degree each half's stage is
    sharded over (DESIGN.md section 11): per-stage estimates divide by the
    degree via :func:`costs.model_parallel_share` (heterogeneous fleets run
    edge_mp=1 against a wide cloud)."""
    cfg: object
    edge: HardwareProfile
    cloud: HardwareProfile
    edge_mp: int = 1
    cloud_mp: int = 1

    def _where(self, where: str):
        if where == "edge":
            return self.edge, self.edge_mp
        return self.cloud, self.cloud_mp

    def _roofline(self, hw: HardwareProfile, flops: float,
                  load: float = 0.0, mp: int = 1) -> float:
        nbytes = flops / max(self.cfg.d_model, 1)      # planner's bytes proxy
        flops, nbytes = costs.model_parallel_share((flops, nbytes), mp)
        return hw.latency_s(flops, nbytes) / max(1e-9, 1.0 - load)

    def edge_prefill_s(self, split: int, seq: int, d_r: int) -> float:
        f = costs.stack_flops(self.cfg, seq, 0, split)
        f += 2 * seq * self.cfg.d_model * d_r          # reduction unit
        return self._roofline(self.edge, f, mp=self.edge_mp)

    def cloud_prefill_s(self, split: int, seq: int, d_r: int,
                        load: float = 0.0) -> float:
        f = costs.stack_flops(self.cfg, seq, split, self.cfg.num_layers)
        f += 2 * seq * d_r * self.cfg.d_model          # restoration unit
        f += costs.embed_flops(self.cfg, seq)
        return self._roofline(self.cloud, f, load, mp=self.cloud_mp)

    def full_prefill_s(self, seq: int, *, where: str,
                       load: float = 0.0) -> float:
        f = costs.stack_flops(self.cfg, seq, 0, self.cfg.num_layers)
        f += costs.embed_flops(self.cfg, seq)
        hw, mp = self._where(where)
        return self._roofline(hw, f, load, mp=mp)

    def decode_step_s(self, batch: int, *, where: str,
                      load: float = 0.0) -> float:
        # decode is weight-bound: every step streams the full parameter set
        hw, mp = self._where(where)
        f, nbytes = costs.model_parallel_share(
            costs.full_decode_step_cost(self.cfg, batch), mp)
        return hw.latency_s(f, nbytes) / max(1e-9, 1.0 - load)

    def edge_energy_mj(self, seconds: float) -> float:
        return seconds * self.edge.compute_power_w * 1e3

    def edge_decode_step_s(self, split: int, d_r: int) -> float:
        """One streamed-decode edge step: embed + layers [0, split) +
        reduce/quantize for a single token."""
        f, b = costs.model_parallel_share(
            costs.edge_decode_step_cost(self.cfg, split, d_r), self.edge_mp)
        return self.edge.latency_s(f, b)

    def cloud_decode_step_s(self, split: int, d_r: int, batch: int = 1,
                            load: float = 0.0) -> float:
        """One streamed-decode cloud turn: restore + layers [split, N) +
        unembed for ``batch`` arrived rows."""
        f, b = costs.model_parallel_share(
            costs.cloud_decode_step_cost(self.cfg, split, d_r, batch),
            self.cloud_mp)
        return self.cloud.latency_s(f, b) / max(1e-9, 1.0 - load)

    def stream_row_bytes(self, wire_mode: str, d_r: int,
                         split: Optional[int] = None) -> float:
        """Per-token uplink bytes of the streamed transport: one boundary
        row in the wire format, and the token's id where a layer past
        ``split`` reads the embeddings."""
        b = Wire.parse(wire_mode, 8, d_r).nbytes(self.cfg, 1)
        if split is not None:
            b += costs.id_wire_bytes(self.cfg, split, 1)
        return b

    def serial_decode_tick_s(self, split: int, d_r: int, *,
                             wire_mode: str = "int8",
                             link_bps: Optional[float] = None,
                             batch: int = 1, load: float = 0.0) -> float:
        """Per-token latency of serial ping-pong decode: the edge step, the
        wire row and the cloud step run strictly in sequence, so one pod
        always idles."""
        t = self.edge_decode_step_s(split, d_r) + \
            self.cloud_decode_step_s(split, d_r, batch, load)
        if link_bps:
            t += self.stream_row_bytes(wire_mode, d_r, split) * 8.0 / \
                link_bps
        return t

    def pipelined_decode_tick_s(self, split: int, d_r: int, *,
                                wire_mode: str = "int8",
                                link_bps: Optional[float] = None,
                                batch: int = 1, load: float = 0.0) -> float:
        """Steady-state per-token cadence of pipelined decode (>= 2
        in-flight microbatches rotating through the 2-pod mesh): the edge
        step for microbatch k+1, the wire row and the cloud step for
        microbatch k all overlap, so the tick is the slowest part instead
        of the sum."""
        parts = [self.edge_decode_step_s(split, d_r),
                 self.cloud_decode_step_s(split, d_r, batch, load)]
        if link_bps:
            parts.append(self.stream_row_bytes(wire_mode, d_r, split) * 8.0
                         / link_bps)
        return max(parts)

    def payload_bytes(self, mode: str, wire_mode: str, seq: int,
                      d_r: int, split: int, new_tokens: int = 1,
                      transport: str = "cache_handoff") -> float:
        """Prefill uplink bytes per request.  Split requests generating more
        than one token additionally ship the edge stage-0 KV cache under the
        ``cache_handoff`` decode transport (counted honestly); the
        ``streamed`` transport keeps that cache on the edge and pays one
        ``stream_row_bytes`` row per later token instead."""
        if mode == "cloud":
            # the frontend's feature output, one raw d_model-wide row per
            # position (the paper ships the raw 224x224x3 image)
            return Wire("raw", 8, d_r).nbytes(self.cfg, seq)
        if mode == "edge":
            return 0.0
        b = Wire.parse(wire_mode, 8, d_r).nbytes(self.cfg, seq)
        b += costs.id_wire_bytes(self.cfg, split, seq)
        if new_tokens > 1 and transport == "cache_handoff":
            b += self.stage0_cache_bytes(seq, split)
        return b

    def stage0_cache_bytes(self, seq: int, split: int) -> float:
        """KV bytes of the edge stage's ``split`` layers (the cache-handoff
        uplink term) — the arch formula lives in :func:`costs.kv_cache_bytes`."""
        return costs.kv_cache_bytes(self.cfg, seq, split)


# ---------------------------------------------------------------------------
# real numerics: one shared backbone, per-split layer ranges
# ---------------------------------------------------------------------------


def _next_bucket(n: int, lo: int = 8) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


class SplitModelBank:
    """One backbone parameter tree serving every candidate split.

    The paper's server hosts M partitioned models and the selection phase
    picks among them; here the M models are layer ranges of a single
    stacked parameter set.  Each jitted program reads its range in place:
    its layer scan takes layer ``i`` of the full stacked leaves by dynamic
    index (``models/transformer.apply_layer_range``), so no call copies the
    range's weights, and materialising more candidates costs only the
    per-split butterfly projections (d*d_r + d_r*d params each) plus compile
    cache entries — not O(num_layers) full parameter copies.

    ``edge_mp``/``cloud_mp`` set the default model-axis degree each half's
    jitted functions run at (DESIGN.md section 11): degree > 1 wraps the
    half in a shard_map over a ``("model",)`` sub-mesh of the first N local
    devices with attention heads / d_ff / experts sharded tensor-parallel
    and kv caches kept as per-rank head slices.  Runners may override per
    half (heterogeneous edge=1, cloud=N), and the compile cache keys on the
    mesh shape — two meshes on one bank never share a jitted step."""

    def __init__(self, base_cfg, d_r: int, *, wire_bits: int = 8,
                 wire_mode: str = "int8", seed: int = 0,
                 edge_mp: int = 1, cloud_mp: int = 1, profiler=None):
        import jax
        import jax.numpy as jnp

        from repro.models import model as M
        from repro.models import transformer as tfm

        assert base_cfg.num_layers >= 2, "need >=2 layers to split"
        self.wire = Wire.parse(wire_mode, wire_bits, d_r)
        if base_cfg.butterfly is not None:
            import dataclasses
            base_cfg = dataclasses.replace(base_cfg, butterfly=None)
        self.base_cfg = base_cfg
        self.d_r = d_r
        self.seed = seed
        self.edge_mp = int(edge_mp)
        self.cloud_mp = int(cloud_mp)
        self._meshes: Dict[int, object] = {}          # mp -> ("model",) Mesh

        # THE one backbone init (regardless of how many splits materialize)
        self.built = M.build(base_cfg)
        self.params, _ = M.init_model(jax.random.key(seed), self.built)
        self._M, self._tfm = M, tfm
        self._dt = jnp.dtype(base_cfg.dtype)
        self._emb_scale = base_cfg.arch_type == "dense" and \
            base_cfg.act == "gelu"
        self._defs = tfm.build_layer_defs(base_cfg)
        # hybrid layers (zamba2) read the token embeddings beside the
        # residual: each program passes them to its layer ranges, and a
        # split before such a layer ships the token ids with the codes
        self._reads_emb = base_cfg.hybrid_layer_ids is not None
        self.ids_dtype = np.dtype(
            f"int{8 * costs.id_width(base_cfg.vocab_size)}")

        # seq bucketing is only numerics-preserving when padded tail rows
        # cannot leak into real rows: pure causal global attention.  Windowed
        # ring caches, SSM/xLSTM recurrent state and MoE capacity contention
        # all observe the padding, so those families compile per exact shape.
        self._seq_bucket_ok = (not base_cfg.is_encdec and all(
            d.mixer == "attn" and d.window is None and not d.cross
            for d in self._defs))
        # batch rows are independent everywhere except MoE (shared capacity);
        # the actors also consult this before coalescing request numerics
        self._batch_bucket_ok = all(d.ffn != "moe" for d in self._defs)
        # decode-row kernel block size, derived ONCE from the wire format
        # instead of per call, and folded into every compile-cache key so
        # int4 and int8 rows (same (B, S) buckets, different packed widths)
        # never alias a jitted step
        from repro.kernels import ops as _kops
        self.row_block = _kops.decode_row_block()
        self._wire_sig = (self.wire.mode, self.wire.bits, self.row_block)

        self._butterfly: Dict[int, dict] = {}
        # runner key: (split, edge_mp, cloud_mp); fn key: (kind, split, mp) —
        # the mesh shape is part of the compile-cache key, so two meshes on
        # one bank never alias a jitted step (and the engine's weak-keyed
        # sampling-step cache sees distinct closures per mesh)
        self._runners: Dict[Tuple[int, int, int], "SplitRunner"] = {}
        self._fns: Dict[Tuple[str, int, int], object] = {}  # compile cache
        self._cache_templates: Dict[Tuple[int, int, int, int], object] = {}
        # (kind, split, mp, B_bkt, S_bkt) + wire signature
        self.jit_cache_keys: set = set()
        # opt-in wall-clock attribution (metrics.JitProfiler) + hit/miss
        # bookkeeping per padded-shape cache entry
        self.profiler = profiler
        self.cache_hits = 0
        self.cache_misses = 0

    # ------------------------------------------------------------------ api
    @property
    def candidates(self) -> Tuple[int, ...]:
        return tuple(range(1, self.base_cfg.num_layers))

    @property
    def jit_cache_entries(self) -> int:
        return len(self.jit_cache_keys)

    def cache_key(self, kind: str, split: int, mp: int, B: int,
                  S: int) -> Tuple:
        """Compile-cache key for one hot-path dispatch: the padded shape
        bucket plus the wire signature (mode, effective bits, decode-row
        kernel block) so differently-packed wires never alias."""
        return (kind, split, mp, B, S) + self._wire_sig

    def timed_call(self, key: Tuple, fn, *args):
        """Run one hot-path dispatch inside a ``dispatch`` span, recording
        its compile-cache key (hit or miss per padded-shape entry) and —
        when a profiler is attached — its wall-clock first-call/steady
        attribution."""
        miss = self.note_key(key)
        with span("dispatch", kind=key[0], miss=int(miss)):
            if self.profiler is None:
                return fn(*args)
            return self.profiler.timed(key, fn, *args)

    def note_key(self, key: Tuple) -> bool:
        """Hit/miss bookkeeping only — for dispatches whose jitted call runs
        elsewhere (the engine's fused sampling steps).  True on a miss."""
        if key in self.jit_cache_keys:
            self.cache_hits += 1
            return False
        self.cache_misses += 1
        self.jit_cache_keys.add(key)
        return True

    def ships_ids(self, split: int) -> bool:
        """Whether a layer past ``split`` reads the token embeddings, so the
        edge's payload carries the token ids (:class:`CodedRows`)."""
        return any(d.hybrid is not None for d in self._defs[split:])

    @property
    def batch_numerics_ok(self) -> bool:
        """Whether independent requests may be stacked into one batch
        without changing any request's numerics (False for MoE, whose
        expert-capacity pool couples the batch)."""
        return self._batch_bucket_ok

    def runner(self, split: int, *, edge_mp: Optional[int] = None,
               cloud_mp: Optional[int] = None) -> "SplitRunner":
        """Facade for one candidate split; ``edge_mp``/``cloud_mp`` override
        the bank defaults so heterogeneous halves (edge=1, cloud=N) share
        the same backbone."""
        from repro.models import transformer as tfm
        edge_mp = self.edge_mp if edge_mp is None else int(edge_mp)
        cloud_mp = self.cloud_mp if cloud_mp is None else int(cloud_mp)
        key = (split, edge_mp, cloud_mp)
        if key not in self._runners:
            assert 0 < split < self.base_cfg.num_layers, split
            for mp in {edge_mp, cloud_mp}:
                tfm.check_tp_divisibility(self._defs, self.base_cfg, mp)
            self._runners[key] = SplitRunner(self, split, edge_mp=edge_mp,
                                             cloud_mp=cloud_mp)
        return self._runners[key]

    def mp_mesh(self, mp: int):
        """The ``("model",)`` sub-mesh of degree ``mp`` over the first mp
        local devices (None for degree 1 — the plain-jit path)."""
        if mp <= 1:
            return None
        if mp not in self._meshes:
            import jax
            assert len(jax.devices()) >= mp, \
                f"model-axis degree {mp} needs >= {mp} devices " \
                f"(have {len(jax.devices())}; set " \
                f"--xla_force_host_platform_device_count on CPU)"
            self._meshes[mp] = jax.sharding.Mesh(
                np.array(jax.devices()[:mp]), ("model",))
        return self._meshes[mp]

    def _pctx(self, mp: int):
        from repro.models.parallel import manual_context
        return manual_context(self.mp_mesh(mp))

    def butterfly_params(self, split: int) -> dict:
        if split not in self._butterfly:
            import jax
            from repro.core.butterfly import init_butterfly
            from repro.configs.base import ButterflyConfig
            key = jax.random.fold_in(jax.random.key(self.seed), split)
            bf = ButterflyConfig(layer=split, d_r=self.d_r,
                                 wire_bits=self.wire.bits)
            self._butterfly[split], _ = init_butterfly(
                key, self.base_cfg.d_model, bf, self._dt)
        return self._butterfly[split]

    # ----------------------------------------------------- bucketing helpers
    def _buckets(self, B: int, S: int) -> Tuple[int, int]:
        Bb = _next_bucket(B, 1) if self._batch_bucket_ok else B
        Sb = _next_bucket(S, 16) if self._seq_bucket_ok else S
        return Bb, Sb

    def _pad_toks(self, toks, Bb: int, Sb: int):
        import jax.numpy as jnp
        toks = jnp.asarray(toks)
        B, S = toks.shape
        if (B, S) != (Bb, Sb):
            toks = jnp.pad(toks, ((0, Bb - B), (0, Sb - S)))
        return toks

    def _cache_template(self, stage: int, split: int, B: int, S: int):
        """ShapeDtypeStruct tree of stage ``stage``'s range cache at true
        (B, S) — used to slice bucket-padded caches back to request shape.
        Cached per instance (an lru_cache on the method would pin the bank —
        and its full backbone — in a class-level cache forever)."""
        import jax
        key = (stage, split, B, S)
        if key not in self._cache_templates:
            lo, hi = (0, split) if stage == 0 else (split,
                                                    self.base_cfg.num_layers)
            segs = self._tfm.range_segments(list(self.built.stages[0]),
                                            lo, hi)
            self._cache_templates[key] = jax.eval_shape(
                lambda: self._tfm.init_stage_cache(segs, self.base_cfg,
                                                   B, S, self._dt))
        return self._cache_templates[key]

    def _slice_cache(self, cache, stage: int, split: int, B: int, S: int):
        import jax
        template = self._cache_template(stage, split, B, S)
        def cut(leaf, t):
            if leaf.shape == t.shape:
                return leaf
            return leaf[tuple(slice(0, s) for s in t.shape)]
        return jax.tree.map(cut, cache, template)

    def engine_stages(self, split: int):
        """Per-stage segmentations of this split's two layer ranges (the
        ServingEngine's cache-pool template for this split)."""
        segs = list(self.built.stages[0])
        return [self._tfm.range_segments(segs, 0, split),
                self._tfm.range_segments(segs, split,
                                         self.base_cfg.num_layers)]

    # --------------------------------------------------- jitted core factory
    def _fn(self, kind: str, split: int, mp: int = 1):
        key = (kind, split, mp) + self._wire_sig
        if key not in self._fns:
            self._fns[key] = getattr(self, f"_make_{kind}")(split, mp)
        return self._fns[key]

    def _tp_specs(self):
        if not hasattr(self, "_tp_specs_tree"):
            self._tp_specs_tree = self._M.tp_param_specs(self.built,
                                                         with_butterfly=True)
        return self._tp_specs_tree

    def _cache_spec_tree(self, stage: int, split: int):
        """Spec tree of stage ``stage``'s range cache under a model mesh:
        attention kv-head dims shard with their head slice; recurrent state
        replicates."""
        return self._tfm.stage_cache_spec(self.engine_stages(split)[stage],
                                          None, None, head_axis="model")

    def _mp_wrap(self, fn, mp: int, specs):
        """shard_map ``fn`` over the degree-``mp`` model mesh (identity for
        mp == 1, keeping single-degree callers on the exact plain-jit path).
        ``specs`` is a zero-arg callable returning ``(in_specs, out_specs)``
        — invoked only when a real mesh exists, because tensor-parallel spec
        construction asserts arch support (e.g. no enc-dec) and must never
        fire for degree-1 callers."""
        mesh = self.mp_mesh(mp)
        if mesh is None:
            return fn
        import jax
        in_specs, out_specs = specs()
        return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    # -------------------------------------- the pieces every program is made of
    def _layers(self, mp: int):
        """``layers(params, x, lo, hi, mode, cache, pos, x_emb) -> (x,
        cache)``: layers [lo, hi) of the stacked backbone at model-axis
        degree ``mp``.  The range function is bound when a program is made,
        not when it is traced."""
        cfg, segs = self.base_cfg, list(self.built.stages[0])
        apply_range, pctx = self._tfm.apply_layer_range, self._pctx(mp)

        def layers(params, x, lo, hi, mode, cache=None, pos=None, x_emb=None):
            x, cache, _ = apply_range(
                segs, params["stages"][0], x, lo, hi, cfg=cfg, pctx=pctx,
                mode=mode, range_cache=cache, pos=pos,
                shared_params=params.get("shared_attn"), x_emb=x_emb)
            return x, cache

        return layers

    def _embed(self, params, toks):
        from repro.models.common import embed
        return embed(params["embed"], toks, scale=self._emb_scale)

    def _front(self, layers, params, toks, split: int, mode: str, cache=None,
               pos=None):
        """The edge's front: embed ``toks`` and run layers [0, split).
        Returns ``(x, cache, x_emb)``; ``x_emb``, the embeddings the hybrid
        layers read beside the residual, is None for other models."""
        x = self._embed(params, toks)
        x_emb = x if self._reads_emb else None
        x, cache = layers(params, x, 0, split, mode, cache, pos, x_emb)
        return x, cache, x_emb

    def _head(self, params, x):
        """Final norm and LM head over the rows ``x``: the logits."""
        from repro.models.common import rms_norm, unembed
        cfg = self.base_cfg
        x = rms_norm(x, params["final_norm"], cfg.rms_eps)
        table = params["embed"] if cfg.tie_embeddings else params["head"]
        return unembed(table, x, cfg.logit_softcap)

    def _wire_out(self, params, x, toks, split: int):
        """The edge's side of the wire: (payload, scales) of the boundary
        rows ``x``, the token ids beside the codes where a layer past
        ``split`` reads the embeddings."""
        payload, scales = self.wire.encode(x, params["butterfly"]["w_reduce"])
        if self.ships_ids(split):
            payload = CodedRows(payload, toks.astype(self.ids_dtype))
        return payload, scales

    def _wire_in(self, params, payload, scales, split: int):
        """The cloud's side of the wire: the restored boundary rows and,
        where the payload carries token ids, their embeddings (else None)."""
        x_emb = None
        if self.ships_ids(split):
            x_emb = self._embed(params, payload.ids.astype("int32"))
            payload = payload.codes
        return self.wire.decode(payload, scales,
                                params["butterfly"]["w_restore"],
                                self._dt), x_emb

    # ------------------------------------------------------------- programs
    def _make_edge(self, split: int, mp: int = 1):
        import jax
        from jax.sharding import PartitionSpec as P
        layers = self._layers(mp)

        def edge(params, toks):
            x, cache0, _ = self._front(layers, params, toks, split, "prefill")
            payload, scales = self._wire_out(params, x, toks, split)
            return payload, scales, cache0

        edge = self._mp_wrap(
            edge, mp, lambda: ((self._tp_specs(), P()),
                               (P(), P(), self._cache_spec_tree(0, split))))
        return jax.jit(edge)

    def _make_cloud(self, split: int, mp: int = 1):
        import jax
        from jax.sharding import PartitionSpec as P
        layers, N = self._layers(mp), self.base_cfg.num_layers

        def cloud(params, payload, scales, length):
            x, x_emb = self._wire_in(params, payload, scales, split)
            x, cache1 = layers(params, x, split, N, "prefill", x_emb=x_emb)
            x = jax.lax.dynamic_slice_in_dim(x, length - 1, 1, axis=1)
            return self._head(params, x)[:, 0], cache1

        cloud = self._mp_wrap(
            cloud, mp, lambda: ((self._tp_specs(), P(), P(), P()),
                                (P(), self._cache_spec_tree(1, split))))
        return jax.jit(cloud)

    def _make_prefill(self, split: int, mp: int = 1):
        """Full hosted-model prefill (both halves + the wire, one graph):
        the engine path for cloud-only / mobile-only serving.  The cloud
        range reads the edge's own embeddings: no ids cross in-graph."""
        import jax
        from jax.sharding import PartitionSpec as P
        layers, N = self._layers(mp), self.base_cfg.num_layers

        def prefill(params, toks, length):
            x, cache0, x_emb = self._front(layers, params, toks, split,
                                           "prefill")
            x = self.wire.through(x, **params["butterfly"])
            x, cache1 = layers(params, x, split, N, "prefill", x_emb=x_emb)
            x = jax.lax.dynamic_slice_in_dim(x, length - 1, 1, axis=1)
            return self._head(params, x), [cache0, cache1]

        prefill = self._mp_wrap(
            prefill, mp,
            lambda: ((self._tp_specs(), P(), P()),
                     (P(), [self._cache_spec_tree(0, split),
                            self._cache_spec_tree(1, split)])))
        return jax.jit(prefill)

    def _make_decode(self, split: int, mp: int = 1):
        """Batched hosted-model decode step for the ServingEngine: fixed
        (max_batch, 1) shapes, ragged per-slot positions, the wire via the
        fused kernels' (B, 1, d) fast path.  NOT jit-wrapped here — the
        engine folds sampling into the same jitted step."""
        from jax.sharding import PartitionSpec as P
        layers, N = self._layers(mp), self.base_cfg.num_layers

        def decode(params, tokens, caches, pos):
            x, nc0, x_emb = self._front(layers, params, tokens, split,
                                        "decode", caches[0], pos)
            x = self.wire.through(x, **params["butterfly"])
            x, nc1 = layers(params, x, split, N, "decode", caches[1], pos,
                            x_emb)
            return self._head(params, x), [nc0, nc1]

        def specs():
            cache_specs = [self._cache_spec_tree(0, split),
                           self._cache_spec_tree(1, split)]
            return ((self._tp_specs(), P(), cache_specs, P()),
                    (P(), cache_specs))

        return self._mp_wrap(decode, mp, specs)

    def _make_edge_step(self, split: int, mp: int = 1):
        """Streamed-decode edge half: embed one token, run layers [0, split)
        against the edge-resident stage-0 decode cache, emit one wire row —
        the per-token payload that replaces the stage-0 cache handoff."""
        import jax
        from jax.sharding import PartitionSpec as P
        layers = self._layers(mp)

        def edge_step(params, tok, cache0, pos):
            x, nc0, _ = self._front(layers, params, tok, split, "decode",
                                    cache0, pos)
            payload, scales = self._wire_out(params, x, tok, split)
            return payload, scales, nc0

        def specs():
            spec0 = self._cache_spec_tree(0, split)
            return ((self._tp_specs(), P(), spec0, P()), (P(), P(), spec0))

        edge_step = self._mp_wrap(edge_step, mp, specs)
        # every caller replaces its cache with the one handed back, so the
        # step takes the old one over and writes its rows in place
        return jax.jit(edge_step, donate_argnums=2)

    def _make_cloud_step(self, split: int, mp: int = 1):
        """Streamed-decode cloud half: restore one arrived row and run layers
        [split, N) against the cloud-resident stage-1 decode cache.  NOT
        jit-wrapped here — the engine folds sampling into the same jitted
        step (serving/engine._sampled_stream_step), shared by every engine of
        this split."""
        from jax.sharding import PartitionSpec as P
        layers, N = self._layers(mp), self.base_cfg.num_layers

        def cloud_step(params, payload, scales, cache1, pos):
            x, x_emb = self._wire_in(params, payload, scales, split)
            x, nc1 = layers(params, x, split, N, "decode", cache1, pos, x_emb)
            return self._head(params, x), nc1

        def specs():
            spec1 = self._cache_spec_tree(1, split)
            return ((self._tp_specs(), P(), P(), spec1, P()), (P(), spec1))

        return self._mp_wrap(cloud_step, mp, specs)

class SplitRunner:
    """Thin facade over the bank's shared backbone + compile cache for one
    candidate split.  ``runner.params`` shares every backbone leaf with
    ``bank.params`` (only the per-split butterfly differs).

    ``edge_mp``/``cloud_mp`` pick each half's model-axis degree: the edge
    half (edge/edge_step) and the cloud half (cloud/cloud_step, plus the
    full-model prefill/decode the cloud engines run) resolve through the
    bank's compile cache under their own mesh shape."""

    def __init__(self, bank: SplitModelBank, split: int, *, edge_mp: int = 1,
                 cloud_mp: int = 1):
        self.bank = bank
        self.split = split
        self.edge_mp = int(edge_mp)
        self.cloud_mp = int(cloud_mp)
        self.cfg = bank.base_cfg.with_butterfly(split, bank.d_r,
                                                bank.wire.bits)
        self.built = bank.built
        # shallow dict: backbone leaves are bank.params' leaves, not copies
        self.params = dict(bank.params)
        self.params["butterfly"] = bank.butterfly_params(split)
        # bytes of one token id on the wire, where the payload carries them
        self.id_bytes = bank.ids_dtype.itemsize if bank.ships_ids(split) \
            else 0

    # ------------------------------------------------------------ split halves
    def edge_half(self, params, toks):
        """Edge stage: layers [0, split) + reduce + quantize.  Accepts
        (B, S) token batches; returns true-shape (payload, scales, cache0)
        — the jitted core runs at bucket-padded (B, S)."""
        import jax.numpy as jnp
        bank = self.bank
        B, S = np.shape(toks)
        with span("split.edge_half", rows=S, ids_bytes=B * S * self.id_bytes):
            toks = jnp.asarray(toks)
            Bb, Sb = bank._buckets(B, S)
            out = bank.timed_call(
                bank.cache_key("edge", self.split, self.edge_mp, Bb, Sb),
                bank._fn("edge", self.split, self.edge_mp),
                params, bank._pad_toks(toks, Bb, Sb))
            payload, scales, cache0 = out
            return (payload[:B, :S], scales[:B, :S],
                    bank._slice_cache(cache0, 0, self.split, B, S))

    def cloud_half(self, params, payload, scales):
        """Cloud stage: restore + layers [split, N) + LM head.  Returns
        (last-position logits (B, V), cache1)."""
        import jax
        import jax.numpy as jnp
        bank = self.bank
        B, S = np.shape(payload)[:2]
        with span("split.cloud_half", rows=S):
            payload = jax.tree.map(jnp.asarray, payload)
            Bb, Sb = bank._buckets(B, S)
            if (Bb, Sb) != (B, S):
                pad = ((0, Bb - B), (0, Sb - S), (0, 0))
                payload, scales = jax.tree.map(
                    lambda a: jnp.pad(jnp.asarray(a), pad[:np.ndim(a)]),
                    (payload, scales))
            logits, cache1 = bank.timed_call(
                bank.cache_key("cloud", self.split, self.cloud_mp, Bb, Sb),
                bank._fn("cloud", self.split, self.cloud_mp),
                params, payload, scales, jnp.int32(S))
            return logits[:B], bank._slice_cache(cache1, 1, self.split, B, S)

    # --------------------------------------------------------- streamed decode
    def edge_step(self, params, tok, cache0, pos):
        """One streamed-decode edge step: ``tok`` (B, 1) int32, ``cache0``
        the edge-resident stage-0 decode cache (pad with
        :meth:`pad_decode_cache` first), ``pos`` (B,) int32 write positions,
        held on the host.  Returns ``(payload, scales, new_cache0)`` — one
        wire row per batch element.  ``cache0`` is donated to the step: the
        caller keeps ``new_cache0`` in its place."""
        import jax.numpy as jnp
        bank = self.bank
        with span("split.edge_step", pos=int(pos[0]),
                  ids_bytes=int(np.shape(tok)[0]) * self.id_bytes):
            tok = jnp.asarray(tok, jnp.int32)
            return bank.timed_call(
                bank.cache_key("edge_step", self.split, self.edge_mp,
                               tok.shape[0], 1),
                bank._fn("edge_step", self.split, self.edge_mp),
                params, tok, cache0, jnp.asarray(pos, jnp.int32))

    def stream_step(self, engine, req, cache, payload, scales, pos: int):
        """One streamed-decode cloud turn through ``engine``'s single-slot
        entry, with the bank's compile-cache bookkeeping (mirrors
        :meth:`edge_step`).  Returns ``(token, new_cache)``."""
        out = engine.stream_step(req, cache, payload, scales, pos)
        self.bank.note_key(
            self.bank.cache_key("cloud_step", self.split, self.cloud_mp,
                                1, 1))
        return out

    def pad_decode_cache(self, cache, stage: int, length: int):
        """Pad a prefill-shaped (B=1, seq=S) stage cache to decode capacity
        ``length`` so per-token steps can write rows past the prompt —
        the streamed analogue of the engine pool's max_len sizing.  Leaves
        without a short seq axis (recurrent state) pass through."""
        import jax
        import jax.numpy as jnp

        def pad(leaf, t):
            if leaf.shape == t.shape:
                return leaf
            pads = [(0, ts - ls) for ls, ts in zip(leaf.shape, t.shape)]
            return jnp.pad(leaf, pads)

        with span("split.pad_cache", stage=stage):
            template = self.bank._cache_template(stage, self.split, 1, length)
            return jax.tree.map(pad, cache, template)

    # ------------------------------------------------------ pipelined decode
    def decode_pipeline(self, mesh, num_microbatches: int, prompt_len: int,
                        microbatch: int, new_tokens: int, *,
                        pipelined: bool = True, use_kernel: bool = False,
                        overlap_psum: bool = False):
        """Multi-token greedy decode over a ``(pod, ...)`` mesh through this
        split: ``serving.pipeline.make_decode_pipeline``'s microbatch
        rotation (or its serial ping-pong reference with
        ``pipelined=False``) over copies of this split's two layer ranges,
        sliced once, eagerly, out of the bank's shared backbone
        (``slice_stage_params``).  Returns ``run(tokens) ->
        (num_microbatches * microbatch, new_tokens)`` greedy ids.  The
        compiled fn + sliced params are cached in the bank's compile cache
        under the wire signature."""
        import jax
        bank = self.bank
        assert bank.wire.quantized, \
            "decode pipeline wires quantized codes (int8/int4/entropy)"
        key = ("decode_pipeline", self.split, id(mesh), num_microbatches,
               prompt_len, microbatch, new_tokens, bool(pipelined),
               bool(use_kernel), bool(overlap_psum)) + bank._wire_sig
        if key not in bank._fns:
            from repro.models.model import BuiltModel
            from repro.serving import pipeline as spl
            tfm = bank._tfm
            segs = list(self.built.stages[0])
            N = bank.base_cfg.num_layers
            s0, p0 = tfm.slice_stage_params(segs, self.params["stages"][0],
                                            0, self.split)
            s1, p1 = tfm.slice_stage_params(segs, self.params["stages"][0],
                                            self.split, N)
            params = dict(self.params)
            params["stages"] = [p0, p1]
            built = BuiltModel(cfg=self.cfg, stages=(tuple(s0), tuple(s1)),
                               enc_segments=(),
                               long_mode=self.built.long_mode)
            fn = spl.make_decode_pipeline(
                built, mesh, num_microbatches, prompt_len, microbatch,
                new_tokens, wire_mode=bank.wire.mode, pipelined=pipelined,
                use_kernel=use_kernel, overlap_psum=overlap_psum)
            bank._fns[key] = (jax.jit(fn), params)
        fn, params = bank._fns[key]

        def run(tokens):
            bank.note_key(key)
            return fn(params, tokens)

        return run

    # ------------------------------------------------------------- engine glue
    def _engine_prefill(self, params, toks, mp: Optional[int] = None):
        import jax.numpy as jnp
        mp = self.cloud_mp if mp is None else mp
        bank = self.bank
        toks = jnp.asarray(toks)
        B, S = toks.shape
        Bb, Sb = bank._buckets(B, S)
        logits, caches = bank.timed_call(
            bank.cache_key("prefill", self.split, mp, Bb, Sb),
            bank._fn("prefill", self.split, mp),
            params, bank._pad_toks(toks, Bb, Sb), jnp.int32(S))
        return logits[:B], [bank._slice_cache(caches[0], 0, self.split, B, S),
                            bank._slice_cache(caches[1], 1, self.split, B, S)]

    def make_engine(self, *, max_batch: int, max_len: int, seed: int = 0,
                    mp: Optional[int] = None):
        """``mp`` — model-axis degree of the engine's whole-model
        prefill/decode steps.  Defaults to the runner's cloud degree (the
        engines live on the cloud server); the mobile-only baseline passes
        its edge degree so an edge-resident engine never compiles — or
        demands the devices of — the cloud's mesh."""
        from functools import partial

        from repro.serving.engine import ServingEngine
        mp = self.cloud_mp if mp is None else int(mp)
        return ServingEngine(self.params, self.built, max_batch=max_batch,
                             max_len=max_len, seed=seed,
                             stages=self.bank.engine_stages(self.split),
                             prefill_fn=partial(self._engine_prefill, mp=mp),
                             decode_fn=self.bank._fn("decode", self.split, mp),
                             stream_fn=self.bank._fn("cloud_step", self.split,
                                                     mp),
                             profiler=self.bank.profiler,
                             profile_key=(self.split, mp))

    # --------------------------------------------------------------- reference
    def reference_prefill(self, toks):
        """Single-mesh forward (what the split path must reproduce): eager,
        reference (non-kernel) wire codec, same wire_mode semantics."""
        import jax.numpy as jnp
        bank, params = self.bank, self.params
        layers, N = bank._layers(1), bank.base_cfg.num_layers
        x, cache0, x_emb = bank._front(layers, params, jnp.asarray(toks),
                                       self.split, "prefill")
        x = bank.wire.through(x, **params["butterfly"], kernel=False)
        x, cache1 = layers(params, x, self.split, N, "prefill", x_emb=x_emb)
        return bank._head(params, x[:, -1:]), [cache0, cache1]
