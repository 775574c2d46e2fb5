"""Batched serving engine: continuous-batching style prefill/decode with a
slot-based KV/state cache pool.

Real-engine behaviours kept: per-request positions (ragged decode), slot
reuse on completion, greedy or temperature sampling, max-token and EOS
stopping.  The decode hot path is one jitted step per batch: sampling
(greedy argmax + temperature categorical) runs *inside* the jitted graph,
so ``step()`` costs a single host sync for the whole slot pool instead of a
per-slot ``device_get`` + Python argmax; per-step logits snapshots are
opt-in (``record_logits``).  Slot admission writes the cache pool through
one jitted donated update instead of an eager per-leaf dispatch.  The
pool is donated to every decode step too: the step writes one row per
slot and layer into it in place and hands it back as ``engine.cache``, so
no caller may keep an old ``engine.cache`` across a step or an admission
(its buffers are deleted).  ``donated_steps`` counts the steps that took
their cache over, beside ``decode_steps``.  A wrapper put in place of
``_step``/``_stream_step`` (to observe or alter a step) runs on the
step that leaves its cache as it was (the same program, run on a copy),
since it may keep or hand back the cache it is given.

The engine's forward functions are pluggable: the split runtime's
``SplitModelBank`` supplies jitted prefill/decode closures over the shared
backbone (one compile per split, shared by every engine of that split);
stand-alone engines default to the single-mesh ``models.model`` forwards.
Model-parallel stages thread through the same seam (DESIGN.md section 11):
a bank closure compiled for a ``(model,)`` mesh arrives as a distinct
callable per mesh shape, so the weak-keyed ``_STEP_FNS``/``_STREAM_STEP_FNS``
caches below — keyed on closure identity — can never hand a step compiled
for one mesh to an engine running another; the cache pool itself stays a
global-shape pytree (shard_map assembles/splits the kv-head shards at the
closure boundary).
For the streamed decode transport the engine adds a single-slot entry
(``submit_streamed`` + ``stream_step``): the request holds no cache-pool
slot — its cloud-side stage cache lives with the caller — and each arrived
``(1, d_r)`` row runs through the bank-shared compiled cloud step with
in-graph sampling, which takes the request's cache over the same way.
"""
from __future__ import annotations

import dataclasses
import functools
import weakref
from typing import Callable, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import model as M
from repro.models import transformer as tfm
from repro.models.parallel import LOCAL, ParallelContext


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray                  # (S,) int32
    max_new_tokens: int = 32
    temperature: float = 0.0
    eos_id: Optional[int] = None
    record_logits: bool = False         # keep per-step logits (host copies)
    # filled by the engine
    generated: list = dataclasses.field(default_factory=list)
    logits_history: list = dataclasses.field(default_factory=list)
    done: bool = False


@functools.partial(jax.jit, donate_argnums=0)
def _write_slot_jit(pool, new, slot):
    """Copy a single-request cache into batch slot ``slot`` of the pool in
    one compiled dispatch; seq axes of attention caches pad to the pool's
    max_len/window.  The pool buffers are donated so admission updates in
    place where the backend allows."""
    def copy(pool_leaf, new_leaf):
        pad = [(0, 0)] * new_leaf.ndim
        changed = False
        for ax in range(2, new_leaf.ndim):
            if new_leaf.shape[ax] < pool_leaf.shape[ax]:
                pad[ax] = (0, pool_leaf.shape[ax] - new_leaf.shape[ax])
                changed = True
        if changed:
            new_leaf = jnp.pad(new_leaf, pad)
        start = (0, slot) + (0,) * (new_leaf.ndim - 2)
        return jax.lax.dynamic_update_slice(
            pool_leaf, new_leaf.astype(pool_leaf.dtype), start)

    return jax.tree.map(copy, pool, new)


def _span(name: str, **args):
    """``repro.runtime.tracing.span``, imported on first use: the runtime
    package builds on this module, so the engine does not import it at
    load time."""
    from repro.runtime.tracing import span
    return span(name, **args)


# decode_fn -> jitted (decode + in-graph sampling) steps, shared by every
# engine using the same decode closure (e.g. all engines of one bank split)
_STEP_FNS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_STREAM_STEP_FNS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


class _Steps(NamedTuple):
    """One compiled sampled step, called two ways: ``donating`` takes its
    cache argument over and updates it in place; ``kept`` leaves the cache
    it is given as it was (it runs the same program on a device copy)."""
    kept: Callable
    donating: Callable

    @classmethod
    def of(cls, step, cache_arg: int) -> "_Steps":
        donating = jax.jit(step, donate_argnums=cache_arg)

        def kept(*args):
            args = list(args)
            args[cache_arg] = jax.device_put(args[cache_arg], may_alias=False)
            return donating(*args)
        return cls(kept, donating)


def _consumed(cache) -> int:
    """1 when a dispatched step took ``cache`` over (its buffers were
    donated and are deleted), else 0.  A host check: it waits for nothing."""
    return int(jax.tree.leaves(cache)[0].is_deleted())


def _program(step, steps: _Steps):
    """What runs for ``step``: the engine's own step donates its cache; a
    step put in its place runs as given."""
    return steps.donating if step is steps.kept else step


def _sample_ingraph(row, key, temps):
    """Greedy argmax + temperature categorical, inside the jitted graph."""
    greedy = jnp.argmax(row, axis=-1).astype(jnp.int32)
    key, sub = jax.random.split(key)
    keys = jax.random.split(sub, row.shape[0])
    safe_t = jnp.maximum(temps, 1e-6)[:, None]
    sampled = jax.vmap(jax.random.categorical)(keys, row / safe_t)
    toks = jnp.where(temps > 0, sampled.astype(jnp.int32), greedy)
    return toks, key


def _sampled_step(decode_fn):
    try:
        return _STEP_FNS[decode_fn]
    except KeyError:
        pass

    # the closure must NOT strongly reference decode_fn: the cached value
    # would then keep its own weak key alive and the entry would be
    # immortal, pinning engines/banks (params + cache pools) forever.  The
    # caller holds decode_fn for the engine's lifetime, so the deref only
    # fails after every user of this entry is already gone.
    ref = weakref.ref(decode_fn)

    def step(params, tokens, caches, pos, key, temps):
        logits, caches = ref()(params, tokens, caches, pos)
        row = logits[:, 0].astype(jnp.float32)             # (B, V)
        toks, key = _sample_ingraph(row, key, temps)
        return toks, row, caches, key

    steps = _Steps.of(step, cache_arg=2)
    _STEP_FNS[decode_fn] = steps
    return steps


def _sampled_stream_step(stream_fn):
    """stream_fn -> jitted (cloud half of one streamed row + in-graph
    sampling), shared by every engine wired to the same cloud-step closure
    (all engines of one bank split).  Same weakref discipline as
    :func:`_sampled_step`."""
    try:
        return _STREAM_STEP_FNS[stream_fn]
    except KeyError:
        pass
    ref = weakref.ref(stream_fn)

    def step(params, payload, scales, cache, pos, key, temps):
        logits, cache = ref()(params, payload, scales, cache, pos)
        row = logits[:, 0].astype(jnp.float32)             # (B, V)
        toks, key = _sample_ingraph(row, key, temps)
        return toks, row, cache, key

    steps = _Steps.of(step, cache_arg=3)
    _STREAM_STEP_FNS[stream_fn] = steps
    return steps


class ServingEngine:
    def __init__(self, params, built: M.BuiltModel, *, max_batch: int = 8,
                 max_len: int = 512, pctx: ParallelContext = LOCAL,
                 seed: int = 0, stages=None,
                 prefill_fn: Optional[Callable] = None,
                 decode_fn: Optional[Callable] = None,
                 stream_fn: Optional[Callable] = None,
                 profiler=None, profile_key: tuple = ()):
        self.params = params
        self.built = built
        self.cfg = built.cfg
        self.max_batch = max_batch
        self.max_len = max_len
        self.pctx = pctx
        dt = jnp.dtype(self.cfg.dtype)
        stage_segs = stages if stages is not None else \
            [list(segs) for segs in built.stages]
        self.cache = [tfm.init_stage_cache(list(segs), self.cfg, max_batch,
                                           max_len, dt)
                      for segs in stage_segs]
        self.positions = np.zeros((max_batch,), np.int32)   # next write pos
        self.active: List[Optional[Request]] = [None] * max_batch
        self.key = jax.random.key(seed)
        self._prefill = prefill_fn or self._default_prefill
        # hold strong refs to the decode/stream closures: the step caches
        # are weak-keyed, so each shared jitted step lives exactly as long
        # as its closure
        self._decode = decode_fn or self._decode_fn
        self._steps = _sampled_step(self._decode)
        self._stream = stream_fn
        self._stream_steps = _sampled_stream_step(stream_fn) \
            if stream_fn is not None else None
        # the steps as the engine calls them; a wrapper put in their place
        # (one that observes or alters a step) may keep or hand back the
        # cache it is given, so what it calls leaves that cache as it was
        self._step = self._steps.kept
        self._stream_step = self._stream_steps.kept \
            if stream_fn is not None else None
        self._last = np.zeros((max_batch, 1), np.int32)     # last token/slot
        self._temps = np.zeros((max_batch,), np.float32)
        self._uid = 0
        self.decode_steps = 0
        self.donated_steps = 0      # steps that consumed the cache passed in
        # opt-in wall-clock attribution of the fused sampling steps
        # (metrics.JitProfiler); profile_key distinguishes engines sharing
        # the module-level step caches (e.g. the bank's (split, mp))
        self._profiler = profiler
        self._profile_key = tuple(profile_key)

    # ------------------------------------------------------------------ api
    def submit(self, prompt, max_new_tokens: int = 32, temperature: float = 0.0,
               eos_id: Optional[int] = None,
               record_logits: bool = False) -> Request:
        req = Request(self._uid, np.asarray(prompt, np.int32),
                      max_new_tokens=max_new_tokens, temperature=temperature,
                      eos_id=eos_id, record_logits=record_logits)
        self._uid += 1
        with _span("engine.admit", uid=req.uid, rows=len(req.prompt),
                   **self._slot_bytes(len(req.prompt))):
            self._prefill_into(self._free_slot(), req)
        return req

    def submit_prefilled(self, prompt_len: int, caches, last_logits,
                         max_new_tokens: int = 32, temperature: float = 0.0,
                         eos_id: Optional[int] = None,
                         record_logits: bool = False) -> Request:
        """Admit a request whose prefill ran elsewhere (the split runtime's
        edge/cloud halves): inject its per-stage caches into a free slot and
        sample the first token from the externally computed last-position
        logits.  ``caches`` must match the engine's stage-cache pytree with
        batch dim 1; seq dims shorter than ``max_len`` are padded."""
        assert prompt_len < self.max_len, "prompt exceeds cache"
        req = Request(self._uid, np.zeros((prompt_len,), np.int32),
                      max_new_tokens=max_new_tokens, temperature=temperature,
                      eos_id=eos_id, record_logits=record_logits)
        self._uid += 1
        with _span("engine.admit", uid=req.uid, rows=prompt_len,
                   **self._slot_bytes(prompt_len)):
            slot = self._free_slot()
            self._write_slot(slot, caches)
            self.positions[slot] = prompt_len
            self.active[slot] = req
            last_logits = jnp.asarray(last_logits)
            if req.record_logits:
                req.logits_history.append(jax.device_get(last_logits))
            self._emit(slot, req, self._sample(last_logits, req))
        return req

    def submit_streamed(self, prompt_len: int, last_logits,
                        max_new_tokens: int = 32, temperature: float = 0.0,
                        eos_id: Optional[int] = None,
                        record_logits: bool = False) -> Request:
        """Admit a streamed-decode request: the edge keeps its half's decode
        cache and streams one reduced row per token, so the request holds NO
        cache-pool slot here — the engine only does sampling and stop
        bookkeeping.  The caller owns the cloud-side stage cache and applies
        each arrived row via :meth:`stream_step`."""
        assert prompt_len < self.max_len, "prompt exceeds cache"
        req = Request(self._uid, np.zeros((prompt_len,), np.int32),
                      max_new_tokens=max_new_tokens, temperature=temperature,
                      eos_id=eos_id, record_logits=record_logits)
        self._uid += 1
        # a streamed request holds no slot: nothing is written to the pool
        with _span("engine.admit", uid=req.uid, rows=prompt_len,
                   state_bytes=0, kv_bytes=0):
            last_logits = jnp.asarray(last_logits)
            if req.record_logits:
                req.logits_history.append(jax.device_get(last_logits))
            tok = self._sample(last_logits, req)
        req.generated.append(tok)
        if (req.eos_id is not None and tok == req.eos_id) or \
                req.max_new_tokens <= 1:
            req.done = True
        return req

    def stream_step(self, req: Request, cache, payload, scales, pos: int):
        """Single-slot streamed decode: apply one externally-computed edge
        row to ``cache`` (the request's cloud-side stage cache) through the
        shared compiled cloud step (one dispatch: restore + layers [split, N)
        + sampling) and return ``(token, new_cache)``.  ``cache`` is donated
        to the step: the caller keeps ``new_cache`` in its place."""
        assert self._stream_step is not None, "engine built without stream_fn"
        with _span("engine.stream_step", uid=req.uid, pos=pos):
            old = cache
            toks, row, cache, self.key = self._dispatch(
                "engine_stream_step",
                _program(self._stream_step, self._stream_steps),
                self.params, jax.tree.map(jnp.asarray, payload),
                jnp.asarray(scales), cache,
                jnp.asarray([pos], jnp.int32), self.key,
                jnp.asarray([req.temperature], jnp.float32))
            self.donated_steps += _consumed(old)
            with _span("sync"):
                tok = int(jax.device_get(toks)[0])
            if req.record_logits:
                req.logits_history.append(np.asarray(jax.device_get(row))[0])
            req.generated.append(tok)
            self.decode_steps += 1
            if (req.eos_id is not None and tok == req.eos_id) or \
                    len(req.generated) >= req.max_new_tokens:
                req.done = True
        return tok, cache

    @property
    def num_active(self) -> int:
        return sum(1 for r in self.active if r is not None)

    def run(self, requests_done: Optional[Callable[[], bool]] = None,
            max_steps: int = 10_000):
        """Decode until all slots drain, ``max_steps`` elapse, or the
        ``requests_done`` predicate (checked between steps) fires."""
        steps = 0
        while any(r is not None for r in self.active) and steps < max_steps:
            if requests_done is not None and requests_done():
                break
            self.step()
            steps += 1

    # ------------------------------------------------------------- internals
    def _slot_bytes(self, rows: int) -> dict:
        """Bytes a request of ``rows`` prompt positions writes into its slot:
        ``state_bytes`` of recurrent state (the whole slot's, whatever the
        length) and ``kv_bytes`` of attention KV for its positions (a
        windowed cache holds at most its window)."""
        state = kv = 0
        for path, leaf in jax.tree_util.tree_flatten_with_path(self.cache)[0]:
            per_slot = leaf.nbytes // self.max_batch   # (repeats, slots, ...)
            if any(getattr(k, "key", None) == "kv" for k in path):
                length = leaf.shape[2]
                kv += per_slot * min(rows, length) // length
            else:
                state += per_slot
        return {"state_bytes": int(state), "kv_bytes": int(kv)}

    def _free_slot(self) -> int:
        for i, r in enumerate(self.active):
            if r is None:
                return i
        raise RuntimeError("engine full; drain before submitting")

    def _default_prefill(self, params, toks):
        batch = {"tokens": jnp.asarray(toks)}
        return M.forward_prefill(params, self.built, batch, self.pctx)

    def _prefill_into(self, slot: int, req: Request):
        S = len(req.prompt)
        assert S < self.max_len, "prompt exceeds cache"
        logits, caches = self._prefill(self.params, req.prompt[None])
        self._write_slot(slot, caches)
        self.positions[slot] = S
        self.active[slot] = req
        if req.record_logits:
            req.logits_history.append(jax.device_get(logits[0, -1]))
        self._emit(slot, req, self._sample(logits[0, -1], req))

    def _emit(self, slot: int, req: Request, tok: int):
        """Record a sampled first token and retire single-token requests."""
        req.generated.append(tok)
        self._last[slot, 0] = tok
        self._temps[slot] = req.temperature
        if (req.eos_id is not None and tok == req.eos_id) or \
                req.max_new_tokens <= 1:
            req.done = True
            self.active[slot] = None

    def _dispatch(self, kind: str, fn, *args):
        """Run a fused step inside a ``dispatch`` span, optionally through
        the wall-clock profiler."""
        with _span("dispatch", kind=kind):
            if self._profiler is None:
                return fn(*args)
            return self._profiler.timed((kind,) + self._profile_key, fn,
                                        *args)

    def _write_slot(self, slot: int, req_cache):
        with _span("engine.write_slot", slot=slot):
            self.cache = _write_slot_jit(self.cache, req_cache,
                                         jnp.int32(slot))

    def _decode_fn(self, params, tokens, caches, pos):
        return M.forward_decode(params, self.built, tokens, caches, pos,
                                self.pctx)

    def _sample(self, logits, req: Request) -> int:
        with _span("engine.sample", uid=req.uid):
            if req.temperature <= 0:
                return int(jnp.argmax(logits))
            self.key, sub = jax.random.split(self.key)
            return int(jax.random.categorical(sub, logits / req.temperature))

    def step(self):
        """One batched decode step over all active slots: a single jitted
        dispatch (forward + sampling) and a single host sync for the
        sampled tokens."""
        active = self.num_active
        if not active:
            return
        with _span("engine.step", active=active,
                   slots=self.max_batch) as step_span:
            # .copy() is load-bearing: on the CPU backend jnp.asarray can
            # alias the numpy buffer zero-copy, and the in-place
            # `positions[i] += 1` below would race with the still-dispatching
            # decode (observed as a rare wrong-slot cache write under load)
            pos = jnp.asarray(self.positions.copy())
            old = self.cache
            toks, logits, self.cache, self.key = self._dispatch(
                "engine_step", _program(self._step, self._steps),
                self.params, jnp.asarray(self._last.copy()), self.cache, pos,
                self.key, jnp.asarray(self._temps.copy()))
            donated = _consumed(old)
            step_span.set_metadata(donated=donated)
            self.donated_steps += donated
            with _span("sync"):                        # the one host sync
                toks_host = np.asarray(jax.device_get(toks))
            logits_host = None
            self.decode_steps += 1
            for i, r in enumerate(self.active):
                if r is None:
                    continue
                self.positions[i] += 1
                tok = int(toks_host[i])
                self._last[i, 0] = tok
                if r.record_logits:
                    if logits_host is None:     # already computed; copy-only
                        logits_host = np.asarray(jax.device_get(logits))
                    r.logits_history.append(logits_host[i])
                r.generated.append(tok)
                if (r.eos_id is not None and tok == r.eos_id) or \
                        len(r.generated) >= r.max_new_tokens or \
                        self.positions[i] >= self.max_len - 1:
                    r.done = True
                    self.active[i] = None
