"""The batched decode step updates its cache pool in place: each layer loop
carries its range's stacked cache, every layer writes one row per slot into
it, and the engine donates the pool to the step.

The oracle is the layer loop as it was before: each span scans its cache as
``xs``, each layer hands its whole cache back and the scan stacks them as
``ys``; and each layer writes its rows as it did before, a scatter into its
own K/V and a recurrent step on its own state (copies kept here, so the
oracle shares no write code with what it checks).  Both run through one
bank (same weights, same prefill) on four stacks: qwen3 (uniform
attention), gemma3 with an 8-position window (ring buffers that wrap, in a
two-layer repeat unit; at head_dim 128 and 64) and zamba2 (Mamba2 state
and two hybrid layers' shared-block KV).  Three
requests of ragged lengths fill slots 0-2 of 4; slot 3 stays empty, slot 1
empties after one step and a fourth request is admitted into it after the
third."""
import dataclasses
import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import attention as attn
from repro.models import transformer as tfm
from repro.runtime.split_exec import SplitModelBank
from repro.serving.engine import ServingEngine

SLOTS, MAX_LEN, STEPS, SPLIT = 4, 24, 6, 2
PROMPTS = (9, 13, 11)            # slots 0, 1, 2; slot 3 is never used
LATE = 7                         # admitted into slot 1 after step 3
# head_dim 128 writes each slot's row with one scatter, 64 with one
# dynamic_update_slice per slot (attention.attention_decode says why)
ARCHS = {"qwen3": ("qwen3-8b", {"head_dim": 128}),
         "windowed": ("gemma3-12b", {"sliding_window": 8, "head_dim": 128}),
         "windowed-hd64": ("gemma3-12b", {"sliding_window": 8}),
         "zamba2": ("zamba2-7b", {"hybrid_layer_ids": (1, 3)})}


def old_attention_decode(params, x, cache, cache_pos, *, cfg, window,
                         rope=True, scale=None, layer):
    """Ragged decode attention as it was before the pool was carried: each
    sequence's new row scattered into the layer's own K/V, the whole layer
    handed back.  The oracle's loop hands it a stack of that one layer."""
    assert layer == 0
    cache = {n: a[0] for n, a in cache.items()}
    B, _, _ = x.shape
    T = cache["k"].shape[1]
    positions = jnp.asarray(cache_pos, jnp.int32)[:, None]
    q, k_new, v_new = attn._project_qkv(params, x, cfg, positions, rope=rope)
    slots = positions[:, 0] % T if window is not None else \
        jnp.minimum(positions[:, 0], T - 1)
    b_idx = jnp.arange(B)
    k = cache["k"].at[b_idx, slots].set(k_new[:, 0].astype(cache["k"].dtype))
    v = cache["v"].at[b_idx, slots].set(v_new[:, 0].astype(cache["v"].dtype))
    scores = attn._gqa_scores(q, k, cfg, scale)
    idx = jnp.arange(T)[None, :]
    if window is not None:
        age = (slots[:, None] - idx) % T
        valid = age < jnp.minimum(positions + 1, window)
    else:
        valid = idx <= positions
    out = attn._attend(scores, v, valid[:, None, None, None, :], x.dtype)
    out = out.reshape(B, 1, -1) @ params["wo"]
    return out, {"k": k[None], "v": v[None]}


def old_state_step(decode_fn, params, h, stack, layer, cfg):
    """A recurrent mixer's decode step as it was: on the layer's own state,
    the new state handed back whole (as a stack of that one layer)."""
    assert layer == 0
    out, st = decode_fn(params, h, {n: a[0] for n, a in stack.items()
                                    if n != "kv"}, cfg=cfg)
    return out, {**stack, **{n: a[None] for n, a in st.items()}}


def xs_ys_layer_range(segments, stage_params, x, lo, hi, *, cfg, pctx, mode,
                      range_cache, pos, enc_out=None, shared_params=None,
                      use_kernel=False, causal=True, x_emb=None):
    """The decode layer loop before the pool was carried: the range's cache
    is scanned as ``xs``, each layer writes through the old per-layer code
    and hands its new cache back whole, stacked as ``ys``."""
    assert mode == "decode"
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(attn, "attention_decode", old_attention_decode)
        mp.setattr(tfm, "_state_step", old_state_step)
        return _xs_ys_spans(segments, stage_params, x, lo, hi, cfg=cfg,
                            pctx=pctx, mode=mode, range_cache=range_cache,
                            pos=pos, enc_out=enc_out,
                            shared_params=shared_params,
                            use_kernel=use_kernel, causal=causal, x_emb=x_emb)


def _xs_ys_spans(segments, stage_params, x, lo, hi, *, cfg, pctx, mode,
                 range_cache, pos, enc_out, shared_params, use_kernel, causal,
                 x_emb):
    new_caches = []
    for k, span in enumerate(tfm._range_spans(segments, lo, hi)):
        if span[0] == "slice":
            _, si, r0, r1 = span
            seg, unit_params = segments[si], stage_params[si]
        else:
            _, si, r0, upos = span
            r1 = r0 + 1
            seg = tfm.Segment(unit=(segments[si].unit[upos],), repeats=1)
            unit_params = [stage_params[si][upos]]

        def body(xc, xs, seg=seg, unit_params=unit_params):
            i, unit_cache = xs
            new = []
            for u, ldef in enumerate(seg.unit):
                lp = jax.tree.map(lambda a: a[i], unit_params[u])
                one = jax.tree.map(lambda a: a[None], unit_cache[u])
                xc, nc, _, _ = tfm.apply_layer(
                    ldef, lp, xc, cfg=cfg, pctx=pctx, mode=mode, cache=one,
                    pos=pos, enc_out=enc_out, shared_params=shared_params,
                    use_kernel=use_kernel, causal=causal, x_emb=x_emb,
                    layer=0)
                new.append(jax.tree.map(lambda a: a[0], nc))
            return xc, new

        x, nc = jax.lax.scan(body, x, (jnp.arange(r0, r1), range_cache[k]))
        new_caches.append(nc)
    return x, new_caches, jnp.zeros((2,), jnp.float32)


def _engines(arch):
    name, over = ARCHS[arch]
    cfg = dataclasses.replace(get_config(name).reduced(), num_layers=4,
                              **over)
    bank = SplitModelBank(cfg, 16, seed=0)
    r = bank.runner(SPLIT)
    new = r.make_engine(max_batch=SLOTS, max_len=MAX_LEN, seed=0)
    # the oracle's decode closure is the bank's own, built over the old loop
    real = bank._tfm
    bank._tfm = types.SimpleNamespace(apply_layer_range=xs_ys_layer_range)
    try:
        old_decode = bank._make_decode(SPLIT)
    finally:
        bank._tfm = real
    old = ServingEngine(r.params, r.built, max_batch=SLOTS, max_len=MAX_LEN,
                        seed=0, stages=bank.engine_stages(SPLIT),
                        prefill_fn=functools.partial(r._engine_prefill, mp=1),
                        decode_fn=old_decode)
    return cfg, new, old


def _host(tree):
    """Host copies that hold no reference to the device buffers (a
    zero-copy view would keep the CPU backend from donating them)."""
    return [np.array(a, copy=True) for a in jax.tree.leaves(tree)]


def _serve(arch):
    """Step both engines side by side; record, per step, the tokens, the
    pools before and after (host copies) and the slots' write positions."""
    cfg, new, old = _engines(arch)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in PROMPTS + (LATE,)]
    new_steps = (STEPS, 1, STEPS, STEPS)     # slot 1 empties after a step
    reqs = {"new": [], "old": []}
    for eng, key in ((new, "new"), (old, "old")):
        for p, n in zip(prompts[:3], new_steps):
            reqs[key].append(eng.submit(p, max_new_tokens=n + 1,
                                        record_logits=True))
    steps, deleted = [], []
    for t in range(STEPS):
        if t == 3:
            for eng, key in ((new, "new"), (old, "old")):
                reqs[key].append(eng.submit(prompts[3], max_new_tokens=3,
                                            record_logits=True))
        active = [r is not None for r in new.active]
        pos = new.positions.copy()
        before = _host(new.cache)
        pool = new.cache
        new.step()
        old.step()
        deleted.append(all(a.is_deleted() for a in jax.tree.leaves(pool)))
        steps.append(dict(active=active, pos=pos, before=before,
                          after=_host(new.cache), oracle=_host(old.cache)))
    paths = [p for p, _ in jax.tree_util.tree_flatten_with_path(new.cache)[0]]
    return dict(cfg=cfg, new=new, old=old, reqs=reqs, steps=steps,
                deleted=deleted, paths=paths)


@pytest.fixture(scope="module", params=sorted(ARCHS))
def served(request):
    return request.param, _serve(request.param)


def _is_kv(path) -> bool:
    return any(getattr(k, "key", None) == "kv" for k in path)


def test_served_tokens_and_logits_match_the_xs_ys_loop(served):
    _, run = served
    for got, want in zip(run["reqs"]["new"], run["reqs"]["old"]):
        assert got.generated == want.generated
        assert len(got.logits_history) == len(want.logits_history) > 1
        for g, w in zip(got.logits_history, want.logits_history):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_pool_after_each_step_matches_the_xs_ys_loop(served):
    """Every leaf, KV and recurrent state alike, in every slot (empty ones
    too, whose rows the step writes as it always did)."""
    _, run = served
    for t, s in enumerate(run["steps"]):
        for path, got, want in zip(run["paths"], s["after"], s["oracle"]):
            np.testing.assert_array_equal(
                got, want, err_msg=f"step {t} {jax.tree_util.keystr(path)}")


def test_one_row_per_slot_and_layer_changes(served):
    """In each KV leaf (layers, slots, length, heads, head_dim) an active
    slot changes exactly its write row in every layer, and no slot changes
    any other row: an empty slot keeps its rows but the one at its stale
    position."""
    arch, run = served
    kv_leaves = 0
    for s in run["steps"]:
        for path, before, after in zip(run["paths"], s["before"],
                                       s["after"]):
            if not _is_kv(path):
                continue
            kv_leaves += 1
            length = before.shape[2]
            changed = np.any(before != after, axis=(3, 4))   # (L, B, T)
            for b in range(SLOTS):
                row = s["pos"][b] % length if length < MAX_LEN else \
                    min(s["pos"][b], length - 1)
                others = np.delete(changed[:, b], row, axis=1)
                assert not others.any(), (arch, path, b)
                if s["active"][b]:
                    assert changed[:, b, row].all(), (arch, path, b)
    assert kv_leaves


def test_write_slot_into_the_donated_pool(served):
    """The request admitted after a donated step lands in the freed slot
    and decodes as the oracle's does."""
    _, run = served
    late_new, late_old = run["reqs"]["new"][3], run["reqs"]["old"][3]
    assert run["new"].active[1] is None and len(late_new.generated) == 3
    assert late_new.generated == late_old.generated
    assert any(s["active"][1] for s in run["steps"][3:])


def test_every_step_donates_its_pool(served):
    _, run = served
    eng = run["new"]
    assert eng.decode_steps == STEPS
    assert eng.donated_steps == eng.decode_steps
    assert all(run["deleted"])
