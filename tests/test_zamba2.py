"""Zamba2 through the split-serving path, against plain float32 oracles
written here: the chunked SSD with a ragged last chunk against the
token-by-token recurrence, grouped B/C, the whole model's prefill and its
decode through the cache (batched engine step and streamed step) against a
teacher-forced reference, and the token ids on the wire.

The model is tiny (d 128, 4 Mamba2 heads of 16 in 2 groups, state 16,
chunk 8, 12 layers with hybrid layers at 2, 5, 8 and 11, so each of the two
shared blocks is invoked twice, adapter rank 4), in float32, with seeded
random weights."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import ModelConfig, SSMConfig
from repro.core.costs import id_wire_bytes
from repro.core.quantization import CodedRows, Wire
from repro.models import ssm as ssm_lib
from repro.runtime.split_exec import SplitModelBank

SPLIT, D_R, PROMPT, NEW, MAX_LEN = 3, 16, 13, 6, 32

CFG = ModelConfig(
    name="zamba2-tiny", arch_type="hybrid", num_layers=12, d_model=128,
    num_heads=4, num_kv_heads=4, head_dim=64, d_ff=256, vocab_size=512,
    act="gelu_exact", rope_theta=1e4, rms_eps=1e-5, tie_embeddings=True,
    hybrid_layer_ids=(2, 5, 8, 11), num_mem_blocks=2, adapter_rank=4,
    ssm=SSMConfig(state_dim=16, num_heads=4, head_dim=16, conv_width=4,
                  chunk_size=8, n_groups=2, dt_min=1e-3),
    dtype="float32")


# ------------------------------------------------------------------ oracles
def _rms(x, w, eps=CFG.rms_eps):
    x = x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps)
    return x * (1.0 + w)


def _silu(x):
    return x / (1.0 + np.exp(-x))


def sequential_mamba(p, x, cfg, group_of=lambda h, H, G: h // (H // G)):
    """One Mamba2 mixer over (S, d), one token at a time in float64: the
    output, the final SSM state and the conv tail (last W-1 pre-conv rows).
    ``group_of`` maps a head to the group of B/C it reads."""
    s = cfg.ssm
    H, P, N, G, W = (s.num_heads, s.head_dim, s.state_dim, s.n_groups,
                     s.conv_width)
    p = {k: np.asarray(v, np.float64) for k, v in p.items()}
    x = np.asarray(x, np.float64)
    S, din = x.shape[0], H * P
    conv_ch = din + 2 * G * N
    proj = x @ p["in_proj"]
    z, xbc, dt = proj[:, :din], proj[:, din:din + conv_ch], \
        proj[:, din + conv_ch:]
    pad = np.concatenate([np.zeros((W - 1, conv_ch)), xbc])
    conv = _silu(sum(p["conv_w"][k] * pad[k:k + S] for k in range(W))
                 + p["conv_b"])
    xs = conv[:, :din].reshape(S, H, P)
    Bm = conv[:, din:din + G * N].reshape(S, G, N)
    Cm = conv[:, din + G * N:].reshape(S, G, N)
    dt = np.maximum(np.log1p(np.exp(dt + p["dt_bias"])), s.dt_min)
    A = -np.exp(p["A_log"])
    state = np.zeros((H, P, N))
    y = np.zeros((S, H, P))
    for t in range(S):
        for h in range(H):
            g = group_of(h, H, G)
            state[h] = np.exp(dt[t, h] * A[h]) * state[h] + \
                dt[t, h] * np.outer(xs[t, h], Bm[t, g])
            y[t, h] = state[h] @ Cm[t, g] + p["D"][h] * xs[t, h]
    gated = (y.reshape(S, din) * _silu(z)).reshape(S, G, din // G)
    out = _rms(gated, p["norm_w"].reshape(G, -1)).reshape(S, din) @ \
        p["out_proj"]
    return out, state, xbc[-(W - 1):]


def _attention(q, k, v, scale):
    S = q.shape[0]
    s = np.einsum("qhd,khd->hqk", q, k) * scale
    s = np.where(np.tril(np.ones((S, S), bool)), s, -np.inf)
    s = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("hqk,khd->qhd", s / s.sum(-1, keepdims=True), v)


def _rope(x, theta):
    S, _, hd = x.shape
    inv = 1.0 / theta ** (np.arange(0, hd, 2) / hd)
    ang = np.arange(S)[:, None, None] * inv
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return np.concatenate([a * np.cos(ang) - b * np.sin(ang),
                           b * np.cos(ang) + a * np.sin(ang)], -1)


def _layers(params):
    """The program's stacked segments as a flat list of per-layer dicts."""
    out = []
    for seg in params["stages"][0]:
        n = jax.tree.leaves(seg[0])[0].shape[0]
        out += [jax.tree.map(lambda a: np.asarray(a[r], np.float64), seg[0])
                for r in range(n)]
    return out


def reference_logits(params, toks):
    """Teacher-forced float64 logits at every position of ``toks``, with
    the int8 butterfly wire after layer SPLIT - 1."""
    f = lambda t: jax.tree.map(lambda a: np.asarray(a, np.float64), t)  # noqa: E731
    emb = np.asarray(params["embed"], np.float64)
    shared = f(params["shared_attn"])
    bf = f(params["butterfly"])
    x_emb = emb[np.asarray(toks)]
    x = x_emb
    S, hd, H = len(toks), CFG.resolved_head_dim, CFG.num_heads
    for i, lp in enumerate(_layers(params)):
        if i == SPLIT:
            r = x @ bf["w_reduce"]
            scale = np.maximum(np.abs(r).max(-1, keepdims=True), 1e-8) / 127
            x = np.clip(np.round(r / scale), -128, 127) * scale @ \
                bf["w_restore"]
        h = x
        if i in CFG.hybrid_layer_ids:
            k = CFG.hybrid_layer_ids.index(i)
            blk = shared["blocks"][k % CFG.num_mem_blocks]
            ad = shared["adapters"][k]
            c = _rms(np.concatenate([x, x_emb], -1), blk["norm_in"])
            q, kk, v = (c @ blk["mixer"][n] for n in ("wq", "wk", "wv"))
            q, kk, v = (a.reshape(S, H, hd) for a in (q, kk, v))
            q, kk = _rope(q, CFG.rope_theta), _rope(kk, CFG.rope_theta)
            o = _attention(q, kk, v, (hd / 2) ** -0.5).reshape(S, -1)
            a = _rms(o @ blk["mixer"]["wo"], blk["norm_ff"])
            gu = a @ ad["a"] @ ad["b"]
            g = a @ blk["ffn"]["w_gate"] + gu[:, :CFG.d_ff]
            u = a @ blk["ffn"]["w_up"] + gu[:, CFG.d_ff:]
            gelu = 0.5 * g * (1 + jax.scipy.special.erf(g / np.sqrt(2)))
            h = x + (np.asarray(gelu) * u) @ blk["ffn"]["w_down"] @ \
                lp["linear"]
        out, _, _ = sequential_mamba(lp["mixer"], _rms(h, lp["norm1"]), CFG)
        x = x + out
    return _rms(x, np.asarray(params["final_norm"], np.float64)) @ emb.T


# -------------------------------------------------------------------- tests
def _mamba_params(cfg, seed=0):
    p, _ = ssm_lib.init_mamba(jax.random.key(seed), cfg, jnp.float32)
    # nonzero gains, biases and skips, so that each of them is checked
    ks = jax.random.split(jax.random.key(seed + 1), 4)
    return dict(p, norm_w=0.1 * jax.random.normal(ks[0], p["norm_w"].shape),
                conv_b=0.1 * jax.random.normal(ks[1], p["conv_b"].shape),
                dt_bias=0.5 * jax.random.normal(ks[2], p["dt_bias"].shape),
                D=jax.random.normal(ks[3], p["D"].shape))


@pytest.mark.parametrize("S", [13, 37])
def test_ragged_ssd_matches_the_sequential_recurrence(S):
    """Chunk 8 over 13 and 37 tokens: the last chunk is ragged.  Output,
    final state and conv tail agree with the recurrence; 1e-4 allows float32
    reassociation across chunks against the float64 oracle."""
    p = _mamba_params(CFG)
    x = jax.random.normal(jax.random.key(2), (1, S, CFG.d_model))
    out, state = ssm_lib.mamba_fullseq(p, x, cfg=CFG, return_state=True)
    want_out, want_state, want_tail = sequential_mamba(p, x[0], CFG)
    np.testing.assert_allclose(out[0], want_out, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(state["ssm"][0], want_state, rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(state["conv"][0], want_tail, rtol=1e-5,
                               atol=1e-5)


def test_grouped_bc_gives_head_h_group_h_div_heads_per_group():
    """Heads 0-1 read group 0 and heads 2-3 group 1: the program matches the
    oracle with that map, within float32 rounding (1e-4), and differs from
    the interleaved map h % G by far more, so the test tells them apart."""
    p = _mamba_params(CFG, seed=3)
    x = jax.random.normal(jax.random.key(4), (1, 16, CFG.d_model))
    out, _ = ssm_lib.mamba_fullseq(p, x, cfg=CFG)
    blocked, _, _ = sequential_mamba(p, x[0], CFG)
    interleaved, _, _ = sequential_mamba(
        p, x[0], CFG, group_of=lambda h, H, G: h % G)
    np.testing.assert_allclose(out[0], blocked, rtol=1e-4, atol=1e-4)
    assert np.abs(np.asarray(out[0]) - interleaved).max() > 1e-2


@pytest.fixture(scope="module")
def served():
    """One prompt through the edge and cloud halves, then NEW - 1 decode
    steps through the batched engine step and through the streamed steps,
    with every step's logits kept."""
    bank = SplitModelBank(CFG, D_R, seed=0)
    r = bank.runner(SPLIT)
    toks = np.random.default_rng(5).integers(
        0, CFG.vocab_size, size=(1, PROMPT)).astype(np.int32)
    payload, scales, c0 = r.edge_half(r.params, toks)
    logits, c1 = r.cloud_half(r.params, payload, scales)
    eng = r.make_engine(max_batch=2, max_len=MAX_LEN, seed=0)
    handoff = eng.submit_prefilled(PROMPT, [c0, c1], logits[0],
                                   max_new_tokens=NEW, record_logits=True)
    while not handoff.done:
        eng.step()
    ec, cc = r.pad_decode_cache(c0, 0, MAX_LEN), r.pad_decode_cache(c1, 1,
                                                                    MAX_LEN)
    streamed = eng.submit_streamed(PROMPT, logits[0], max_new_tokens=NEW,
                                   record_logits=True)
    pos, rows = PROMPT, []
    while not streamed.done:
        tok = np.asarray([[streamed.generated[-1]]], np.int32)
        row, sc, ec = r.edge_step(r.params, tok, ec, [pos])
        rows.append((row, tok))
        _, cc = r.stream_step(eng, streamed, cc, row, sc, pos)
        pos += 1
    return r, toks, payload, rows, {"cache_handoff": handoff,
                                    "streamed": streamed}


@pytest.mark.parametrize("transport", ["cache_handoff", "streamed"])
def test_prefill_and_decode_match_the_teacher_forced_reference(served,
                                                              transport):
    """Logits of the prompt's last position and of every decode step equal
    the float64 reference run over the prompt and the served tokens.  1e-3
    of absolute error on logits of size ~4: float32 through 12 layers, and
    the int8 wire's rounding, which the two sides compute in a different
    order (a code that lands on the other side of a rounding boundary moves
    the restored row by one step)."""
    r, toks, _, _, reqs = served
    req = reqs[transport]
    seq = np.concatenate([toks[0], np.asarray(req.generated[:-1])])
    ref = reference_logits(r.params, seq)[PROMPT - 1:]
    got = np.stack([np.asarray(row, np.float64).reshape(-1)
                    for row in req.logits_history])
    assert got.shape == ref.shape == (NEW, CFG.vocab_size)
    np.testing.assert_allclose(got, ref, rtol=0, atol=1e-3)
    assert reqs["cache_handoff"].generated == reqs["streamed"].generated


def test_ids_cross_the_wire_in_both_transports(served):
    r, toks, payload, rows, _ = served
    assert isinstance(payload, CodedRows)
    np.testing.assert_array_equal(np.asarray(payload.ids), toks)
    assert payload.ids.dtype == jnp.int16
    assert payload.nbytes == payload.codes.nbytes + 2 * PROMPT
    for row, tok in rows:
        assert isinstance(row, CodedRows) and row.nbytes == \
            row.codes.nbytes + 2
        np.testing.assert_array_equal(np.asarray(row.ids), tok)
    # the runtime's byte accounting counts the ids too
    from repro.core.profiler import GTX_1080TI, JETSON_TX2
    from repro.runtime.split_exec import CostModel
    cm = CostModel(CFG, JETSON_TX2, GTX_1080TI)
    assert cm.payload_bytes("split", "int8", PROMPT, D_R, SPLIT) == \
        Wire("int8", 8, D_R).nbytes(CFG, PROMPT) + 2 * PROMPT
    assert cm.stream_row_bytes("int8", D_R, SPLIT) == \
        Wire("int8", 8, D_R).nbytes(CFG, 1) + 2
    # a split past the last hybrid layer ships no ids
    edge_only = dataclasses.replace(CFG, hybrid_layer_ids=(2,))
    assert id_wire_bytes(edge_only, SPLIT, PROMPT) == 0
    assert id_wire_bytes(CFG, SPLIT, PROMPT) == 2 * PROMPT


def test_qwen3_wire_is_unchanged():
    """A model with no hybrid layer ships the codes alone, and its byte
    accounting is the wire format's."""
    from repro.core.profiler import GTX_1080TI, JETSON_TX2
    from repro.runtime.split_exec import CostModel
    cfg = dataclasses.replace(get_config("qwen3-8b").reduced(), num_layers=2)
    bank = SplitModelBank(cfg, 16, seed=0)
    r = bank.runner(1)
    toks = np.random.default_rng(5).integers(
        0, cfg.vocab_size, size=(1, 10)).astype(np.int32)
    payload, scales, _ = r.edge_half(r.params, toks)
    assert isinstance(payload, jax.Array) and payload.dtype == jnp.int8
    assert payload.nbytes == 10 * 16 and r.id_bytes == 0
    cm = CostModel(cfg, JETSON_TX2, GTX_1080TI)
    assert cm.payload_bytes("split", "int8", 10, 16, 1) == \
        Wire("int8", 8, 16).nbytes(cfg, 10)
    assert cm.stream_row_bytes("int8", 16, 1) == \
        cm.stream_row_bytes("int8", 16) == Wire("int8", 8, 16).nbytes(cfg, 1)


@pytest.mark.parametrize("transport", ["cache_handoff", "streamed"])
def test_runtime_serves_it_in_both_transports(transport):
    """The virtual-clock runtime, with real numerics, serves the model in
    either transport; both give the engine's own greedy tokens."""
    from repro.runtime.simulator import SimConfig, Simulation
    sc = SimConfig(cfg=CFG, mode="split", wire_mode="int8", network="wifi",
                   num_devices=2, num_requests=2, arrival_rate=20.0,
                   prompt_len=PROMPT, max_new_tokens=3, d_r=D_R,
                   numerics=True, max_concurrent=2, transport=transport,
                   seed=1, initial_split=SPLIT)
    sim = Simulation(sc)
    sim.run()
    runner = sim.bank.runner(SPLIT)
    eng = runner.make_engine(max_batch=2, max_len=MAX_LEN, seed=0)
    for req in sim.requests:
        assert req.trace.split == SPLIT
        ref = eng.submit(req.tokens, max_new_tokens=3)
        eng.run()
        assert list(ref.generated) == list(req.engine_req.generated)
