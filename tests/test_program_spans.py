"""Program spans on the profiler's clock (``repro.runtime.tracing.span``):
a tiny split bank and engine serve one cache-handoff request and one
streamed request under ``jax.profiler`` on the CPU, and the trace holds every
span of the served path with its args, each child inside its parent."""
import dataclasses
import glob
import os

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.runtime.split_exec import SplitModelBank
from repro.runtime.tracing import SPAN_PREFIX

PROMPT, NEW, MAX_LEN = 10, 3, 20

# child -> the spans it may sit in (a dispatch by its kind)
PARENTS = {
    ("dispatch", "edge"): ("split.edge_half",),
    ("dispatch", "cloud"): ("split.cloud_half",),
    ("dispatch", "edge_step"): ("split.edge_step",),
    ("dispatch", "engine_step"): ("engine.step",),
    ("dispatch", "engine_stream_step"): ("engine.stream_step",),
    ("sync", None): ("engine.step", "engine.stream_step"),
    ("engine.write_slot", None): ("engine.admit",),
    ("engine.sample", None): ("engine.admit",),
}

ARGS = {"split.edge_half": {"rows", "ids_bytes"},
        "split.cloud_half": {"rows"},
        "split.edge_step": {"pos", "ids_bytes"}, "split.pad_cache": {"stage"},
        "engine.admit": {"uid", "rows", "state_bytes", "kv_bytes"},
        "engine.write_slot": {"slot"},
        "engine.sample": {"uid"},
        "engine.step": {"active", "slots", "donated"},
        "engine.stream_step": {"uid", "pos"}, "sync": set()}


def program_spans(logdir):
    """``(name, start, end, args)`` of every host event the program opened,
    with the ``repro/`` prefix dropped."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(files) == 1, files
    out = []
    for plane in ProfileData.from_file(files[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    out.append((ev.name[len(SPAN_PREFIX):], ev.start_ns,
                                ev.end_ns, dict(ev.stats)))
    return sorted(out, key=lambda s: s[1])


def _serve(cfg, logdir):
    """Spans of one handoff request (two batched steps with two slots, one
    of them in use) and one streamed request (two streamed tokens), split
    after layer 1."""
    bank = SplitModelBank(cfg, 16, seed=0)
    r = bank.runner(1)
    eng = r.make_engine(max_batch=2, max_len=MAX_LEN, seed=0)
    toks = np.random.default_rng(5).integers(
        0, cfg.vocab_size, size=(1, PROMPT)).astype(np.int32)
    jax.profiler.start_trace(logdir)
    try:
        payload, scales, c0 = r.edge_half(r.params, toks)
        logits, c1 = r.cloud_half(r.params, payload, scales)
        handoff = eng.submit_prefilled(PROMPT, [c0, c1], logits[0],
                                       max_new_tokens=NEW)
        while not handoff.done:
            eng.step()
        edge_cache = r.pad_decode_cache(c0, 0, MAX_LEN)
        cloud_cache = r.pad_decode_cache(c1, 1, MAX_LEN)
        streamed = eng.submit_streamed(PROMPT, logits[0], max_new_tokens=NEW)
        pos = PROMPT
        while not streamed.done:
            tok = np.asarray([[streamed.generated[-1]]], np.int32)
            row, sc, edge_cache = r.edge_step(r.params, tok, edge_cache,
                                              [pos])
            _, cloud_cache = r.stream_step(eng, streamed, cloud_cache, row,
                                           sc, pos)
            pos += 1
        jax.block_until_ready((edge_cache, cloud_cache))
    finally:
        jax.profiler.stop_trace()
    return program_spans(logdir), handoff, streamed, eng


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    cfg = dataclasses.replace(get_config("qwen3-8b").reduced(), num_layers=2)
    return _serve(cfg, str(tmp_path_factory.mktemp("trace")))[:3]


def test_every_span_with_its_args(served):
    spans, handoff, streamed = served
    names = {n for n, *_ in spans}
    assert names == set(ARGS) | {"dispatch"}
    for name, _, _, args in spans:
        if name == "dispatch":
            assert args["kind"] in ("edge", "cloud", "edge_step",
                                    "engine_step", "engine_stream_step")
            assert ("miss" in args) == (args["kind"] in ("edge", "cloud",
                                                          "edge_step"))
        else:
            assert set(args) == ARGS[name], (name, args)
    by = lambda n: [a for m, _, _, a in spans if m == n]  # noqa: E731
    assert [a["rows"] for a in by("split.edge_half")] == [PROMPT]
    assert [a["rows"] for a in by("engine.admit")] == [PROMPT, PROMPT]
    assert [a["uid"] for a in by("engine.admit")] == [handoff.uid,
                                                     streamed.uid]
    assert sorted(a["stage"] for a in by("split.pad_cache")) == [0, 1]
    # each streamed token after the first: one edge step and one cloud step,
    # at the positions after the prompt
    steps = list(range(PROMPT, PROMPT + NEW - 1))
    assert [a["pos"] for a in by("split.edge_step")] == steps
    assert [a["pos"] for a in by("engine.stream_step")] == steps
    # the bank's first call of each shape misses, every later one hits
    edge_steps = [a["miss"] for a in by("dispatch")
                  if a["kind"] == "edge_step"]
    assert edge_steps == [1] + [0] * (len(steps) - 1)


def test_children_sit_inside_their_parents(served):
    spans, _, _ = served
    for name, s, e, args in spans:
        key = (name, args["kind"] if name == "dispatch" else None)
        if key not in PARENTS:
            continue
        assert any(p in PARENTS[key] and ps <= s and e <= pe
                   for p, ps, pe, _ in spans), (name, args)
    # one sync inside each step, streamed or batched
    steps = [(s, e) for n, s, e, _ in spans
             if n in ("engine.step", "engine.stream_step")]
    syncs = [(s, e) for n, s, e, _ in spans if n == "sync"]
    assert len(syncs) == len(steps) and all(
        sum(ps <= s and e <= pe for s, e in syncs) == 1 for ps, pe in steps)


def test_step_counts_the_slots_in_use(served):
    spans, _, _ = served
    steps = [a for n, _, _, a in spans if n == "engine.step"]
    assert len(steps) == NEW - 1
    assert all(a == {"active": 1, "slots": 2, "donated": 1} for a in steps)



def test_hybrid_spans_count_ids_and_state_bytes(tmp_path):
    """zamba2 (a Mamba2 layer, then a hybrid layer past the split): the edge
    puts 2 bytes of token id a row on the wire, and admission writes the
    slot's recurrent state whole and the hybrid invocation's KV for the
    prompt's rows; a streamed request writes nothing to the pool."""
    cfg = dataclasses.replace(get_config("zamba2-7b").reduced(),
                              num_layers=2)
    spans, handoff, streamed, eng = _serve(cfg, str(tmp_path))
    by = lambda n: [a for m, _, _, a in spans if m == n]  # noqa: E731
    assert [a["ids_bytes"] for a in by("split.edge_half")] == [2 * PROMPT]
    assert [a["ids_bytes"] for a in by("split.edge_step")] == \
        [2] * (NEW - 1)
    s = cfg.ssm
    state = 2 * (s.num_heads * s.head_dim * s.state_dim * 4 + (
        s.conv_width - 1) * (s.num_heads * s.head_dim +
                             2 * s.n_groups * s.state_dim) * 4)
    kv = 2 * PROMPT * cfg.num_kv_heads * cfg.resolved_head_dim * 4
    admits = {a["uid"]: (a["state_bytes"], a["kv_bytes"])
              for a in by("engine.admit")}
    assert admits == {handoff.uid: (state, kv), streamed.uid: (0, 0)}
    assert eng._slot_bytes(PROMPT) == {"state_bytes": state, "kv_bytes": kv}
