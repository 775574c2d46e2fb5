"""Zamba2 in the harness (``arch/zamba2.py``): the module passes the
interface check and reads its configuration file, its leaf table at the
published widths holds the published parameter count and the program's
parameter tree, its counts are pinned, and at a tiny size on the CPU the
check that decides ``correct`` passes the program and fails a planted fault
and the float8 control."""
import json
import math

import jax
import pytest

import devtrace
import run
import spec
import tiny
import weights as W

CONFIG = "zamba2-7b-instruct.L24"
SEED = 2 ** 31 + 11
SECONDS = 0.8


def published():
    with open(spec.FILES / "configs" / f"{CONFIG}.json") as f:
        c = json.load(f)
    return spec.model_from_config(CONFIG, c), c


def tiny_model():
    """Two groups of two Mamba2 heads, both shared blocks invoked twice (at
    layers 2 to 5, all past the split after layer 2), chunk 8."""
    return spec.arch_module("zamba2").Dims(
        name="tiny-zamba2", num_layers=6, d_model=128, num_heads=4,
        head_dim=64, d_ff=256, vocab=512, rope_theta=1e4, rms_eps=1e-5,
        mamba_heads=4, mamba_head_dim=32, d_state=16, n_groups=2, d_conv=4,
        chunk=8, dt_min=1e-3, dt_max=0.1, dt_floor=1e-4, adapter_rank=8,
        num_mem_blocks=2,
        hybrid_layer_ids=(2, 3, 4, 5), split=2, d_r=32, wire_bits=8)


def tiny_cell():
    # From CPU readings of this cell (seeds 2**31 + 11, 2**31 + 12 and 7):
    # the sound program's widest gap read 0.030 to 0.047, the float8
    # control's 1.87 to 2.13, and a decode step that keeps the Mamba2 state
    # 2.81 to 3.45.  The limit lies between the program and the control.
    traffic = {"rate_per_s": 8.0, "block": 4,
               "prompt": {"dist": "uniform", "min": 13, "max": 13},
               "output": {"dist": "uniform", "min": 16, "max": 24},
               "transport": "cache_handoff", "link": "inter_pod",
               "slots": 4}
    return spec.Cell(name="tiny-zamba2", chips=1, model=tiny_model(),
                     traffic=traffic,
                     check={"max_logit_gap": 0.5, "min_tokens": 24,
                            "max_requests": 4})


def test_module_passes_the_interface_and_reads_its_config():
    mod = spec.arch_module("zamba2")
    m, c = published()
    assert isinstance(m, mod.Dims) and m.arch == "zamba2"
    assert (m.num_layers, m.d_model, m.mamba_heads, m.mamba_head_dim,
            m.n_groups, m.d_state, m.chunk) == (24, 3584, 112, 64, 2, 64, 256)
    assert m.hybrid_layer_ids == (6, 11, 17, 23)
    assert (m.num_heads, m.head_dim, m.adapter_rank, m.num_mem_blocks) == \
        (32, 224, 128, 2)
    assert not set(mod.LEAF_IDS.values()) & set(W.BUTTERFLY_IDS.values())
    assert len(set(mod.LEAF_IDS.values())) == len(mod.LEAF_IDS)
    for bad in ({"use_shared_attention_adapter": True},
                {"hybrid_layer_ids": [6, 12, 17, 23]},
                {"tie_word_embeddings": False}):
        with pytest.raises(ValueError):
            mod.dims(CONFIG, {**c, **bad})


def test_leaves_hold_the_published_parameter_count_and_program_tree():
    """The published model's 24-layer cut: 24 Mamba2 layers, four hybrid
    linears and adapters, two shared blocks and the tied table, with the
    butterfly's two leaves besides."""
    from repro.models import model as M
    mod = spec.arch_module("zamba2")
    m, _ = published()
    n = sum(math.prod(s) for i in range(m.num_layers)
            for s in W.layer_leaves(m, i).values())
    n += sum(math.prod(s) for k, s in W.global_leaves(m).items()
             if k not in W.BUTTERFLY_IDS)
    assert n == mod.param_count(m) == 2_733_050_240
    tree = jax.eval_shape(lambda k: M.init_model(
        k, M.build(mod.program_config(m)))[0], jax.random.key(0))
    assert sum(a.size for a in jax.tree.leaves(tree)) == n


def test_counts_are_pinned():
    """Recorded from this module at the published widths: the programs'
    model FLOPs and the batched decode step's least bytes."""
    mod = spec.arch_module("zamba2")
    m, _ = published()
    assert mod.edge_prefill(m, 1024) == 329_620_127_744
    assert mod.cloud_prefill(m, 3968) == 26_054_768_099_328
    assert mod.edge_step(m, 100) == 318_144_512
    assert mod.cloud_step(m, 4000) == 6_986_219_520
    assert mod.decode_token(m, 4000) == mod.edge_step(m, 4000) + \
        mod.cloud_step(m, 4000)
    assert mod.ssd(m, 300) == 2 * (2 * 64 + 112 * 64) * (
        256 * 257 // 2 + 44 * 45 // 2) + 4 * 300 * 112 * 64 * 64
    assert mod.decode_step_bytes(m, []) == 2 * (2_733_050_240 + 2 * 3584 * 128)
    assert mod.decode_step_bytes(m, [1000, 3000]) == 6_107_353_856


def test_decode_roofline_by_hand():
    """Two batched steps of 4 ms in a 10 ms slice: the least bytes of each
    at the positions the serve loop noted, over 8 ms; Qwen3 has no count."""
    ms = 1_000_000
    t = devtrace.Trace.build((0, 10 * ms), [["jit_step", 1 * ms, 5 * ms],
                                            ["jit_step", 5 * ms, 9 * ms]],
                             [], [["trace_window", 0, 10 * ms]])
    calls = [{"kind": "decode", "pos": [20, 7]},
             {"kind": "decode", "pos": [21, 8, 3]}]
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    m = tiny_model()
    ctx = run.Context(trace=t, calls=calls, model=m, peaks=peaks,
                      transport="cache_handoff")
    mod = spec.arch_module("zamba2")
    least = (mod.decode_step_bytes(m, [20, 7]) +
             mod.decode_step_bytes(m, [21, 8, 3])) / 1e9
    read = run.load_reader("decode_roofline")
    assert read(ctx) == pytest.approx(100 * least / 0.008)
    assert read(run.Context(trace=t, calls=calls, model=tiny.MODEL,
                            peaks=peaks, transport="cache_handoff")) is None
    assert read(run.Context(trace=t, calls=calls, model=m, peaks=peaks,
                            transport="streamed")) is None


def test_correct_passes_the_program_and_fails_a_fault_and_the_control():
    cell = tiny_cell()
    w = run.serve_window(cell, SEED, SECONDS, require_tpu=False)
    gap, n, failed = run.compare(cell, SEED, w["reqs"])
    limit = cell.check["max_logit_gap"]
    assert n >= 10 and gap <= limit and failed == 0
    ctl, n_ctl, _ = run.compare(cell, SEED, w["reqs"], control=True)
    assert n_ctl == n and ctl > limit

    def altered_token(server):
        eng = server.engine
        orig = eng._step
        eng._step = lambda *a: (lambda out: (
            (out[0] + 1) % cell.model.vocab,) + out[1:])(orig(*a))

    result = run.run(cell, SEED, SECONDS, False, require_tpu=False,
                     fault=altered_token)
    assert result["correct"] is False


def mamba2_state_unchanged(server):
    """The batched decode step writes every cache but the Mamba2 mixers'
    recurrent state, which it hands back as it came."""
    eng = server.engine
    orig = eng._step

    def keep(new, old):
        return jax.tree_util.tree_map_with_path(
            lambda path, n, o: o if any(getattr(k, "key", None) == "ssm"
                                        for k in path) else n, new, old)

    eng._step = lambda p, t, caches, *a: (
        lambda out: (out[0], out[1], keep(out[2], caches), out[3]))(
            orig(p, t, caches, *a))


def test_decode_that_keeps_the_mamba2_state_is_not_correct():
    cell = tiny_cell()
    result = run.run(cell, SEED, SECONDS, False, require_tpu=False,
                     fault=mamba2_state_unchanged)
    assert result["correct"] is False
    check = result["checks"]["max_logit_gap"]
    assert check["value"] > check["limit"]
