"""The paper's contribution: butterfly unit semantics, compression accounting
(paper Sec III-D numbers reproduced exactly), stage splitting."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.resnet50 import PAPER_MIN_DR, resnet50
from repro.core import butterfly as bf
from repro.core.quantization import Wire, dequantize
from repro.models import model as M


def test_compression_ratio_paper_rb1():
    """Paper Sec III-D: butterfly after RB1 compresses 256 -> 1 channels =
    256x ratio (8-bit wire vs 8-bit baseline features)."""
    assert bf.compression_ratio(d=256, d_r=1, act_bits=8, wire_bits=8) == 256.0


def test_paper_offloaded_bytes_table5():
    """Offloaded data sizes in Table V: 3136 B after RB1 (d_r=1, 56x56) and
    980~1000 B after RB8 (d_r=5, 14x14)."""
    cfg = resnet50()
    assert cfg.feature_bytes(1, bits=8, channels=1) == 3136
    assert cfg.feature_bytes(8, bits=8, channels=5) == 14 * 14 * 5  # 980
    # cloud-only input: 224*224*3 = 150528 (Table V)
    assert cfg.image_size ** 2 * 3 == 150528


def test_paper_min_dr_monotone_in_depth():
    """Fig. 7: deeper splits need larger D_r."""
    vals = [PAPER_MIN_DR[i] for i in range(1, 17)]
    assert vals == sorted(vals)
    assert vals[0] == 1 and vals[-1] == 10


def test_reduce_restore_units_roundtrip():
    key = jax.random.key(0)
    params, _ = bf.init_butterfly(key, d=64, bf=get_config("qwen3-8b")
                                  .reduced().with_butterfly(1, 16).butterfly,
                                  dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(1), (4, 8, 64))
    wire = Wire("int8", 8, 16)
    codes, scales = wire.encode(x, params["w_reduce"], kernel=False)
    assert codes.dtype == jnp.int8 and codes.shape == (4, 8, 16)
    out = wire.decode(codes, scales, params["w_restore"], jnp.float32,
                      kernel=False)
    assert out.shape == x.shape
    # identical to the in-graph fake-quant form
    ref = bf.apply_butterfly(params, x, train=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_kernel_path_matches_reference_path():
    params = {
        "w_reduce": jax.random.normal(jax.random.key(2), (64, 16)) * 0.1,
        "w_restore": jax.random.normal(jax.random.key(3), (16, 64)) * 0.1,
    }
    x = jax.random.normal(jax.random.key(4), (2, 8, 64))
    wire = Wire("int8", 8, 16)
    c1, s1 = wire.encode(x, params["w_reduce"], kernel=False)
    c2, s2 = wire.encode(x, params["w_reduce"], kernel=True)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-6)


def test_stage_split_layer_counts():
    cfg = get_config("gemma3-12b").reduced().with_butterfly(layer=1, d_r=8)
    built = M.build(cfg)
    n0 = sum(s.num_layers for s in built.stages[0])
    n1 = sum(s.num_layers for s in built.stages[1])
    assert n0 == 1 and n0 + n1 == cfg.num_layers


@pytest.mark.parametrize("wire_bits", [4, 8, 16])
def test_butterfly_wire_bits(wire_bits):
    cfg = get_config("qwen3-8b").reduced().with_butterfly(1, 16, wire_bits)
    built = M.build(cfg)
    params, _ = M.init_model(jax.random.key(0), built)
    toks = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)
    logits, _ = M.forward_train(params, built, {"tokens": toks})
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_int4_wire_accuracy_within_paper_bound():
    """The paper's D_r selection criterion (<2% accuracy loss) applied to the
    wire width: on a briefly-trained model, dropping the wire from int8 to
    int4 moves held-out next-token accuracy by less than 2 points."""
    import dataclasses
    from repro.data import lm_batches
    from repro.training import AdamWConfig, adamw_init, constant_schedule, \
        make_train_step

    cfg = dataclasses.replace(get_config("qwen3-8b").reduced(), vocab_size=64)
    cfg = cfg.with_butterfly(layer=1, d_r=32)
    built = M.build(cfg)
    params, _ = M.init_model(jax.random.key(0), built)
    opt = adamw_init(params)
    step = jax.jit(make_train_step(built, AdamWConfig(lr=constant_schedule(3e-3))))
    stream = iter(lm_batches(cfg.vocab_size, 32, 8, seed=7))
    for _, raw in zip(range(60), stream):
        batch = {k: jnp.asarray(v) for k, v in raw.items()}
        params, opt, _ = step(params, opt, batch)
    held_out = [jnp.asarray(next(stream)["tokens"]) for _ in range(4)]

    def accuracy(bits):
        c = dataclasses.replace(
            cfg, butterfly=dataclasses.replace(cfg.butterfly, wire_bits=bits))
        b = M.build(c)
        fwd = jax.jit(lambda p, t: M.forward_train(p, b, {"tokens": t})[0])
        hits = tot = 0
        for toks in held_out:
            pred = jnp.argmax(fwd(params, toks)[:, :-1], -1)
            hits += float((pred == toks[:, 1:]).sum())
            tot += pred.size
        return hits / tot

    acc8, acc4 = accuracy(8), accuracy(4)
    assert acc8 > 0.25, f"model failed to learn the chain ({acc8})"
    assert abs(acc8 - acc4) < 0.02, (acc8, acc4)


def test_butterfly_gradients_flow_to_both_stages():
    """End-to-end training through the wire: every stage gets gradient."""
    cfg = get_config("qwen3-8b").reduced().with_butterfly(layer=1, d_r=16)
    built = M.build(cfg)
    params, _ = M.init_model(jax.random.key(0), built)
    toks = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)

    def loss(p):
        lg, _ = M.forward_train(p, built, {"tokens": toks})
        return M.lm_loss(lg[:, :-1], toks[:, 1:])

    g = jax.grad(loss)(params)
    for stage in (0, 1):
        norms = [float(jnp.sum(jnp.square(x)))
                 for x in jax.tree.leaves(g["stages"][stage])]
        assert sum(norms) > 0, f"stage {stage} got no gradient"
    assert float(jnp.sum(jnp.abs(g["butterfly"]["w_reduce"]))) > 0
    assert float(jnp.sum(jnp.abs(g["butterfly"]["w_restore"]))) > 0
