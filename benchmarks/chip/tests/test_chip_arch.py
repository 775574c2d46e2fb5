"""A configuration of a new architecture joins the harness as files alone:
an architecture module and a configuration file in a directory of their
own, found by the configuration's ``model_type``.  The only thing patched is
the directory the harness looks in (``spec.FILES``).

The toy architecture has what a hybrid stack such as Zamba2 needs of the
interface: two kinds of layer with different leaves, a global leaf that
every layer of one kind shares, state carried beside the residual (the
embeddings, read again by the second kind) and a head tied to the
embedding."""
import json

import numpy as np
import pytest

import reference
import spec
import weights as W
import work

TOY = '''
from dataclasses import dataclass

import jax
import jax.numpy as jnp

import reference as R
import weights as W


@dataclass(frozen=True)
class Dims:
    name: str
    num_layers: int
    d_model: int
    d_ff: int
    vocab: int
    rms_eps: float
    split: int
    d_r: int
    wire_bits: int
    arch: str = "toy"


def dims(name, c):
    bf = c["butterfly"]
    return Dims(name=name, num_layers=c["num_hidden_layers"],
                d_model=c["hidden_size"], d_ff=c["intermediate_size"],
                vocab=c["vocab_size"], rms_eps=c["rms_norm_eps"],
                split=bf["after_layer"], d_r=bf["d_r"],
                wire_bits=bf["wire_bits"])


LEAF_IDS = {"norm1": 0, "w_in": 1, "w_out": 2, "w_mix": 3, "embed": 4,
            "final_norm": 5, "shared": 6}


def layer_leaves(m, layer):
    d = m.d_model
    if layer % 2 == 0:
        return {"norm1": (d,), "w_in": (d, m.d_ff), "w_out": (m.d_ff, d)}
    return {"norm1": (d,), "w_mix": (2 * d, d)}


def global_leaves(m):
    return {"embed": (m.vocab, m.d_model), "final_norm": (m.d_model,),
            "shared": (m.d_model, m.d_model)}


def program_config(m):
    raise NotImplementedError("the toy has no program")


def program_tree(w, m):
    raise NotImplementedError("the toy has no program")


def forward(seed, m, xs, control):
    emb = xs
    shared = W.draw_global(seed, m, "shared").astype(jnp.float32)
    for li in range(m.num_layers):
        lw = R.f32(W.draw_layer(seed, m, li))
        out = []
        for x, e in zip(xs, emb):
            h = R.rms_norm(x, lw["norm1"], m.rms_eps)
            if "w_in" in lw:
                h = R._mm(jax.nn.silu(R._mm(h, lw["w_in"], control)),
                          lw["w_out"], control)
            else:
                h = R._mm(R._mm(jnp.concatenate([h, e], -1), lw["w_mix"],
                                control), shared, control)
            out.append(x + h)
        xs = out
        if li == m.split - 1:
            xs = R.wire_rows(seed, m, xs, control)
    return xs


def edge_prefill(m, seq):
    return 1000 + seq


def cloud_prefill(m, seq):
    return 2000 + seq


def edge_step(m, pos):
    return 3000 + pos


def cloud_step(m, pos):
    return 4000 + pos


def decode_token(m, pos):
    return edge_step(m, pos) + cloud_step(m, pos)
'''

CONFIG = {"model_type": "toy", "num_hidden_layers": 4, "hidden_size": 64,
          "intermediate_size": 96, "vocab_size": 256, "rms_norm_eps": 1e-6,
          "butterfly": {"after_layer": 2, "d_r": 16, "wire_bits": 8}}
TRAFFIC = {"rate_per_s": 8.0, "block": 4,
           "prompt": {"dist": "loguniform", "min": 16, "max": 48},
           "output": {"dist": "uniform", "min": 4, "max": 8},
           "transport": "streamed", "link": "wifi", "slots": 4}
SEED = 2 ** 33 + 11


def lay_out(root, config=CONFIG, module=TOY):
    """A checkout's files for one cell of configuration ``toy``."""
    for sub in ("arch", "configs", "traffic", "checks"):
        (root / sub).mkdir()
    (root / "arch" / "toy.py").write_text(module)
    (root / "configs" / "toy.json").write_text(json.dumps(config))
    (root / "traffic" / "short.json").write_text(json.dumps(TRAFFIC))
    (root / "checks" / "toy.short.json").write_text(json.dumps(
        {"max_logit_gap": 0.08, "min_tokens": 24, "max_requests": 4}))
    (root / "BENCHMARK.json").write_text(json.dumps({"workloads": [
        {"name": "toy.short", "config": "toy", "traffic": "short",
         "chips": 1}]}))


@pytest.fixture
def toy(tmp_path, monkeypatch):
    lay_out(tmp_path)
    monkeypatch.setattr(spec, "FILES", tmp_path)
    return spec.load_cell("toy.short", tmp_path).model


def test_load_reaches_the_module(toy, tmp_path):
    assert toy.arch == "toy" and toy.name == "toy"
    assert type(toy) is spec.arch_module("toy").Dims
    assert spec.arch_module("toy").__file__ == str(tmp_path / "arch"
                                                   / "toy.py")


def test_layer_draws_match_the_whole_draw_for_each_kind(toy):
    w = W.draw(SEED, toy)
    kinds = {tuple(W.layer_leaves(toy, li)) for li in range(toy.num_layers)}
    assert len(kinds) == 2
    assert w["layers"]["w_in"].shape[0] == w["layers"]["w_mix"].shape[0] == 2
    for li in range(toy.num_layers):
        lw = W.draw_layer(SEED, toy, li)
        whole = W.layer_of(w, li)
        assert lw.keys() == whole.keys() == W.layer_leaves(toy, li).keys()
        for name in lw:
            assert np.array_equal(np.asarray(lw[name]),
                                  np.asarray(whole[name])), (li, name)
    assert set(W.global_leaves(toy)) == {"embed", "final_norm", "shared",
                                         "w_reduce", "w_restore"}
    for name in W.global_leaves(toy):
        assert np.array_equal(np.asarray(w[name]),
                              np.asarray(W.draw_global(SEED, toy, name)))


def test_reference_runs_on_it(toy):
    rng = np.random.default_rng(3)
    seqs = [(rng.integers(0, toy.vocab, n).astype(np.int32),
             rng.integers(0, toy.vocab, k).astype(np.int32))
            for n, k in ((20, 6), (33, 4))]
    for control in (False, True):
        gaps = reference.token_gaps(SEED, toy, seqs, control=control)
        assert [len(g) for g in gaps] == [6, 4]
        assert all(np.isfinite(g).all() and (g >= 0).all() for g in gaps)
    # random tokens lie below the reference's best somewhere
    assert max(g.max() for g in reference.token_gaps(SEED, toy, seqs)) > 0


def test_work_counts_dispatch_to_it(toy):
    assert work.edge_prefill(toy, 7) == 1007
    assert work.cloud_prefill(toy, 7) == 2007
    assert work.edge_step(toy, 9) == 3009
    assert work.cloud_step(toy, 9) == 4009
    assert work.decode_token(toy, 9) == 7018


def test_unknown_model_type_is_refused(tmp_path, monkeypatch):
    lay_out(tmp_path, dict(CONFIG, model_type="nosuch"))
    monkeypatch.setattr(spec, "FILES", tmp_path)
    with pytest.raises(ValueError) as e:
        spec.load_cell("toy.short", tmp_path)
    assert str(tmp_path / "arch" / "nosuch.py") in str(e.value)


def test_module_short_of_the_interface_is_refused(tmp_path, monkeypatch):
    lay_out(tmp_path, module=TOY.replace("def forward(", "def fwd("))
    monkeypatch.setattr(spec, "FILES", tmp_path)
    with pytest.raises(ValueError, match="lacks forward of the architecture"):
        spec.load_cell("toy.short", tmp_path)
