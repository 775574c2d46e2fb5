"""The benchmark's weights: one jitted draw of the whole model, and the
reference's layer-by-layer draws, give the same values, in the program's
parameter layout."""
import jax
import numpy as np

import run
import tiny
import weights as W

SEED = 2 ** 33 + 5


def test_layer_draws_match_the_whole_draw():
    m = tiny.MODEL
    w = W.draw(SEED, m)
    for li in range(m.num_layers):
        lw = W.draw_layer(SEED, m, li)
        assert lw.keys() == W.layer_leaves(m, li).keys()
        for name in lw:
            assert np.array_equal(np.asarray(w["layers"][name][li]),
                                  np.asarray(lw[name])), (li, name)
    for name in W.global_leaves(m):
        assert np.array_equal(np.asarray(w[name]),
                              np.asarray(W.draw_global(SEED, m, name)))
    other = W.draw(SEED + 1, m)
    assert not np.array_equal(np.asarray(w["embed"]),
                              np.asarray(other["embed"]))


def test_program_tree_matches_the_program():
    from repro.models import model as M
    m = tiny.MODEL
    params, bf = W.program_tree(W.draw(SEED, m))
    cfg = run.program_config(m)
    want = jax.eval_shape(lambda k: M.init_model(k, M.build(cfg))[0],
                          jax.random.key(0))
    assert jax.tree.structure(want) == jax.tree.structure(params)
    assert [(a.shape, a.dtype) for a in jax.tree.leaves(want)] == \
        [(a.shape, a.dtype) for a in jax.tree.leaves(params)]
    assert bf["w_reduce"].shape == (m.d_model, m.d_r)
