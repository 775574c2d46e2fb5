"""``apply_layer_range`` reads a range of a shared stacked stage in place;
it must compute exactly what slicing the range out first
(``slice_stage_params``) and running ``apply_stage`` on the slices does:
same layers, same weights, same order, so float32 results agree bitwise.

Splits cover a uniform stack (qwen3), a mixture-of-experts stack (a nonzero
auxiliary loss), stacks whose repeat unit holds two layers (gemma3's
local/global pair, xlstm's mLSTM/sLSTM pair), where odd boundaries peel
single layers out of a unit, and zamba2's hybrid stack, whose hybrid layer
reads the token embeddings carried beside the residual."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import model as M
from repro.models import transformer as tfm
from repro.models.parallel import LOCAL

B, S = 2, 12
# stack depth of each config; the splits are every boundary inside it
CONFIGS = {"qwen3-8b": 4, "qwen3-moe-235b-a22b": 4, "gemma3-12b": 4,
           "zamba2-7b": 4, "xlstm-125m": 4}
CASES = [(name, split) for name, n in CONFIGS.items()
         for split in range(1, n)]


def _build(name):
    cfg = get_config(name).reduced()
    cfg = dataclasses.replace(cfg, num_layers=CONFIGS[name], butterfly=None)
    built = M.build(cfg)
    params, _ = M.init_model(jax.random.PRNGKey(0), built)
    return cfg, list(built.stages[0]), params


def _run_both(cfg, segs, params, lo, hi, x, *, mode, cache, pos, x_emb):
    kw = dict(cfg=cfg, pctx=LOCAL, mode=mode, pos=pos,
              shared_params=params.get("shared_attn"), x_emb=x_emb)

    @jax.jit
    def in_place(stage, x, cache):
        return tfm.apply_layer_range(segs, stage, x, lo, hi,
                                     range_cache=cache, **kw)

    @jax.jit
    def sliced(stage, x, cache):
        rsegs, rparams = tfm.slice_stage_params(segs, stage, lo, hi)
        return tfm.apply_stage(rsegs, rparams, x, stage_cache=cache, **kw)

    stage = params["stages"][0]
    return in_place(stage, x, cache), sliced(stage, x, cache)


def _assert_bitwise(got, want, what):
    flat_got, tree_got = jax.tree.flatten(got)
    flat_want, tree_want = jax.tree.flatten(want)
    assert tree_got == tree_want, what
    for g, w in zip(flat_got, flat_want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=what)


@pytest.mark.parametrize("name,split", CASES)
def test_in_place_range_matches_sliced_range(name, split):
    cfg, segs, params = _build(name)
    assert cfg.dtype == "float32"
    rng = np.random.default_rng(split)
    hybrid = cfg.hybrid_layer_ids is not None

    def rows(n):
        return jnp.asarray(rng.standard_normal((B, n, cfg.d_model)),
                           jnp.float32)
    for lo, hi in ((0, split), (split, cfg.num_layers)):
        x = rows(S)
        got, want = _run_both(cfg, segs, params, lo, hi, x, mode="prefill",
                              cache=None, pos=None,
                              x_emb=rows(S) if hybrid else None)
        _assert_bitwise(got, want, f"prefill [{lo}, {hi})")
        # one decode step against the range's prefill caches
        step = rows(1)
        pos = jnp.full((B,), S - 1, jnp.int32)
        got, want = _run_both(cfg, segs, params, lo, hi, step,
                              mode="decode", cache=want[1], pos=pos,
                              x_emb=rows(1) if hybrid else None)
        _assert_bitwise(got, want, f"decode [{lo}, {hi})")


def test_cases_peel_inside_multi_layer_units():
    """The two-layer-unit configs really exercise peels: some split of
    each lands inside a unit."""
    for name in ("gemma3-12b", "xlstm-125m"):
        _, segs, _ = _build(name)
        assert [(len(s.unit), s.repeats) for s in segs] == [(2, 2)], name
        kinds = {span[0] for split in range(1, CONFIGS[name])
                 for lo, hi in ((0, split), (split, CONFIGS[name]))
                 for span in tfm._range_spans(segs, lo, hi)}
        assert kinds == {"slice", "peel"}, name
