"""Property tests for the windowed ring-buffer KV cache (hypothesis):
prefill-then-decode through arbitrary window/length combinations must equal
the full-sequence computation."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property suites need hypothesis "
                    "(pip install -r requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.models import attention as attn
from repro.models.transformer import to_ring


@settings(max_examples=15, deadline=None)
@given(st.integers(2, 6), st.integers(1, 12), st.data())
def test_ring_decode_matches_fullseq(window, extra, data):
    """Decode `extra` tokens after a prefill of `pre` tokens with a ring
    cache of size `window`; last-token attention output must match the
    full-sequence windowed attention."""
    cfg = dataclasses.replace(get_config("qwen3-8b").reduced(),
                              num_heads=2, num_kv_heads=1, head_dim=16,
                              qk_norm=False)
    pre = data.draw(st.integers(1, 10))
    S = pre + extra
    params, _ = attn.init_attention(jax.random.key(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (1, S, cfg.d_model)) * 0.3

    full, kv = attn.attention_fullseq(params, x, cfg=cfg, window=window)

    # prefill the first `pre` tokens, ring-ify, then decode the rest
    _, kv_pre = attn.attention_fullseq(params, x[:, :pre], cfg=cfg,
                                       window=window)
    cache = to_ring(kv_pre, window)
    if cache["k"].shape[1] < window:      # pad short prefill up to window
        pad = window - cache["k"].shape[1]
        cache = {k: jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
                 for k, v in cache.items()}
    cache = {k: v[None] for k, v in cache.items()}   # a stack of one layer
    out = None
    for t in range(pre, S):
        out, cache = attn.attention_decode(params, x[:, t:t + 1], cache,
                                           jnp.asarray(t, jnp.int32),
                                           cfg=cfg, window=window, layer=0)
    np.testing.assert_allclose(np.asarray(full[:, -1]), np.asarray(out[:, 0]),
                               rtol=2e-4, atol=2e-4)


def test_to_ring_is_permutation():
    kv = {"k": jnp.arange(2 * 10 * 1 * 4, dtype=jnp.float32).reshape(2, 10, 1, 4),
          "v": jnp.zeros((2, 10, 1, 4))}
    W = 4
    ring = to_ring(kv, W)
    assert ring["k"].shape[1] == W
    # positions 6..9 land at slots 6%4..9%4 = 2,3,0,1
    tail = np.asarray(kv["k"][:, -W:])
    got = np.asarray(ring["k"])
    for i, p in enumerate(range(10 - W, 10)):
        np.testing.assert_array_equal(got[:, p % W], tail[:, i])
