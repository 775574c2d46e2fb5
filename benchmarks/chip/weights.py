"""Random weights for a benchmark configuration, drawn on the device from the
run's seed.

The benchmark makes the weights, not the program: :func:`draw` makes every
leaf in one jitted call, in the type it is served in, and :func:`draw_layer`
makes one layer's leaves alone with the same keys and shapes.  So the
reference (``reference.py``) rebuilds the very same values layer by layer
without taking anything from the program, and the program receives them
through :func:`program_tree`.

Which leaves a model has, their shapes and the program's layout of them
are its architecture's (``arch/<model_type>.py``, ``spec.py``); the key
scheme, the init rules and the butterfly's two leaves are shared.  Every
leaf has its own key, ``fold_in(fold_in(root, leaf), layer)`` for a layer's
leaf and ``fold_in(root, leaf)`` for a global one, so a leaf's values do not
depend on which other leaves are drawn with it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import spec

# the butterfly's leaves, in every architecture; never renumber
BUTTERFLY_IDS = {"w_reduce": 14, "w_restore": 15}

# Norm leaves hold the offset ``w`` of a gain ``1 + w``.  A spread of 0.1
# keeps the gains near one but makes a norm that is skipped or misapplied
# change the result.
NORM_STD = 0.1


def seed_words(seed: int):
    """The seed as two uint32 words: seeds go past 32 bits."""
    if seed < 0 or seed >= 2 ** 64:
        raise ValueError(f"seed {seed} is not in [0, 2**64)")
    return (jnp.uint32(seed & 0xFFFFFFFF), jnp.uint32(seed >> 32))


def _root(words):
    lo, hi = words
    return jax.random.fold_in(jax.random.fold_in(jax.random.key(0), lo), hi)


def _arch(m):
    return spec.arch_module(m.arch)


def layer_leaves(m, layer: int) -> dict:
    """Shapes of layer ``layer``'s leaves, by name."""
    return _arch(m).layer_leaves(m, layer)


def global_leaves(m) -> dict:
    """Shapes of the leaves outside the layers, the butterfly's with them."""
    return {**_arch(m).global_leaves(m), "w_reduce": (m.d_model, m.d_r),
            "w_restore": (m.d_r, m.d_model)}


def _key_id(m, name: str) -> int:
    if name in BUTTERFLY_IDS:
        return BUTTERFLY_IDS[name]
    i = _arch(m).LEAF_IDS[name]
    if i in BUTTERFLY_IDS.values():
        raise ValueError(f"{m.arch}: leaf {name} takes the butterfly's key "
                         f"index {i}")
    return i


def _leaf(key, name: str, shape, dtype):
    if name.endswith("norm") or name in ("norm1", "norm2"):
        std = NORM_STD
    elif name in ("embed", "head"):
        std = shape[1] ** -0.5
    else:   # fan-in of an (..., in, out) matrix
        std = shape[-2 if len(shape) > 1 else 0] ** -0.5
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def _layer(root, m, layer, leaves: dict, dtype) -> dict:
    return {n: _leaf(jax.random.fold_in(jax.random.fold_in(
                root, _key_id(m, n)), layer), n, s, dtype)
            for n, s in leaves.items()}


def _global(root, m, name, dtype):
    return _leaf(jax.random.fold_in(root, _key_id(m, name)), name,
                 global_leaves(m)[name], dtype)


def draw(seed: int, m, dtype=jnp.bfloat16) -> dict:
    """Every weight of the model, on the device, in one jitted call:
    ``{"layers": {leaf: (layers that have it, ...)}, <global leaf>: ...,
    "model": m}``."""
    def fn(words):
        root = _root(words)
        layers = [_layer(root, m, i, layer_leaves(m, i), dtype)
                  for i in range(m.num_layers)]
        out = {n: _global(root, m, n, dtype) for n in global_leaves(m)}
        names = dict.fromkeys(n for lw in layers for n in lw)
        out["layers"] = {n: jnp.stack([lw[n] for lw in layers if n in lw])
                         for n in names}
        return out
    return {**jax.jit(fn)(seed_words(seed)), "model": m}


def layer_of(w: dict, layer: int) -> dict:
    """Layer ``layer``'s leaves, taken from :func:`draw`'s stacks."""
    m = w["model"]
    return {n: w["layers"][n][sum(n in layer_leaves(m, i)
                                  for i in range(layer))]
            for n in layer_leaves(m, layer)}


def draw_layer(seed: int, m, layer: int, dtype=jnp.bfloat16) -> dict:
    """Layer ``layer``'s leaves alone: the same values :func:`draw` stacks."""
    return _jit_layer(m, layer_leaves(m, layer), dtype)(
        seed_words(seed), jnp.int32(layer))


def draw_global(seed: int, m, name: str, dtype=jnp.bfloat16):
    return jax.jit(lambda w: _global(_root(w), m, name, dtype))(
        seed_words(seed))


_LAYER_FNS: dict = {}


def _jit_layer(m, leaves: dict, dtype):
    """One compiled draw per kind of layer: the layer index is traced."""
    key = (m, tuple(leaves.items()), jnp.dtype(dtype).name)
    if key not in _LAYER_FNS:
        _LAYER_FNS[key] = jax.jit(
            lambda w, i: _layer(_root(w), m, i, leaves, dtype))
    return _LAYER_FNS[key]


def program_tree(w: dict) -> tuple:
    """The program's parameter tree (the architecture's layout) and its
    butterfly params, sharing ``w``'s arrays."""
    params = _arch(w["model"]).program_tree(w, w["model"])
    butterfly = {"w_reduce": w["w_reduce"], "w_restore": w["w_restore"]}
    return params, butterfly
