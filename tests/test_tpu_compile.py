"""Ahead-of-time compiles for a described TPU v5e at qwen3-8b's widths
(d=4096, d_r=128): the main-path Pallas kernels and the split bank's jitted
programs.  Nothing runs; the TPU compiler refuses here what the
chip would refuse (VMEM overflow, unaligned tiles), and interpret-mode tests
cannot see either.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library."""
import functools
import re

import jax
import jax.numpy as jnp
import pytest

D, D_R, ROWS = 4096, 128, 512


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        # the TPU library writes log files to the temp dir unless told not to
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the kernels lowered through Mosaic (the
    backend here is the CPU, whose branch would pick the interpreter) and
    the persistent compilation cache off: a described-chip executable is
    written but cannot be read back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    from repro.kernels import ops
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    jax.clear_caches()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "interpret_mode", lambda: False)
        yield SingleDeviceSharding(topo.devices[0])
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", was)


def _compile_text(fn, *args) -> str:
    return fn.lower(*args).compile().as_text()


def _kernel_cases():
    from repro.kernels import ops
    bf16, f32, i8 = jnp.bfloat16, jnp.float32, jnp.int8
    x = ((ROWS, D), bf16)
    w_reduce = ((D, D_R), bf16)
    codes, scales = ((ROWS, D_R), i8), ((ROWS, 1), f32)
    w_restore, norm_w = ((D_R, D), bf16), ((D,), bf16)
    return {
        "reduce_quant": (ops.butterfly_reduce_quant, (x, w_reduce)),
        "reduce_quant_bincount": (ops.butterfly_reduce_quant_bincount,
                                  (x, w_reduce)),
        "dequant_restore": (ops.butterfly_dequant_restore,
                            (codes, scales, w_restore)),
        "restore_norm_bf16": (ops.butterfly_restore_norm,
                              (codes, scales, w_restore, norm_w)),
        "restore_norm_f32": (
            functools.partial(ops.butterfly_restore_norm, out_dtype=f32),
            (codes, scales, w_restore, norm_w)),
    }


@pytest.mark.parametrize("name", ["reduce_quant", "reduce_quant_bincount",
                                  "dequant_restore", "restore_norm_bf16",
                                  "restore_norm_f32"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = _kernel_cases()[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = _compile_text(jax.jit(fn), *args)
    assert "tpu_custom_call" in text
    _assert_named(_kernel_instructions(text), name)


def _shapes_bank(one_chip, layers: int, split: int, arch: str = "qwen3-8b",
                 **over):
    """Full-width ``arch`` (qwen3-8b) cut to ``layers`` layers in its own
    dtype (bf16), with shapes in place of weights (the bank's init is
    swapped for its ``jax.eval_shape`` so nothing is allocated), and the
    params of the runner for ``split`` as shapes on the described chip."""
    import dataclasses

    from repro.configs import get_config
    from repro.models import model as M
    from repro.runtime.split_exec import SplitModelBank
    cfg = dataclasses.replace(get_config(arch), num_layers=layers, **over)
    with pytest.MonkeyPatch.context() as mp:
        real_init = M.init_model
        mp.setattr(M, "init_model", lambda key, built: (jax.eval_shape(
            lambda k: real_init(k, built)[0], key), None))
        bank = SplitModelBank(cfg, d_r=D_R)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        bank.runner(split).params)
    return bank, params


@pytest.fixture(scope="module")
def bank_runner(one_chip):
    """A 2-layer slice of qwen3-8b, split after layer 1."""
    return _shapes_bank(one_chip, 2, 1)


@pytest.mark.parametrize("half", ["edge", "cloud"])
def test_bank_half_compiles_for_v5e(bank_runner, one_chip, half):
    bank, params = bank_runner
    B, S = 8, ROWS

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    if half == "edge":
        args = (sds((B, S), jnp.int32),)
    else:
        args = (sds((B, S, D_R), jnp.int8), sds((B, S, 1), jnp.float32),
                sds((), jnp.int32))
    text = _compile_text(bank._fn(half, 1), params, *args)
    assert "tpu_custom_call" in text


STACK, SPLIT = 4, 2


@pytest.fixture(scope="module")
def stack_bank(one_chip):
    """A stack of 4 layers of qwen3-8b, split after layer 2: both ranges
    are proper two-layer sub-ranges of the one stacked backbone, as the
    served cells' ranges are."""
    return _shapes_bank(one_chip, STACK, SPLIT)


def _program_args(bank, program, sds):
    """Arguments after ``params`` of each bank program: one 128-token
    prompt, or one decode step of 4 slots over 128-long caches."""
    S, slots = 128, 4

    def cache(stage, b):
        return jax.tree.map(lambda t: sds(t.shape, t.dtype),
                            bank._cache_template(stage, SPLIT, b, S))

    payload = (sds((1, S, D_R), jnp.int8), sds((1, S, 1), jnp.float32))
    row = (sds((1, 1, D_R), jnp.int8), sds((1, 1, 1), jnp.float32))
    return {
        "edge": (sds((1, S), jnp.int32),),
        "cloud": payload + (sds((), jnp.int32),),
        "prefill": (sds((1, S), jnp.int32), sds((), jnp.int32)),
        "decode": (sds((slots, 1), jnp.int32), [cache(0, slots),
                                                cache(1, slots)],
                   sds((slots,), jnp.int32)),
        "edge_step": (sds((1, 1), jnp.int32), cache(0, 1),
                      sds((1,), jnp.int32)),
        "cloud_step": row + (cache(1, 1), sds((1,), jnp.int32)),
    }[program]


@pytest.mark.parametrize("program", ["edge", "cloud", "prefill", "decode",
                                     "edge_step", "cloud_step"])
def test_bank_program_reads_stacked_weights_in_place(stack_bank, one_chip,
                                                     program):
    """Each bank program scans its layer range over the whole stacked
    leaves: the compiler keeps less temp than one range's weights (a copy
    of the range would need all of it), and no slice reads a stacked
    weight parameter but a one-layer prefetch into VMEM of a leaf named in
    ``PREFETCHED_ONE_LAYER``."""
    bank, params = stack_bank

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    compiled = jax.jit(bank._fn(program, SPLIT)).lower(
        params, *_program_args(bank, program, sds)).compile()
    stack_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(params["stages"][0]))
    range_bytes = stack_bytes * SPLIT // STACK    # both ranges: 2 of 4 layers
    assert compiled.memory_analysis().temp_size_in_bytes < range_bytes
    weight_slices = _stacked_weight_slices(compiled.as_text())
    assert not weight_slices, weight_slices


# The stacked leaves whose layers the compiler may prefetch into on-chip
# memory one at a time: in the decode program, whose layer loops carry the
# cache pool, it reads each layer of ``wv`` with a ``slice-start`` into
# VMEM, where it had prefetched the whole stack with a ``copy-start``.
PREFETCHED_ONE_LAYER = ("mixer____wv",)


def _stacked_weight_slices(text: str) -> list:
    """The ``slice``/``slice-start`` instructions of compiled ``text`` that
    read a stacked weight parameter, less the one exemption: a slice of one
    layer (leading dimension 1) of a leaf in ``PREFETCHED_ONE_LAYER`` whose
    result lies in VMEM (``S(1)``), which copies nothing in HBM."""
    found = []
    for m in re.finditer(r"= (.*?) \b(slice(?:-start)?)\((%params__stages"
                         r"\S*?)[,)]", text):
        result, op, leaf = m.groups()
        shapes = re.findall(r"\w+\[([\d,]*)\]\{([^}]*)\}", result)
        # a slice-start's result is (operand, slice, context)
        dims, layout = shapes[1] if op == "slice-start" else shapes[0]
        one_layer = dims.split(",")[0] == "1"
        if not (one_layer and "S(1)" in layout
                and any(n in leaf for n in PREFETCHED_ONE_LAYER)):
            found.append(m.group(0))
    return found


_WV_PREFETCH = ("%slice-start = ((bf16[4,4096,1024]{2,1,0:T(8,128)(2,1)}), "
                "bf16[1,4096,1024]{2,1,0:T(8,128)(2,1)S(1)}, s32[]{:S(2)}) "
                "slice-start(%params__stages___0__0__0___mixer____wv__.1), "
                "slice={[0:1], [0:4096], [0:1024]}")


@pytest.mark.parametrize("case,line,flagged", [
    ("one_layer_of_wv_into_vmem", _WV_PREFETCH, False),
    ("two_layers_of_wv_into_vmem",
     _WV_PREFETCH.replace("bf16[1,4096", "bf16[2,4096"), True),
    ("one_layer_of_wv_into_hbm", _WV_PREFETCH.replace("S(1)", ""), True),
    ("one_layer_of_wq_into_vmem",
     _WV_PREFETCH.replace("____wv__", "____wq__"), True),
    ("plain_slice_of_one_layer",
     "%slice.3 = bf16[1,4096,1024]{2,1,0:T(8,128)(2,1)} "
     "slice(%params__stages___0__0__0___mixer____wv__.1), "
     "slice={[0:1], [0:4096], [0:1024]}", True),
])
def test_weight_slice_check(case, line, flagged):
    """The check above passes only the one-layer VMEM prefetch of a named
    leaf: more layers, HBM, another leaf or a plain slice still fail."""
    assert bool(_stacked_weight_slices(line)) == flagged


def _sampled_step_compiled(bank, params, sds, slots: int, length: int):
    """The engine's sampled decode step (``serving.engine._sampled_step``)
    over the bank's batched decode for ``slots`` slots of ``length``
    positions, compiled for the described chip; and the pool's bytes."""
    from repro.serving.engine import _sampled_step
    pool = [jax.tree.map(lambda t: sds(t.shape, t.dtype),
                         bank._cache_template(stage, SPLIT, slots, length))
            for stage in (0, 1)]
    key = jax.eval_shape(lambda: jax.random.key(0))
    compiled = _sampled_step(bank._fn("decode", SPLIT)).donating.lower(
        params, sds((slots, 1), jnp.int32), pool, sds((slots,), jnp.int32),
        sds(key.shape, key.dtype), sds((slots,), jnp.float32)).compile()
    return compiled, sum(t.size * t.dtype.itemsize
                         for t in jax.tree.leaves(pool))


def test_engine_step_updates_its_pool_in_place(stack_bank, one_chip):
    """The engine's step over 16 slots of 1024 positions takes its cache
    pool over: the whole pool is aliased to the step's output, the
    temporaries are under 150 MB (the transposed ``wq`` stack copy of 134 MB
    is most of them; the pool's per-layer copies took them to 203 MB), and
    no dynamic-update-slice fusion writes a range's stacked K or V."""
    bank, params = stack_bank

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    slots, length = 16, 1024
    compiled, pool_bytes = _sampled_step_compiled(bank, params, sds, slots,
                                                  length)
    mem = compiled.memory_analysis()
    assert pool_bytes == 268_435_456
    assert mem.alias_size_in_bytes == pool_bytes
    assert mem.temp_size_in_bytes < 150_000_000
    stacked = f"bf16[{SPLIT},{slots},{length},8,128]"
    writes = [line for line in compiled.as_text().splitlines()
              if re.search(r"%\S*dynamic-update-slice\S*fusion\S* = ", line)
              and stacked in line.split(" = ", 1)[1].split(" ", 1)[0]]
    assert not writes, writes


def test_hybrid_engine_step_updates_its_pool_in_place(one_chip):
    """zamba2 at its published widths, 4 layers with the shared blocks'
    invocations at layers 1 and 3 (head_dim 224, so the chip's default
    layout puts the hybrid KV's positions minor): the 8-slot step aliases
    its whole pool (some state leaves with the tiles' padding) and relayouts
    none of it, so its temporaries stay under one hybrid layer's K."""
    bank, params = _shapes_bank(one_chip, 4, SPLIT, "zamba2-7b",
                                hybrid_layer_ids=(1, 3))

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    slots, length = 8, 1024
    compiled, pool_bytes = _sampled_step_compiled(bank, params, sds, slots,
                                                  length)
    mem = compiled.memory_analysis()
    layer_k = slots * length * 32 * 224 * 2
    # every leaf aliased; the chip's tiles pad the small state leaves
    assert pool_bytes <= mem.alias_size_in_bytes < pool_bytes * 1.02
    assert mem.temp_size_in_bytes < layer_k


# each kernel's custom call, by the name of its wrapper in kernels/ops.py
KERNEL_NAMES = {"reduce_quant": "butterfly_reduce_quant",
                "reduce_quant_bincount": "butterfly_reduce_quant_bincount",
                "dequant_restore": "butterfly_dequant_restore",
                "restore_norm_bf16": "butterfly_restore_norm",
                "restore_norm_f32": "butterfly_restore_norm"}


def _kernel_instructions(text: str) -> list:
    """Names of the Mosaic custom calls in a compiled HLO text."""
    return [line.split(" = ", 1)[0].split()[-1].lstrip("%")
            for line in text.splitlines() if "tpu_custom_call" in line]


def _assert_named(found: list, name: str) -> None:
    """The trace names a kernel's op after its HLO instruction: each
    custom call found carries the name of the kernel's ops.py wrapper."""
    want = KERNEL_NAMES[name]
    assert found, "no Mosaic kernel in the compiled program"
    assert all(n == want or n.startswith(want + ".") for n in found), found


def _raw_kernel(name):
    """The ``pallas_call`` itself, without the ops.py wrapper's jit, at the
    shapes of :func:`_kernel_cases` in tiles of 128 rows (a tile that fits
    VMEM at every output dtype; the wrapper picks its own)."""
    from repro.kernels import butterfly_kernel as bk
    return {
        "reduce_quant": functools.partial(bk.butterfly_reduce_quant_kernel,
                                          block_t=128),
        "reduce_quant_bincount": functools.partial(
            bk.butterfly_reduce_quant_bincount_kernel, block_t=128),
        "dequant_restore": functools.partial(
            bk.butterfly_dequant_restore_kernel, out_dtype=jnp.bfloat16,
            block_t=128),
        "restore_norm_bf16": lambda c, s, w, n: (
            bk.butterfly_dequant_restore_norm_kernel(
                c, s, w, n.reshape(1, -1), out_dtype=jnp.bfloat16,
                block_t=128)),
        "restore_norm_f32": lambda c, s, w, n: (
            bk.butterfly_dequant_restore_norm_kernel(
                c, s, w, n.reshape(1, -1), block_t=128)),
    }[name]


@pytest.mark.parametrize("name", sorted(KERNEL_NAMES))
def test_kernel_keeps_its_name_in_another_program(one_chip, name):
    """Compiled straight into another jitted function, without its ops.py
    wrapper's jit, a kernel still carries the wrapper's name."""
    _, shapes = _kernel_cases()[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    kernel = _raw_kernel(name)

    def wire_probe(*a):
        return jax.tree.map(lambda y: y * 1, kernel(*a))
    _assert_named(_kernel_instructions(
        _compile_text(jax.jit(wire_probe), *args)), name)
