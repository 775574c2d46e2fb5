"""Ahead-of-time compiles for a described TPU v5e at qwen3-8b's widths
(d=4096, d_r=128): the main-path Pallas kernels and the split bank's jitted
edge and cloud halves.  Nothing runs; the TPU compiler refuses here what the
chip would refuse (VMEM overflow, unaligned tiles), and interpret-mode tests
cannot see either.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library."""
import functools

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
    assert "tpu_custom_call" in _compile_text(jax.jit(fn), *args)


@pytest.fixture(scope="module")
def bank_runner(one_chip):
    """A 2-layer slice of full-width qwen3-8b in its own dtype (bf16),
    split after layer 1, with shapes in place of weights: the bank's init
    is swapped for its ``jax.eval_shape`` so nothing is allocated."""
    import dataclasses

    from repro.configs import get_config
    from repro.models import model as M
    from repro.runtime.split_exec import SplitModelBank
    cfg = dataclasses.replace(get_config("qwen3-8b"), num_layers=2)
    with pytest.MonkeyPatch.context() as mp:
        real_init = M.init_model
        mp.setattr(M, "init_model", lambda key, built: (jax.eval_shape(
            lambda k: real_init(k, built)[0], key), None))
        bank = SplitModelBank(cfg, d_r=D_R)
    runner = bank.runner(1)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        runner.params)
    return bank, params


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
