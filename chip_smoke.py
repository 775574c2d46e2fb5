#!/usr/bin/env python3
"""Bring-up smoke test of the split-serving path on TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: the pod x model pipelines

The model is qwen3-8b at its published widths (d_model 4096, 32 query and 8
kv heads of 128, d_ff 12288, vocabulary 151936, qk-norm, untied head, bf16)
with its depth cut from 36 layers to 8 so that it fits one 16 GB chip.  The
butterfly sits after layer 2 with d_r=128 (a 32x channel reduction) and an
int8 wire.  Weights are random, drawn from a seed.

One chip runs these phases in order:

1. device: the backend must be a TPU; there is no CPU fallback.
2. cache: JAX's persistent compilation cache (``repro.compile_cache``).
3. serving: 8 requests of 512 prompt tokens and 16 new tokens through
   ``Simulation`` (SplitModelBank -> ServingEngine -> decode transport),
   once with the cache_handoff transport and once with streamed.  Every
   request must finish with 16 tokens and every recorded logit be finite.
4. agreement: the served first-token logits (edge half -> int8 wire ->
   cloud half) against the bank's one-graph prefill and its eager
   reference prefill (no kernels) on the same prompts.
5. kernels: the lowered edge and cloud programs hold Mosaic kernels
   (``tpu_custom_call``); peak device memory is printed.

``--chips 4`` runs only the pod-split pipelines on a (pod=2, model=2) mesh
built from ``jax.devices()``: ``make_split_pipeline`` and
``make_decode_pipeline`` with the fused kernels, against the same
parameters' one-device prefill and greedy decode, and checks that they and
``SplitModelBank.mp_mesh`` place their shards on all four devices.

A failed phase exits non-zero.  Only a run in which every phase passed
prints its last line, ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen3-8b"
LAYERS = 8              # of the published 36
SPLIT = 2               # butterfly after this many layers
D_R = 128               # 4096 / 128 = 32x channel reduction, lane-aligned
REQUESTS = 8
PROMPT_LEN = 512
NEW_TOKENS = 16
SEED = 0

# Two differently compiled bf16 graphs of the same math never agree bitwise.
# bf16 keeps 8 significant bits (a relative step of 2**-8 = 0.4%), and the
# roundings of eight layers add up: on the CPU, at d_model 512 to 2048 and
# 8 layers with random weights, the bf16 model's last-token logits differ
# from the same model run in float32 by 1.3% to 1.8% of the row's L2 norm,
# and two bf16 graphs of it (eager wire vs fused kernels, one device vs a
# tensor-parallel mesh) differ from each other by as much.  The bound is
# about three times that floor.  A wrong wire, cache or weight slice moves
# the row by the order of its own norm.
LOGIT_REL_TOL = 0.05


class SmokeFailure(SystemExit):
    """A failed phase: exits with code 1 and its message on stderr."""

    def __init__(self, msg: str):
        super().__init__(f"FAIL: {msg}")


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def phase(name: str):
    print(f"== {name}", flush=True)
    return time.perf_counter()


def done(t0: float, what: str = "") -> None:
    print(f"   ok {what}({time.perf_counter() - t0:.1f} s)", flush=True)


def memory(label: str) -> dict:
    """Print and return device 0's memory counters."""
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    print(f"   memory {label}: bytes_in_use="
          f"{stats.get('bytes_in_use', -1)} peak_bytes_in_use="
          f"{stats.get('peak_bytes_in_use', -1)}", flush=True)
    return stats


def smoke_config():
    """qwen3-8b at its published widths, depth cut to ``LAYERS``."""
    from repro.configs import get_config
    full = get_config(ARCH)
    cfg = dataclasses.replace(full, num_layers=LAYERS)
    print(f"config: {ARCH} ({full.source}) d_model={cfg.d_model} "
          f"heads={cfg.num_heads}/{cfg.num_kv_heads}x{cfg.head_dim} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} qk_norm={cfg.qk_norm} "
          f"tied={cfg.tie_embeddings} dtype={cfg.dtype}; cut: num_layers "
          f"{full.num_layers} -> {LAYERS}, butterfly after layer {SPLIT} "
          f"with d_r={D_R} and an int8 wire, random weights (seed {SEED})",
          flush=True)
    return cfg


def device_phase(min_chips: int) -> dict:
    import jax
    t0 = phase("device")
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print(f"   platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}")
    check(info["platform"] == "tpu",
          f"needs a TPU, JAX found {info['platform']!r}")
    check(info["count"] >= min_chips,
          f"needs {min_chips} chips, JAX found {info['count']}")
    done(t0)
    return info


def cache_phase() -> None:
    from repro.compile_cache import configure_compile_cache
    t0 = phase("compile cache")
    print(f"   dir={configure_compile_cache()}")
    done(t0)


def rel_err(a, b):
    """Per-row relative L2 error of ``a`` against ``b`` (rows on axis 0)."""
    import numpy as np
    a = np.asarray(a, np.float64).reshape(len(a), -1)
    b = np.asarray(b, np.float64).reshape(len(b), -1)
    return np.linalg.norm(a - b, axis=1) / np.linalg.norm(b, axis=1)


def serving_phase(cfg, transport: str, *, requests: int = REQUESTS,
                  prompt_len: int = PROMPT_LEN,
                  new_tokens: int = NEW_TOKENS):
    """One ``Simulation`` run; returns it for the later phases."""
    import numpy as np

    from repro.runtime.simulator import SimConfig, Simulation
    t0 = phase(f"serving: {transport}")
    sim = Simulation(SimConfig(
        cfg=cfg, mode="split", transport=transport, numerics=True,
        num_devices=requests, num_requests=requests, arrival_rate=100.0,
        prompt_len=prompt_len, max_new_tokens=new_tokens, d_r=D_R,
        initial_split=SPLIT, max_concurrent=requests, seed=SEED,
        record_logits=True))
    memory("after the bank drew its weights")
    tel = sim.run()
    memory("after serving")
    check(len(sim.requests) == requests,
          f"{len(sim.requests)} of {requests} requests were issued")
    for req in sim.requests:
        er = req.engine_req
        check(er is not None and req.finished,
              f"request {req.uid} did not finish")
        check(len(er.generated) == new_tokens and
              req.trace.new_tokens == new_tokens,
              f"request {req.uid} made {len(er.generated)} tokens, "
              f"wanted {new_tokens}")
        logits = np.stack([np.asarray(r, np.float32)
                           for r in er.logits_history])
        check(logits.shape == (new_tokens, cfg.vocab_size),
              f"request {req.uid} logits {logits.shape}")
        check(bool(np.isfinite(logits).all()),
              f"request {req.uid} has non-finite logits")
    c = tel.counters
    print(f"   {requests} requests x {new_tokens} tokens, logits finite; "
          f"edge batches {c['edge_numerics_batches']:.0f}, cloud batches "
          f"{c['cloud_numerics_batches']:.0f}, compiled entries "
          f"{sorted(k[:5] for k in sim.bank.jit_cache_keys)}")
    done(t0, "(wall time includes compilation) ")
    return sim


def agreement_phase(sim, *, tol: float = LOGIT_REL_TOL) -> None:
    """Served first-token logits against two references on the same
    prompts: the bank's one-graph prefill (both halves and the Mosaic wire
    kernels in one program, through a plain engine) and its eager
    reference prefill (the wire through the jnp codec, no kernels)."""
    import numpy as np
    t0 = phase("agreement: split path vs one-graph and reference prefill")
    runner = sim.bank.runner(SPLIT)
    prompt_len = len(sim.requests[0].tokens)
    eng = runner.make_engine(max_batch=1, max_len=prompt_len + 2)
    served = np.stack([np.asarray(r.engine_req.logits_history[0], np.float32)
                       for r in sim.requests])
    one_graph = np.stack([np.asarray(eng.submit(
        r.tokens, max_new_tokens=1, record_logits=True).logits_history[0],
        np.float32) for r in sim.requests])
    reference = np.asarray(runner.reference_prefill(
        np.stack([r.tokens for r in sim.requests]))[0][:, 0], np.float32)
    for name, want in (("one-graph prefill", one_graph),
                       ("reference prefill", reference)):
        err = rel_err(served, want)
        same_top1 = int((served.argmax(-1) == want.argmax(-1)).sum())
        print(f"   vs {name}: relative L2 error per prompt max "
              f"{err.max():.3e} mean {err.mean():.3e} (bound {tol}); same "
              f"greedy token {same_top1}/{len(served)}")
        check(float(err.max()) <= tol,
              f"split logits differ from the {name} by {err.max():.3e}")
    done(t0)


def kernels_phase(sim) -> None:
    """Lower the edge and cloud programs at the (B, S) shapes the serving
    phase ran and count their Mosaic kernels."""
    import jax
    import jax.numpy as jnp
    t0 = phase("kernels: Mosaic calls in the edge and cloud programs")
    bank = sim.bank
    runner = bank.runner(SPLIT)
    sds = jax.ShapeDtypeStruct
    served = sorted({k[:5] for k in bank.jit_cache_keys
                     if k[0] in ("edge", "cloud")})
    check({k[0] for k in served} == {"edge", "cloud"},
          f"the serving phase ran no edge or no cloud program: {served}")
    for kind, split, mp, B, S in served:
        if kind == "edge":
            args = (sds((B, S), jnp.int32),)
        else:
            args = (sds((B, S, D_R), jnp.int8), sds((B, S, 1), jnp.float32),
                    sds((), jnp.int32))
        text = bank._fn(kind, split, mp).lower(runner.params, *args).as_text()
        n = text.count("tpu_custom_call")
        print(f"   {kind} at (B={B}, S={S}): {n} tpu_custom_call")
        check(n > 0, f"the {kind} program holds no Mosaic kernel")
    memory("at the end")
    done(t0)


def single_chip(cfg) -> None:
    import gc
    sim = serving_phase(cfg, "cache_handoff")
    del sim
    gc.collect()            # free the first bank before the second builds
    memory("after freeing the first bank")
    sim = serving_phase(cfg, "streamed")
    agreement_phase(sim)
    kernels_phase(sim)


# ---------------------------------------------------------------- 4 chips


def greedy_agreement(pipe_toks, ref_reqs, tie_tol: float):
    """Pipeline greedy ids against the reference engine's, request by
    request.  They must agree up to the first step where they differ, and
    there the reference must score the two tokens within ``tie_tol`` of
    each other: a near tie that bf16 rounding may break either way.  After
    that step the two continue from different tokens and are not compared.
    Returns the number of steps compared and matched."""
    import numpy as np
    matched = 0
    for row, ref in zip(np.asarray(pipe_toks), ref_reqs):
        for t, (p, r) in enumerate(zip(row.tolist(), ref.generated)):
            if p == r:
                matched += 1
                continue
            logits = np.asarray(ref.logits_history[t], np.float32)
            gap = float(logits[r] - logits[p])
            print(f"   request {ref.uid} step {t}: pipeline {p}, reference "
                  f"{r}, reference logit gap {gap:.4f} (tie bound "
                  f"{tie_tol:.4f})")
            check(gap <= tie_tol,
                  f"request {ref.uid} step {t}: greedy token differs "
                  f"beyond a near tie (gap {gap:.4f})")
            break
    return matched


def shard_devices(arr, label: str) -> int:
    devs = {s.device for s in arr.addressable_shards}
    for s in arr.addressable_shards:
        print(f"   {label} shard {s.index} on {s.device}")
    return len(devs)


def pipeline_phase(cfg, *, seq: int = 128, new_tokens: int = 8,
                   microbatches: int = 2, microbatch: int = 2,
                   tol: float = LOGIT_REL_TOL) -> None:
    """The pod-split pipelines on a (pod=2, model=2) mesh against the same
    parameters' one-device prefill and greedy decode (eager wire, no
    kernels, one device), then the placement of the bank's model mesh."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding

    from repro.models import model as M
    from repro.serving.engine import ServingEngine
    from repro.serving.pipeline import (make_decode_pipeline,
                                        make_split_pipeline,
                                        pipeline_param_specs)
    t0 = phase("pipelines on a (pod=2, model=2) mesh")
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("pod", "model"))
    built = M.build(cfg.with_butterfly(SPLIT, D_R))
    params, _ = M.init_model(jax.random.key(SEED), built)
    memory("after drawing the weights")
    n = microbatches * microbatch
    toks = jax.random.randint(jax.random.key(SEED + 1), (n, seq), 0,
                              cfg.vocab_size)

    specs = pipeline_param_specs(built, 2)
    placed = jax.device_put(params, jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec)))
    wq = placed["stages"][1][0][0]["mixer"]["wq"]
    n_dev = shard_devices(wq, "cloud stage layer 0 wq")
    check(n_dev == 4, f"pipeline params sit on {n_dev} devices, not 4")

    split_fn = jax.jit(make_split_pipeline(
        built, mesh, microbatches, seq, microbatch, use_kernel=True))
    pipe_logits = split_fn(placed, toks)
    decode_fn = jax.jit(make_decode_pipeline(
        built, mesh, microbatches, seq, microbatch, new_tokens,
        use_kernel=True))
    pipe_toks = decode_fn(placed, toks)
    print(f"   split pipeline output on {len(pipe_logits.sharding.device_set)}"
          f" devices; decode pipeline output on "
          f"{len(pipe_toks.sharding.device_set)} devices")

    ref_prefill = jax.jit(lambda p, t: M.forward_prefill(p, built,
                                                         {"tokens": t}))
    eng = ServingEngine(params, built, max_batch=n,
                        max_len=seq + new_tokens + 1, prefill_fn=ref_prefill)
    refs = [eng.submit(np.asarray(t), max_new_tokens=new_tokens,
                       record_logits=True) for t in toks]
    eng.run()

    ref_first = np.stack([np.asarray(r.logits_history[0], np.float32)
                          for r in refs])
    err = rel_err(pipe_logits, ref_first)
    print(f"   split pipeline vs one-device prefill: relative L2 error max "
          f"{err.max():.3e} mean {err.mean():.3e} (bound {tol})")
    check(float(err.max()) <= tol,
          f"split pipeline logits differ by {err.max():.3e}")
    tie_tol = 2.0 * float(np.abs(np.asarray(pipe_logits) - ref_first).max())
    matched = greedy_agreement(pipe_toks, refs, tie_tol)
    print(f"   decode pipeline vs one-device greedy decode: {matched} of "
          f"{n * new_tokens} tokens matched before any near-tie divergence")
    memory("at the end")
    bank_mesh_check()
    done(t0)


def bank_mesh_check() -> None:
    """``SplitModelBank.mp_mesh`` places the cloud half's shards on four
    devices, and that half agrees with its one-device run.  A reduced
    float32 config: this checks placement, not width."""
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.runtime.split_exec import SplitModelBank
    print("   bank: cloud half over a 4-device model mesh", flush=True)
    small = dataclasses.replace(get_config(ARCH).reduced(), num_heads=8,
                                num_kv_heads=4)
    bank = SplitModelBank(small, d_r=16, cloud_mp=4, seed=SEED)
    mesh = bank.mp_mesh(4)
    print(f"   mp_mesh devices: {[str(d) for d in mesh.devices.flat]}")
    r4, r1 = bank.runner(1), bank.runner(1, cloud_mp=1)
    prompt = (np.arange(1, 33, dtype=np.int32) * 7) % small.vocab_size
    payload, scales, _ = r1.edge_half(r1.params, prompt[None])
    l4, cache4 = r4.cloud_half(r4.params, payload, scales)
    l1, _ = r1.cloud_half(r1.params, payload, scales)
    kv = jax.tree.leaves(cache4)[0]
    n_dev = shard_devices(kv, "cloud kv cache")
    check(n_dev == 4, f"cloud kv cache sits on {n_dev} devices, not 4")
    err = rel_err(l4, l1)
    print(f"   cloud_mp=4 vs cloud_mp=1 logits: relative L2 error "
          f"{err.max():.3e} (float32, bound 1e-3)")
    check(float(err.max()) <= 1e-3, "model-parallel cloud half disagrees")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    info = device_phase(args.chips)
    cache_phase()
    cfg = smoke_config()
    if args.chips == 4:
        pipeline_phase(cfg)
    else:
        single_chip(cfg)
    print(json.dumps({"ok": True, "device": info}))


if __name__ == "__main__":
    main()
