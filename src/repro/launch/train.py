"""Training launcher.

Two modes:
  * local (default): really train a (reduced or custom) config on the
    synthetic LM pipeline on the available devices — the end-to-end driver.
  * --lower-only: AOT-lower the full config's train step on the production
    mesh (512 host devices) and print memory/cost analysis (the dry-run path
    for one arch; see launch/dryrun.py for the sweep).

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --reduced \
      --steps 200 --batch 16 --seq 128 --butterfly-layer 1 --d-r 32
"""
from __future__ import annotations

import argparse
import time

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--vocab", type=int, default=0, help="override vocab (synthetic data)")
    ap.add_argument("--butterfly-layer", type=int, default=None)
    ap.add_argument("--d-r", type=int, default=32)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--lower-only", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()

    if args.lower_only:
        # delegate to the dry-run (sets device count before jax init)
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
        from repro.launch.dryrun import run_pair
        run_pair(args.arch, "train_4k", args.multi_pod, "experiments/dryrun")
        return

    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.compile_cache import configure_compile_cache
    from repro.configs import get_config
    from repro.data import lm_batches
    from repro.models import model as M
    from repro.training import (AdamWConfig, adamw_init, cosine_schedule,
                                make_train_step)
    from repro.training.checkpoint import save_checkpoint

    configure_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.vocab:
        cfg = dataclasses.replace(cfg, vocab_size=args.vocab)
    if args.butterfly_layer is not None:
        cfg = cfg.with_butterfly(args.butterfly_layer, args.d_r)
    built = M.build(cfg)
    params, _ = M.init_model(jax.random.key(0), built)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M butterfly={cfg.butterfly}")

    opt_state = adamw_init(params)
    opt_cfg = AdamWConfig(lr=cosine_schedule(args.lr, 20, args.steps))
    step_fn = jax.jit(make_train_step(built, opt_cfg))
    stream = lm_batches(cfg.vocab_size, args.seq, args.batch)

    t0 = time.time()
    for i, raw in zip(range(args.steps), stream):
        batch = {k: jnp.asarray(v) for k, v in raw.items()}
        if cfg.num_patches:
            batch["patches"] = jnp.zeros((args.batch, cfg.num_patches, cfg.d_model),
                                         jnp.dtype(cfg.dtype))
            batch["targets"] = jnp.concatenate(
                [jnp.full((args.batch, cfg.num_patches), -1, jnp.int32),
                 batch["targets"]], axis=1)
        if cfg.is_encdec:
            batch["frames"] = jnp.zeros((args.batch, cfg.encoder_frames,
                                         cfg.d_model), jnp.dtype(cfg.dtype))
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        if i % args.log_every == 0 or i == args.steps - 1:
            tput = (i + 1) * args.batch * args.seq / (time.time() - t0)
            print(f"step {i:5d} loss {float(metrics['loss']):.4f} "
                  f"gnorm {float(metrics['grad_norm']):.2f} "
                  f"tok/s {tput:,.0f}")
    if args.checkpoint:
        path = save_checkpoint(args.checkpoint, params, opt_state,
                               step=args.steps, metadata={"arch": cfg.name})
        print("saved", path)


if __name__ == "__main__":
    main()
