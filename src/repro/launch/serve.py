"""Serving launcher: batched requests through the ServingEngine (single-mesh
baseline) or the 2-pod split pipeline (--split, the paper's deployment).

Example:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --prompts "hello" "world"
  PYTHONPATH=src python -m repro.launch.serve --arch gemma3-12b --split
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --full --layers 8
"""
from __future__ import annotations

import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--full", action="store_true",
                    help="run the arch at its published widths and dtype "
                         "instead of its reduced smoke variant")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the arch to this many layers (depth only)")
    ap.add_argument("--prompts", nargs="*", default=["the quick brown fox",
                                                     "once upon a time"])
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--split", action="store_true",
                    help="2-pod split pipeline demo (needs >=2 devices; "
                         "set XLA_FLAGS=--xla_force_host_platform_device_count=2)")
    ap.add_argument("--butterfly-layer", type=int, default=1)
    ap.add_argument("--d-r", type=int, default=32)
    args = ap.parse_args()

    if args.split:
        import os
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=2")

    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.compile_cache import configure_compile_cache
    from repro.configs import get_config
    from repro.data import tokenizer as tok
    from repro.models import model as M

    configure_compile_cache()
    cfg = get_config(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    if args.layers:
        cfg = dataclasses.replace(cfg, num_layers=args.layers)
    cfg = dataclasses.replace(cfg, vocab_size=max(cfg.vocab_size, tok.VOCAB_SIZE))
    if args.split:
        cfg = cfg.with_butterfly(args.butterfly_layer, args.d_r)
    built = M.build(cfg)
    params, _ = M.init_model(jax.random.key(0), built)
    if args.checkpoint:
        from repro.training.checkpoint import restore_checkpoint
        params, _, meta = restore_checkpoint(args.checkpoint, params)
        print("restored", meta)

    if args.split:
        from repro.serving.pipeline import make_split_pipeline, wire_stats
        mesh = jax.make_mesh((2, 1), ("pod", "data"))
        S = 32
        toks = np.stack([np.resize(tok.encode(p), S) for p in args.prompts])
        Mmb = len(args.prompts)
        pipe = jax.jit(make_split_pipeline(built, mesh, Mmb, S, 1))
        logits = pipe(params, jnp.asarray(toks))
        stats = wire_stats(cfg, 1, S)
        print(f"split pipeline over pod axis: wire={stats['wire_bytes']}B/mb "
              f"raw={stats['raw_boundary_bytes']}B compression={stats['compression']:.1f}x")
        for p, l in zip(args.prompts, logits):
            print(f"  {p!r} -> next-token id {int(jnp.argmax(l))}")
        return

    from repro.serving.engine import ServingEngine
    eng = ServingEngine(params, built, max_batch=max(4, len(args.prompts)),
                        max_len=256)
    reqs = [eng.submit(tok.encode(p), max_new_tokens=args.max_new_tokens,
                       temperature=args.temperature)
            for p in args.prompts]
    eng.run()
    for p, r in zip(args.prompts, reqs):
        print(f"  {p!r} -> {tok.decode(r.generated)!r} (ids {r.generated})")


if __name__ == "__main__":
    main()
