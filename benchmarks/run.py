# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV rows (us_per_call = wall time of the benchmarked call on this host;
# derived = the paper-comparable quantity).
#
#   fig7        accuracy vs D_r x split (tiny ResNet, synthetic images)
#   table4      per-split latency/energy profile via Algorithm 1 profiling
#   table5      selection phase on the paper's published Table IV -> exact
#               reproduction of the paper's chosen splits + improvements
#   sec3d       compression ratios (butterfly vs raw features)
#   wire        beyond-paper: pod-boundary wire bytes per arch
#   transport   decode-transport smoke: streamed vs cache-handoff parity
#   roofline    aggregated dry-run roofline table (reads experiments/dryrun)
#   micro       kernel/system microbenchmarks (us/call)
#
# Run: PYTHONPATH=src python -m benchmarks.run [names...]
from __future__ import annotations

import json
import os
import sys
import time


def _timeit(fn, n=3):
    fn()                                   # warmup/compile
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n * 1e6


def bench_fig7():
    """Fig. 7 miniature: accuracy for (split x D_r) on the synthetic task."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "examples"))
    from train_resnet_butterfly import train_and_eval
    from repro.configs.resnet50 import resnet50

    base = resnet50().reduced()
    t0 = time.perf_counter()
    target = train_and_eval(base, steps=60)
    rows = []
    for split in (1, 2):
        for d_r in (1, 4):
            acc = train_and_eval(base.with_butterfly(split, d_r), steps=60)
            rows.append((split, d_r, acc))
    us = (time.perf_counter() - t0) * 1e6
    for split, d_r, acc in rows:
        print(f"fig7/rb{split}_dr{d_r},{us/5:.0f},acc={acc:.3f}(target={target:.3f})")
    # the paper's qualitative claim: larger D_r never hurts
    for split in (1, 2):
        a1 = next(a for s, d, a in rows if s == split and d == 1)
        a4 = next(a for s, d, a in rows if s == split and d == 4)
        print(f"fig7/monotone_rb{split},0,larger_dr_better={a4 >= a1 - 0.05}")


def bench_table4():
    """Table IV analogue from the roofline profiler (full ResNet-50)."""
    from repro.configs.resnet50 import PAPER_MIN_DR, resnet50
    from repro.core import costs
    from repro.core.planner import TrainingPhaseResult, profiling_phase
    from repro.core.profiler import GTX_1080TI, JETSON_TX2
    from repro.core.wireless import NETWORKS

    cfg = resnet50()
    trained = [TrainingPhaseResult(s, PAPER_MIN_DR[s], 0.74) for s in range(1, 17)]

    def split_costs(split, d_r):
        ef, cf, wire = costs.resnet_split_flops(cfg, split, d_r)
        return ef, ef / 10, cf, cf / 10, wire

    t0 = time.perf_counter()
    profiles = profiling_phase(trained, split_costs, JETSON_TX2, GTX_1080TI)
    us = (time.perf_counter() - t0) * 1e6
    for p in profiles[:4] + profiles[7:8] + profiles[15:]:
        lat3g = p.latency(NETWORKS["3g"]) * 1e3
        latwifi = p.latency(NETWORKS["wifi"]) * 1e3
        print(f"table4/rb{p.split},{us/16:.0f},"
              f"wire={p.wire_bytes}B lat3g={lat3g:.2f}ms latwifi={latwifi:.2f}ms")


def bench_table5():
    """Selection phase on the paper's OWN published profile: must reproduce
    Table V exactly (RB8 for 3G, RB1 for 4G/Wi-Fi) + headline factors."""
    from repro.core.planner import select_from_table
    from repro.core.profiler import PAPER_CLOUD_ONLY, paper_profiles

    profs = paper_profiles()
    t0 = time.perf_counter()
    out = {}
    for net in ("3g", "4g", "wifi"):
        for obj in ("latency", "energy"):
            out[(net, obj)] = select_from_table(profs[net], obj)
    us = (time.perf_counter() - t0) * 1e6
    for net in ("3g", "4g", "wifi"):
        sel = out[(net, "latency")]
        row = profs[net][sel]
        lat_x = PAPER_CLOUD_ONLY[net][0] / row["latency_ms"]
        en_x = PAPER_CLOUD_ONLY[net][1] / row["energy_mj"]
        print(f"table5/{net},{us/6:.0f},split=RB{sel} lat_x={lat_x:.0f} "
              f"en_x={en_x:.0f} (paper: RB{'8' if net=='3g' else '1'})")
    avg_lat = sum(PAPER_CLOUD_ONLY[n][0] / profs[n][out[(n, 'latency')]]["latency_ms"]
                  for n in ("3g", "4g", "wifi")) / 3
    avg_en = sum(PAPER_CLOUD_ONLY[n][1] / profs[n][out[(n, 'energy')]]["energy_mj"]
                 for n in ("3g", "4g", "wifi")) / 3
    print(f"table5/headline,0,avg_lat_x={avg_lat:.0f}(paper=53) "
          f"avg_en_x={avg_en:.0f}(paper=68)")


def bench_sec3d():
    """Sec III-D compression ratios."""
    from repro.configs import get_config
    from repro.configs.all import ASSIGNED
    from repro.core.butterfly import compression_ratio
    # paper: RB1 reduces 256 -> 1 channels = 256x
    print(f"sec3d/resnet_rb1,0,compression={compression_ratio(256, 1, 8, 8):.0f}x"
          f"(paper=256x, prior art 3.3x)")
    for arch in ASSIGNED:
        cfg = get_config(arch)
        d_r = max(8, cfg.d_model // 64)
        c = compression_ratio(cfg.d_model, d_r, 16, 8)
        print(f"sec3d/{arch},0,d{cfg.d_model}->dr{d_r} wire_compression={c:.0f}x")


def bench_wire():
    """Beyond-paper: pod-boundary bytes for the split pipeline per arch."""
    from repro.configs import get_config
    from repro.serving.pipeline import wire_stats

    for arch in ("qwen3-8b", "gemma3-12b", "zamba2-7b", "xlstm-125m"):
        base = get_config(arch)
        cfg = base.with_butterfly(layer=max(1, base.num_layers // 8),
                                  d_r=max(16, base.d_model // 64))
        s = wire_stats(cfg, microbatch=8, seq=4096)
        print(f"wire/{arch},0,wire={s['wire_bytes']/1e6:.2f}MB "
              f"raw={s['raw_boundary_bytes']/1e6:.2f}MB "
              f"compression={s['compression']:.1f}x")


def bench_roofline():
    """Aggregate the dry-run artifacts into the section-Roofline table."""
    d = os.path.join(os.path.dirname(__file__), "..", "experiments", "dryrun")
    if not os.path.isdir(d):
        print("roofline/none,0,run launch/dryrun first")
        return
    rows = []
    for f in sorted(os.listdir(d)):
        if not f.endswith(".json"):
            continue
        rec = json.load(open(os.path.join(d, f)))
        if "compute_s" not in rec:
            continue
        rows.append(rec)
    for r in rows:
        if r["mesh"] != "16x16":
            continue
        print(f"roofline/{r['arch']}/{r['shape']},{r['compile_s']*1e6:.0f},"
              f"compute={r['compute_s']*1e3:.2f}ms memory={r['memory_s']*1e3:.2f}ms "
              f"collective={r['collective_s']*1e3:.2f}ms "
              f"bottleneck={r['bottleneck']} useful={r['useful_ratio']:.2f}")
    n_mp = sum(1 for r in rows if r["mesh"] == "2x16x16")
    print(f"roofline/multi_pod_compiles,0,count={n_mp}")


def bench_micro():
    """Microbenchmarks: butterfly kernel, flash attention, model forward."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.configs import get_config
    from repro.models import model as M

    x = jax.random.normal(jax.random.key(0), (1024, 512), jnp.float32)
    w = jax.random.normal(jax.random.key(1), (512, 32), jnp.float32) * 0.05
    us = _timeit(lambda: jax.block_until_ready(
        ops.butterfly_reduce_quant(x, w)))
    print(f"micro/butterfly_reduce_quant_1024x512,{us:.0f},interpret_mode")

    q = jax.random.normal(jax.random.key(2), (1, 256, 4, 64), jnp.float32)
    k = jax.random.normal(jax.random.key(3), (1, 256, 2, 64), jnp.float32)
    us = _timeit(lambda: jax.block_until_ready(
        ops.flash_attention(q, k, k, block_q=128, block_k=128)))
    print(f"micro/flash_attention_256,{us:.0f},interpret_mode")

    cfg = get_config("qwen3-8b").reduced()
    built = M.build(cfg)
    params, _ = M.init_model(jax.random.key(0), built)
    toks = jnp.ones((4, 128), jnp.int32)
    fwd = jax.jit(lambda p, t: M.forward_train(p, built, {"tokens": t})[0])
    us = _timeit(lambda: jax.block_until_ready(fwd(params, toks)))
    tokps = 4 * 128 / (us / 1e6)
    print(f"micro/reduced_qwen3_fwd_4x128,{us:.0f},tok_per_s={tokps:,.0f}")


def bench_wirebits():
    """Beyond-paper (the paper's stated future work: 'the extent of reduction
    ... can be explored'): trade accuracy vs wire precision.  Tiny LM +
    butterfly trained end-to-end with a 4/8/16-bit wire."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.core.quantization import wire_bytes
    from repro.data import lm_batches
    from repro.models import model as M
    from repro.training import (AdamWConfig, adamw_init, constant_schedule,
                                make_train_step)

    d_r = 16
    for bits in (4, 8, 16):
        cfg = dataclasses.replace(get_config("qwen3-8b").reduced(),
                                  vocab_size=64)
        cfg = cfg.with_butterfly(layer=1, d_r=d_r, wire_bits=bits)
        built = M.build(cfg)
        params, _ = M.init_model(jax.random.key(0), built)
        opt = adamw_init(params)
        step = jax.jit(make_train_step(
            built, AdamWConfig(lr=constant_schedule(3e-3))))
        import time as _t
        t0 = _t.perf_counter()
        last = None
        for i, raw in zip(range(60), lm_batches(cfg.vocab_size, 32, 8, seed=5)):
            batch = {k: jnp.asarray(v) for k, v in raw.items()}
            params, opt, m = step(params, opt, batch)
            last = float(m["loss"])
        us = (_t.perf_counter() - t0) / 60 * 1e6
        wb = wire_bytes((8, 32, d_r), bits)
        print(f"wirebits/{bits}bit,{us:.0f},final_loss={last:.3f} "
              f"wire_bytes_per_batch={wb}")


def bench_bank():
    """Shared-weight split bank vs per-split model init (the tentpole's
    before/after): build time, parameter bytes and compile-cache entries for
    the full candidate sweep of an 8-layer config."""
    import dataclasses
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.models import model as M
    from repro.runtime.split_exec import SplitModelBank

    def tree_bytes(trees):
        seen, total = set(), 0
        for t in trees:
            for leaf in jax.tree.leaves(t):
                if id(leaf) not in seen:
                    seen.add(id(leaf))
                    total += leaf.nbytes
        return total

    cfg = dataclasses.replace(get_config("qwen3-8b").reduced(), num_layers=8)
    d_r = 16
    splits = list(range(1, cfg.num_layers))

    # before: one full model init per candidate split (what SplitRunner did)
    t0 = time.perf_counter()
    naive_params = []
    for s in splits:
        scfg = cfg.with_butterfly(s, d_r)
        built = M.build(scfg)
        p, _ = M.init_model(jax.random.key(0), built)
        naive_params.append(p)
    naive_s = time.perf_counter() - t0
    naive_bytes = tree_bytes(naive_params)

    # after: one backbone + per-split butterfly views
    t0 = time.perf_counter()
    bank = SplitModelBank(cfg, d_r)
    runners = [bank.runner(s) for s in splits]
    bank_s = time.perf_counter() - t0
    bank_bytes = tree_bytes([r.params for r in runners])

    print(f"bank/build_naive,{naive_s*1e6:.0f},"
          f"{len(splits)}_inits bytes={naive_bytes/1e6:.1f}MB")
    print(f"bank/build_shared,{bank_s*1e6:.0f},"
          f"1_init+{len(splits)}_butterflies bytes={bank_bytes/1e6:.1f}MB")
    print(f"bank/reduction,0,build_time={naive_s/max(bank_s,1e-9):.1f}x "
          f"param_bytes={naive_bytes/bank_bytes:.1f}x")

    # compile-cache behaviour: a candidate sweep at one prompt length plus a
    # prompt-length sweep on one split — bucketing folds shapes together
    toks = np.ones((1, 16), np.int32)
    t0 = time.perf_counter()
    for r in runners:
        payload, scales, _ = r.edge_half(r.params, toks)
        r.cloud_half(r.params, payload, scales)
    sweep_entries = bank.jit_cache_entries
    seqs = (24, 31, 40)                  # 3 fresh shapes -> 2 seq buckets
    for S in seqs:
        runners[0].edge_half(runners[0].params, np.ones((1, S), np.int32))
    us = (time.perf_counter() - t0) * 1e6
    added = bank.jit_cache_entries - sweep_entries
    print(f"bank/jit_cache,{us/(len(splits)*2+len(seqs)):.0f},"
          f"entries_full_split_sweep={sweep_entries} "
          f"seq_sweep_{'_'.join(map(str, seqs))}_added={added} "
          f"(exact-shape compiles would add {len(seqs)})")


def _wire_codec_report():
    """Entropy-wire codec economics with the REAL rANS coder on held-out
    codes.  Both models branch off a shared 120-step rate-free prefix and
    take a second epoch over the same 120-batch shard — identical data,
    equal total steps: the baseline continues rate-free, the entropy
    branch adds the rate term to the loss (learn the task first, compress
    after — training with the rate term from step 0 lands on a much worse
    accuracy/rate frontier).  The prior is fit on the tail of the entropy
    branch's training shard and priced on held-out batches of the same
    Markov language."""
    import dataclasses
    import itertools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.core.quantization import Wire
    from repro.core import wire_codec
    from repro.data import lm_batches
    from repro.models import model as M
    from repro.models import transformer as tfm
    from repro.training import (AdamWConfig, adamw_init, constant_schedule,
                                make_train_step)

    d_r, steps, rate_weight = 32, 120, 0.35

    def batches(skip, n):
        return list(itertools.islice(lm_batches(64, 32, 8, seed=5),
                                     skip, skip + n))

    def make(rw):
        cfg = dataclasses.replace(get_config("qwen3-8b").reduced(),
                                  vocab_size=64)
        cfg = cfg.with_butterfly(layer=1, d_r=d_r, wire_bits=8,
                                 rate_weight=rw)
        built = M.build(cfg)
        step = jax.jit(make_train_step(
            built, AdamWConfig(lr=constant_schedule(3e-3))))
        return built, step

    def run(step, params, skip):
        opt = adamw_init(params)
        for raw in batches(skip, steps):
            batch = {k: jnp.asarray(v) for k, v in raw.items()}
            params, opt, _ = step(params, opt, batch)
        return params

    def boundary_codes(params, built, batch):
        x = M._embed_inputs(params, built, batch)
        x, _, _ = tfm.apply_stage(
            list(built.stages[0]), params["stages"][0], x, cfg=built.cfg,
            pctx=M.LOCAL, mode="train", stage_cache=None, pos=None,
            enc_out=None, shared_params=params.get("shared_attn"),
            use_kernel=False)
        codes, _ = Wire("int8", 8, d_r).encode(
            x, params["butterfly"]["w_reduce"], kernel=False)
        return np.asarray(codes).reshape(-1, d_r)

    def eval_loss(params, built):
        losses = []
        for raw in batches(steps, 32):
            batch = {k: jnp.asarray(v) for k, v in raw.items()}
            logits, _ = M.forward_train(params, built, batch)
            losses.append(float(M.lm_loss(logits, batch["targets"])))
        return float(np.mean(losses))

    built0, step0 = make(0.0)
    built_r, step_r = make(rate_weight)
    params, _ = M.init_model(jax.random.key(0), built0)
    prefix = run(step0, params, 0)
    base = run(step0, prefix, 0)         # epoch 2, rate-free
    ent = run(step_r, prefix, 0)         # epoch 2, rate-aware
    base_loss = eval_loss(base, built0)
    ent_loss = eval_loss(ent, built_r)

    counts = np.zeros((d_r, 256), np.int64)
    for raw in batches(steps - 8, 8):
        batch = {k: jnp.asarray(v) for k, v in raw.items()}
        counts += wire_codec.channel_counts(
            boundary_codes(ent, built_r, batch), 8)
    prior = wire_codec.WirePrior.from_counts(counts, 8)
    nbytes, rows = 0, 0
    for raw in batches(steps, 8):
        batch = {k: jnp.asarray(v) for k, v in raw.items()}
        codes = boundary_codes(ent, built_r, batch)
        nbytes += len(wire_codec.encode(codes, prior)) + 4 * codes.shape[0]
        rows += codes.shape[0]
    ent_bpt = nbytes / rows
    int8_bpt = float(d_r + 4)            # codes + one f32 scale per row
    return {"d_r": d_r, "rate_weight": rate_weight,
            "train_steps": 2 * steps,
            "int8_bytes_per_token": round(int8_bpt, 2),
            "entropy_bytes_per_token": round(ent_bpt, 2),
            "entropy_bytes_reduction": round(int8_bpt / ent_bpt, 2),
            "eval_loss_base": round(base_loss, 4),
            "eval_loss_entropy": round(ent_loss, 4),
            "eval_loss_delta_pct": round(
                100.0 * (ent_loss - base_loss) / base_loss, 2)}


def bench_runtime():
    """Split-serving runtime: cloud-only (raw upload) vs the butterfly split
    under identical Poisson traffic, a streamed vs cache-handoff decode
    transport comparison on a long-prompt/multi-token workload (both runs on
    the SAME arrival trace via the shared builder), the adaptive
    controller's split trajectory under a cloud-load ramp, and a multi-cell
    topology scenario (heterogeneous fleets on per-cell radios vs the same
    fleet through one shared 3g wire, per-cell controllers diverging), and a
    resilience scenario (the same topology under a chaos fault schedule —
    availability, tail latency and migration/retry counts vs the calm run),
    and an entropy-wire scenario (trained-prior codec economics plus the
    four wire/transport configs on one long-prompt trace).
    Emits one JSON document (runtime/json row) with the full comparison."""
    import dataclasses

    from repro.configs import get_config
    from repro.core.profiler import JETSON_TX2
    from repro.runtime.simulator import (CellSpec, SimConfig, Simulation,
                                         WorkloadSpec, poisson_arrivals,
                                         ramp_load)

    cfg = dataclasses.replace(get_config("qwen3-8b").reduced(), num_layers=4)
    base = SimConfig(cfg=cfg, network="3g", num_devices=4, num_requests=32,
                     arrival_rate=20.0, prompt_len=32, max_new_tokens=1,
                     d_r=16, numerics=False, seed=0)
    from repro.runtime.telemetry import SCHEMA_VERSION
    result = {"schema_version": SCHEMA_VERSION,
              "workload": {"arch": cfg.name, "layers": cfg.num_layers,
                           "devices": 4, "requests": 32, "prompt_len": 32,
                           "d_r": 16}, "networks": {}}
    t0 = time.perf_counter()
    for net in ("3g", "4g", "wifi"):
        row = {}
        for label, mode, wm in (("cloud_only", "cloud", "int8"),
                                ("split_raw", "split", "raw"),
                                ("split_reduced", "split", "reduced"),
                                ("split_int8", "split", "int8")):
            sc = dataclasses.replace(base, network=net, mode=mode,
                                     wire_mode=wm)
            s = Simulation(sc).run().summary()
            row[label] = {"latency_p50_ms": round(s["latency_p50_ms"], 3),
                          "latency_p95_ms": round(s["latency_p95_ms"], 3),
                          "latency_p99_ms": round(s["latency_p99_ms"], 3),
                          "mean_wire_kb": round(s["mean_wire_kb"], 3),
                          "mean_mobile_energy_mj":
                              round(s["mean_mobile_energy_mj"], 3)}
            if s["throughput_rps"] == s["throughput_rps"]:  # skip NaN
                row[label]["throughput_rps"] = round(s["throughput_rps"], 2)
        row["split_speedup_vs_cloud"] = round(
            row["cloud_only"]["latency_p50_ms"] /
            row["split_int8"]["latency_p50_ms"], 2)
        result["networks"][net] = row
        print(f"runtime/{net},0,split_p50="
              f"{row['split_int8']['latency_p50_ms']:.2f}ms "
              f"cloud_p50={row['cloud_only']['latency_p50_ms']:.2f}ms "
              f"speedup={row['split_speedup_vs_cloud']:.1f}x")
    # decode transports head to head: long prompt + multi-token generation
    # on 3g, identical arrival trace (the shared Poisson builder) — cache
    # handoff pays prompt-proportional KV bytes up front, streamed pays one
    # (1, d_r) row + one id RTT per token
    tr_prompt, tr_tokens = 128, 8
    arrivals = poisson_arrivals(num_devices=4, num_requests=24,
                                arrival_rate=20.0, prompt_len=tr_prompt,
                                seed=0)
    tr = {"workload": {"prompt_len": tr_prompt, "max_new_tokens": tr_tokens,
                       "network": "3g", "requests": 24}}
    for tp in ("cache_handoff", "streamed"):
        sc = dataclasses.replace(base, network="3g", mode="split",
                                 wire_mode="int8", transport=tp,
                                 num_requests=24, prompt_len=tr_prompt,
                                 max_new_tokens=tr_tokens, arrivals=arrivals)
        s = Simulation(sc).run().summary()
        tr[tp] = {"latency_p50_ms": round(s["latency_p50_ms"], 3),
                  "ttft_p50_ms": round(s["ttft_p50_ms"], 3),
                  "mean_uplink_kb": round(s["mean_wire_kb"], 3),
                  "mean_downlink_b": round(s["mean_downlink_b"], 3),
                  "mean_stream_rtt_ms": round(s["mean_stream_rtt_ms"], 4)}
    tr["streamed_uplink_reduction"] = round(
        tr["cache_handoff"]["mean_uplink_kb"] /
        tr["streamed"]["mean_uplink_kb"], 2)
    result["transports"] = tr
    print(f"runtime/transports,0,uplink handoff="
          f"{tr['cache_handoff']['mean_uplink_kb']:.2f}kB streamed="
          f"{tr['streamed']['mean_uplink_kb']:.2f}kB "
          f"({tr['streamed_uplink_reduction']:.1f}x less) p50 handoff="
          f"{tr['cache_handoff']['latency_p50_ms']:.2f}ms streamed="
          f"{tr['streamed']['latency_p50_ms']:.2f}ms")
    # adaptive split under a load ramp: cloud starts 10x the edge, external
    # tenants ramp to 97% — the controller must push the split deeper as the
    # derated cloud drops below edge speed (load > 0.9)
    sc = dataclasses.replace(
        base, mode="split", wire_mode="int8", num_requests=64,
        arrival_rate=40.0, adapt=True, control_interval_s=0.02,
        cloud=JETSON_TX2.scaled(10, "cloud_slice"),
        background_load=ramp_load(0.0, 0.25, 0.0, 0.97))
    tel = Simulation(sc).run()
    traj = [{"t": round(d["t"], 3), "cloud_load": round(d["cloud_load"], 3),
             "split": d["split"]} for d in tel.split_trajectory()]
    result["adaptive"] = {
        "cloud_over_edge": 10.0,
        "trajectory": traj,
        "split_at_low_load": traj[0]["split"],
        "split_at_high_load": traj[-1]["split"],
        "moved_deeper_past_0.9": traj[-1]["split"] > traj[0]["split"],
    }
    print(f"runtime/adaptive,0,split "
          f"{traj[0]['split']}->{traj[-1]['split']} as load crosses 0.9")
    # multi-cell topology: jetson-class gateways on a 3g backhaul + phones
    # on home wifi, one cloud at 95% background load.  Device class is the
    # split-depth lever (the fast edge absorbs the congested cloud's work),
    # the radio is the transport/contention lever — so the per-cell
    # controllers must diverge.  The baseline forces the SAME fleet through
    # ONE shared 3g wire (a single wire group), which couples the cells'
    # contention and erases the wifi cell's advantage.
    cells = (CellSpec(name="3g-jet", network="3g", num_devices=4,
                      device="jetson"),
             CellSpec(name="wifi-ph", network="wifi", num_devices=4,
                      device="phone"))
    shared = tuple(dataclasses.replace(c, network="3g", wire="up0")
                   for c in cells)
    topo_base = dataclasses.replace(
        base, num_requests=48, prompt_len=64, max_new_tokens=8,
        adapt=True, transport="auto", control_interval_s=0.02,
        background_load=lambda t: 0.95)
    topo = {"spec": "3g:4xjetson + wifi:4xphone @ cloud load 0.95",
            "cells": {}}
    sim = Simulation(dataclasses.replace(topo_base, topology=cells))
    tel = sim.run()
    per_cell = tel.cell_summary()
    for cell in sim.cells:
        last = [d for d in tel.decisions if d.cell == cell.name][-1]
        row = per_cell[cell.name]
        topo["cells"][cell.name] = {
            "latency_p50_ms": round(row["latency_p50_ms"], 3),
            "mean_uplink_wait_ms": round(row["mean_uplink_wait_ms"], 3),
            "mean_mobile_energy_mj": round(row["mean_mobile_energy_mj"], 3),
            "final_split": last.new_split,
            "final_transport": last.transport,
        }
    fair = tel.fairness()
    topo["fairness"] = {k: round(v, 4) for k, v in fair.items()}
    finals = [(c["final_split"], c["final_transport"])
              for c in topo["cells"].values()]
    topo["controllers_diverged"] = finals[0] != finals[1]
    assert topo["controllers_diverged"], \
        f"per-cell controllers failed to diverge: {topo['cells']}"
    assert topo["cells"]["3g-jet"]["final_split"] > \
        topo["cells"]["wifi-ph"]["final_split"], \
        "3g cell did not settle on the deeper split"
    shared_tel = Simulation(dataclasses.replace(
        topo_base, topology=shared)).run()
    topo["shared_3g_wire"] = {
        "latency_p50_ms": round(shared_tel.summary()["latency_p50_ms"], 3),
        "fairness_jain": round(shared_tel.fairness()["jain_index"], 4),
    }
    topo["isolated_vs_shared_p50_speedup"] = round(
        shared_tel.summary()["latency_p50_ms"] /
        tel.summary()["latency_p50_ms"], 2)
    result["topology"] = topo
    # resilience: the same heterogeneous topology under a chaos schedule
    # (device churn, a 3g->wifi handover, a wire blackout, a cloud outage
    # window, a mid-run join) vs the calm run above — what availability and
    # tail latency survive, and how much migration/retry work it took
    chaos_cfg = dataclasses.replace(
        topo_base, topology=cells,
        faults="handover@0.05:3g-jet>wifi,blackout@0.08:wifi-ph+0.03,"
               "outage@0.12+0.1,leave@0.15:1,join@0.2:3g-jet")
    chaos = Simulation(chaos_cfg).run().summary()
    calm = tel.summary()
    result["resilience"] = {
        "faults": chaos_cfg.faults,
        "availability_pct": round(chaos["availability_pct"], 2),
        "latency_p99_ms": round(chaos["latency_p99_ms"], 3),
        "baseline_p99_ms": round(calm["latency_p99_ms"], 3),
        "n_migrated": int(chaos["n_migrated"]),
        "n_retried": int(chaos["n_retried"]),
        "n_failed": int(chaos["n_failed"]),
        "n_edge_fallback": int(chaos["n_fallback"]),
    }
    print(f"runtime/resilience,0,"
          f"avail={chaos['availability_pct']:.1f}% "
          f"p99={chaos['latency_p99_ms']:.2f}ms "
          f"(calm {calm['latency_p99_ms']:.2f}ms) "
          f"migrated={result['resilience']['n_migrated']} "
          f"retried={result['resilience']['n_retried']} "
          f"failed={result['resilience']['n_failed']}")
    # decode pipelining: per-token microbatch rotation on the 2-pod mesh
    # (serving/pipeline.make_decode_pipeline).  The CPU simulator cannot
    # time real cross-pod overlap, so the tick cadence comes from the same
    # profiled roofline the planner trusts: serial = edge + wire + cloud in
    # sequence, pipelined = max of the three (>= 2 in-flight microbatches).
    from repro.core.profiler import GTX_1080TI
    from repro.core.wireless import NETWORKS
    from repro.runtime.split_exec import CostModel

    cost = CostModel(cfg, JETSON_TX2, GTX_1080TI)
    dp_split, dp_dr, dp_net = 1, 16, "4g"
    link_bps = NETWORKS[dp_net].uplink_mbps * 1e6
    serial_s = cost.serial_decode_tick_s(dp_split, dp_dr, wire_mode="int8",
                                         link_bps=link_bps)
    pipe_s = cost.pipelined_decode_tick_s(dp_split, dp_dr, wire_mode="int8",
                                          link_bps=link_bps)
    row8 = cost.stream_row_bytes("int8", dp_dr)
    row4 = cost.stream_row_bytes("int4", dp_dr)
    scale_b = row8 - dp_dr                   # f32 scales, same either way
    dp = {
        "workload": {"split": dp_split, "d_r": dp_dr, "network": dp_net,
                     "num_microbatches": 2},
        "serial_tick_us": round(serial_s * 1e6, 2),
        "pipelined_tick_us": round(pipe_s * 1e6, 2),
        "tokens_per_s_serial": round(1.0 / serial_s, 1),
        "tokens_per_s_pipelined": round(1.0 / pipe_s, 1),
        "pipeline_speedup": round(serial_s / pipe_s, 3),
        "wire_row_bytes_int8": row8,
        "wire_row_bytes_int4": row4,
        "int4_code_reduction": round((row8 - scale_b) / (row4 - scale_b), 2),
        "int4_uplink_reduction": round(row8 / row4, 3),
    }
    assert dp["pipeline_speedup"] >= 1.5, dp
    assert dp["int4_code_reduction"] == 2.0, dp
    result["decode_pipeline"] = dp
    print(f"runtime/decode_pipeline,0,"
          f"serial={dp['serial_tick_us']:.1f}us "
          f"pipelined={dp['pipelined_tick_us']:.1f}us "
          f"speedup={dp['pipeline_speedup']:.2f}x "
          f"int4_row={row4:.0f}B vs int8_row={row8:.0f}B "
          f"({dp['int4_uplink_reduction']:.2f}x less)")
    # gateway: a 10^5-request Pareto-gap flash crowd on a cloud-bound
    # 2-pod topology (negligible inter-pod wire, 95% background tenants,
    # so the shared slot pool is the contended resource).  SLO-classed
    # shedding on vs off, same arrival trace: without admission control
    # the queue melts and interactive p99 is the whole backlog; with
    # "priority,shed" the batch class absorbs the shed and interactive
    # requests keep their SLO through the spike.
    gw_pods = (CellSpec(name="pod-jet", network="inter_pod", num_devices=4,
                        device="jetson"),
               CellSpec(name="pod-ph", network="inter_pod", num_devices=4,
                        device="phone"))
    gw_wl = WorkloadSpec(kind="flash", rate=6.0, n=100_000, alpha=1.5,
                         interactive=0.25, at=5.0, dur=30.0, burst=20.0)
    gw_policy = "priority,shed,slo=150/1000,reserve=1"
    gw_base = dataclasses.replace(
        base, topology=gw_pods, num_requests=0, max_new_tokens=16,
        max_concurrent=4, workload=gw_wl,
        background_load=lambda t: 0.95)
    gw_t0 = time.perf_counter()
    gw_off = Simulation(gw_base).run()
    gw_on = Simulation(dataclasses.replace(
        gw_base, gateway=gw_policy)).run()
    off_cls, on_cls = gw_off.class_summary(), gw_on.class_summary()
    on_sum = gw_on.summary()
    gw_speedup = round(off_cls["interactive"]["latency_p99_ms"] /
                       on_cls["interactive"]["latency_p99_ms"], 1)
    gw = {
        "workload": {"kind": gw_wl.kind, "rate": gw_wl.rate, "n": gw_wl.n,
                     "alpha": gw_wl.alpha, "interactive": gw_wl.interactive,
                     "at": gw_wl.at, "dur": gw_wl.dur, "burst": gw_wl.burst,
                     "policy": gw_policy},
        "interactive_p99_off_ms": round(
            off_cls["interactive"]["latency_p99_ms"], 3),
        "interactive_p99_on_ms": round(
            on_cls["interactive"]["latency_p99_ms"], 3),
        "batch_p99_off_ms": round(off_cls["batch"]["latency_p99_ms"], 3),
        "batch_p99_on_ms": round(on_cls["batch"]["latency_p99_ms"], 3),
        "shed_interactive_p99_speedup": gw_speedup,
        "n_shed": int(on_sum["n_shed"]),
        "n_shed_interactive": int(on_cls["interactive"]["n_shed"]),
        "wall_s_100k_pair": round(time.perf_counter() - gw_t0, 1),
    }
    # acceptance floor (ISSUE 9): shedding buys >= 3x interactive p99
    assert gw["shed_interactive_p99_speedup"] >= 3.0, gw
    assert on_sum["n_done"] + on_sum["n_failed"] + on_sum["n_shed"] == \
        gw_wl.n, on_sum
    assert gw["n_shed_interactive"] == 0, \
        f"shed fell on the protected class: {gw}"
    result["gateway"] = gw
    print(f"runtime/gateway,0,"
          f"int_p99_on={gw['interactive_p99_on_ms']:.1f}ms "
          f"int_p99_off={gw['interactive_p99_off_ms']:.1f}ms "
          f"speedup={gw_speedup:.0f}x shed={gw['n_shed']} "
          f"(interactive shed {gw['n_shed_interactive']}) "
          f"100k_pair={gw['wall_s_100k_pair']:.0f}s")
    us = (time.perf_counter() - t0) * 1e6
    print(f"runtime/topology,{us/15:.0f},"
          f"3g-jet=(s{topo['cells']['3g-jet']['final_split']},"
          f"{topo['cells']['3g-jet']['final_transport']}) "
          f"wifi-ph=(s{topo['cells']['wifi-ph']['final_split']},"
          f"{topo['cells']['wifi-ph']['final_transport']}) "
          f"jain={topo['fairness']['jain_index']} "
          f"shared_3g_p50={topo['shared_3g_wire']['latency_p50_ms']:.2f}ms "
          f"({topo['isolated_vs_shared_p50_speedup']}x slower than "
          f"per-cell radios)")
    # wire: the learned entropy-coded wire.  Part A prices the codec with
    # a trained per-channel prior (real encoder, held-out codes); Part B
    # replays one long-prompt 3g trace through the four wire/transport
    # configurations.  The trace runs a deeper model against a slow cloud
    # (12 layers, cloud at 0.5x edge) so prefill compute is substantial —
    # the regime where the progressive transport's upload/prefill overlap
    # pays; on shallow/fast-cloud workloads the 4-byte refinement header
    # is all you see.
    wt0 = time.perf_counter()
    codec = _wire_codec_report()
    wire_cfg = dataclasses.replace(cfg, num_layers=12)
    wire_arrivals = poisson_arrivals(num_devices=4, num_requests=24,
                                     arrival_rate=4.0, prompt_len=128,
                                     vocab_size=cfg.vocab_size, seed=0)
    wire_base = SimConfig(
        cfg=wire_cfg, mode="split", network="3g", num_devices=4,
        num_requests=24, arrival_rate=4.0, prompt_len=128, max_new_tokens=8,
        d_r=16, numerics=False, seed=0, edge=JETSON_TX2,
        cloud=JETSON_TX2.scaled(0.5, "cloud_slice"), arrivals=wire_arrivals)
    wire_modes = {}
    for label, wm, tp in (("int8", "int8", "streamed"),
                          ("int4", "int4", "streamed"),
                          ("entropy", "entropy", "streamed"),
                          ("entropy_progressive", "entropy", "progressive")):
        s = Simulation(dataclasses.replace(
            wire_base, wire_mode=wm, transport=tp)).run().summary()
        row = {"mean_wire_kb": round(s["mean_wire_kb"], 3),
               "ttft_p50_ms": round(s["ttft_p50_ms"], 3),
               "latency_p50_ms": round(s["latency_p50_ms"], 3)}
        if "compression_ratio" in s:
            row["compression_ratio"] = round(s["compression_ratio"], 3)
        wire_modes[label] = row
    prog_ttft_speedup = round(wire_modes["entropy"]["ttft_p50_ms"] /
                              wire_modes["entropy_progressive"]["ttft_p50_ms"],
                              3)
    wire = {"codec": codec,
            "workload": {"network": "3g", "prompt_len": 128,
                         "max_new_tokens": 8, "layers": 12, "cloud_x": 0.5,
                         "requests": 24},
            "modes": wire_modes,
            "progressive_ttft_p50_speedup": prog_ttft_speedup,
            "progressive_latency_p50_speedup": round(
                wire_modes["entropy"]["latency_p50_ms"] /
                wire_modes["entropy_progressive"]["latency_p50_ms"], 3)}
    # acceptance floors (ISSUE 10): >=2x coded bytes at <2% eval-loss
    # delta, and the overlap must actually buy first-token latency
    assert codec["entropy_bytes_reduction"] >= 2.0, codec
    assert codec["eval_loss_delta_pct"] < 2.0, codec
    assert prog_ttft_speedup > 1.0, wire
    result["wire"] = wire
    print(f"runtime/wire,{(time.perf_counter() - wt0) * 1e6 / 6:.0f},"
          f"codec={codec['entropy_bytes_per_token']:.1f}B/tok vs "
          f"int8={codec['int8_bytes_per_token']:.0f}B/tok "
          f"({codec['entropy_bytes_reduction']:.2f}x) "
          f"dloss={codec['eval_loss_delta_pct']:+.2f}% "
          f"prog_ttft={prog_ttft_speedup:.3f}x "
          f"prog_p50={wire['progressive_latency_p50_speedup']:.3f}x")
    print(f"runtime/json,0,{json.dumps(result, sort_keys=True)}")
    _append_runtime_artifact(result)


def bench_transport():
    """Decode-transport smoke (CI): tiny 2-layer config with real numerics,
    both transports on the identical arrival trace — greedy token streams
    must match each other and the hosted single-mesh reference exactly, the
    downlink must carry the sampled ids, and streamed uplink bytes must
    undercut the cache handoff.  Raises on any violation."""
    import dataclasses

    from repro.configs import get_config
    from repro.runtime.simulator import SimConfig, Simulation, poisson_arrivals

    cfg = dataclasses.replace(get_config("qwen3-8b").reduced(), num_layers=2)
    arrivals = poisson_arrivals(num_devices=2, num_requests=4,
                                arrival_rate=20.0, prompt_len=16,
                                vocab_size=cfg.vocab_size, seed=0)
    t0 = time.perf_counter()
    streams, sims, summaries = {}, {}, {}
    for tp in ("cache_handoff", "streamed"):
        sc = SimConfig(cfg=cfg, mode="split", wire_mode="int8", network="3g",
                       num_devices=2, num_requests=4, arrival_rate=20.0,
                       prompt_len=16, max_new_tokens=3, d_r=16, numerics=True,
                       max_concurrent=2, transport=tp, seed=0,
                       arrivals=arrivals)
        sim = Simulation(sc)
        tel = sim.run()
        sims[tp], summaries[tp] = sim, tel.summary()
        streams[tp] = {r.uid: list(r.engine_req.generated)
                       for r in sim.requests}
        assert summaries[tp]["total_downlink_kb"] > 0, \
            f"{tp}: downlink carried no sampled ids"
    assert streams["cache_handoff"] == streams["streamed"], \
        "transport parity violated: greedy streams differ"
    runner = sims["streamed"].bank.runner(1)
    eng = runner.make_engine(max_batch=2, max_len=24, seed=0)
    for req in sims["streamed"].requests:
        ref = eng.submit(req.tokens, max_new_tokens=3)
        eng.run()
        assert list(ref.generated) == streams["streamed"][req.uid], \
            f"uid {req.uid}: streamed != single-mesh reference"
    up_h = summaries["cache_handoff"]["mean_wire_kb"]
    up_s = summaries["streamed"]["mean_wire_kb"]
    assert up_s < up_h, "streamed did not reduce uplink bytes"
    us = (time.perf_counter() - t0) * 1e6
    print(f"transport/parity,{us/2:.0f},greedy_streams_match=3way "
          f"uplink handoff={up_h:.2f}kB streamed={up_s:.2f}kB")
    print(f"transport/downlink,0,"
          f"handoff={summaries['cache_handoff']['total_downlink_kb']*1e3:.0f}B "
          f"streamed={summaries['streamed']['total_downlink_kb']*1e3:.0f}B "
          f"rtt={summaries['streamed']['mean_stream_rtt_ms']:.2f}ms")


def _append_runtime_artifact(result: dict) -> None:
    """Append this run's runtime JSON to experiments/BENCH_runtime.json via
    the one writer in experiments/aggregate.py (which also renders it)."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "experiments"))
    from aggregate import append_runs
    append_runs([result])


BENCHES = {
    "fig7": bench_fig7,
    "bank": bench_bank,
    "runtime": bench_runtime,
    "transport": bench_transport,
    "wirebits": bench_wirebits,
    "table4": bench_table4,
    "table5": bench_table5,
    "sec3d": bench_sec3d,
    "wire": bench_wire,
    "roofline": bench_roofline,
    "micro": bench_micro,
}


def main() -> None:
    names = sys.argv[1:] or list(BENCHES)
    print("name,us_per_call,derived")
    for n in names:
        BENCHES[n]()


if __name__ == '__main__':
    main()
