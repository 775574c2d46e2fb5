#!/usr/bin/env python3
"""Chip benchmark of the split-serving path: one run of one cell.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine whose JAX finds a TPU with
at least the chips the cell asks for; anything else exits non-zero and
prints no result.  In order, a run:

1. places JAX's persistent compilation cache (``repro.compile_cache``);
2. draws the weights from the seed on the device and builds one
   ``SplitModelBank`` with them, its runner and a ``ServingEngine``;
3. warms up every shape the cell's traffic uses, through the same loop;
4. serves the cell's traffic for ``--seconds`` on the wall clock
   (``serve.py``); ``setup_s`` runs from the start of the process to the
   first request due;
5. with ``--trace 1``, traces slices spread over the window and reduces
   them to the cell's per-layer metrics (``metrics/<name>.py``);
6. frees the program and compares a seeded sample of the finished requests
   with the float32 reference (``reference.py``): ``correct`` holds when no
   served token's reference logit lies further below the reference's best
   than the cell's limit (``checks/<cell>.json``).

The last line of standard output is the result as one JSON object; the
numbers compared, with their limits, are the last lines of standard error
and the last key of that object.  Other counts go to standard error first.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from unittest import mock  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import spec  # noqa: E402
import traffic as T  # noqa: E402

# traced slices, as (start, end) shares of the window: spread over it, so
# that together they hold many blocks of the traffic and no single one
# decides a reading
TRACE_SLICES = ((0.10, 0.22), (0.30, 0.42), (0.50, 0.62), (0.70, 0.82))
TRACE_DIR = "benchmarks/chip/.trace"
HOST_SPANS = ("edge_half", "cloud_half", "submit", "pad_cache",
              "engine_step", "edge_step", "stream_step", "idle")


def keep_logs_in_checkout() -> None:
    """libtpu writes its logs under /tmp unless told otherwise; a run keeps
    everything it writes inside its checkout."""
    os.environ.setdefault("TPU_LOG_DIR", str(spec.HERE / ".tpu_logs"))


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def percentile(xs, q):
    import numpy as np
    return float(np.percentile(np.asarray(xs, np.float64), q))


# ------------------------------------------------------------------ devices
def devices(chips: int, require_tpu: bool) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_tpu and info["platform"] != "tpu":
        raise SystemExit(f"needs a TPU; JAX found {info['platform']!r}")
    if require_tpu and info["count"] < chips:
        raise SystemExit(f"needs {chips} chips; JAX found {info['count']}")
    return info


def memory_peak(chips: int) -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


class WindowEvents:
    """What JAX and Python did that a warm window should not need: XLA
    compilations, programs loaded from the persistent compilation cache,
    jaxpr traces and lowerings (a shape that warm-up missed shows in one of
    these), and garbage collections of the oldest generation, with their
    longest pause."""

    DURATIONS = {"/jax/core/compile/backend_compile_duration": "compiles",
                 "/jax/core/compile/jaxpr_trace_duration": "traces",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration":
                     "lowerings"}
    EVENTS = {"/jax/compilation_cache/cache_hits": "cache loads"}

    def __init__(self):
        import jax
        self.n = dict.fromkeys([*self.DURATIONS.values(),
                                *self.EVENTS.values(), "gc2"], 0)
        self.gc_max_s, self._gc_t = 0.0, None
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        gc.callbacks.append(self._gc)

    def _duration(self, event, duration, **kw):
        if event in self.DURATIONS:
            self.n[self.DURATIONS[event]] += 1

    def _event(self, event, **kw):
        if event in self.EVENTS:
            self.n[self.EVENTS[event]] += 1

    def _gc(self, phase, info):
        if info["generation"] != 2:
            return
        if phase == "start":
            self._gc_t = time.perf_counter()
        elif self._gc_t is not None:
            self.n["gc2"] += 1
            self.gc_max_s = max(self.gc_max_s,
                                time.perf_counter() - self._gc_t)

    def snapshot(self) -> dict:
        return dict(self.n)


# -------------------------------------------------------------------- build
def program_config(m):
    """The program's ``ModelConfig`` for dims ``m``, by their architecture."""
    return spec.arch_module(m.arch).program_config(m)


def build(cell, seed: int):
    """The program, holding the benchmark's weights: (runner, engine)."""
    import jax
    from repro.models import model as M
    from repro.runtime.split_exec import SplitModelBank

    import weights as W
    m, tr = cell.model, cell.traffic
    cfg = program_config(m)
    params, butterfly = W.program_tree(W.draw(seed, m))
    want = jax.eval_shape(lambda k: M.init_model(k, M.build(cfg))[0],
                          jax.random.key(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or \
            jax.tree.leaves(want) != jax.tree.leaves(got):
        raise RuntimeError("the benchmark's weights do not match the "
                           "program's parameter tree")
    # The bank draws its own weights in its constructor; it is given the
    # benchmark's instead, so that the reference can rebuild them.
    with mock.patch.object(M, "init_model", lambda key, built: (params, None)):
        bank = SplitModelBank(cfg, d_r=m.d_r, wire_bits=m.wire_bits,
                              wire_mode="int8", seed=0)
    runner = bank.runner(m.split)
    runner.params["butterfly"] = butterfly
    handoff = tr["transport"] == "cache_handoff"
    engine = runner.make_engine(max_batch=tr["slots"] if handoff else 1,
                                max_len=max_len(tr), seed=seed & 0x7FFFFFFF)
    return runner, engine


def max_len(tr: dict) -> int:
    n = tr["prompt"]["max"] + tr["output"]["max"] + 1
    return -(-n // 128) * 128


def warm_up(server, cell, seed: int, seconds: float) -> int:
    """Serve one short request of every prompt length the traffic can send,
    so that every program the window runs is compiled."""
    from serve import Open, Req
    m, tr = cell.model, cell.traffic
    reqs = [Req(-1 - k, 0.0, T.prompt_ids(seed, 10 ** 9 + k, n, m.vocab), 2)
            for k, n in enumerate(T.shapes(tr))]
    server.run(Open(reqs), None)
    return len(reqs)


def source(cell, seed: int, seconds: float):
    from serve import Open, Req
    m, tr = cell.model, cell.traffic
    due = T.arrivals(tr, seconds)
    return Open([Req(q.idx, float(t),
                     T.prompt_ids(seed, q.idx, q.prompt_len, m.vocab),
                     q.out_len)
                 for q, t in zip(T.requests(tr, seconds), due)])


# ------------------------------------------------------------------- window
class Tracer:
    """Starts and stops the profiler for each traced slice, on the serve
    loop's clock.  The loop's clock stands still while the profiler starts
    and stops, so that the traffic after a slice meets no backlog that the
    profiler made."""

    def __init__(self, server, logdir: Path, slices):
        self.server, self.logdir = server, logdir
        self.ann = None
        self.dirs = []
        for start, stop in slices:
            server.hooks += [(start, self.start), (stop, self.stop)]
        server.hooks.sort(key=lambda h: h[0])

    def _paused(fn):
        def wrapped(self):
            t = time.perf_counter()
            fn(self)
            self.server.t0 += time.perf_counter() - t
        return wrapped

    @_paused
    def start(self):
        import jax
        d = self.logdir / str(len(self.dirs))
        shutil.rmtree(d, ignore_errors=True)
        self.dirs.append(d)
        self.server.sync()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(d), profiler_options=opts)
        from devtrace import WINDOW
        self.ann = jax.profiler.TraceAnnotation(WINDOW)
        self.ann.__enter__()
        self.server.recording = True

    @_paused
    def stop(self):
        import jax
        self.server.sync()
        self.server.recording = False
        self.ann.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def read(self):
        """The slices' traces as one, and the directories removed."""
        import devtrace
        t = devtrace.Trace.merge([devtrace.load(str(d), HOST_SPANS)
                                  for d in self.dirs])
        shutil.rmtree(self.logdir, ignore_errors=True)
        return t


def token_gaps(reqs, seconds: float) -> list:
    """Every gap between consecutive tokens that reached a client in the
    window, in seconds."""
    gaps = []
    for r in reqs:
        seen = [t for t in r.tok_times if t <= seconds]
        gaps += [b - a for a, b in zip(seen, seen[1:])]
    return gaps


def end_to_end(reqs, seconds: float) -> dict:
    """The user-facing numbers over every request due in the window."""
    ttft, tokens = [], 0
    for r in reqs:
        seen = [t for t in r.tok_times if t <= seconds]
        ttft.append((seen[0] if seen else seconds) - r.due)
        tokens += len(seen)
    gaps = token_gaps(reqs, seconds)
    out = {"ttft_p90_ms": percentile(ttft, 90) * 1e3,
           "output_tokens_per_s": tokens / seconds}
    if gaps:
        out["itl_p95_ms"] = percentile(gaps, 95) * 1e3
    return out


def sample(reqs, check: dict, seed: int) -> list:
    """The finished requests to compare: the longest, then others in seeded
    order until ``min_tokens`` served tokens or ``max_requests``."""
    import numpy as np
    done = [r for r in reqs if r.done]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.prompt) + r.out_len, -r.idx))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([seed, 5]).permutation(len(rest))
    out = [longest]
    for i in order:
        if sum(r.out_len for r in out) >= check["min_tokens"] or \
                len(out) >= check["max_requests"]:
            break
        out.append(rest[i])
    return out


# -------------------------------------------------------------- per layer
def load_reader(name: str):
    path = HERE / "metrics" / f"{name}.py"
    s = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class Context:
    """What a per-layer reader is given: the traced slice, the program calls
    made in it, the model's dims, the chip's peaks and the transport."""
    trace: object
    calls: list
    model: object
    peaks: dict
    transport: str


def per_layer(cell, ctx, root: Path) -> dict:
    units = {m["name"]: m["unit"]
             for m in json.loads((root / "BENCHMARK.json").read_text())[
                 "per_layer"]}
    out = {}
    for name in spec.per_layer_metrics(cell.name, root):
        value = load_reader(name)(ctx)
        if value is None:
            log(f"per-layer {name}: nothing to read in this trace")
            continue
        out[name] = {"value": value, "unit": units[name]}
    return out


# --------------------------------------------------------------------- run
def build_server(cell, seed: int, *, require_tpu: bool = True,
                 annotate: bool = False):
    """Check the devices, place the compilation cache and build the program
    behind a serve loop: (device info, server)."""
    import jax
    info = devices(cell.chips, require_tpu)
    if require_tpu:
        from repro.compile_cache import configure_compile_cache
        configure_compile_cache()
        # every program the window runs is cached, however fast it compiles
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from serve import Server
    tr = cell.traffic
    runner, engine = build(cell, seed)
    return info, Server(runner, engine, transport=tr["transport"],
                        link=spec.link(tr["link"]), slots=tr["slots"],
                        max_len=max_len(tr), annotate=annotate)


def serve_window(cell, seed: int, seconds: float, *, trace: bool = False,
                 require_tpu: bool = True, fault=None, root: Path = spec.ROOT):
    """Set-up and window of one run.  Returns a dict of what was measured,
    with the requests and, for a traced run, the trace's context."""
    info, server = build_server(cell, seed, require_tpu=require_tpu,
                                annotate=trace)
    runner, engine = server.runner, server.engine
    events = WindowEvents()
    tr = cell.traffic
    n_warm = warm_up(server, cell, seed, seconds)
    if fault is not None:
        fault(server)
    src = source(cell, seed, seconds)
    bank = runner.bank
    before = (bank.cache_hits, bank.cache_misses, engine.decode_steps,
              events.snapshot())
    tracer = None
    if trace:
        tracer = Tracer(server, root / TRACE_DIR,
                        [(a * seconds, b * seconds) for a, b in TRACE_SLICES])
    server.slowest.clear()
    server.t0 = time.perf_counter()
    setup_s = server.t0 - T_START
    reqs = server.run(src, seconds)
    reqs += src.pop_due(seconds)            # due, but not yet taken up
    after = (bank.cache_hits, bank.cache_misses, engine.decode_steps,
             events.snapshot())
    info["memory_peak_bytes"] = memory_peak(cell.chips)
    client = end_to_end(reqs, seconds)
    lag = [r.picked - r.due for r in reqs if r.picked is not None]
    log(f"counts: warm-up requests {n_warm}; window requests due "
        f"{len(reqs)}, finished {sum(r.done for r in reqs)}, tokens "
        f"{sum(len(r.tok_times) for r in reqs)}; bank cache hits "
        f"{after[0] - before[0]} misses {after[1] - before[1]}; engine "
        f"decode steps {after[2] - before[2]}")
    log("in window: " + ", ".join(f"{k} {after[3][k] - before[3][k]}"
                                  for k in after[3])
        + f"; longest gc2 pause {events.gc_max_s * 1e3:.3f} ms")
    if lag:
        log(f"generator lag: p50 {percentile(lag, 50) * 1e3:.3f} ms, max "
            f"{max(lag) * 1e3:.3f} ms")
    log("client: " + ", ".join(f"{k} {v}" for k, v in client.items()))
    gaps = token_gaps(reqs, seconds)
    if gaps:
        log(f"token gaps: {len(gaps)}, ms at percentiles " + ", ".join(
            f"p{q} {percentile(gaps, q) * 1e3:.3f}"
            for q in (50, 80, 90, 93, 95, 97, 99)))
    log("longest loop turns (s, at s, edge prefills, cloud prefills, "
        "decoding): " + "; ".join(
            f"{d:.4f} at {t:.3f}: {a}, {b}, {c}"
            for d, t, a, b, c in sorted(server.slowest, reverse=True)))
    out = {"info": info, "reqs": reqs, "setup_s": setup_s,
           "metrics": client}
    if trace:
        out["ctx"] = Context(trace=tracer.read(), calls=server.calls,
                             model=cell.model,
                             peaks=spec.peaks(info["kind"]),
                             transport=tr["transport"])
    del server, runner, engine, bank, src
    gc.collect()
    return out


def compare(cell, seed: int, reqs, *, control: bool = False):
    """(widest gap, tokens compared, requests over the limit) on a seeded
    sample of the finished requests."""
    import numpy as np

    import reference
    picked = sample(reqs, cell.check, seed)
    if not picked:
        return None, 0, 0
    seqs = [(r.prompt, np.asarray(r.toks[:r.out_len], np.int32))
            for r in picked]
    gaps = reference.token_gaps(seed, cell.model, seqs, control=control)
    limit = cell.check["max_logit_gap"]
    return (float(max(g.max() for g in gaps)), sum(len(g) for g in gaps),
            sum(int(g.max() > limit) for g in gaps))


def run(cell, seed: int, seconds: float, trace: bool, *,
        require_tpu: bool = True, fault=None, root: Path = spec.ROOT) -> dict:
    w = serve_window(cell, seed, seconds, trace=trace,
                     require_tpu=require_tpu, fault=fault, root=root)
    result = {"attempted": len(w["reqs"]), "device": w["info"]}
    if trace:
        ctx = w["ctx"]
        result["metrics"] = per_layer(cell, ctx, root)
        result["device"]["busy_s"] = ctx.trace.busy_s()
        result["device"]["window_s"] = ctx.trace.window_s
        result["breakdown"] = {"device_ops": ctx.trace.top_ops(),
                               "idle_gaps": ctx.trace.idle_gaps()}
    else:
        measured = dict(w["metrics"], setup_s=w["setup_s"])
        result["metrics"] = {
            k: {"value": measured[k], "unit": unit}
            for k, unit in spec.end_to_end_metrics(cell.name, root).items()
            if k in measured}
    gap, n_tokens, failed = compare(cell, seed, w["reqs"])
    limit = cell.check["max_logit_gap"]
    log(f"compared {n_tokens} served tokens with the float32 reference")
    correct = gap is not None and gap <= limit
    result["correct"] = correct
    result["failed"] = failed
    result["checks"] = {"max_logit_gap": {"value": gap, "limit": limit}}
    log(f"check max_logit_gap: {gap} (limit {limit})")
    return result


def emit(result: dict) -> None:
    keys = ["correct", "attempted", "failed", "metrics", "device",
            "breakdown", "checks"]
    print(json.dumps({k: result[k] for k in keys if k in result}),
          flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    keep_logs_in_checkout()
    sys.path.insert(0, str(spec.ROOT / "src"))
    cell = spec.load_cell(args.workload)
    emit(run(cell, args.seed, args.seconds, bool(args.trace)))


if __name__ == "__main__":
    main()
