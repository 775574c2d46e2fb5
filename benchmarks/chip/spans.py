#!/usr/bin/env python3
"""Where the device idles, by the program's own spans: one traced run of a
cell, read also for the spans the program opens on the profiler's clock
(``repro/<name>``, listed in ``repro.runtime.tracing``).

    python3 benchmarks/chip/spans.py --workload <cell> --seed <n> \\
        --seconds <s>

The run is ``run.py``'s with ``--trace 1``.  Before its traced slices are
removed, each is also read for every host event whose name starts with
``repro/``, with its args; a span counts in the slice in which it starts.
From them, for the cell's transport:

- ``dispatch_ms.stream``: time inside ``dispatch`` spans of kinds
  ``edge_step`` and ``engine_stream_step`` per ``engine.stream_step``
  (streamed transport);
- ``dispatch_ms.decode``: time inside ``dispatch`` spans of kind
  ``engine_step`` per ``engine.step`` (cache handoff);
- ``host_bound_pct.stream`` / ``host_bound_pct.batched``: the device's idle
  time in the slices that lies under some program span, over the slices'
  time: idle that the program's host code holds, as against the serve
  loop's own waiting;
- the device's idle seconds by the innermost program span over them (its
  self time: ``dispatch``, ``sync``, ``engine.sample``, ... ; ``(loop)``
  where no program span is open), each with the part of it that fell
  between the ops of a device program while it ran, and the mean
  ``active`` over ``slots`` of the batched steps.

These go to standard error after the run's own lines, and under the key
``program`` of the last line of standard output, which is otherwise
``run.py``'s result.  A program that opens no spans reads none of them.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from collections import defaultdict
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import devtrace  # noqa: E402
import run  # noqa: E402
import spec  # noqa: E402

PREFIX = "repro/"
LOOP = "(loop)"         # idle under no program span


def load(logdir: str, window) -> list:
    """``(name, start, end, args)`` of the program's spans that start inside
    ``window``, with the prefix dropped, in order of start."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {logdir}, found {files}")
    t0, t1 = window
    out = []
    for plane in ProfileData.from_file(files[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX) and t0 <= ev.start_ns < t1:
                    out.append((ev.name[len(PREFIX):], ev.start_ns,
                                ev.end_ns, dict(ev.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def idle_gaps(trace) -> list:
    """The windows of a slice's trace less its device-busy time."""
    gaps = []
    busy = trace.busy_intervals()
    for t0, t1 in trace.windows:
        t = t0
        for s, e in busy:
            if e <= t0 or s >= t1:
                continue
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < t1:
            gaps.append((t, t1))
    return gaps


def _overlaps(gaps, pieces) -> list:
    """The overlap with ``gaps`` of each of ``pieces``, both sorted lists of
    disjoint intervals (a piece may carry a name after its bounds)."""
    out, j = [], 0
    for s, e, *_ in pieces:
        while j < len(gaps) and gaps[j][1] <= s:
            j += 1
        tot, k = 0, j
        while k < len(gaps) and gaps[k][0] < e:
            tot += min(e, gaps[k][1]) - max(s, gaps[k][0])
            k += 1
        out.append(tot)
    return out


def innermost(spans) -> list:
    """Disjoint ``(start, end, name)`` pieces of the time under the spans,
    each named after the innermost span open then.  Spans of one thread
    nest; a child is cut at its parent's end."""
    out, stack, t = [], [], None        # stack of (end, name)
    for name, s, e, _ in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][0] <= s:
            end, top = stack.pop()
            if t < end:
                out.append((t, end, top))
                t = end
        if stack and t < s:
            out.append((t, s, stack[-1][1]))
        stack.append((min(e, stack[-1][0]) if stack else e, name))
        t = s
    while stack:
        end, top = stack.pop()
        if t < end:
            out.append((t, end, top))
            t = end
    return out


# ------------------------------------------------------------------ readers
# Each reader takes the slices, as a list of (trace, spans) with the trace
# of devtrace.load and the spans of load(), and returns None where the
# slices hold nothing to read.

def dispatch_ms(slices, kinds, per: str):
    """Milliseconds inside ``dispatch`` spans of ``kinds``, per ``per``
    span."""
    n = secs = 0
    for _, spans in slices:
        n += sum(1 for name, *_ in spans if name == per)
        secs += sum(e - s for name, s, e, a in spans
                    if name == "dispatch" and a.get("kind") in kinds) * 1e-9
    if not n:
        return None
    return 1e3 * secs / n


def _intersect(a, b) -> list:
    """The intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def idle_by_span(slices) -> dict:
    """The device's idle seconds by the innermost program span over them,
    each as ``[idle, of it between the ops of a running device program]``:
    the second part is idle that a program's own schedule leaves, not
    idle that the host leaves before the program starts."""
    tot = defaultdict(lambda: [0, 0])
    for trace, spans in slices:
        gaps = idle_gaps(trace)
        inside = _intersect(gaps, _union((s, e) for _, s, e in trace.modules))
        pieces = innermost(spans)
        for key, part in ((0, gaps), (1, inside)):
            held = _overlaps(part, pieces)
            for (_, _, name), ns in zip(pieces, held):
                tot[name][key] += ns
            tot[LOOP][key] += sum(e - s for s, e in part) - sum(held)
    if not any(spans for _, spans in slices):
        return {}
    return {k: [v[0] * 1e-9, v[1] * 1e-9]
            for k, v in sorted(tot.items(), key=lambda kv: -kv[1][0])}


def host_bound_pct(slices):
    """Share of the slices' time in which the device idled while the host
    was inside a program span."""
    window = sum(trace.window_s for trace, _ in slices)
    idle = idle_by_span(slices)
    if window <= 0 or not idle:
        return None
    return 100.0 * sum(a for name, (a, _) in idle.items()
                       if name != LOOP) / window


def batch_fill(slices):
    """Mean share of the slots that held a request, over the batched
    steps."""
    fill = [a["active"] / a["slots"] for _, spans in slices
            for name, _, _, a in spans if name == "engine.step"]
    return sum(fill) / len(fill) if fill else None


def read(slices, transport: str) -> dict:
    """The metrics of a cell with ``transport``, each left out where it
    reads nothing, and the idle seconds by span."""
    if transport == "streamed":
        values = {"dispatch_ms.stream": dispatch_ms(
                      slices, ("edge_step", "engine_stream_step"),
                      "engine.stream_step"),
                  "host_bound_pct.stream": host_bound_pct(slices)}
    else:
        values = {"dispatch_ms.decode": dispatch_ms(
                      slices, ("engine_step",), "engine.step"),
                  "host_bound_pct.batched": host_bound_pct(slices),
                  "batch_fill": batch_fill(slices)}
    out = {k: v for k, v in values.items() if v is not None}
    idle = idle_by_span(slices)
    if idle:
        out["idle_s_by_span"] = idle
    return out


# --------------------------------------------------------------------- run
def program_tracer(slices: list):
    """A ``run.Tracer`` that also appends each traced slice to ``slices``,
    as ``(trace, spans)``, before the slices are removed."""
    class Tracer(run.Tracer):
        def read(self):
            for d in self.dirs:
                t = devtrace.load(str(d), run.HOST_SPANS)
                slices.append((t, load(str(d), t.windows[0])))
            return super().read()
    return Tracer


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    run.keep_logs_in_checkout()
    sys.path.insert(0, str(spec.ROOT / "src"))
    cell = spec.load_cell(args.workload)
    slices = []
    run.Tracer = program_tracer(slices)
    result = run.run(cell, args.seed, args.seconds, True)
    program = read(slices, cell.traffic["transport"])
    for k, v in program.items():
        if k == "idle_s_by_span":
            run.log("device idle by innermost program span (s; in brackets, "
                    "of it between the ops of a running device program): "
                    + ", ".join(f"{name} {a} ({b})"
                                for name, (a, b) in v.items()))
        else:
            run.log(f"program {k}: {v}")
    print(json.dumps(dict(result, program=program)), flush=True)


if __name__ == "__main__":
    main()
