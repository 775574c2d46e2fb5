"""The program's spans in a traced run (``spans.py``): its readers on a
hand-made slice whose answers are worked out by hand, and a traced run of a
tiny cell on the CPU, whose slices hold the program's spans with their args
while the trace that run.py's readers see is the same as without them."""
import pytest

import devtrace
import run
import spans
import tiny

MS = 1_000_000      # ns
SEED = 2 ** 31 + 13


def hand_trace():
    # window 0-10 ms; device busy 1-3, 4-8 and 9-10 (clipped from 9-11), so
    # idle 0-1, 3-4 and 8-9
    return devtrace.Trace.build(
        (0, 10 * MS),
        [["jit_edge_step", 1 * MS, 3 * MS], ["jit_step", 4 * MS, 8 * MS],
         ["jit_step", 9 * MS, 11 * MS]],
        [["fusion.1", 1 * MS, 3 * MS, ""], ["fusion.2", 4 * MS, 8 * MS, ""],
         ["fusion.3", 9 * MS, 11 * MS, ""]],
        [["trace_window", 0, 10 * MS], ["edge_step", 0, 1.2 * MS],
         ["idle", 3 * MS, 3.2 * MS], ["stream_step", 3.2 * MS, 4.5 * MS],
         ["stream_step", 8.5 * MS, 9.5 * MS]])


def ms(a, b):
    return int(a * MS), int(b * MS)


# one edge step and two streamed cloud steps, each with its dispatch and
# the second and third with their sync
STREAMED = [
    ("split.edge_step", *ms(0, 1.2), {"pos": 5}),
    ("dispatch", *ms(0.2, 0.6), {"kind": "edge_step", "miss": 0}),
    ("engine.stream_step", *ms(3.2, 4.5), {"uid": 0, "pos": 5}),
    ("dispatch", *ms(3.3, 3.5), {"kind": "engine_stream_step"}),
    ("sync", *ms(3.6, 4.4), {}),
    ("engine.stream_step", *ms(8.5, 9.5), {"uid": 0, "pos": 6}),
    ("dispatch", *ms(8.6, 8.7), {"kind": "engine_stream_step"}),
    ("sync", *ms(8.8, 9.4), {}),
]


def test_readers_by_hand():
    slices = [(hand_trace(), STREAMED)]
    got = spans.read(slices, "streamed")
    # (0.4 + 0.2 + 0.1) ms of dispatch over two streamed steps
    assert got["dispatch_ms.stream"] == pytest.approx(0.35)
    # idle under a span: 0-1, 3.2-4 and 8.5-9 of the 10 ms
    assert got["host_bound_pct.stream"] == pytest.approx(23.0)
    # by innermost span, with the part that fell inside a running program
    # (none here: every gap lies between two programs)
    assert got["idle_s_by_span"] == {
        k: [pytest.approx(v), 0] for k, v in {
            "split.edge_step": 0.0006, "dispatch": 0.0007, "sync": 0.0006,
            "engine.stream_step": 0.0004, spans.LOOP: 0.0007}.items()}
    assert "dispatch_ms.decode" not in got


def test_batched_readers_by_hand():
    batched = [
        ("engine.step", *ms(0, 1), {"active": 3, "slots": 4}),
        ("dispatch", *ms(0.1, 0.4), {"kind": "engine_step"}),
        ("sync", *ms(0.5, 1), {}),
        ("engine.step", *ms(3, 4), {"active": 1, "slots": 4}),
        ("dispatch", *ms(3.1, 3.2), {"kind": "engine_step"}),
        ("sync", *ms(3.3, 4), {}),
    ]
    got = spans.read([(hand_trace(), batched)], "cache_handoff")
    assert got["dispatch_ms.decode"] == pytest.approx(0.2)
    assert got["host_bound_pct.batched"] == pytest.approx(20.0)
    assert got["batch_fill"] == pytest.approx(0.5)
    assert got["idle_s_by_span"][spans.LOOP][0] == pytest.approx(0.001)
    assert "dispatch_ms.stream" not in got


def test_slices_add_up():
    one = spans.read([(hand_trace(), STREAMED)], "streamed")
    two = spans.read([(hand_trace(), STREAMED)] * 2, "streamed")
    assert two["dispatch_ms.stream"] == pytest.approx(
        one["dispatch_ms.stream"])
    assert two["host_bound_pct.stream"] == pytest.approx(
        one["host_bound_pct.stream"])
    assert two["idle_s_by_span"] == {
        k: [pytest.approx(2 * v[0]), pytest.approx(2 * v[1])]
        for k, v in one["idle_s_by_span"].items()}


def test_host_bound_is_part_of_idle():
    t = hand_trace()
    ctx = run.Context(trace=t, calls=[], model=tiny.MODEL, peaks={},
                      transport="streamed")
    idle = run.load_reader("idle_pct.stream")(ctx)
    got = spans.read([(t, STREAMED)], "streamed")
    assert got["host_bound_pct.stream"] <= idle
    # what no program span holds is the rest of the idle time
    assert got["host_bound_pct.stream"] + 100 * got["idle_s_by_span"][
        spans.LOOP][0] / t.window_s == pytest.approx(idle)


def test_idle_between_the_ops_of_a_running_program():
    # the edge step's program runs 1-3 ms with a gap between its two ops,
    # 1.5-2 ms, while the host waits in sync
    t = devtrace.Trace.build(
        (0, 4 * MS), [["jit_edge_step", 1 * MS, 3 * MS]],
        [["fusion.1", 1 * MS, 1.5 * MS, ""], ["fusion.2", 2 * MS, 3 * MS, ""]],
        [["trace_window", 0, 4 * MS]])
    sp = [("engine.stream_step", *ms(0, 4), {"uid": 0, "pos": 1}),
          ("dispatch", *ms(0, 1), {"kind": "engine_stream_step"}),
          ("sync", *ms(1, 4), {})]
    got = spans.read([(t, sp)], "streamed")["idle_s_by_span"]
    assert got["dispatch"] == [pytest.approx(0.001), 0]
    assert got["sync"] == [pytest.approx(0.0015), pytest.approx(0.0005)]


def test_a_program_without_spans_reads_nothing():
    for transport in ("streamed", "cache_handoff"):
        assert spans.read([(hand_trace(), [])], transport) == {}


def test_innermost_cuts_a_child_at_its_parent():
    pieces = spans.innermost([("a", 0, 10, {}), ("b", 2, 12, {}),
                              ("c", 20, 30, {})])
    assert pieces == [(0, 2, "a"), (2, 10, "b"), (20, 30, "c")]


@pytest.mark.parametrize("transport", ["streamed", "cache_handoff"])
def test_traced_run_reads_the_program_spans(tmp_path, monkeypatch,
                                            transport):
    monkeypatch.setattr(run.spec, "peaks", lambda kind: {})   # no CPU peaks
    slices = []
    monkeypatch.setattr(run, "Tracer", spans.program_tracer(slices))
    w = run.serve_window(tiny.cell(transport), SEED, 1.0, trace=True,
                         require_tpu=False, root=tmp_path)
    assert len(slices) == len(run.TRACE_SLICES)
    names = {n for _, sp in slices for n, *_ in sp}
    steps = {"streamed": {"split.edge_step", "engine.stream_step"},
             "cache_handoff": {"engine.step"}}[transport]
    assert steps | {"dispatch", "sync"} <= names
    for trace, sp in slices:
        (t0, t1), = trace.windows
        assert all(t0 <= s < t1 for _, s, _, _ in sp)
        # run.py's readers see the loop's spans alone
        assert {h[0] for h in trace.host} <= {devtrace.WINDOW,
                                              *run.HOST_SPANS}
    if transport == "cache_handoff":
        steps = [a for _, sp in slices for n, _, _, a in sp
                 if n == "engine.step"]
        assert all(1 <= a["active"] <= a["slots"] == 4 for a in steps)
    got = spans.read(slices, transport)
    kind = "stream" if transport == "streamed" else "decode"
    assert got[f"dispatch_ms.{kind}"] > 0
    pct = "host_bound_pct." + ("stream" if kind == "stream" else "batched")
    window = sum(t.window_s for t, _ in slices)
    assert w["ctx"].trace.window_s == pytest.approx(window)
    idle = 100 * (1 - w["ctx"].trace.busy_s() / window)
    assert 0 < got[pct] <= idle + 1e-9
