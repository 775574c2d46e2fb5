"""Split-serving runtime: deterministic scheduler, uplink contention math,
slot reuse, adaptive split control, and end-to-end numerics (the split path
must reproduce the single-mesh forward up to f32 rounding, and the emitted
greedy tokens exactly)."""
import dataclasses

import numpy as np
import pytest

from repro.configs import get_config
from repro.core import quantization
from repro.core.planner import select_split_online
from repro.core.profiler import JETSON_TX2
from repro.core.wireless import NETWORKS, get_link
from repro.runtime.clock import EventLoop
from repro.runtime.simulator import SimConfig, Simulation, ramp_load
from repro.runtime.telemetry import percentile
from repro.runtime.wire import Wire


def small_cfg(layers=4):
    return dataclasses.replace(get_config("qwen3-8b").reduced(),
                               num_layers=layers)


def timing_cfg(**kw):
    defaults = dict(cfg=small_cfg(), mode="split", wire_mode="int8",
                    network="3g", num_devices=4, num_requests=16,
                    arrival_rate=20.0, prompt_len=32, max_new_tokens=1,
                    d_r=16, numerics=False, seed=0)
    defaults.update(kw)
    return SimConfig(**defaults)


# ---------------------------------------------------------------------------
# event loop
# ---------------------------------------------------------------------------


def test_event_loop_orders_by_time_then_fifo():
    loop = EventLoop()
    order = []
    loop.schedule_at(2.0, lambda: order.append("late"))
    loop.schedule_at(1.0, lambda: order.append("a"))
    loop.schedule_at(1.0, lambda: order.append("b"))     # tie: FIFO
    loop.schedule_at(0.5, lambda: order.append("first"))
    loop.run()
    assert order == ["first", "a", "b", "late"]
    assert loop.now == 2.0


def test_event_loop_rejects_past_and_nested_schedules_run():
    loop = EventLoop()
    seen = []
    loop.schedule_at(1.0, lambda: loop.schedule(0.5, lambda: seen.append(2)))
    loop.schedule_at(1.2, lambda: seen.append(1))
    loop.run()
    assert seen == [1, 2]
    with pytest.raises(ValueError):
        loop.schedule_at(0.0, lambda: None)              # now == 1.5


# ---------------------------------------------------------------------------
# wire / contention
# ---------------------------------------------------------------------------


def test_uplink_contention_serializes_transfers():
    net = NETWORKS["3g"]
    up = Wire(net)
    nbytes = 11_000                       # 11kB over 1.1Mbps = 80ms
    dur = net.uplink_seconds(nbytes)
    s1, d1 = up.transfer(nbytes, 0.0)
    s2, d2 = up.transfer(nbytes, 0.0)     # same instant: must queue
    s3, d3 = up.transfer(nbytes, d2)      # after drain: immediate
    assert (s1, d1) == (0.0, pytest.approx(dur))
    assert s2 == pytest.approx(d1) and d2 == pytest.approx(2 * dur)
    assert s3 == pytest.approx(d2) and d3 == pytest.approx(3 * dur)
    assert up.stats.wait_s == pytest.approx(dur)          # only transfer 2
    assert up.stats.busy_s == pytest.approx(3 * dur)
    assert up.stats.bytes_sent == 3 * nbytes
    # goodput includes the queueing: 3B over (3*dur busy + dur wait)
    assert up.stats.energy_mj == pytest.approx(
        3 * net.uplink_energy_mj(nbytes))
    assert up.observed_bytes_per_s(d3) == pytest.approx(
        3 * nbytes / (4 * dur))


def test_get_link_names():
    assert get_link("3g").uplink_mbps == 1.1
    assert get_link("inter_pod").uplink_seconds(50e9) == pytest.approx(1.0)
    with pytest.raises(KeyError):
        get_link("5g")


def test_percentile_linear_interpolation():
    xs = [1.0, 2.0, 3.0, 4.0]
    assert percentile(xs, 50) == pytest.approx(2.5)
    assert percentile(xs, 100) == 4.0
    assert percentile(xs, 0) == 1.0
    assert percentile([7.0], 99) == 7.0


# ---------------------------------------------------------------------------
# scheduler semantics (timing-only mode)
# ---------------------------------------------------------------------------


def test_traces_complete_and_breakdown_sums():
    sim = Simulation(timing_cfg(max_new_tokens=4))
    tel = sim.run()
    assert len(tel.traces) == 16
    for t in tel.traces:
        parts = sum(t.breakdown().values())
        assert parts == pytest.approx(t.latency_s, abs=1e-12)
        assert t.t_arrival <= t.t_edge_start <= t.t_edge_done \
            <= t.t_uplink_start <= t.t_uplink_done <= t.t_cloud_start \
            <= t.t_first_token <= t.t_done


def test_deterministic_replay():
    t1 = Simulation(timing_cfg(max_new_tokens=3)).run()
    t2 = Simulation(timing_cfg(max_new_tokens=3)).run()
    a = [(t.uid, t.t_arrival, t.t_done, t.wire_bytes) for t in t1.traces]
    b = [(t.uid, t.t_arrival, t.t_done, t.wire_bytes) for t in t2.traces]
    assert a == b


def test_cloud_slots_bounded_and_reused():
    # instant wire + congested cloud: payloads pile up against 2 slots
    sc = timing_cfg(network="inter_pod", num_devices=8, num_requests=24,
                    arrival_rate=500.0, max_new_tokens=8, max_concurrent=2,
                    background_load=lambda t: 0.9)
    sim = Simulation(sc)
    tel = sim.run()
    assert len(tel.traces) == 24
    assert sim.server.peak_active <= 2
    slots_used = {s for _, s in sim.server.slot_history}
    assert slots_used == {0, 1}                       # both slots exercised
    reuse_counts = [sum(1 for _, s in sim.server.slot_history if s == k)
                    for k in (0, 1)]
    assert all(c >= 2 for c in reuse_counts)          # ... more than once
    assert len(sim.server.slot_history) == 24


def test_device_queue_is_serial():
    # one device, instantaneous uplink contention aside: edge starts are
    # spaced by at least the edge compute duration
    sc = timing_cfg(num_devices=1, num_requests=8, arrival_rate=1e5)
    tel = Simulation(sc).run()
    ts = sorted((t.t_edge_start, t.t_edge_done) for t in tel.traces)
    for (s0, d0), (s1, _) in zip(ts, ts[1:]):
        assert s1 >= d0 - 1e-15


# ---------------------------------------------------------------------------
# the paper's comparisons
# ---------------------------------------------------------------------------


def test_int8_wire_beats_raw_offload_on_3g():
    int8 = Simulation(timing_cfg(wire_mode="int8")).run().summary()
    raw = Simulation(timing_cfg(wire_mode="raw")).run().summary()
    cloud = Simulation(timing_cfg(mode="cloud")).run().summary()
    assert int8["latency_p50_ms"] < raw["latency_p50_ms"] / 10
    assert int8["latency_p50_ms"] < cloud["latency_p50_ms"] / 10
    assert int8["mean_mobile_energy_mj"] < cloud["mean_mobile_energy_mj"]
    assert int8["mean_wire_kb"] < cloud["mean_wire_kb"] / 10


def test_wire_mode_bytes_ordering():
    cfg = small_cfg()
    raw, red, q = (quantization.Wire(m, 8, 16).nbytes(cfg, 32)
                   for m in ("raw", "reduced", "int8"))
    assert q < red < raw
    assert q == 32 * 16 + 32 * 4                      # codes + f32 scales


# ---------------------------------------------------------------------------
# adaptive split control
# ---------------------------------------------------------------------------


def test_online_selection_moves_deeper_with_load():
    cfg = small_cfg()
    edge = JETSON_TX2
    cloud = edge.scaled(10)
    link = NETWORKS["3g"].uplink_mbps * 1e6 / 8
    picks = []
    for load in (0.0, 0.5, 0.89, 0.95, 0.975):
        best, rows = select_split_online(
            cfg, 32, 16, candidate_splits=[1, 2, 3], edge=edge, cloud=cloud,
            link_bytes_per_s=link, cloud_load=load)
        picks.append(best["split"])
        assert len(rows) == 3
    assert picks[0] == 1                              # idle cloud: shallow
    assert picks == sorted(picks)                     # monotone in load
    assert picks[-1] == 3                             # congested: deep


def test_controller_moves_split_past_090():
    sc = timing_cfg(num_requests=64, arrival_rate=40.0, adapt=True,
                    control_interval_s=0.02,
                    cloud=JETSON_TX2.scaled(10, "cloud_slice"),
                    background_load=ramp_load(0.0, 0.25, 0.0, 0.97))
    tel = Simulation(sc).run()
    assert tel.decisions, "controller never ran"
    low = [d.new_split for d in tel.decisions if d.cloud_load < 0.5]
    high = [d.new_split for d in tel.decisions if d.cloud_load > 0.93]
    assert low and high
    assert max(low) < min(high)                       # strictly deeper
    # and requests admitted after the move actually carry the deeper split
    deep = {t.split for t in tel.traces if t.t_arrival > 0.3}
    assert deep and min(deep) > 1


# ---------------------------------------------------------------------------
# end-to-end numerics (real jax)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def numerics_sim():
    sc = SimConfig(cfg=small_cfg(layers=2), mode="split", wire_mode="int8",
                   network="3g", num_devices=2, num_requests=4,
                   arrival_rate=20.0, prompt_len=16, max_new_tokens=3,
                   d_r=16, numerics=True, max_concurrent=2, seed=0)
    sim = Simulation(sc)
    tel = sim.run()
    return sim, tel


def test_e2e_split_prefill_matches_reference(numerics_sim):
    import jax.numpy as jnp
    sim, tel = numerics_sim
    runner = sim.bank.runner(1)
    for req in sim.requests:
        payload, scales, _ = runner.edge_half(runner.params,
                                              req.tokens[None])
        logits, _ = runner.cloud_half(runner.params, payload, scales)
        ref, _ = runner.reference_prefill(req.tokens[None])
        # jit (split halves) vs eager (reference) differ only in f32 rounding
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(ref[:, -1]),
                                   rtol=1e-4, atol=1e-4)
        # the token the runtime actually emitted == greedy argmax of the
        # reference single-mesh forward
        assert req.engine_req.generated[0] == int(jnp.argmax(ref[0, -1]))


def test_bank_holds_one_backbone_param_copy():
    """Materializing every candidate split must not copy the backbone: each
    runner's param dict shares the bank's leaves by identity, and the unique
    parameter bytes across all runners stay within the tiny per-split
    butterfly overhead of a single model's footprint."""
    import jax
    from repro.runtime.split_exec import SplitModelBank

    bank = SplitModelBank(small_cfg(layers=4), 16, seed=0)
    runners = [bank.runner(s) for s in bank.candidates]
    assert len(runners) == 3

    backbone_ids = {id(l) for l in jax.tree.leaves(bank.params)}
    backbone_bytes = sum(l.nbytes for l in jax.tree.leaves(bank.params))
    seen, total = set(), 0
    for r in runners:
        # the stages/embed/norm subtrees ARE the bank's objects, not copies
        assert r.params["stages"] is bank.params["stages"]
        assert r.params["embed"] is bank.params["embed"]
        for leaf in jax.tree.leaves(r.params):
            if id(leaf) not in seen:
                seen.add(id(leaf))
                total += leaf.nbytes
    butterfly_bytes = total - backbone_bytes
    assert backbone_ids <= seen
    # 3 splits x (d*d_r + d_r*d) f32 — well under 10% of one backbone
    assert butterfly_bytes < 0.1 * backbone_bytes
    d = bank.base_cfg.d_model
    assert butterfly_bytes == 3 * 2 * d * 16 * 4


@pytest.mark.parametrize("wm", ["raw", "reduced", "int8", "int4",
                                "entropy"])
def test_cache_injection_parity_all_wire_modes(wm):
    """Edge half -> wire -> cloud half -> submit_prefilled must reproduce
    the single-mesh reference forward (logits) and the engine's own
    full-prefill decode (tokens) for every wire mode."""
    import jax.numpy as jnp
    from repro.runtime.split_exec import SplitModelBank

    rng = np.random.default_rng(7)
    toks = rng.integers(0, 512, size=(1, 16)).astype(np.int32)
    bank = SplitModelBank(small_cfg(layers=2), 16, wire_mode=wm, seed=0)
    r = bank.runner(1)
    payload, scales, c0 = r.edge_half(r.params, toks)
    logits, c1 = r.cloud_half(r.params, payload, scales)
    ref, _ = r.reference_prefill(toks)
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(ref[:, -1]),
                               rtol=1e-4, atol=1e-4, err_msg=wm)
    # inject the handed-off caches and decode greedily ...
    eng = r.make_engine(max_batch=2, max_len=24, seed=0)
    inj = eng.submit_prefilled(16, [c0, c1], logits[0], max_new_tokens=4)
    eng.run()
    # ... and compare against the same engine prefilling from scratch
    ref_req = eng.submit(toks[0], max_new_tokens=4)
    eng.run()
    assert inj.generated[0] == int(jnp.argmax(ref[0, -1])), wm
    assert inj.generated == ref_req.generated, wm


def test_bank_unaligned_boundary_peels_units():
    """xLSTM alternates mlstm/slstm in 2-layer repeat units, so odd splits
    land inside a unit: the range view must peel only the unaligned edges
    (keeping the stacked middle) and still match the reference forward.
    Recurrent state also disables seq bucketing — shapes stay exact."""
    from repro.models.transformer import range_segments
    from repro.runtime.split_exec import SplitModelBank

    cfg = dataclasses.replace(get_config("xlstm-125m").reduced(),
                              num_layers=4)
    bank = SplitModelBank(cfg, 16, seed=0)
    assert not bank._seq_bucket_ok
    segs = list(bank.built.stages[0])
    assert [(len(s.unit), s.repeats) for s in segs] == [(2, 2)]
    # split 1: peel layer 0 | peel layer 1 + slice repeats [1, 2)
    assert [(len(s.unit), s.repeats)
            for s in range_segments(segs, 0, 1)] == [(1, 1)]
    assert [(len(s.unit), s.repeats)
            for s in range_segments(segs, 1, 4)] == [(1, 1), (2, 1)]

    toks = np.random.default_rng(3).integers(
        0, cfg.vocab_size, size=(1, 12)).astype(np.int32)
    for split in bank.candidates:
        r = bank.runner(split)
        payload, scales, c0 = r.edge_half(r.params, toks)
        logits, c1 = r.cloud_half(r.params, payload, scales)
        ref, _ = r.reference_prefill(toks)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(ref[:, -1]),
                                   rtol=1e-4, atol=1e-4, err_msg=str(split))


def test_submit_prefilled_boundary_prompt_len():
    """prompt_len == max_len - 1 is admissible: the first decode step writes
    the cache's last row, then the position guard retires the request."""
    from repro.runtime.split_exec import SplitModelBank

    bank = SplitModelBank(small_cfg(layers=2), 16, seed=0)
    r = bank.runner(1)
    toks = np.arange(16, dtype=np.int32)[None]
    payload, scales, c0 = r.edge_half(r.params, toks)
    logits, c1 = r.cloud_half(r.params, payload, scales)
    eng = r.make_engine(max_batch=1, max_len=17, seed=0)   # prompt_len + 1
    req = eng.submit_prefilled(16, [c0, c1], logits[0], max_new_tokens=8)
    eng.run()
    assert req.done
    assert len(req.generated) == 2          # first token + one decode step
    with pytest.raises(AssertionError):
        eng.submit_prefilled(17, [c0, c1], logits[0])      # == max_len


def test_engines_share_compiled_decode_step(numerics_sim):
    """Every engine of one bank split reuses the same jitted decode+sample
    step (the bank's compile cache, not a per-engine jit)."""
    sim, tel = numerics_sim
    r = sim.bank.runner(1)
    e1 = r.make_engine(max_batch=2, max_len=32)
    e2 = r.make_engine(max_batch=4, max_len=32)
    assert e1._step is e2._step
    assert tel.counters["engine_decode_steps"] > 0
    assert tel.counters["bank_jit_cache_entries"] > 0


def test_e2e_decode_runs_and_traces_close(numerics_sim):
    sim, tel = numerics_sim
    assert len(tel.traces) == 4
    for t in tel.traces:
        assert t.new_tokens == 3
        assert t.wire_bytes > 0
        assert sum(t.breakdown().values()) == pytest.approx(t.latency_s,
                                                            abs=1e-12)
    for req in sim.requests:
        assert req.engine_req.done
        assert len(req.engine_req.generated) == 3
    # every engine drained its slots (they were reused, not leaked)
    for eng in sim.server._engines.values():
        assert eng.num_active == 0
