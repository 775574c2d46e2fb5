"""Decode transports: streamed per-token rows vs stage-0 cache handoff.

Covers the Wire's new downlink (FIFO contention per direction or shared),
the wireless downlink models, transport parity (streamed greedy token
streams must be bitwise-identical to cache handoff and the hosted
single-mesh reference for every wire mode), the flat-uplink regression
(streamed uplink bytes must not grow with prompt length beyond the prefill
codes, while handoff bytes do), and (split, transport) co-selection in the
planner and the closed-loop controller."""
import dataclasses

import numpy as np
import pytest

from repro.configs import get_config
from repro.core import quantization
from repro.core.costs import TOKEN_BYTES
from repro.core.planner import select_split_online
from repro.core.profiler import GTX_1080TI, JETSON_TX2
from repro.core.wireless import INTER_POD, NETWORKS
from repro.runtime.simulator import (SimConfig, Simulation, poisson_arrivals)
from repro.runtime.transports import get_transport
from repro.runtime.wire import Wire


def small_cfg(layers=4):
    return dataclasses.replace(get_config("qwen3-8b").reduced(),
                               num_layers=layers)


def timing_cfg(**kw):
    defaults = dict(cfg=small_cfg(), mode="split", wire_mode="int8",
                    network="3g", num_devices=4, num_requests=16,
                    arrival_rate=20.0, prompt_len=32, max_new_tokens=4,
                    d_r=16, numerics=False, seed=0)
    defaults.update(kw)
    return SimConfig(**defaults)


# ---------------------------------------------------------------------------
# wire: downlink + duplex contention
# ---------------------------------------------------------------------------


def test_wireless_downlink_models():
    net = NETWORKS["3g"]
    # asymmetric: 3.15 Mbps down vs 1.1 Mbps up
    assert net.downlink_seconds(1e6) == pytest.approx(8.0 / 3.15)
    assert net.downlink_seconds(1e6) < net.uplink_seconds(1e6)
    # downlink radio power uses the MobiSys'12 alpha_d
    assert net.downlink_power_mw() == pytest.approx(
        122.12 * 3.15 + 817.88)
    assert net.downlink_energy_mj(1000) > 0
    # the interconnect is symmetric
    assert INTER_POD.downlink_seconds(1e9) == INTER_POD.uplink_seconds(1e9)


def test_downlink_fifo_contention_and_stats():
    net = NETWORKS["3g"]
    w = Wire(net)                          # duplex="split": independent FIFOs
    dur = net.downlink_seconds(10_000)
    s1, d1 = w.transfer_down(10_000, 0.0)
    s2, d2 = w.transfer_down(10_000, 0.0)  # same instant: must queue
    assert (s1, d1) == (0.0, pytest.approx(dur))
    assert s2 == pytest.approx(d1) and d2 == pytest.approx(2 * dur)
    assert w.down_stats.wait_s == pytest.approx(dur)
    assert w.down_stats.bytes_sent == 20_000
    assert w.down_stats.energy_mj == pytest.approx(
        2 * net.downlink_energy_mj(10_000))
    # split duplex: the uplink frontier is untouched by downlink traffic
    su, du = w.transfer(1000, 0.0)
    assert su == 0.0
    # rtt combines both directions at nominal rates
    assert w.rtt_s(1000, 4) == pytest.approx(
        net.uplink_seconds(1000) + net.downlink_seconds(4))


def test_shared_duplex_serializes_both_directions():
    net = NETWORKS["3g"]
    w = Wire(net, duplex="shared")
    _, d_up = w.transfer(10_000, 0.0)
    s_dn, d_dn = w.transfer_down(4, 0.0)   # must wait for the uplink drain
    assert s_dn == pytest.approx(d_up)
    s_up2, _ = w.transfer(100, 0.0)        # and vice versa
    assert s_up2 == pytest.approx(d_dn)


# ---------------------------------------------------------------------------
# scheduler semantics (timing-only)
# ---------------------------------------------------------------------------


def test_streamed_traces_complete_and_breakdown_sums():
    sim = Simulation(timing_cfg(transport="streamed"))
    tel = sim.run()
    assert len(tel.traces) == 16
    for t in tel.traces:
        assert t.transport == "streamed"
        assert sum(t.breakdown().values()) == pytest.approx(t.latency_s,
                                                            abs=1e-12)
        assert t.downlink_bytes == TOKEN_BYTES * t.new_tokens
        assert t.new_tokens == 4
        assert t.stream_steps == 3            # per token after the first
        assert t.stream_rtt_s > 0
        assert t.t_arrival <= t.t_edge_start <= t.t_edge_done \
            <= t.t_uplink_start <= t.t_uplink_done <= t.t_cloud_start \
            <= t.t_first_token <= t.t_cloud_done <= t.t_done
    # every decode step crossed the wire: prefill + (T-1) rows per request
    assert sim.uplink.stats.n_transfers == 16 * 4
    assert tel.counters["stream_rows"] == 16 * 3


def test_handoff_downlink_ships_ids_once():
    tel = Simulation(timing_cfg(transport="cache_handoff")).run()
    for t in tel.traces:
        assert t.transport == "cache_handoff"
        assert t.downlink_bytes == TOKEN_BYTES * t.new_tokens
        assert t.stream_steps == 0
        # batch return: the mobile's first token arrives with the last, so
        # TTFT is stamped at delivery — same observation point as streamed
        assert t.t_first_token == t.t_done
        assert sum(t.breakdown().values()) == pytest.approx(t.latency_s,
                                                            abs=1e-12)


def test_streamed_uplink_flat_in_prompt_len():
    """The regression the transport exists for: past the prefill codes,
    streamed uplink bytes must not grow with prompt length, while the
    cache handoff's stage-0 KV bytes grow linearly."""
    totals = {}
    for tp in ("cache_handoff", "streamed"):
        for S in (32, 128):
            tel = Simulation(timing_cfg(transport=tp, prompt_len=S,
                                        num_requests=8)).run()
            totals[(tp, S)] = sum(t.wire_bytes for t in tel.traces)
    wire = quantization.Wire("int8", 8, 16)
    codes_delta = 8 * (wire.nbytes(small_cfg(), 128) -
                       wire.nbytes(small_cfg(), 32))
    stream_growth = totals[("streamed", 128)] - totals[("streamed", 32)]
    handoff_growth = totals[("cache_handoff", 128)] - \
        totals[("cache_handoff", 32)]
    assert stream_growth == pytest.approx(codes_delta)      # codes only
    assert handoff_growth > 4 * stream_growth               # + KV cache
    assert totals[("streamed", 128)] < totals[("cache_handoff", 128)]


def test_streamed_deterministic_replay():
    a = Simulation(timing_cfg(transport="streamed")).run()
    b = Simulation(timing_cfg(transport="streamed")).run()
    ka = [(t.uid, t.t_done, t.wire_bytes, t.downlink_bytes) for t in a.traces]
    kb = [(t.uid, t.t_done, t.wire_bytes, t.downlink_bytes) for t in b.traces]
    assert ka == kb


def test_shared_arrival_trace_is_identical_across_transports():
    arr = poisson_arrivals(num_devices=4, num_requests=16, arrival_rate=20.0,
                           prompt_len=32, seed=0)
    t_h = Simulation(timing_cfg(transport="cache_handoff", arrivals=arr)).run()
    t_s = Simulation(timing_cfg(transport="streamed", arrivals=arr)).run()
    assert [(t.uid, t.device, round(t.t_arrival, 12)) for t in t_h.traces] \
        == [(t.uid, t.device, round(t.t_arrival, 12)) for t in t_s.traces]
    # and the default (builder-less) path produces the same trace
    t_d = Simulation(timing_cfg(transport="cache_handoff")).run()
    assert [round(t.t_arrival, 12) for t in t_d.traces] \
        == [round(t.t_arrival, 12) for t in t_h.traces]


# ---------------------------------------------------------------------------
# transport selection (planner + controller)
# ---------------------------------------------------------------------------


def test_planner_scores_transport_pairs():
    cfg = small_cfg()
    cost_kw = dict(candidate_splits=[1, 2, 3], edge=JETSON_TX2,
                   cloud=GTX_1080TI, wire_mode="int8",
                   link_bytes_per_s=NETWORKS["3g"].uplink_mbps * 1e6 / 8,
                   downlink_bytes_per_s=NETWORKS["3g"]._down_mbps * 1e6 / 8,
                   transports=("cache_handoff", "streamed"))
    # long prompt, long generation, heavy per-layer handoff bytes: the KV
    # shipment dominates and streaming wins
    best, rows = select_split_online(
        cfg, 512, 16, new_tokens=32, handoff_bytes_per_layer=2e5, **cost_kw)
    assert len(rows) == 6                    # (split x transport) pairs
    assert best["transport"] == "streamed"
    # single-token requests tie on decode cost: handoff (listed first) wins
    best, _ = select_split_online(
        cfg, 32, 16, new_tokens=1, handoff_bytes_per_layer=0.0, **cost_kw)
    assert best["transport"] == "cache_handoff"
    # short prompt + tiny handoff vs many RTTs on a slow downlink: handoff
    slow = dict(cost_kw, downlink_bytes_per_s=50.0)
    best, _ = select_split_online(
        cfg, 4, 16, new_tokens=32, handoff_bytes_per_layer=16.0, **slow)
    assert best["transport"] == "cache_handoff"


def test_controller_auto_picks_streamed_for_long_prompts():
    sc = timing_cfg(transport="auto", adapt=True, prompt_len=128,
                    max_new_tokens=8, num_requests=8, control_interval_s=0.02)
    sim = Simulation(sc)
    tel = sim.run()
    assert tel.decisions
    assert all(d.transport == "streamed" for d in tel.decisions), \
        "128-token 3g prompts: the KV handoff should always lose"
    # requests arriving after the first decision carry the picked transport
    t0 = tel.decisions[0].t
    picked = {t.transport for t in tel.traces if t.t_arrival > t0}
    assert picked == {"streamed"}


def test_get_transport_registry():
    assert get_transport("streamed").streams_tokens
    assert not get_transport("cache_handoff").streams_tokens
    with pytest.raises(KeyError):
        get_transport("carrier_pigeon")


# ---------------------------------------------------------------------------
# end-to-end numerics parity (real jax)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("wire_mode", ["raw", "reduced", "int8"])
def test_streamed_matches_handoff_and_reference(wire_mode):
    """Greedy token streams must be bitwise-identical across the streamed
    transport, the cache handoff, and the hosted single-mesh engine."""
    cfg = small_cfg(layers=2)
    arr = poisson_arrivals(num_devices=2, num_requests=3, arrival_rate=20.0,
                           prompt_len=12, vocab_size=cfg.vocab_size, seed=1)
    streams, sims = {}, {}
    for tp in ("cache_handoff", "streamed"):
        sc = SimConfig(cfg=cfg, mode="split", wire_mode=wire_mode,
                       network="3g", num_devices=2, num_requests=3,
                       arrival_rate=20.0, prompt_len=12, max_new_tokens=3,
                       d_r=16, numerics=True, max_concurrent=2, transport=tp,
                       seed=1, arrivals=arr)
        sims[tp] = Simulation(sc)
        sims[tp].run()
        streams[tp] = {r.uid: list(r.engine_req.generated)
                       for r in sims[tp].requests}
        assert all(len(s) == 3 for s in streams[tp].values())
    assert streams["cache_handoff"] == streams["streamed"]
    runner = sims["streamed"].bank.runner(1)
    eng = runner.make_engine(max_batch=2, max_len=20, seed=0)
    for req in sims["streamed"].requests:
        ref = eng.submit(req.tokens, max_new_tokens=3)
        eng.run()
        assert list(ref.generated) == streams["streamed"][req.uid], wire_mode


def test_engine_single_slot_stream_entry():
    """submit_streamed + stream_step reproduce the engine's own decode for
    one request, and engines of a split share the compiled stream step."""
    from repro.runtime.split_exec import SplitModelBank

    cfg = small_cfg(layers=2)
    bank = SplitModelBank(cfg, 16, seed=0)
    r = bank.runner(1)
    toks = np.random.default_rng(5).integers(
        0, cfg.vocab_size, size=(1, 10)).astype(np.int32)
    payload, scales, c0 = r.edge_half(r.params, toks)
    logits, c1 = r.cloud_half(r.params, payload, scales)

    eng = r.make_engine(max_batch=2, max_len=20, seed=0)
    ref = eng.submit(toks[0], max_new_tokens=4)
    eng.run()

    sreq = eng.submit_streamed(10, logits[0], max_new_tokens=4)
    edge_cache = r.pad_decode_cache(c0, 0, 20)
    cloud_cache = r.pad_decode_cache(c1, 1, 20)
    pos = 10
    while not sreq.done:
        tok = np.asarray([[sreq.generated[-1]]], np.int32)
        row, sc_, edge_cache = r.edge_step(r.params, tok, edge_cache, [pos])
        _, cloud_cache = eng.stream_step(sreq, cloud_cache, row, sc_, pos)
        pos += 1
    assert sreq.generated == ref.generated
    # the jitted stream step is shared across engines of the split
    eng2 = r.make_engine(max_batch=1, max_len=20, seed=0)
    assert eng._stream_step is eng2._stream_step
    # streamed admissions hold no cache-pool slot
    assert eng.num_active == 0


def test_streamed_e2e_numerics_traces():
    cfg = small_cfg(layers=2)
    sc = SimConfig(cfg=cfg, mode="split", wire_mode="int8", network="wifi",
                   num_devices=2, num_requests=4, arrival_rate=20.0,
                   prompt_len=16, max_new_tokens=3, d_r=16, numerics=True,
                   max_concurrent=2, transport="streamed", seed=0)
    sim = Simulation(sc)
    tel = sim.run()
    assert len(tel.traces) == 4
    for t in tel.traces:
        assert t.new_tokens == 3
        assert t.stream_steps == 2
        assert t.downlink_bytes == 3 * TOKEN_BYTES
        assert sum(t.breakdown().values()) == pytest.approx(t.latency_s,
                                                            abs=1e-12)
    assert tel.counters["stream_rows"] == 8
    assert tel.counters["stream_edge_steps"] == 8
    # per-token edge/cloud steps landed in the bank's compile cache
    kinds = {k[0] for k in sim.bank.jit_cache_keys}
    assert {"edge_step", "cloud_step"} <= kinds
    # cloud slots drained; engine pool untouched by streamed requests
    assert sim.server.num_active == 0
    for eng in sim.server._engines.values():
        assert eng.num_active == 0


@pytest.mark.parametrize("transport", ["cache_handoff", "streamed",
                                       "progressive"])
def test_record_logits_reaches_every_decode_step(transport):
    """SimConfig.record_logits keeps one finite logits row per generated
    token on each request, and greedy decoding picked each row's argmax."""
    cfg = small_cfg(layers=2)
    sc = SimConfig(cfg=cfg, mode="split", wire_mode="int8", network="wifi",
                   num_devices=2, num_requests=3, arrival_rate=20.0,
                   prompt_len=12, max_new_tokens=3, d_r=16, numerics=True,
                   max_concurrent=2, transport=transport, seed=2,
                   record_logits=True)
    sim = Simulation(sc)
    sim.run()
    for req in sim.requests:
        er = req.engine_req
        rows = np.stack([np.asarray(r, np.float32)
                         for r in er.logits_history])
        assert rows.shape == (3, cfg.vocab_size)
        assert np.isfinite(rows).all()
        assert rows.argmax(-1).tolist() == list(er.generated)
