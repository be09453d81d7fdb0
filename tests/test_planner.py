"""Placement planner + device-resident hot path (docs/PLANNER.md).

Covers the PR-6 acceptance contract:

* cost-model decisions are pure and deterministic (pinned inputs ->
  pinned outputs, monotone in RTT / host rate);
* ``.with_placement('device'|'host'|'auto')`` on the TPU builders pins
  or delegates the lane, results are lane-independent, and the
  resolution lands in the stats JSON (``Placements``);
* the adaptive x2 / /2 batch resize converges on scripted latency
  traces (win_seq_gpu.hpp:574-592 analogue);
* the parallel zero-copy feed plane (ingest/feed.py) conserves every
  tuple and every window across feeder counts, through the graph
  (FeedSource) and channel-free (ParallelColumnFeeder) paths;
* per-launch device timing (``Device_time_ms``, launches, bytes per
  launch) is recorded for placed engines without tracing.
"""
import json
import threading

import numpy as np
import pytest

import windflow_tpu as wf
from windflow_tpu.core.tuples import TupleBatch
from windflow_tpu.graph.planner import (PlacementInputs, decide_placement,
                                        launch_profile, plan_window_operator,
                                        select_strategy)
from windflow_tpu.ingest.feed import FeedSource, ParallelColumnFeeder
from windflow_tpu.operators.basic_ops import Sink
from windflow_tpu.operators.batch_ops import BatchSource
from windflow_tpu.operators.tpu.win_seq_tpu import (AdaptiveBatcher,
                                                    WinSeqTPU,
                                                    WinSeqTPULogic)

N_KEYS = 8
WIN, SLIDE = 64, 32


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def batch_source(n, sb=4096, vmod=97):
    state = {"i": 0}

    def fn(ctx):
        i = state["i"]
        if i >= n:
            return None
        m = min(sb, n - i)
        idx = np.arange(i, i + m)
        ids = idx // N_KEYS
        state["i"] = i + m
        return TupleBatch({"key": idx % N_KEYS, "id": ids, "ts": ids,
                           "value": (idx % vmod).astype(np.float64)})

    return fn


def window_dict_sink():
    res = {}
    lock = threading.Lock()

    def sink(item):
        if item is None:
            return
        with lock:
            if isinstance(item, TupleBatch):
                for j in range(len(item)):
                    res[(int(item.key[j]), int(item.id[j]))] = \
                        float(item["value"][j])
            else:
                res[(item.key, item.id)] = item.value

    return res, sink


def expected_windows(n, vmod=97):
    """Host oracle: per-key TB sliding sums over the dense stream."""
    idx = np.arange(n)
    out = {}
    for k in range(N_KEYS):
        vals = (idx[idx % N_KEYS == k] % vmod).astype(np.float64)
        ids = idx[idx % N_KEYS == k] // N_KEYS
        hi = int(ids.max())
        w = 0
        while w * SLIDE + WIN <= hi + 1:
            lo, end = w * SLIDE, w * SLIDE + WIN
            out[(k, w)] = float(vals[(ids >= lo) & (ids < end)].sum())
            w += 1
        # EOS fires the opened partial windows too
        while w * SLIDE <= hi:
            lo = w * SLIDE
            out[(k, w)] = float(vals[ids >= lo].sum())
            w += 1
    return out


def run_graph(n, placement, env=None, monkeypatch=None, **op_kwargs):
    if env:
        for k, v in env.items():
            monkeypatch.setenv(k, v)
    res, sink = window_dict_sink()
    g = wf.PipeGraph(f"plan_{placement}", wf.Mode.DEFAULT)
    op = WinSeqTPU("sum", WIN, SLIDE, wf.WinType.TB, batch_len=128,
                   emit_batches=True, placement=placement, **op_kwargs)
    g.add_source(BatchSource(batch_source(n))).add(op).add_sink(Sink(sink))
    g.run()
    return res, g


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------

def test_decision_deterministic():
    inp = PlacementInputs(rtt_floor_ms=70.0, host_rate_tps=50e6,
                          tuples_per_launch=4096 * 2048,
                          bytes_per_launch=20_000)
    d1, d2 = decide_placement(inp), decide_placement(inp)
    assert d1 == d2
    assert d1["placement"] in ("device", "host")


def test_decision_monotone_in_rtt():
    base = dict(host_rate_tps=50e6, tuples_per_launch=4096 * 2048,
                bytes_per_launch=20_000)
    fast = decide_placement(PlacementInputs(rtt_floor_ms=0.1, **base))
    slow = decide_placement(PlacementInputs(rtt_floor_ms=10_000.0, **base))
    assert fast["placement"] == "device"
    assert slow["placement"] == "host"


def test_decision_monotone_in_host_rate():
    base = dict(rtt_floor_ms=10.0, tuples_per_launch=4096 * 64,
                bytes_per_launch=20_000)
    weak = decide_placement(PlacementInputs(host_rate_tps=1e3, **base))
    strong = decide_placement(PlacementInputs(host_rate_tps=1e12, **base))
    assert weak["placement"] == "device"
    assert strong["placement"] == "host"


def test_small_launches_behind_long_rtt_go_host():
    """The VERDICT scenario: application-family configs whose windows
    fire in dribbles behind a ~70 ms launch floor must not stay on
    device."""
    inp = PlacementInputs(rtt_floor_ms=70.0, host_rate_tps=60e6,
                          tuples_per_launch=256 * 16,  # tiny batches
                          bytes_per_launch=4_000)
    assert decide_placement(inp)["placement"] == "host"


def test_launch_profile_scales_with_params():
    a = WinSeqTPULogic("sum", 4096, 2048, wf.WinType.TB, batch_len=4096)
    b = WinSeqTPULogic("sum", 4096, 2048, wf.WinType.TB, batch_len=64)
    ta, _ = launch_profile(a)
    tb, _ = launch_profile(b)
    assert ta == 4096 * 2048 and tb == 64 * 2048


# ---------------------------------------------------------------------------
# strategy selection (decision table)
# ---------------------------------------------------------------------------

def test_strategy_table():
    # associative + long panes -> pane decomposition
    assert select_strategy("sum", 4096, 2048, 64) == "pane_farm"
    assert select_strategy("count", 1 << 18, 1 << 17, 1000) == "pane_farm"
    # heavy overlap, panes too short to pre-reduce -> incremental tree
    assert select_strategy("max", 1024, 1, 1) == "ffat"
    # custom combine, many keys -> key-sharded farm
    assert select_strategy(lambda *a: 0.0, 100, 7, 64) == "key_farm"
    # single key, huge windows, custom combine -> window parallelism
    assert select_strategy(lambda *a: 0.0, 1 << 17, 7, 1) == "win_farm"
    # nothing to exploit -> single engine
    assert select_strategy(lambda *a: 0.0, 100, 7, 1) == "win_seq"
    with pytest.raises(ValueError):
        select_strategy("sum", 0, 1)


def test_plan_window_operator_builds_selected():
    from windflow_tpu.operators.tpu.farms_tpu import KeyFarmTPU, PaneFarmTPU
    op = plan_window_operator("sum", 4096, 2048, wf.WinType.TB,
                              key_cardinality=64)
    assert isinstance(op, PaneFarmTPU)
    op = plan_window_operator(lambda *a: 0.0, 100, 7, wf.WinType.TB,
                              key_cardinality=64, parallelism=3)
    assert isinstance(op, KeyFarmTPU)
    assert op.parallelism == 3


# ---------------------------------------------------------------------------
# placement override + lane equivalence + stats JSON
# ---------------------------------------------------------------------------

N_EVENTS = 120_000


def test_placement_pins_and_auto(monkeypatch):
    res_dev, g_dev = run_graph(N_EVENTS, "device")
    res_host, g_host = run_graph(N_EVENTS, "host")
    want = expected_windows(N_EVENTS)
    assert set(res_dev) == set(want) == set(res_host)
    for k in want:
        assert res_dev[k] == pytest.approx(want[k], rel=1e-5)
        assert res_host[k] == pytest.approx(want[k], rel=1e-5)
    assert g_dev.placements[0]["placement"] == "device"
    assert g_dev.placements[0]["reason"] == "pinned"
    assert g_host.placements[0]["placement"] == "host"

    # auto, forced both ways through the measured-input overrides
    res_a, g_a = run_graph(
        N_EVENTS, "auto", monkeypatch=monkeypatch,
        env={"WINDFLOW_RTT_FLOOR_MS": "1000",
             "WINDFLOW_HOST_RATE_TPS": "1e12"})
    assert g_a.placements[0]["placement"] == "host"
    res_b, g_b = run_graph(
        N_EVENTS, "auto", monkeypatch=monkeypatch,
        env={"WINDFLOW_RTT_FLOOR_MS": "0.01",
             "WINDFLOW_HOST_RATE_TPS": "1"})
    assert g_b.placements[0]["placement"] == "device"
    for k in want:  # identical results whichever lane wins
        assert res_a[k] == pytest.approx(want[k], rel=1e-5)
        assert res_b[k] == pytest.approx(want[k], rel=1e-5)
    # the decision record carries the measured inputs it was made from
    assert g_a.placements[0]["rtt_floor_ms"] == 1000
    assert g_a.placements[0]["host_rate_tps"] == 1e12


def test_auto_decision_deterministic_per_process(monkeypatch):
    monkeypatch.setenv("WINDFLOW_RTT_FLOOR_MS", "50")
    monkeypatch.setenv("WINDFLOW_HOST_RATE_TPS", "1e9")
    _, g1 = run_graph(40_000, "auto")
    _, g2 = run_graph(40_000, "auto")
    assert g1.placements[0]["placement"] == g2.placements[0]["placement"]


def test_placements_and_device_time_in_stats_json():
    _, g = run_graph(N_EVENTS, "device")
    rep = json.loads(g.stats.to_json())
    assert rep["Placements"] and \
        rep["Placements"][0]["placement"] == "device"
    recs = [r for o in rep["Operators"] for r in o["Replicas"]
            if "win_seq" in o["Operator_name"]]
    assert recs, "placed engine got no stats record"
    rec = recs[0]
    assert rec["Device_launches"] > 0
    assert rec["Device_time_ms"] > 0
    assert rec["Device_ms_per_launch"] > 0
    assert rec["Device_bytes_per_launch"] > 0
    # the roofline estimate needs the device's published peak: the CPU
    # backend's device_kind is not in the table, so the field is omitted
    assert "Device_roofline_frac" not in rec


def test_host_lane_reports_engine_time_too():
    _, g = run_graph(N_EVENTS, "host")
    rep = json.loads(g.stats.to_json())
    recs = [r for o in rep["Operators"] for r in o["Replicas"]
            if "win_seq" in o["Operator_name"]]
    assert recs[0]["Device_launches"] > 0  # host-lane launches counted


def test_host_placement_rejects_custom_combine():
    with pytest.raises(ValueError):
        WinSeqTPULogic(lambda gwid, cols, mask: 0.0, WIN, SLIDE,
                       wf.WinType.TB, placement="host")


def test_builder_placement_flows_through():
    op = wf.WinSeqTPUBuilder("sum").with_tb_windows(WIN, SLIDE) \
        .with_placement("host").build()
    assert op.kwargs["placement"] == "host"
    with pytest.raises(ValueError):
        wf.WinSeqTPUBuilder("sum").with_placement("gpu")
    # device-pinned families reject the knob loudly
    with pytest.raises(ValueError):
        wf.WinSeqFFATTPUBuilder(lambda t: t.value, "sum") \
            .with_tb_windows(WIN, SLIDE).with_placement("host").build()


def test_kf_builder_placement(monkeypatch):
    monkeypatch.setenv("WINDFLOW_RTT_FLOOR_MS", "1000")
    monkeypatch.setenv("WINDFLOW_HOST_RATE_TPS", "1e12")
    res, sink = window_dict_sink()
    g = wf.PipeGraph("plan_kf", wf.Mode.DEFAULT)
    op = wf.KeyFarmTPUBuilder("sum").with_tb_windows(WIN, SLIDE) \
        .with_batch(128).with_batch_output() \
        .with_placement("auto").build()
    g.add_source(BatchSource(batch_source(N_EVENTS))) \
        .add(op).add_sink(Sink(sink))
    g.run()
    assert g.placements[0]["placement"] == "host"
    want = expected_windows(N_EVENTS)
    assert set(res) == set(want)
    for k in want:
        assert res[k] == pytest.approx(want[k], rel=1e-5)


# ---------------------------------------------------------------------------
# adaptive batch resize (scripted traces)
# ---------------------------------------------------------------------------

def test_adaptive_grows_when_transport_bound():
    ab = AdaptiveBatcher(256, floor_ms=10.0, patience=3)
    for _ in range(6):
        ab.observe(11.0)  # ~ the floor: batch too small
    assert ab.batch_len == 1024
    assert ab.resizes == [("x2", 512), ("x2", 1024)]


def test_adaptive_shrinks_when_latency_bound():
    ab = AdaptiveBatcher(1024, floor_ms=10.0, patience=3)
    for _ in range(6):
        ab.observe(200.0)  # >> the floor: latency grows with batch
    assert ab.batch_len == 256
    assert ab.resizes == [("/2", 512), ("/2", 256)]


def test_adaptive_stable_in_band_and_clamped():
    ab = AdaptiveBatcher(512, floor_ms=10.0, patience=2, lo=128, hi=1024)
    for _ in range(20):
        ab.observe(40.0)  # between 2x and 8x the floor: keep
    assert ab.batch_len == 512 and ab.resizes == []
    for _ in range(40):
        ab.observe(11.0)
    assert ab.batch_len == 1024  # clamped at hi
    for _ in range(60):
        ab.observe(500.0)
    assert ab.batch_len == 128   # clamped at lo
    # mixed trace: streaks reset, no thrash
    ab2 = AdaptiveBatcher(512, floor_ms=10.0, patience=3)
    for lat in (11.0, 11.0, 200.0, 11.0, 11.0, 200.0) * 4:
        ab2.observe(lat)
    assert ab2.batch_len == 512 and ab2.resizes == []


def test_adaptive_converges_on_amortizing_trace():
    """Latency proportional to batch (plus the floor): the loop must
    settle inside the [2x, 8x] band instead of oscillating."""
    ab = AdaptiveBatcher(64, floor_ms=10.0, patience=2)
    for _ in range(100):
        ab.observe(10.0 + ab.batch_len / 100.0)
    final = ab.batch_len
    assert 10.0 + final / 100.0 <= 8 * 10.0   # inside the band
    assert final >= 1024                       # actually grew
    for _ in range(20):                        # and stays there
        ab.observe(10.0 + ab.batch_len / 100.0)
    assert ab.batch_len == final


def test_adaptive_resize_live_in_graph(monkeypatch):
    monkeypatch.setenv("WINDFLOW_RTT_FLOOR_MS", "50")
    monkeypatch.setenv("WINDFLOW_HOST_RATE_TPS", "1")
    res, g = run_graph(N_EVENTS, "auto", adaptive_batch=True)
    from windflow_tpu.graph.fuse import find_logic
    logic = find_logic(g, lambda lg: isinstance(lg, WinSeqTPULogic))
    assert logic._adaptive is not None
    assert logic._adaptive.floor_ms == 50.0
    # launches on this box complete in ~us << 2x50ms: every observation
    # is a grow vote, so the batch must have grown (results unchanged)
    assert logic.batch_len > 128
    want = expected_windows(N_EVENTS)
    assert set(res) == set(want)


def test_adaptive_band_widens_for_explicit_config():
    # an explicitly configured batch_len outside [64, 65536] widens the
    # band instead of being silently clamped on the first observation
    ab = AdaptiveBatcher(1 << 17, floor_ms=10.0)
    assert ab.batch_len == 1 << 17 and ab.hi == 1 << 17
    ab.observe(40.0)  # in-band: hold, no silent rewrite
    assert ab.batch_len == 1 << 17 and ab.resizes == []
    ab2 = AdaptiveBatcher(32, floor_ms=10.0)
    assert ab2.batch_len == 32 and ab2.lo == 32


def test_finish_normalizes_launch_wall_by_inflight_depth():
    # a saturated pipeline queues launches behind each other: the raw
    # submit->result wall of a depth-8 entry reads ~8x the per-launch
    # service, which must not register as a shrink vote
    import time as _t
    from windflow_tpu.telemetry import spans

    class _H:
        def block(self):
            return np.empty(0, np.float32)

    logic = WinSeqTPULogic("sum", WIN, SLIDE, wf.WinType.TB)
    logic._adaptive = AdaptiveBatcher(256, floor_ms=10.0, patience=1)
    t_sub = _t.perf_counter() - 0.080  # 80 ms wall, 8 deep => 10 ms each
    none = np.empty(0, np.int64)   # a batch of no rows
    logic._finish((_H(), (none, none, none), t_sub, 8, 0,
                   logic._launches.open(0, 0, t_sub)), lambda *_: None,
                  spans.FORCED)
    # ~floor after normalization: a grow vote (raw 80 ms >= 8x floor
    # would have halved the batch)
    assert logic._adaptive.resizes == [("x2", 512)]


def test_plan_window_operator_ffat_rejects_lane_knobs():
    from windflow_tpu.operators.tpu.farms_tpu import WinSeqFFATTPU
    # 'max', panes < 16, win/slide >= 8 resolves to the device-pinned
    # FFAT tree: lane knobs must fail loudly, not with a TypeError
    assert select_strategy("max", 120, 15) == "ffat"
    op = plan_window_operator("max", 120, 15, wf.WinType.CB)
    assert isinstance(op, WinSeqFFATTPU)
    with pytest.raises(ValueError, match="device-pinned"):
        plan_window_operator("max", 120, 15, wf.WinType.CB,
                             placement="host")
    with pytest.raises(ValueError, match="device-pinned"):
        plan_window_operator("max", 120, 15, wf.WinType.CB,
                             adaptive_batch=True)


# ---------------------------------------------------------------------------
# parallel zero-copy feed plane
# ---------------------------------------------------------------------------

FEED_SB = 8192
FEED_CHUNKS = 24


def feed_chunk_fn(i, take):
    if i >= FEED_CHUNKS:
        return None
    idx = take(FEED_SB, np.int64)
    idx[:] = np.arange(i * FEED_SB, (i + 1) * FEED_SB)
    keys = np.mod(idx, N_KEYS, out=take(FEED_SB, np.int64))
    vals = np.mod(idx, 97, out=take(FEED_SB, np.int64)) \
        .astype(np.float64)
    ids = np.floor_divide(idx, N_KEYS, out=idx)
    return keys, ids, ids, vals


@pytest.mark.parametrize("feeders", [1, 4])
def test_feed_source_conserves_windows(feeders):
    res, sink = window_dict_sink()
    g = wf.PipeGraph(f"feed{feeders}", wf.Mode.DEFAULT)
    op = WinSeqTPU("sum", WIN, SLIDE, wf.WinType.TB, batch_len=256,
                   emit_batches=True)
    g.add_source(FeedSource(feed_chunk_fn, feeders=feeders)) \
        .add(op).add_sink(Sink(sink))
    g.run()
    want = expected_windows(FEED_SB * FEED_CHUNKS)
    assert set(res) == set(want)
    for k in want:
        assert res[k] == pytest.approx(want[k], rel=1e-5)


def test_parallel_feeder_direct_into_staging():
    """Channel-free: N feeder threads write through the pooled arena
    straight into WinSeqTPULogic staging; every tuple and window of
    the single-feeder run is recovered."""
    def run(feeders):
        logic = WinSeqTPULogic("sum", WIN, SLIDE, wf.WinType.TB,
                               batch_len=256, emit_batches=True,
                               async_dispatch=False)
        got = {}

        def emit(item):
            for j in range(len(item)):
                got[(int(item.key[j]), int(item.id[j]))] = \
                    float(item["value"][j])

        feeder = ParallelColumnFeeder(
            feed_chunk_fn,
            lambda k, i, t, v: logic.feed_columns(k, i, t, v, emit),
            feeders=feeders)
        fed = feeder.run()
        logic.feed_eos(emit)
        return fed, got, feeder

    fed1, got1, _ = run(1)
    fed4, got4, feeder4 = run(4)
    assert fed1 == fed4 == FEED_SB * FEED_CHUNKS
    assert got1 == got4
    assert feeder4.chunks_fed == FEED_CHUNKS
    # the arena actually recycled (zero-copy steady state)
    stats = feeder4.pool.stats()
    assert stats["hits"] > stats["misses"]


def test_parallel_feeder_into_native_record_plane():
    """The same feeder plane drives the native record pipeline's
    columnar feed() (SPSC ring; serialized by the turnstile)."""
    from windflow_tpu.runtime.native import (NativeRecordPipeline,
                                             native_available)
    if not native_available():
        pytest.skip("native runtime unavailable")
    rp = NativeRecordPipeline("threaded", 1)
    rp.add_window(WIN, SLIDE, True, "sum")
    rp.set_feed()
    rp.start()
    feeder = ParallelColumnFeeder(
        feed_chunk_fn, lambda k, i, t, v: rp.feed(k, i, t, v), feeders=3)
    fed = feeder.run()
    rp.feed_eos()
    n_results, total, _dropped = rp.wait()
    assert fed == FEED_SB * FEED_CHUNKS
    want = expected_windows(FEED_SB * FEED_CHUNKS)
    # the record plane fires only complete windows (no EOS partials
    # with renumber off -- it emits opened windows at EOS too), so
    # compare against the full oracle sum
    assert n_results == len(want)
    assert total == pytest.approx(sum(want.values()), rel=1e-9)


def test_feeder_error_propagates():
    def bad_chunk(i, take):
        if i == 3:
            raise RuntimeError("boom")
        return feed_chunk_fn(i, take)

    feeder = ParallelColumnFeeder(bad_chunk, lambda *a: None, feeders=2)
    with pytest.raises(RuntimeError, match="boom"):
        feeder.run()


def test_feed_source_error_ends_peer_feeders():
    """A chunk_fn failure in one FeedSource replica must end the
    turnstile: peer feeders blocked in wait_turn unwind through EOS
    instead of deadlocking the graph (the cursor is not a channel, so
    poisoning cannot reach them)."""
    def bad_chunk(i, take):
        if i == 2:
            raise RuntimeError("feeder boom")
        return feed_chunk_fn(i, take)

    res, sink = window_dict_sink()
    g = wf.PipeGraph("feed_err", wf.Mode.DEFAULT)
    op = WinSeqTPU("sum", WIN, SLIDE, wf.WinType.TB, batch_len=256,
                   emit_batches=True)
    g.add_source(FeedSource(bad_chunk, feeders=3)) \
        .add(op).add_sink(Sink(sink))
    with pytest.raises(RuntimeError) as ei:
        g.run()  # hangs here without cursor.end() on the raise path
    assert "feeder boom" in str(ei.value)
