"""``nexmark_q5_ooo`` (Q5 with the generator's own delayed events) and
the ``over`` mix: the law of the delays, the configuration's reference
against a brute-force recomputation, a whole run through
``runner.run_cell`` at a tiny size, sound and with a fault planted where
the stragglers are held for, the control, the three readers, and the
schedule of the new traffic file."""
import io
import json
import os
import time

import numpy as np
import pytest

from benchmarks.harness import check, runner
from benchmarks.harness.generator import Schedule
from benchmarks.tests import control
from benchmarks.tests.conftest import ROOT, _patch

SEED = 2_147_483_659      # more than 32 signed bits hold
TINY = {"win_events": 4096, "slide_events": 2048, "delay_events": 1228,
        "pool_rows": 1 << 15}
CELL = "nexmark_q5_ooo.sat"


def ooo(manifest):
    conf = next(c for c in manifest["configs"]
                if c["name"] == "nexmark_q5_ooo")
    with open(os.path.join(ROOT, conf["file"])) as f:
        cfg = json.load(f)
    return cfg, runner.load_module(
        os.path.join(ROOT, os.path.dirname(conf["file"]), "pipeline.py"),
        "q5_ooo_under_test")


@pytest.fixture
def tiny_ooo(tiny_bench):
    manifest, bench_dir = tiny_bench
    _patch(os.path.join(bench_dir, "configs", "nexmark_q5_ooo",
                        "config.json"), TINY)
    return manifest, bench_dir


def run(tiny, tamper=None, seconds=0.6, cell=CELL):
    manifest, bench_dir = tiny
    err = io.StringIO()
    result = runner.run_cell(
        manifest, cell, SEED, seconds, False,
        runner.Doors(require_tpu=False, bench_dir=bench_dir,
                     out=io.StringIO(), err=err, tamper=tamper))
    return result, err.getvalue()


# -- the law and the reference -----------------------------------------------

def test_the_file_states_the_deployment(manifest):
    cfg, pipeline = ooo(manifest)
    live = json.load(open(os.path.join(
        ROOT, "benchmarks", "configs", "nexmark_q5_live", "config.json")))
    assert (cfg["win_events"], cfg["slide_events"], cfg["delay_events"],
            cfg["prob_delayed_event"], cfg["occasional_delay_sec"]) \
        == (1 << 20, 1 << 19, 314_572, 0.1, 3)
    assert pipeline.delay_ids(cfg) == 3 * (1 << 20) // 10 == 314_572
    with pytest.raises(ValueError, match="delay_events"):
        pipeline.delay_ids(dict(cfg, win_events=4096))
    # the guarantees of nexmark_q5_live word for word, and one more
    for k, text in live["guarantees"].items():
        assert cfg["guarantees"][k].startswith(text)
    assert "no bid is late" in cfg["guarantees"]["completeness"]
    assert cfg["reduced"] == ["win_events", "slide_events", "delay_events"]
    for k in ("person_proportion", "auction_proportion", "bid_proportion",
              "num_in_flight_auctions", "auction_id_lead",
              "hot_auction_ratio", "first_auction_id", "pool_rows",
              "device_batch", "source_window_s", "source_slide_s"):
        assert cfg[k] == live[k], k
    assert {"every arrival slot carries one event", "delays in ids",
            "streaming mode", "Beam's constants"} <= set(cfg["assumed"])


def test_one_bid_in_ten_is_delayed_uniformly_up_to_three_seconds(manifest):
    cfg, pipeline = ooo(manifest)
    cfg = dict(cfg, pool_rows=1 << 20)
    pool = pipeline.make_pool(cfg, SEED)
    delay, d = pool["delay"], cfg["delay_events"]
    assert (delay > 0).mean() == pytest.approx(0.1, abs=0.002)
    held = delay[delay > 0]
    assert held.min() >= 1 and held.max() <= d
    assert held.max() > 0.999 * d and held.min() < 0.001 * d
    assert held.mean() == pytest.approx(d / 2, rel=0.01)
    assert np.bincount(held * 10 // (d + 1)) \
        == pytest.approx(len(held) / 10, rel=0.05)
    # the other columns are nexmark_q5_live's, from the same seed
    live = pipeline._live.make_pool(cfg, SEED)
    assert all((pool[k] == live[k]).all() for k in live)
    assert (pipeline.make_pool(cfg, SEED + 1)["delay"] != delay).any()
    # the stream time trails no bid by more than D, and a tenth are behind
    i = np.arange(1 << 20, dtype=np.int64) + 5_000_000
    e = pipeline.created_at(i, delay)
    front = np.maximum.accumulate(e)
    assert (front - e).max() <= d
    assert (e[1:] < front[:-1]).mean() == pytest.approx(0.1, abs=0.002)
    assert (pipeline.created_at(np.arange(5), np.array([0, 9, 1, 0, 2]))
            == [0, 0, 1, 3, 2]).all()


def test_the_auction_is_the_one_drawn_at_creation_element_by_element(
        manifest):
    cfg, pipeline = ooo(manifest)
    live = pipeline._live
    rng = np.random.default_rng(3)
    draw = live.make_pool(dict(cfg, pool_rows=1 << 16), SEED)["key"]
    # past the head, in any order: the live law at each element
    e = rng.integers(2_000, 50_000_000, 1 << 16)
    want = np.concatenate([live.auction_of(cfg, e[j:j + 1], draw[j:j + 1])
                           for j in range(0, 1 << 16, 257)])
    assert (pipeline.auction_of(cfg, e, draw)[::257] == want).all()
    # a straggler from the head among bids past it, and the other way
    e = np.array([5_000_000, 40, 5_000_001, 0, 1_533, 1_534, 9_999_999])
    d7 = np.array([110, 110, live.HOT, 55, 0, 0, live.HOT])
    each = [int(live.auction_of(cfg, e[j:j + 1], d7[j:j + 1])[0])
            for j in range(7)]
    assert pipeline.auction_of(cfg, e, d7).tolist() == each
    assert pipeline.auction_of(cfg, e[::-1].copy(),
                               d7[::-1].copy()).tolist() == each[::-1]


@pytest.mark.parametrize("seed", [1, SEED, 77])
def test_reference_equals_a_brute_force_recomputation(manifest, seed):
    cfg, pipeline = ooo(manifest)
    cfg = dict(cfg, **TINY)
    n, win, slide = (1 << 14) + 321, TINY["win_events"], TINY["slide_events"]
    keys, wids, counts, kept = pipeline.reference(cfg, seed, n)
    pool = pipeline.make_pool(cfg, seed)
    i = np.arange(n, dtype=np.int64)
    row = i % len(pool["key"])
    e = np.maximum(i - pool["delay"][row], 0)
    auction = pipeline.auction_of(cfg, e, pool["key"][row])
    want = {}
    for t, a in zip(e.tolist(), auction.tolist()):
        for w in range(0 if t < win else (t - win) // slide + 1,
                       t // slide + 1):
            want[(a, w)] = want.get((a, w), 0) + 1
    assert kept == n
    assert dict(zip(zip(keys.tolist(), wids.tolist()), counts.tolist())) \
        == want
    assert len(want) > 0.13 * n
    assert (np.diff(wids) >= 0).all()     # in window order, as the fold reads
    assert (e != i).mean() == pytest.approx(0.1, abs=0.01)
    # a stream cut inside the first pane, and none
    assert pipeline.reference(cfg, seed, 0)[3] == 0
    k1, w1, c1, _ = pipeline.reference(cfg, seed, 700)
    assert c1.sum() == 700 and (w1 == 0).all()


@pytest.mark.parametrize("seed", [1, SEED])
def test_control_fails_at_the_cells_own_windows(manifest, seed):
    """The reference with its panes added in bfloat16 in the program's
    place: the hot auctions' counts are far over 256, so their rows and
    every full window's fold come out wrong; the others are exact."""
    cfg, pipeline = ooo(manifest)
    cfg["pool_rows"] = 4 * cfg["slide_events"]
    n_events = 3 * cfg["win_events"] + 12345
    numbers, sound = control.control_numbers("nexmark_q5_ooo", cfg, seed,
                                             n_events)
    assert check.verdict(sound, io.StringIO())[0]
    assert not check.verdict(numbers, io.StringIO())[0]
    assert numbers["rows_wrong"] > 100 and numbers["folds_wrong"] > 0
    assert numbers["rows_missing"] == numbers["rows_unexpected"] == 0


# -- a whole run ---------------------------------------------------------------

def window_logic(graph):
    from windflow_tpu.graph.fuse import iter_logics
    return next(lg for _, lg in iter_logics(graph)
                if hasattr(lg, "launched_batches"))


def test_cell_is_correct_and_prints_the_contracts_line(tiny_ooo):
    seen = {}

    def look(graph, gen, sink):
        seen["logic"] = window_logic(graph)

    result, err = run(tiny_ooo, tamper=look)
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "compared"]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 1000
    assert set(result["metrics"]) == {"events_per_s", "setup_s"}
    assert set(result["compared"]) == set(check.LIMITS)
    assert all(v["value"] == 0 for v in result["compared"].values())
    assert err.strip().splitlines()[-1] == "check correct: True"
    logic = seen["logic"]
    assert logic.triggering_delay == TINY["delay_events"]
    s = logic._native.snapshot()
    assert s["inputs_ignored"] == 0 and s["anchors_moved"] > 0
    assert s["keys_opened"] - s["keys_evicted"] == s["keys_live"]
    assert s["keys_opened"] > 10 * s["keys_live_peak"]
    # those first seen in a window, a slide and the delay, and the chunk
    assert s["keys_live_peak"] \
        < 3 * (4096 + 2048 + 1228 + 1024) // 46 + 300


class FiresWithoutWaiting:
    """The engine the operator would have built with no delay: windows
    fire as the stream passes their end, and the stragglers behind them
    are dropped."""

    def __init__(self):
        from windflow_tpu.runtime.native import NativeWindowEngine
        self.engine = NativeWindowEngine(TINY["win_events"],
                                         TINY["slide_events"], True, 0,
                                         kind="count")

    def __getattr__(self, name):
        return getattr(self.engine, name)


def fire_without_waiting(graph, gen, sink):
    logic = window_logic(graph)
    logic._native = FiresWithoutWaiting()


class LosesWhatLiesBeforeTheAnchor:
    """The parent's rule, as far as a wrapper can plant it: the first
    time a key's anchor moves back, the rows of that call's flush lose
    their last window."""

    def __init__(self, engine):
        self.engine, self.done = engine, False

    def __getattr__(self, name):
        return getattr(self.engine, name)

    def flush(self, max_windows):
        out = self.engine.flush(max_windows)
        if out is not None and not self.done \
                and self.engine.snapshot()["anchors_moved"] > 0:
            self.done = True
            out = tuple(a if j == 0 or j > 5 else a[:-1]
                        for j, a in enumerate(out))
        return out


def lose_a_window(graph, gen, sink):
    logic = window_logic(graph)
    logic._native = LosesWhatLiesBeforeTheAnchor(logic._native)


@pytest.mark.parametrize("fault,number", [
    (fire_without_waiting, "rows_wrong"),
    (lose_a_window, "rows_missing")])
def test_a_planted_fault_is_not_correct(tiny_ooo, fault, number):
    result, err = run(tiny_ooo, tamper=fault)
    assert result["correct"] is False
    assert result["compared"][number]["value"] > 0
    assert "check correct: False" in err


def test_the_parents_engine_is_refused_before_the_graph_starts(
        manifest, monkeypatch):
    from windflow_tpu.runtime import native
    _, pipeline = ooo(manifest)
    parents = tuple(n for n in native.NativeWindowEngine.STATS
                    if n not in ("late_accepted", "anchors_moved",
                                 "inputs_ignored"))
    monkeypatch.setattr(native.NativeWindowEngine, "STATS", parents)
    t0 = time.perf_counter()
    with pytest.raises(SystemExit, match="refusing to start"):
        pipeline.build(None, None, None, None, 0)
    with pytest.raises(SystemExit, match="anchors_moved"):
        pipeline.require_program()
    assert time.perf_counter() - t0 < 1.0


def test_per_layer_readers_find_what_the_program_counts(tiny_ooo):
    manifest, bench_dir = tiny_ooo
    seen = {}

    def look(graph, gen, sink):
        seen["gen"] = gen

    run(tiny_ooo, tamper=look)
    from windflow_tpu.telemetry import spans
    g = spans.graph("bench_nexmark_q5_ooo")
    assert g is not None and g.counters
    cfg = dict(TINY, name="nexmark_q5_ooo")
    rec = {"config": cfg, "window_s": 30.0,
           "_window_of": (time.perf_counter() - 30.0, time.perf_counter()),
           "events": seen["gen"].sent, "rows": 9000, "launches": 3}
    cell = runner.Cell(manifest, CELL, bench_dir)
    assert cell.reader("late_event_share")(rec) \
        == pytest.approx(0.1, abs=0.01)
    per_slide = cell.reader("anchors_moved_per_slide")(rec)
    c = next(iter(g.counters.values()))
    assert per_slide == pytest.approx(
        c.values["anchors_moved"] * 2048 / seen["gen"].sent)
    assert 0 < per_slide < 20
    assert cell.reader("ignored_tuples")(rec) == 0
    assert 100 < cell.reader("live_keys_peak")(rec) < 1000
    assert 0 < cell.reader("key_churn_share")(rec) < 1
    # an engine that drops stragglers: the reading says where to look
    run(tiny_ooo, tamper=fire_without_waiting)
    assert cell.reader("ignored_tuples")(rec) > 100
    g = spans.graph("bench_nexmark_q5_ooo")     # the second run's entry
    # a program without the series or the counts: nothing, and no raise
    none = {"config": {"name": "no_such_graph", "slide_events": 2048},
            "window_s": 1.0, "_window_of": (0.0, 1.0), "events": 10}
    import types
    g.counters["parent"] = types.SimpleNamespace(   # the parent's Counters
        values={"keys_live": 3}, folded_between=lambda t0, t1: (1, 0))
    for name in ("late_event_share", "anchors_moved_per_slide",
                 "ignored_tuples"):
        assert cell.reader(name)(none) is None, name
        assert cell.reader(name)(rec) is None, name
    del g.counters["parent"]
    assert {m["name"] for m in cell.per_layer} >= {
        "late_event_share", "anchors_moved_per_slide", "ignored_tuples",
        "live_keys_peak", "key_churn_share", "rows_per_launch",
        "fold_ns_per_event", "fold_by_key_share", "chain_ns_per_event",
        "pacing_thread_busy_share", "span_coverage_share",
        "longest_stall_ms", "xla_hbm_roofline", "device_idle_share"}
    entries = {m["name"]: m for m in manifest["per_layer"]}
    for name in ("late_event_share", "anchors_moved_per_slide",
                 "ignored_tuples"):
        assert entries[name]["workloads"] == [CELL]
        assert entries[name]["moves"] == "events_per_s"
        assert entries[name]["source"] == "program_counter"
        assert entries[name]["layer"] \
            == "host operators and native pane fold"


# -- the mix of nexmark_q5.over --------------------------------------------------

def over():
    with open(os.path.join(ROOT, "benchmarks", "traffic", "over.json")) as f:
        return json.load(f)


def test_over_parses_and_schedules_as_written():
    traffic = over()
    assert traffic["mode"] == "paced"
    assert traffic["rate_events_per_s"] == 132_000_000
    assert traffic["chunk_events"] == 65_536
    assert (traffic["warmup_s"], traffic["warmup_min_result_batches"],
            traffic["settle_lag_chunks"], traffic["settle_s"],
            traffic["settle_max_s"]) == (3.0, 8, 1.0, 3.0, 3.0)
    with open(os.path.join(ROOT, "benchmarks", "traffic", "sat.json")) as f:
        assert json.load(f)["chunk_events"] == traffic["chunk_events"]
    sched = Schedule(traffic)
    assert sched.peak_rate == 132e6
    # a chunk is due every 0.4965 ms; 20 s hold 2.64 G events, 5,035 slides
    assert float(sched.created_s(traffic["chunk_events"])) \
        == pytest.approx(65_536 / 132e6)
    assert float(sched.created_s(2_640_000_000)) == pytest.approx(20.0)


def test_over_opens_its_window_at_the_cap_and_counts_what_is_completed(
        tiny_bench):
    """Above what the graph sustains the lag never settles: the window
    opens at ``settle_max_s``, every hand-off in it is late, and the cell
    reports the events completed a second, all of them counted."""
    manifest, bench_dir = tiny_bench
    if not any(w["name"] == "nexmark_q5.over"
               for w in manifest["workloads"]):
        manifest = json.loads(json.dumps(manifest))
        manifest["workloads"].append({
            "name": "nexmark_q5.over", "config": "nexmark_q5",
            "traffic": "over", "chips": 1, "why": "not admitted"})
    _patch(os.path.join(bench_dir, "traffic", "over.json"),
           {"chunk_events": 1024, "rate_events_per_s": 200_000_000,
            "warmup_s": 0.3, "warmup_min_result_batches": 2,
            "settle_s": 0.2, "settle_max_s": 0.3})
    seen = {}

    def look(graph, gen, sink):
        seen["gen"] = gen

    result, err = run((manifest, bench_dir), tamper=look,
                      cell="nexmark_q5.over")
    assert result["correct"] is True
    assert set(result["metrics"]) == {"events_per_s", "setup_s"}
    gen = seen["gen"]
    assert gen.settled_since is None          # the lag never settled
    assert gen.t_open - gen.t0 >= 0.6
    t, due, _blocked = gen.window()
    assert len(t) > 10 and (t - due).min() > 0    # every hand-off late
    assert (t - due)[-1] > (t - due)[0]           # and the lag grows
    assert result["metrics"]["events_per_s"]["value"] < 200e6
