"""``nexmark_q5_live`` (Q5 on the generator's advancing auctions) and the
``paced_40m`` mix: the configuration's reference against a brute-force
recomputation, a whole run through ``runner.run_cell`` at a tiny size,
sound and with a fault planted where keys are evicted, the control, and
the schedule of the new traffic file."""
import io
import json
import os

import numpy as np
import pytest

from benchmarks.harness import check, runner
from benchmarks.harness.generator import Schedule
from benchmarks.tests import control
from benchmarks.tests.conftest import ROOT, _patch

SEED = 2_147_483_659      # more than 32 signed bits hold
TINY = {"win_events": 4096, "slide_events": 2048, "pool_rows": 1 << 15}
CELL = "nexmark_q5_live.sat"


def live(manifest):
    conf = next(c for c in manifest["configs"]
                if c["name"] == "nexmark_q5_live")
    with open(os.path.join(ROOT, conf["file"])) as f:
        cfg = json.load(f)
    return cfg, runner.load_module(
        os.path.join(ROOT, os.path.dirname(conf["file"]), "pipeline.py"),
        "q5_live_under_test")


@pytest.fixture
def tiny_live(tiny_bench):
    manifest, bench_dir = tiny_bench
    _patch(os.path.join(bench_dir, "configs", "nexmark_q5_live",
                        "config.json"), TINY)
    return manifest, bench_dir


def run(tiny, tamper=None, seconds=0.6):
    manifest, bench_dir = tiny
    err = io.StringIO()
    result = runner.run_cell(
        manifest, CELL, SEED, seconds, False,
        runner.Doors(require_tpu=False, bench_dir=bench_dir,
                     out=io.StringIO(), err=err, tamper=tamper))
    return result, err.getvalue()


# -- the law and the reference -----------------------------------------------

def test_auctions_advance_by_the_generators_law(manifest):
    cfg, pipeline = live(manifest)
    draw = pipeline.make_pool(dict(cfg, pool_rows=1 << 20), SEED)["key"]
    assert draw.min() == pipeline.HOT and draw.max() == 110
    assert (draw == pipeline.HOT).mean() == pytest.approx(0.5, abs=0.003)
    i = np.arange(10_000_000, 10_000_000 + (1 << 20), dtype=np.int64)
    auction = pipeline.auction_of(cfg, i, draw) - cfg["first_auction_id"]
    last = i * 3 // 46
    hot = draw == pipeline.HOT
    assert (auction[hot] == last[hot] // 100 * 100).all()
    off = auction[~hot] - last[~hot]
    assert off.min() == -100 and off.max() == 10
    assert np.bincount(off + 100) == pytest.approx((1 << 19) / 111, rel=0.1)
    # an auction takes bids for 111 x 46 / 3 = 1,702 events, then never
    first = np.flatnonzero(auction == auction[~hot].max() - 200)
    assert 0 < first.max() - first.min() <= 1_702 + 46
    # the head: fewer than 100 auctions exist, the range is the shorter
    head = pipeline.auction_of(cfg, np.arange(46, dtype=np.int64),
                               np.full(46, 110)) - cfg["first_auction_id"]
    assert (head == np.arange(46) * 3 // 46 + 10).all()


@pytest.mark.parametrize("seed", [1, SEED, 77])
def test_reference_equals_a_brute_force_recomputation(manifest, seed):
    cfg, pipeline = live(manifest)
    cfg = dict(cfg, **TINY)
    n, win, slide = (1 << 14) + 321, TINY["win_events"], TINY["slide_events"]
    keys, wids, counts, kept = pipeline.reference(cfg, seed, n)
    draw = pipeline.make_pool(cfg, seed)["key"]
    i = np.arange(n, dtype=np.int64)
    auction = pipeline.auction_of(cfg, i, draw[i % len(draw)])
    want = {}
    for t, a in zip(i.tolist(), auction.tolist()):
        for w in range(0 if t < win else (t - win) // slide + 1,
                       t // slide + 1):
            want[(a, w)] = want.get((a, w), 0) + 1
    assert kept == n
    assert dict(zip(zip(keys.tolist(), wids.tolist()), counts.tolist())) \
        == want
    assert len(want) > 0.13 * n           # 2 x 3 / 46 rows a bid, and the head
    assert (np.diff(wids) >= 0).all()     # in window order, as the fold reads


@pytest.mark.parametrize("seed", [1, SEED])
def test_control_fails_at_the_cells_own_windows(manifest, seed):
    """The reference with its panes added in bfloat16 in the program's
    place: the hot auctions' counts are far over 256, so their rows and
    every full window's fold come out wrong; the others are exact."""
    cfg, pipeline = live(manifest)
    cfg["pool_rows"] = 4 * cfg["slide_events"]
    n_events = 3 * cfg["win_events"] + 12345
    numbers, sound = control.control_numbers("nexmark_q5_live", cfg, seed,
                                             n_events)
    assert check.verdict(sound, io.StringIO())[0]
    assert not check.verdict(numbers, io.StringIO())[0]
    assert numbers["rows_wrong"] > 100 and numbers["folds_wrong"] > 0
    assert numbers["rows_missing"] == numbers["rows_unexpected"] == 0


# -- a whole run ---------------------------------------------------------------

def test_cell_is_correct_and_prints_the_contracts_line(tiny_live):
    manifest, _ = tiny_live
    result, err = run(tiny_live)
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "compared"]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 1000
    assert set(result["metrics"]) == {"events_per_s", "setup_s"}
    assert set(result["compared"]) == set(check.LIMITS)
    assert all(v["value"] == 0 for v in result["compared"].values())
    assert err.strip().splitlines()[-1] == "check correct: True"


def window_logic(graph):
    from windflow_tpu.graph.fuse import iter_logics
    return next(lg for _, lg in iter_logics(graph)
                if hasattr(lg, "launched_batches"))


class EvictsEarly:
    """The engine, with one auction dropped one window early: the last
    window of one firing never leaves it."""

    def __init__(self, engine):
        self.engine, self.n = engine, 0

    def __getattr__(self, name):
        return getattr(self.engine, name)

    def flush(self, max_windows):
        out = self.engine.flush(max_windows)
        self.n += 1
        if out is not None and self.n == 4:
            out = tuple(a if j == 0 or j > 5 else a[:-1]
                        for j, a in enumerate(out))
        return out


def evict_a_window_early(graph, gen, sink):
    logic = window_logic(graph)
    logic._native = EvictsEarly(logic._native)


def carry_a_count_over(graph, gen, sink):
    """A returning key's count carried over from its first life."""
    logic = window_logic(graph)
    real = logic._emit_results
    state = {"n": 0}

    def emit_results(results, *args, **kw):
        state["n"] += 1
        if state["n"] == 3:
            results = np.asarray(results).copy()
            results[-1] += results[0]
        return real(results, *args, **kw)

    logic._emit_results = emit_results


@pytest.mark.parametrize("fault,number", [
    (evict_a_window_early, "rows_missing"),
    (carry_a_count_over, "rows_wrong")])
def test_a_planted_fault_is_not_correct(tiny_live, fault, number):
    result, err = run(tiny_live, tamper=fault)
    assert result["correct"] is False
    assert result["compared"][number]["value"] > 0
    assert "check correct: False" in err


def test_rows_come_when_the_stream_passes_them_and_state_is_bounded(
        tiny_live):
    """Most rows reach the sink before the stream ends, and the engine
    holds the live population, not every auction it has seen."""
    seen = {}

    def look(graph, gen, sink):
        seen["logic"], seen["sink"], seen["gen"] = \
            window_logic(graph), sink, gen
        real = seen["logic"].eos_flush

        def eos_flush(emit):
            seen["rows_before_eos"] = sum(
                len(b[0]) for b in list(sink.batches))
            return real(emit)

        seen["logic"].eos_flush = eos_flush

    result, _ = run(tiny_live, tamper=look)
    assert result["correct"] is True
    s = seen["logic"]._native.snapshot()
    assert s["keys_opened"] - s["keys_evicted"] == s["keys_live"]
    assert s["keys_opened"] > 10 * s["keys_live_peak"]
    # those first seen in the last window and slide, and the chunk in hand
    assert s["keys_live_peak"] < 3 * (4096 + 2048 + 1024) // 46 + 250
    # all but the last two firings' rows and the dispatcher's backlog
    assert seen["rows_before_eos"] > 0.7 * result["attempted"]


def test_the_parents_engine_is_refused_before_the_graph_starts(
        manifest, monkeypatch):
    from windflow_tpu.runtime import native
    _, pipeline = live(manifest)
    monkeypatch.delattr(native.NativeWindowEngine, "snapshot")
    with pytest.raises(SystemExit, match="refusing to start"):
        pipeline.build(None, None, None, None, 0)


def test_per_layer_readers_find_what_the_program_counts(tiny_live):
    manifest, bench_dir = tiny_live
    run(tiny_live)
    from windflow_tpu.telemetry import spans
    g = spans.graph("bench_nexmark_q5_live")
    assert g is not None and g.counters
    import time
    rec = {"config": {"name": "nexmark_q5_live"}, "window_s": 30.0,
           "_window_of": (time.perf_counter() - 30.0, time.perf_counter()),
           "rows": 9000, "launches": 3}
    cell = runner.Cell(manifest, CELL, bench_dir)
    peak = cell.reader("live_keys_peak")(rec)
    churn = cell.reader("key_churn_share")(rec)
    assert 100 < peak < 1000
    assert 0 < churn < 1
    assert cell.reader("rows_per_launch")(rec) == 3000
    assert cell.reader("rows_per_launch")(dict(rec, launches=0)) is None
    # a program without the counters or the phases: nothing, and no raise
    none = {"config": {"name": "no_such_graph"}, "window_s": 1.0,
            "_window_of": (0.0, 1.0)}
    assert cell.reader("live_keys_peak")(none) is None
    assert cell.reader("key_churn_share")(none) is None
    assert {m["name"] for m in cell.per_layer} >= {
        "live_keys_peak", "key_churn_share", "rows_per_launch",
        "pacing_thread_busy_share", "span_coverage_share",
        "longest_stall_ms", "xla_hbm_roofline"}


# -- the paced mix of ysb.paced --------------------------------------------------

def test_paced_40m_parses_and_schedules_as_written():
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "paced_40m.json")) as f:
        traffic = json.load(f)
    assert traffic["mode"] == "paced"
    assert traffic["rate_events_per_s"] == 40_000_000
    assert traffic["chunk_events"] == 100_000
    assert (1 << 20) % traffic["chunk_events"] != 0
    assert (traffic["warmup_s"], traffic["warmup_min_result_batches"],
            traffic["settle_lag_chunks"], traffic["settle_s"],
            traffic["settle_max_s"]) == (3.0, 8, 1.0, 3.0, 15.0)
    sched = Schedule(traffic)
    assert sched.peak_rate == 40e6
    # a chunk is 2.5 ms at rate; 20 s hold 763 tumbling firings of 2**20
    assert float(sched.created_s(traffic["chunk_events"])) \
        == pytest.approx(2.5e-3)
    assert float(sched.created_s(800_000_000)) == pytest.approx(20.0)
    assert int(20 * 40e6) // (1 << 20) == 762
