"""``smartgrid_sg2`` (SABER's Smart Grid query SG2 over the DEBS 2014
smart plugs) and the ``burst`` mix: the law of the pool, the
configuration's reference against a brute-force recomputation, a whole
run through ``runner.run_cell`` at a cut size, sound and with a fault
planted, the control, a program without the wide combine refused, and
the three new readers with and without what they read."""
import io
import json
import os
import time
import types

import numpy as np
import pytest

from benchmarks.harness import check, runner
from benchmarks.harness.generator import Schedule
from benchmarks.tests import control
from benchmarks.tests.conftest import ROOT, _patch

SEED = 2_147_483_659      # more than 32 signed bits hold
# 25 plugs in 5 houses, a window of 360 slides: the shape's ratios are
# the cell's own but for the window's length (3,600 slides there)
CUT = {"n_plugs": 25, "n_houses": 5, "events_per_s_of_stream": 25,
       "slide_events": 25, "source_window_s": 360, "win_events": 9000,
       "pool_rows": 1 << 15}
CELL = "smartgrid_sg2.sat"


def sg2(manifest):
    conf = next(c for c in manifest["configs"]
                if c["name"] == "smartgrid_sg2")
    with open(os.path.join(ROOT, conf["file"])) as f:
        cfg = json.load(f)
    return cfg, runner.load_module(
        os.path.join(ROOT, os.path.dirname(conf["file"]), "pipeline.py"),
        "sg2_under_test")


@pytest.fixture
def cut_sg2(tiny_bench):
    manifest, bench_dir = tiny_bench
    _patch(os.path.join(bench_dir, "configs", "smartgrid_sg2",
                        "config.json"), CUT)
    return manifest, bench_dir


def run(cut, tamper=None, seconds=0.6, cell=CELL):
    manifest, bench_dir = cut
    err = io.StringIO()
    result = runner.run_cell(
        manifest, cell, SEED, seconds, False,
        runner.Doors(require_tpu=False, bench_dir=bench_dir,
                     out=io.StringIO(), err=err, tamper=tamper))
    return result, err.getvalue()


# -- the law and the reference -----------------------------------------------

def test_the_file_states_the_deployment(manifest):
    cfg, pipeline = sg2(manifest)
    assert (cfg["n_plugs"], cfg["n_houses"], cfg["source_window_s"],
            cfg["source_slide_s"], cfg["events_per_s_of_stream"]) \
        == (2125, 40, 3600, 1, 2125)
    assert (cfg["win_events"], cfg["slide_events"], cfg["pool_rows"]) \
        == (7_650_000, 2125, 1 << 22)
    assert pipeline.shape(cfg) == (7_650_000, 2125)
    assert cfg["win_events"] // cfg["slide_events"] == 3600
    with pytest.raises(ValueError, match="win_events"):
        pipeline.shape(dict(cfg, win_events=1 << 20))
    assert cfg["reduced"] == [] and len(cfg["source"]) <= 200
    live = json.load(open(os.path.join(
        ROOT, "benchmarks", "configs", "nexmark_q5_live", "config.json")))
    assert cfg["guarantees"]["delivery"] == live["guarantees"]["delivery"]
    assert "emitted exactly once" in cfg["guarantees"]["completeness"]
    assert "bit for bit" in cfg["guarantees"]["exactness"]
    assert "host" in cfg["arithmetic"] and "float32" in cfg["arithmetic"]
    assert {"the stream", "which plug reports", "loads are whole watts",
            "every event is a load reading", "houses and households",
            "constants from memory"} <= set(cfg["assumed"])
    # 36 bytes a row, and not the 14,424 a pane read 3,600 times would be
    assert pipeline.logical_bytes_per_row(cfg) == 36
    from benchmarks.harness.window import fold_bytes_per_row
    assert fold_bytes_per_row(cfg["win_events"], cfg["slide_events"]) \
        > 14_000


def test_the_pool_follows_the_published_shape(manifest):
    cfg, pipeline = sg2(manifest)
    cfg = dict(cfg, pool_rows=1 << 20)
    pool = pipeline.make_pool(cfg, SEED)
    assert set(pool) == {"house", "household", "key", "property", "value"}
    index = pipeline.plug_index(cfg, SEED)
    plug = index[pool["house"], pool["household"], pool["key"]]
    # 2,125 plugs in 40 houses, each drawn about as often as the others
    assert plug.min() == 0 and plug.max() == 2124
    assert len(np.unique(plug)) == 2125
    assert len(np.unique(pool["house"])) == 40
    per_plug = np.bincount(plug, minlength=2125)
    assert per_plug.std() < 1.2 * np.sqrt(per_plug.mean())
    house, household, local = pipeline.deal(cfg, SEED)
    assert sorted(np.bincount(house).tolist())[::39] == [53, 54]
    assert household.max() < cfg["max_households_per_house"]
    # the triple names the plug, and nothing else does
    assert (index[house, household, local] == np.arange(2125)).all()
    assert (index >= 0).sum() == 2125
    # whole watts inside the clip, a plug round its own level
    load = pool["value"]
    assert (load == np.round(load)).all()
    assert 0 <= load.min() and load.max() <= cfg["load_clip_w"]
    spread = [np.ptp(load[plug == p]) for p in (0, 7, 2124)]
    assert max(spread) <= 2 * cfg["load_noise_w"]
    assert (pool["property"] == 1).all()
    # another seed, another deal and other readings
    other = pipeline.make_pool(cfg, SEED + 1)
    assert (other["value"] != load).any()
    assert (pipeline.deal(cfg, SEED + 1)[0] != house).any()


def test_every_windows_sum_is_exact_in_float32(manifest):
    """At the published widths over a pool pass and a half: the widest
    window's sum stays under 2**24 (the reference asserts it), and a law
    that did not keep it there is refused."""
    cfg, pipeline = sg2(manifest)
    keys, wids, values, kept = pipeline.reference(cfg, SEED, 9_000_000)
    assert kept == 9_000_000 and len(keys) > 8_900_000
    assert values.max() <= cfg["load_base_max_w"] + cfg["load_noise_w"]
    hot = dict(cfg, load_base_max_w=60_000, load_clip_w=65_535)
    with pytest.raises(AssertionError, match="not exact in float32"):
        pipeline.reference(hot, SEED, 9_000_000)


@pytest.mark.parametrize("seed", [1, SEED, 77])
def test_reference_equals_a_brute_force_recomputation(manifest, seed):
    cfg, pipeline = sg2(manifest)
    cfg = dict(cfg, **dict(CUT, source_window_s=36, win_events=900,
                           pool_rows=1 << 12))
    n, win, slide = 5000 + 17, 900, 25
    keys, wids, values, kept = pipeline.reference(cfg, seed, n)
    pool = pipeline.make_pool(cfg, seed)
    index = pipeline.plug_index(cfg, seed)
    i = np.arange(n, dtype=np.int64)
    row = i % len(pool["key"])
    plug = index[pool["house"][row], pool["household"][row],
                 pool["key"][row]]
    held = {}
    for t, k, v in zip(i.tolist(), plug.tolist(),
                       pool["value"][row].tolist()):
        for w in range(0 if t < win else (t - win) // slide + 1,
                       t // slide + 1):
            held.setdefault((k, w), []).append(v)
    want = {kw: float(np.float32(sum(vs)) / np.float32(len(vs)))
            for kw, vs in held.items()}
    assert kept == n
    assert dict(zip(zip(keys.tolist(), wids.tolist()), values.tolist())) \
        == want
    assert len(want) > 0.9 * n           # about a row an event
    assert (np.diff(wids) >= 0).all()
    # a stream cut inside the first pane, and none
    assert pipeline.reference(cfg, seed, 0)[3] == 0
    k1, w1, v1, _ = pipeline.reference(cfg, seed, 20)
    assert (w1 == 0).all() and len(k1) == len(np.unique(plug[:20]))


@pytest.mark.parametrize("seed", [1, SEED])
def test_control_fails_nearly_every_row(manifest, seed):
    """The reference with its panes added in bfloat16 in the program's
    place: a running sum of thousands of watts has eight bits."""
    cfg, pipeline = sg2(manifest)
    cfg = dict(cfg, **CUT)
    numbers, sound = control.control_numbers("smartgrid_sg2", cfg, seed,
                                             40_000)
    assert check.verdict(sound, io.StringIO())[0]
    assert not check.verdict(numbers, io.StringIO())[0]
    assert numbers["rows_wrong"] > 0.95 * 39_000
    assert numbers["rows_missing"] == numbers["rows_unexpected"] == 0


# -- a whole run ---------------------------------------------------------------

def window_logic(graph):
    from windflow_tpu.graph.fuse import iter_logics
    return next(lg for _, lg in iter_logics(graph)
                if hasattr(lg, "launched_batches"))


def test_cell_is_correct_and_prints_the_contracts_line(cut_sg2):
    seen = {}

    def look(graph, gen, sink):
        seen["logic"] = window_logic(graph)

    result, err = run(cut_sg2, tamper=look)
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "compared"]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 10_000
    assert set(result["metrics"]) == {"events_per_s", "setup_s"}
    assert set(result["compared"]) == set(check.LIMITS) - {"folds_wrong"}
    assert all(v["value"] == 0 for v in result["compared"].values())
    assert err.strip().splitlines()[-1] == "check correct: True"
    logic = seen["logic"]
    assert logic._native is not None and logic.engine.kind == "mean"
    s = logic._native.snapshot()
    assert s["keys_live_peak"] == 25 and s["inputs_ignored"] == 0
    assert s["folded_by_key"] == 0            # MEAN folds one by one
    assert s["windows_staged"] == s["windows_fired"] == result["attempted"]
    # a launch stages a key's span once for the 41 windows of a chunk
    assert 5 < s["panes_staged"] / s["windows_staged"] < 30


class DropsAPane:
    """The first wide launch loses the newest pane of every key: its
    partial pair reads nothing."""

    def __init__(self, engine):
        self.engine, self.done = engine, False

    def __getattr__(self, name):
        return getattr(self.engine, name)

    def flush(self, max_windows):
        out = self.engine.flush(max_windows)
        if out is not None and not self.done and len(out[1]) > 500:
            self.done = True
            cols, starts, ends = out[0], out[1], out[2]
            last = np.unique(ends) - 1
            cols["value"][last] = 0.0
            cols["count"][last] = 0.0
        return out


def drop_a_pane(graph, gen, sink):
    logic = window_logic(graph)
    logic._native = DropsAPane(logic._native)


def add_in_bfloat16(graph, gen, sink):
    """The control, planted under a whole run: the launch's partials as
    a program computing in bfloat16 would hold them."""
    import ml_dtypes
    logic = window_logic(graph)
    engine = logic._helper_engine("mean_panes")
    compute = engine.compute

    def low(cols, starts, ends, gwids):
        cols = {k: np.asarray(v).astype(ml_dtypes.bfloat16)
                .astype(np.float64) for k, v in cols.items()}
        return compute(cols, starts, ends, gwids)

    engine.compute = low


@pytest.mark.parametrize("fault", [drop_a_pane, add_in_bfloat16])
def test_a_planted_fault_is_not_correct(cut_sg2, fault):
    result, err = run(cut_sg2, tamper=fault)
    assert result["correct"] is False
    assert result["compared"]["rows_wrong"]["value"] > 0
    assert result["compared"]["rows_missing"]["value"] == 0
    assert "check correct: False" in err


def test_a_program_without_the_wide_combine_is_refused(manifest,
                                                       monkeypatch):
    from windflow_tpu.ops import window_compute
    _, pipeline = sg2(manifest)
    monkeypatch.delattr(window_compute, "_block_sum_program")
    t0 = time.perf_counter()
    with pytest.raises(SystemExit, match="refusing to start"):
        pipeline.build(None, None, None, None, 0)
    with pytest.raises(SystemExit, match="_block_sum_program"):
        pipeline.require_program()
    assert time.perf_counter() - t0 < 1.0


# -- the three readers ---------------------------------------------------------

NEW = ("device_bytes_in_per_event", "panes_staged_per_row",
       "flush_ns_per_row")


def test_per_layer_readers_find_what_the_program_counts(cut_sg2):
    manifest, bench_dir = cut_sg2
    seen = {}

    def look(graph, gen, sink):
        seen["gen"], seen["logic"] = gen, window_logic(graph)

    result, _ = run(cut_sg2, tamper=look)
    from windflow_tpu.telemetry import spans
    g = spans.graph("bench_smartgrid_sg2")
    assert g is not None and g.counters and g.rings
    now = time.perf_counter()
    rec = {"config": dict(CUT, name="smartgrid_sg2"), "window_s": 60.0,
           "_window_of": (now - 60.0, now), "events": seen["gen"].sent,
           "rows": result["attempted"], "launches": 30}
    cell = runner.Cell(manifest, CELL, bench_dir)
    s = seen["logic"]._native.snapshot()
    assert cell.reader("panes_staged_per_row")(rec) \
        == pytest.approx(s["panes_staged"] / s["windows_staged"])
    # two float64 columns of the staged panes, the extents and the ids
    ring = next(iter(g.rings.values()))
    shipped = sum(r.bytes_in for r in ring.finished())
    assert cell.reader("device_bytes_in_per_event")(rec) \
        == pytest.approx(shipped / seen["gen"].sent)
    assert shipped >= 16 * s["panes_staged"] + 24 * s["windows_staged"]
    assert 10 < cell.reader("flush_ns_per_row")(rec) < 1e6
    # a program without the two counts (the parent's Counters), without
    # launch records, without a flush span: nothing, and no raise
    none = {"config": {"name": "no_such_graph"}, "window_s": 1.0,
            "_window_of": (0.0, 1.0), "events": 10, "rows": 10}
    for name in NEW:
        assert cell.reader(name)(none) is None, name
    g.counters["parent"] = types.SimpleNamespace(
        values={"keys_live": 3},
        moved_between=lambda t0, t1: {"key_touches": 5, "walked_ahead": 0})
    assert cell.reader("panes_staged_per_row")(rec) is None
    del g.counters["parent"]
    g.counters["older"] = types.SimpleNamespace(values={"keys_live": 3})
    assert cell.reader("panes_staged_per_row")(rec) is None
    del g.counters["older"]
    assert cell.reader("device_bytes_in_per_event")(
        dict(rec, events=0)) is None
    assert cell.reader("flush_ns_per_row")(dict(rec, rows=0)) is None
    mine = {m["name"] for m in cell.per_layer}
    assert mine >= set(NEW) | {
        "live_keys_peak", "key_churn_share", "rows_per_launch",
        "fold_ns_per_event", "fold_by_key_share", "chain_ns_per_event",
        "key_touches_per_event", "pacing_thread_busy_share",
        "span_coverage_share", "longest_stall_ms", "xla_hbm_roofline",
        "device_idle_share"}


def test_the_manifest_names_them(manifest):
    entries = {m["name"]: m for m in manifest["per_layer"]}
    cells = [w["name"] for w in manifest["workloads"]]
    assert entries["device_bytes_in_per_event"]["workloads"] == cells
    assert entries["device_bytes_in_per_event"]["layer"] == "dispatch"
    assert entries["panes_staged_per_row"]["workloads"] == [
        CELL, "nexmark_q5_live.sat"]
    assert entries["flush_ns_per_row"]["workloads"] == [
        CELL, "nexmark_q5_live.sat", "nexmark_q5_ooo.sat"]
    for name in NEW:
        assert entries[name]["moves"] == "events_per_s"
    for name in ("panes_staged_per_row", "flush_ns_per_row"):
        assert entries[name]["layer"] \
            == "host operators and native pane fold"
    assert entries["flush_ns_per_row"]["source"] == "program_span"
    conf = next(c for c in manifest["configs"]
                if c["name"] == "smartgrid_sg2")
    assert conf["reduced"] == []


@pytest.mark.parametrize("workload,lo,hi", [
    ("nexmark_q5_live.sat", 1.5, 3.5), ("nexmark_q5.sat", None, None)])
def test_the_accepted_cells_read_the_new_metrics(tiny_bench, manifest,
                                                 workload, lo, hi):
    """A two-pane window stages about two panes a row; a cell the
    manifest does not list for a reader does not report it."""
    manifest, bench_dir = tiny_bench
    err = io.StringIO()
    out = io.StringIO()
    runner.run_cell(manifest, workload, SEED, 0.6, False,
                    runner.Doors(require_tpu=False, bench_dir=bench_dir,
                                 out=out, err=err))
    layer = json.loads(out.getvalue().splitlines()[0])["per_layer"]
    assert 0 < layer["device_bytes_in_per_event"] < 100
    if lo is None:
        assert "panes_staged_per_row" not in layer
    else:
        assert lo < layer["panes_staged_per_row"] < hi
        assert layer["flush_ns_per_row"] > 0


# -- the mix of nexmark_q5.burst ---------------------------------------------

def burst():
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "burst.json")) as f:
        return json.load(f)


def test_burst_parses_and_schedules_as_written():
    traffic = burst()
    assert traffic["mode"] == "paced"
    assert traffic["phases"] == [[2.0, 20_000_000], [2.0, 0]]
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "paced.json")) as f:
        paced = json.load(f)
    for k in ("chunk_events", "warmup_s", "warmup_min_result_batches",
              "settle_lag_chunks", "settle_s", "settle_max_s"):
        assert traffic[k] == paced[k], k
    sched = Schedule(traffic)
    assert sched.peak_rate == 20e6 and sched.cycle_s == 4.0
    assert sched.cycle_events / sched.cycle_s == 10e6     # the mean
    # the 40 millionth event is the last of the first burst; the next
    # exists two silent seconds later
    assert float(sched.created_s(39_999_999)) == pytest.approx(2.0, 1e-6)
    assert float(sched.created_s(40_000_000)) == pytest.approx(4.0)
    assert float(sched.created_s(60_000_000)) == pytest.approx(5.0)


def test_burst_runs_through_the_generator_that_is_there(tiny_bench):
    """Data alone: the mix under the accepted configuration, on and off,
    every hand-off of a burst at the burst's rate and none in between."""
    manifest, bench_dir = tiny_bench
    manifest = json.loads(json.dumps(manifest))
    if not any(w["name"] == "nexmark_q5.burst"
               for w in manifest["workloads"]):
        manifest["workloads"].append({
            "name": "nexmark_q5.burst", "config": "nexmark_q5",
            "traffic": "burst", "chips": 1, "why": "not admitted"})
        for m in manifest["end_to_end"] + manifest["per_layer"]:
            if "nexmark_q5.paced" in m.get("workloads", []):
                m["workloads"].append("nexmark_q5.burst")
    _patch(os.path.join(bench_dir, "traffic", "burst.json"),
           {"chunk_events": 1000, "phases": [[0.2, 400_000], [0.2, 0]],
            "warmup_s": 0.3, "warmup_min_result_batches": 2,
            "settle_s": 0.2, "settle_max_s": 1.0})
    seen = {}

    def look(graph, gen, sink):
        seen["gen"] = gen

    result, _ = run((manifest, bench_dir), tamper=look, seconds=1.2,
                    cell="nexmark_q5.burst")
    assert result["correct"] is True
    assert set(result["metrics"]) == {"events_per_s",
                                      "result_latency_p50_ms", "setup_s"}
    t, due, _blocked = seen["gen"].window()
    gaps = np.diff(due)
    assert (gaps > 0.15).sum() >= 2           # the silences
    assert np.median(gaps) == pytest.approx(1000 / 400_000, rel=0.01)
    # half the time at twice the mean
    assert result["metrics"]["events_per_s"]["value"] \
        == pytest.approx(200_000, rel=0.25)
