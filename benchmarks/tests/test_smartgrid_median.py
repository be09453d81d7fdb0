"""``smartgrid_median`` (DEBS 2014 Query 2's per-plug median over the
smart plugs SG2 runs on): the file, the counting reference against a
per-window sort, the control, a program without the raw-tuple lane
refused, and a whole run through ``runner.run_cell`` at a cut size,
sound and with the loads planted in bfloat16, with the two new readers."""
import io
import json
import os
import time

import numpy as np
import pytest

from benchmarks.harness import check, runner
from benchmarks.tests import control, control_median
from benchmarks.tests.conftest import ROOT, _patch

SEED = 2_147_483_659      # more than 32 signed bits hold
# 25 plugs in 5 houses, a window of 360 slides: the shape's ratios are
# the cell's own but for the window's length (3,600 slides there)
CUT = {"n_plugs": 25, "n_houses": 5, "events_per_s_of_stream": 25,
       "slide_events": 25, "source_window_s": 360, "win_events": 9000,
       "pool_rows": 1 << 15}
CELL = "smartgrid_median.sat"
NEW = ("fn_elems_per_row", "fn_tile_fill_share")


def median_cfg(manifest):
    conf = next(c for c in manifest["configs"]
                if c["name"] == "smartgrid_median")
    with open(os.path.join(ROOT, conf["file"])) as f:
        cfg = json.load(f)
    return conf, cfg, control.load_pipeline("smartgrid_median")


@pytest.fixture
def cut_median(tiny_bench):
    manifest, bench_dir = tiny_bench
    _patch(os.path.join(bench_dir, "configs", "smartgrid_median",
                        "config.json"), CUT)
    return manifest, bench_dir


def run(cut, tamper=None, seconds=0.6):
    manifest, bench_dir = cut
    err, out = io.StringIO(), io.StringIO()
    result = runner.run_cell(
        manifest, CELL, SEED, seconds, False,
        runner.Doors(require_tpu=False, bench_dir=bench_dir, out=out,
                     err=err, tamper=tamper))
    return result, json.loads(out.getvalue().splitlines()[0]), \
        err.getvalue()


# -- the file, the shape and the reference ---------------------------------

def test_the_file_states_the_deployment(manifest):
    conf, cfg, pipeline = median_cfg(manifest)
    assert conf["source"] == cfg["source"] and "DEBS 2014" in cfg["source"]
    assert conf["reduced"] == cfg["reduced"] == []
    assert (cfg["n_plugs"], cfg["n_houses"]) == (2125, 40)
    assert pipeline.shape(cfg) == (7_650_000, 2125)
    for left_out in ("Q2's global median and per-house share",
                     "the 24 h window"):
        assert left_out in cfg["assumed"]
    sg2 = control.load_pipeline("smartgrid_sg2")
    assert (pipeline.make_pool(cfg, 5)["value"]
            == sg2.make_pool(cfg, 5)["value"]).all()
    # a row reads the window's 3,600 readings, its extent and its result
    assert pipeline.logical_bytes_per_row(cfg) == 4 * 3600 + 8 + 4


def test_the_shape_is_checked_against_the_stream(manifest):
    _, cfg, pipeline = median_cfg(manifest)
    with pytest.raises(ValueError, match="slide_events"):
        pipeline.shape(dict(cfg, win_events=cfg["win_events"] + 1))
    assert pipeline.shape(dict(cfg, **CUT)) == (9000, 25)


@pytest.mark.parametrize("seed", [1, SEED])
def test_the_counting_reference_is_a_per_window_sort(manifest, seed):
    _, cfg, pipeline = median_cfg(manifest)
    cfg = dict(cfg, **dict(CUT, source_window_s=60, win_events=1500,
                           pool_rows=1 << 13))
    n, slide, win = 20_000, 25, 1500
    keys, wids, vals, kept = pipeline.reference(cfg, seed, n)
    pool = pipeline.make_pool(cfg, seed)
    plug = pipeline.plug_index(cfg, seed)[pool["house"], pool["household"],
                                          pool["key"]]
    ev = np.arange(n)
    ev_plug, ev_load = plug[ev % len(plug)], pool["value"][ev % len(plug)]
    want = {}
    for k in range(cfg["n_plugs"]):
        mine = ev[ev_plug == k]
        for w in range(mine.max() // slide + 1):
            got = np.sort(ev_load[mine[(mine >= w * slide)
                                       & (mine < w * slide + win)]])
            if len(got):
                m = len(got)
                want[(k, w)] = (got[(m - 1) // 2] + got[m // 2]) / 2
    assert kept == n and len(keys) == len(want)
    assert {(int(k), int(w)): float(v)
            for k, w, v in zip(keys, wids, vals)} == want
    assert pipeline.reference(cfg, seed, 0)[3] == 0


@pytest.mark.parametrize("seed", [1, SEED])
def test_control_fails_nearly_every_row(manifest, seed):
    """The loads through bfloat16 in the program's place: above 256 W a
    load moves by watts."""
    _, cfg, _ = median_cfg(manifest)
    cfg = dict(cfg, **CUT)
    numbers, sound = control.control_numbers("smartgrid_median", cfg, seed,
                                             40_000)
    assert check.verdict(sound, io.StringIO())[0]
    assert not check.verdict(numbers, io.StringIO())[0]
    assert numbers["rows_wrong"] > 0.5 * 39_000
    assert numbers["rows_missing"] == numbers["rows_unexpected"] == 0


def test_a_program_without_the_lane_is_refused(manifest, monkeypatch):
    from windflow_tpu.ops import window_compute
    from windflow_tpu.runtime.native import NativeWindowEngine
    _, _, pipeline = median_cfg(manifest)
    pipeline.require_program()
    kinds = {k: v for k, v in NativeWindowEngine.KINDS.items()
             if k != "tuples"}
    t0 = time.perf_counter()
    with monkeypatch.context() as m:
        m.setattr(NativeWindowEngine, "KINDS", kinds)
        with pytest.raises(SystemExit, match="refusing to start"):
            pipeline.build(None, None, None, None, 0)
    with monkeypatch.context() as m:
        m.delattr(window_compute, "fn_rows")
        with pytest.raises(SystemExit, match="fn_rows"):
            pipeline.require_program()
    assert time.perf_counter() - t0 < 1.0


# -- a whole run -----------------------------------------------------------

def test_cell_is_correct_and_reads_what_the_function_read(cut_median):
    from windflow_tpu.graph.fuse import iter_logics
    seen = {}

    def look(graph, gen, sink):
        seen["logic"] = next(lg for _, lg in iter_logics(graph)
                             if hasattr(lg, "launched_batches"))

    result, notes, err = run(cut_median, tamper=look)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 10_000
    assert set(result["metrics"]) == {"events_per_s", "setup_s"}
    assert all(v["value"] == 0 for v in result["compared"].values())
    assert err.strip().splitlines()[-1] == "check correct: True"
    logic = seen["logic"]
    assert logic._native is not None and callable(logic.engine.kind)
    s = logic._native.snapshot()
    assert s["windows_staged"] == s["windows_fired"] == result["attempted"]
    assert s["tuples_staged"] > s["windows_staged"]
    layer = notes["per_layer"]
    # a window of 360 one-second panes holds 360 readings of a plug
    assert 300 < layer["fn_elems_per_row"] < 420
    assert 0 < layer["fn_tile_fill_share"] <= 1
    manifest, bench_dir = cut_median
    listed = runner.Cell(manifest, CELL, bench_dir).per_layer
    assert {m["name"] for m in listed} - {"xla_hbm_roofline",
                                          "device_idle_share"} \
        <= set(layer)


def test_the_loads_in_bfloat16_are_not_correct(cut_median):
    result, _notes, err = run(cut_median,
                              tamper=control_median.through_bfloat16)
    assert result["correct"] is False
    assert result["compared"]["rows_wrong"]["value"] > 0
    assert result["compared"]["rows_missing"]["value"] == 0
    assert "check correct: False" in err


def test_the_manifest_names_them(manifest):
    entries = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW:
        assert entries[name]["workloads"] == [CELL]
        assert entries[name]["layer"] == "XLA programs"
        assert entries[name]["moves"] == "events_per_s"
        assert entries[name]["source"] == "program_counter"
    assert entries["fn_elems_per_row"]["better"] == "lower"
    assert entries["fn_tile_fill_share"]["better"] == "higher"
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "smartgrid_median", "sat", 1)


def test_the_readers_read_nothing_where_nothing_was_counted():
    from benchmarks.harness.runner import load_module
    for name in NEW:
        reader = load_module(os.path.join(
            ROOT, "benchmarks", "metrics", name + ".py"), "m_" + name).read
        assert reader({"config": {"name": "no_such_graph"}, "rows": 10,
                       "window_s": 1.0, "_window_of": (0.0, 1.0)}) is None
