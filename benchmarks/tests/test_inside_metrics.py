"""The eight readers of the inside of ``fold``, ``flush`` and
``dispatch`` (PR 37): each gives a finite number in every cell that
lists it, on the tiny bench; nothing on a program without the clocks or
the stamps (the parent's); and each part stays inside the whole it is a
part of, **cut where the part is cut**: ``fold_python + fold_tuple_walk
+ fold_key_walk <= fold_ns_per_event`` (each a share, between two notes
of the series, of the ``fold`` span between the same two instants),
``flush_stage + flush_copy_out <=`` the ``flush`` span between two
notes over the windows staged between them, ``launch_pack + launch_call
<=`` the mean ``dispatch`` of the same launches.  Against
``flush_ns_per_row`` itself, which is cut at the window's instants and
divided by the sink's rows, the two parts hold to a tolerance that
shrinks with the window (:data:`EDGE`)."""
import importlib
import io
import json
import math
import os
import types

import pytest

from benchmarks.harness import program_spans, runner
from benchmarks.tests.conftest import _patch
from benchmarks.tests.test_nexmark_q5_live import TINY as TINY_LIVE
from benchmarks.tests.test_nexmark_q5_ooo import TINY as TINY_OOO
from benchmarks.tests.test_smartgrid_sg2 import CUT as CUT_SG2

SEED = 2_147_483_711      # more than 32 signed bits hold
FOLD = ("fold_python_ns_per_event", "fold_tuple_walk_ns_per_event",
        "fold_key_walk_ns_per_event")
FLUSH = ("flush_stage_ns_per_row", "flush_copy_out_ns_per_row",
         "panes_shifted_per_row")
LAUNCH = ("launch_pack_mean_ms", "launch_call_mean_ms")
SEVEN = ("nexmark_q5.sat", "ysb.sat", "nexmark_q5.paced",
         "nexmark_q5_live.sat", "nexmark_q5_ooo.sat", "smartgrid_sg2.sat",
         "nexmark_q5.burst")
THREE = ("smartgrid_sg2.sat", "nexmark_q5_live.sat", "nexmark_q5_ooo.sat")
MS = 1_000_000
# ``flush_ns_per_row`` is cut at the window's instants (the span
# timelines' 100 ms buckets, the edge buckets by their covered share)
# and divided by the sink's rows, its parts between two notes of the
# series and by the windows staged between them: both read the same
# steady rate, and differ by what a bucket and a launch at each end can
# hold.  A tiny run's window is 1.2 s (twelve buckets, a few dozen
# launches): a quarter; a chip run's is 20 s (526 launches in SG2),
# where the builder's runs read the parts 0.987-0.998 of the whole
# (PERF.md section 6, PR 37).
EDGE = 1.25


def reader(name):
    return importlib.import_module("benchmarks.metrics." + name).read


def clocks():
    return importlib.import_module("benchmarks.metrics.engine_clocks")


def test_the_manifest_lists_the_eight_where_the_issue_says(manifest):
    listed = {m["name"]: m for m in manifest["per_layer"]}
    for name in FOLD + FLUSH + LAUNCH:
        m = listed[name]
        assert (m["source"], m["moves"], m["better"]) == (
            "program_counter" if name == "panes_shifted_per_row"
            else "program_span", "events_per_s", "lower")
        assert m["layer"] == ("dispatch" if name in LAUNCH else
                              listed["fold_ns_per_event"]["layer"])
        assert tuple(m["workloads"]) == (THREE if name in FLUSH else SEVEN)
    assert tuple(listed["fold_ns_per_event"]["workloads"]) == SEVEN
    assert tuple(listed["flush_ns_per_row"]["workloads"]) == THREE


# -- every cell, at a size a CPU holds --------------------------------------

@pytest.fixture
def seven_cells(tiny_bench):
    manifest, bench_dir = tiny_bench
    for config, cut in (("nexmark_q5_live", TINY_LIVE),
                        ("nexmark_q5_ooo", TINY_OOO),
                        ("smartgrid_sg2", CUT_SG2)):
        _patch(os.path.join(bench_dir, "configs", config, "config.json"),
               cut)
    _patch(os.path.join(bench_dir, "traffic", "burst.json"),
           {"chunk_events": 1000, "phases": [[0.2, 400_000], [0.2, 0]],
            "warmup_s": 0.3, "warmup_min_result_batches": 2,
            "settle_s": 0.2, "settle_max_s": 1.0})
    return manifest, bench_dir


@pytest.mark.parametrize("workload", SEVEN)
def test_every_reader_gives_a_number_where_it_is_listed(seven_cells,
                                                        workload):
    manifest, bench_dir = seven_cells
    out = io.StringIO()
    result = runner.run_cell(
        manifest, workload, SEED, 1.2, False,
        runner.Doors(require_tpu=False, bench_dir=bench_dir, out=out,
                     err=io.StringIO()))
    assert result["correct"] is True
    got = json.loads(out.getvalue().splitlines()[0])["per_layer"]
    listed = [m["name"] for m in manifest["per_layer"]
              if m["name"] in FOLD + FLUSH + LAUNCH
              and workload in m["workloads"]]
    assert set(listed) >= set(FOLD + LAUNCH)
    assert (set(FLUSH) <= set(listed)) == (workload in THREE)
    for name in listed:
        assert math.isfinite(got[name]) and got[name] > 0, name
    # the fold's parts are shares of the accepted whole: never over it
    assert sum(got[n] for n in FOLD) <= got["fold_ns_per_event"]
    # the flush's are ratios of two counts of the series, and the whole
    # is cut at the window's instants and divided by the sink's rows
    if workload in THREE:
        assert got["flush_stage_ns_per_row"] \
            + got["flush_copy_out_ns_per_row"] \
            <= EDGE * got["flush_ns_per_row"]
    # against the same spans cut where the parts are cut: between two
    # notes of the series, here the run's first and its last
    kept, = program_spans.graph_of(
        {"config": {"name": workload.split(".")[0]}}).counters.values()
    noted = sorted(kept.series)
    t0, t1 = (noted[0] + 1) / 10, noted[-1] / 10 + 0.05
    rec = {"config": {"name": workload.split(".")[0]}, "events": 1e6,
           "rows": 1e6, "window_s": t1 - t0, "_window_of": (t0, t1)}
    (ingest, by_tuple, by_key, staged), rec_at = clocks().at_the_notes(
        rec, ("ingest_ns", "tuple_walk_ns", "key_walk_ns", "windows_staged"))
    assert 0 < by_tuple + by_key <= ingest
    span_ns = reader("fold_ns_per_event")(rec_at) * rec_at["events"]
    whole = reader("fold_ns_per_event")(rec)
    assert sum(reader(n)(rec) for n in FOLD) == pytest.approx(
        whole * (1 - (ingest - by_tuple - by_key) / span_ns))
    assert reader("fold_python_ns_per_event")(rec) > 0
    if workload in THREE:
        whole = reader("flush_ns_per_row")(dict(rec_at, rows=staged))
        assert reader("flush_stage_ns_per_row")(rec) \
            + reader("flush_copy_out_ns_per_row")(rec) <= whole
    # the mean ``dispatch`` of the same launches (the manifest lists
    # ``launch_dispatch_mean_ms`` in the two open-loop cells only)
    rec = {"config": {"name": workload.split(".")[0]},
           "_window_of": (0.0, 1e12)}
    whole = program_spans.launch_mean_ms(rec, "t_dispatched", "t_picked")
    assert reader("launch_pack_mean_ms")(rec) \
        + reader("launch_call_mean_ms")(rec) <= whole


# -- the cuts, on a clock the test moves --------------------------------------

@pytest.fixture
def registry(monkeypatch):
    """A span registry entry ``bench_x`` on a clock the test moves, and
    a run record whose window is [1 s, 3 s) of it."""
    spans = program_spans.span_layer()
    clock = types.SimpleNamespace(ns=0)
    monkeypatch.setattr(spans, "_now", lambda: clock.ns)
    g = spans.start_graph("bench_x")
    rec = {"config": {"name": "x"}, "events": 1000, "rows": 200,
           "window_s": 2.0, "_window_of": (1.0, 3.0)}
    yield spans, g, clock, rec
    spans.end_graph(g)


def note(spans, c, ms, **values):
    c.note(ms * MS, [values.get(n, 0) for n in spans.ENGINE_COUNTERS])


def test_the_clock_readers_divide_by_what_the_same_two_notes_bracket(
        registry):
    """The window is [1 s, 3 s); the series' last note before its first
    bucket is at 0.75 s and the last inside its last at 2.95 s: what
    moved, moved in those 2.2 s, and is set against the ``fold`` span of
    those 2.2 s (a share, of ``fold_ns_per_event`` at the window) and
    the windows staged between the same two notes."""
    spans, g, clock, rec = registry
    tr = spans.Track("source")
    tr.graph = g
    g.tracks.append(tr)
    for at in (800, 1200, 1300, 1400, 1500, 3050):  # 10 ms of fold a chunk
        clock.ns = at * MS
        tr.begin("wf/q5/fold")
        clock.ns += 10 * MS
        tr.end()
    clock.ns = 4000 * MS
    c = g.counters_of("q5")
    note(spans, c, 750, ingest_ns=5 * MS, tuple_walk_ns=3 * MS,
         key_walk_ns=1 * MS, stage_ns=1 * MS, copy_out_ns=1 * MS,
         panes_shifted=100, windows_staged=10)        # before: the base
    note(spans, c, 2950, ingest_ns=35 * MS, tuple_walk_ns=23 * MS,
         key_walk_ns=4 * MS, stage_ns=5 * MS, copy_out_ns=3 * MS,
         panes_shifted=1300, windows_staged=410)      # the last inside it
    note(spans, c, 3400, ingest_ns=90 * MS, tuple_walk_ns=80 * MS,
         key_walk_ns=9 * MS, stage_ns=50 * MS, copy_out_ns=30 * MS,
         panes_shifted=9000, windows_staged=900)      # after it
    # the whole at the window's instants: four chunks; between the
    # notes: five (the one at 0.8 s too), of which the clocks' shares
    whole = 40e6 / 1000
    assert reader("fold_ns_per_event")(rec) == pytest.approx(whole)
    assert reader("fold_python_ns_per_event")(rec) \
        == pytest.approx(whole * (50 - 30) / 50)
    assert reader("fold_tuple_walk_ns_per_event")(rec) \
        == pytest.approx(whole * 20 / 50)
    assert reader("fold_key_walk_ns_per_event")(rec) \
        == pytest.approx(whole * 3 / 50)
    assert reader("flush_stage_ns_per_row")(rec) == pytest.approx(4e6 / 400)
    assert reader("flush_copy_out_ns_per_row")(rec) \
        == pytest.approx(2e6 / 400)
    assert reader("panes_shifted_per_row")(rec) == pytest.approx(3.0)
    (moved,), rec_at = clocks().at_the_notes(rec, ("ingest_ns",))
    assert moved == 30 * MS and rec_at["_window_of"] == (0.75, 2.95)
    # a feed in bursts: the same clocks and spans, half the events (the
    # shares ask nothing of the rate events come at)
    assert reader("fold_tuple_walk_ns_per_event")(dict(rec, events=500)) \
        == pytest.approx(2 * whole * 20 / 50)
    # the rows the sink received are not what the flush readers divide by
    assert reader("flush_stage_ns_per_row")(dict(rec, rows=0)) \
        == pytest.approx(4e6 / 400)
    # nothing moved inside the window, or no note in front of it
    for name in FOLD + FLUSH:
        assert reader(name)(dict(rec, _window_of=(5.0, 6.0))) is None, name
    for name in FOLD:
        assert reader(name)(dict(rec, _window_of=(0.5, 3.0))) is None, name


def test_an_engine_without_the_clocks_reads_nothing(registry, monkeypatch):
    spans, g, clock, rec = registry
    tr = spans.Track("source")
    tr.graph = g
    g.tracks.append(tr)
    clock.ns = 1200 * MS
    tr.begin("wf/q5/fold")
    clock.ns += 10 * MS
    tr.end()
    clock.ns = 4000 * MS
    # the parent's counters: a series and its four readers, no ``between``
    g.counters["q5"] = types.SimpleNamespace(
        values={"keys_live": 3, "folded_by_key": 7},
        folded_between=lambda t0, t1: (1, 0),
        moved_between=lambda t0, t1: {"folded_by_key": 1})
    assert reader("fold_ns_per_event")(rec) is not None
    for name in FOLD + FLUSH:
        assert reader(name)(rec) is None, name
    # the Python store: no counters at all
    g.counters.clear()
    for name in FOLD + FLUSH:
        assert reader(name)(rec) is None, name
    # and no span layer at all: nothing, and no raise
    monkeypatch.setattr(program_spans, "span_layer", lambda: None)
    for name in FOLD + FLUSH + LAUNCH:
        assert reader(name)(rec) is None, name


def launches(spans, stamps, with_parts=True):
    g = spans.start_graph("bench_x")
    ring = g.ring("op")
    for t in stamps:
        r = ring.open(1, 64, t - 0.0040)
        r.t_picked, r.t_dispatched = t - 0.0030, t - 0.0020
        if with_parts:
            r.t_packed, r.t_called = t - 0.0028, t - 0.0022
        r.t_ready_seen = r.t_on_host = t
        r.t_emitted = t + 0.001
    return ring


def test_the_two_launch_readers_cut_the_ring_at_the_window():
    spans = program_spans.span_layer()
    ring = launches(spans, [0.9, 1.1, 1.2, 3.5])
    ring.records[0].t_packed = ring.records[0].t_picked    # outside it
    rec = {"config": {"name": "x"}, "window_s": 2.0,
           "_window_of": (1.0, 3.0)}
    pack, call = (reader(n)(rec) for n in LAUNCH)
    assert pack == pytest.approx(0.2) and call == pytest.approx(0.6)
    assert pack + call <= program_spans.launch_mean_ms(
        rec, "t_dispatched", "t_picked")
    assert reader("launch_pack_mean_ms")(
        dict(rec, _window_of=(5.0, 6.0))) is None     # no launch in it
    # a lane that takes no stamps (host, mesh): its launches are skipped
    launches(spans, [1.1, 1.2], with_parts=False)
    assert all(reader(n)(rec) is None for n in LAUNCH)
    # the parent's records: no such fields, and no raise
    ring = launches(spans, [1.1, 1.2])
    old = [types.SimpleNamespace(
        t_emitted=r.t_emitted, t_on_host=r.t_on_host, t_picked=r.t_picked,
        t_dispatched=r.t_dispatched) for r in ring.records]
    ring.records.clear()
    ring.records.extend(old)
    assert all(reader(n)(rec) is None for n in LAUNCH)
    assert program_spans.launch_mean_ms(
        rec, "t_dispatched", "t_picked") == pytest.approx(1.0)
