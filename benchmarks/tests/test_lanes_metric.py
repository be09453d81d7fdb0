"""``walked_in_lanes_share`` (PR 39): of the tuples handed to the window
engine in the window, the share its calls walked in slices on the host
lanes.  Finite and in [0, 1] in every cell that lists it, on the tiny
bench; about 1 where the calls are long and the table fits the caches;
0 where the table runs ahead of itself, however long the calls; nothing
on a program whose engine does not count it (the parent's)."""
import ctypes
import io
import json
import math
import os
import types

import pytest

from benchmarks.harness import runner
from benchmarks.tests.conftest import _patch
from benchmarks.tests.test_fold_metrics import MS, reader  # noqa: F401
from benchmarks.tests.test_fold_metrics import registry  # noqa: F401
from benchmarks.tests.test_inside_metrics import SEED, SEVEN
from benchmarks.tests.test_inside_metrics import seven_cells  # noqa: F401

NAME = "walked_in_lanes_share"


def lanes_here():
    """The lanes the engine's rule gives this process (1: none)."""
    from windflow_tpu.runtime.native import get_lib
    out = (ctypes.c_longlong * 4)()
    get_lib().wfn_lanes_info(out)
    return out[2]


def run(manifest, bench_dir, workload):
    out = io.StringIO()
    result = runner.run_cell(
        manifest, workload, SEED, 1.2, False,
        runner.Doors(require_tpu=False, bench_dir=bench_dir, out=out,
                     err=io.StringIO()))
    assert result["correct"] is True
    return json.loads(out.getvalue().splitlines()[0])["per_layer"]


def test_the_manifest_lists_it_in_every_cell(manifest):
    entry, = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert tuple(entry["workloads"]) == SEVEN == tuple(
        w["name"] for w in manifest["workloads"])
    assert (entry["source"], entry["moves"], entry["better"],
            entry["unit"]) == ("program_counter", "events_per_s", "higher",
                               "share")
    assert entry["layer"] == "host operators and native pane fold"
    assert manifest["per_layer"][-1] is entry     # added at the end


@pytest.mark.parametrize("workload", SEVEN)
def test_it_reads_a_share_in_every_cell(seven_cells, workload):
    """The tiny bench's chunks (1,000-1,024 events) are shorter than any
    call the engine splits: 0, and a number."""
    manifest, bench_dir = seven_cells
    got = run(manifest, bench_dir, workload)
    assert math.isfinite(got[NAME]) and 0.0 <= got[NAME] <= 1.0
    assert got[NAME] == 0.0


@pytest.mark.parametrize("workload,cut,ahead", [
    # a Q5 chunk of 65,536 on 111 keys: the calls split
    ("nexmark_q5.sat", {"pool_rows": 1 << 17}, False),
    # the live auctions over windows of 16,384 ids: some 1,700 keys live,
    # a table of 16,384 records that runs ahead: no call split
    ("nexmark_q5_live.sat", {"win_events": 16384, "slide_events": 8192,
                             "pool_rows": 1 << 17}, True)])
def test_long_calls_walk_in_lanes_where_the_table_fits(
        seven_cells, workload, cut, ahead):
    manifest, bench_dir = seven_cells
    _patch(os.path.join(bench_dir, "configs", workload.split(".")[0],
                        "config.json"), cut)
    _patch(os.path.join(bench_dir, "traffic", "sat.json"),
           {"chunk_events": 1 << 16})
    got = run(manifest, bench_dir, workload)
    if ahead:
        assert got["walked_ahead_share"] == 1.0
        assert got[NAME] == 0.0
    elif lanes_here() > 1:
        # a call that came over 1 ms after the last walks alone (a CPU
        # shared with other tests' processes): most do not
        assert 0.5 < got[NAME] <= 1.0
    else:                       # fewer than three cores: no lane
        assert got[NAME] == 0.0


def test_it_cuts_the_counts_at_the_window(registry):  # noqa: F811
    spans, g, _clock, rec = registry
    c = g.counters_of("q5")
    names = spans.ENGINE_COUNTERS

    def note(ms, lanes, by_key, singly, ignored):
        values = dict.fromkeys(names, 0)
        values.update(walked_in_lanes=lanes, folded_by_key=by_key,
                      folded_singly=singly, inputs_ignored=ignored)
        c.note(ms * MS, [values[n] for n in names])
    note(500, 0, 100, 0, 0)                 # before the window: the base
    note(1500, 65536, 60000, 5536, 0)
    note(2950, 131072, 125000, 11072, 100)  # the last inside it
    note(3400, 900000, 900000, 0, 0)        # after it
    assert reader(NAME)(rec) == pytest.approx(131072 / 136072)
    # no tuple handed over between the two notes: nothing
    assert reader(NAME)(dict(rec, _window_of=(5.0, 6.0))) is None


def test_it_reads_nothing_where_the_engine_does_not_count(
        registry):  # noqa: F811
    _spans, g, _clock, rec = registry
    assert reader(NAME)(rec) is None                     # no counters
    # the parent's counters: a series and its readers, no walked_in_lanes
    g.counters["q5"] = types.SimpleNamespace(
        values={"keys_live": 3, "folded_by_key": 7},
        between=lambda names, t0, t1: (0,) * len(names),
        folded_between=lambda t0, t1: (1, 0))
    assert reader(NAME)(rec) is None
