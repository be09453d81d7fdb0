"""``one_key_walk_share``: of the tuples handed to the window
engine in the window, the share its one-key walk took.  About 1 on a
bench with one global key (``nexmark_q7.sat`` at a cut size), 0 on one
with many keys a call; nothing on a program whose engine does not count
it (the parent's)."""
import io
import json
import os
import types

import pytest

from benchmarks.harness import runner
from benchmarks.tests.conftest import _patch
from benchmarks.tests.test_fold_metrics import MS, reader  # noqa: F401
from benchmarks.tests.test_fold_metrics import registry  # noqa: F401

NAME = "one_key_walk_share"
SEED = 2_147_483_719      # more than 32 signed bits hold
EIGHT = ("nexmark_q5.sat", "ysb.sat", "nexmark_q5.paced",
         "nexmark_q5_live.sat", "nexmark_q5_ooo.sat", "smartgrid_sg2.sat",
         "nexmark_q5.burst", "nexmark_q7.sat")
CUT_Q7 = {"win_events": 4096, "slide_events": 4096, "pool_rows": 1 << 15}


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
    assert tuple(entry["workloads"]) == EIGHT == tuple(
        w["name"] for w in manifest["workloads"])
    assert (entry["source"], entry["moves"], entry["better"],
            entry["unit"]) == ("program_counter", "events_per_s", "higher",
                               "share")
    assert entry["layer"] == "host operators and native pane fold"
    assert manifest["per_layer"][-1] is entry     # added at the end


@pytest.mark.parametrize("workload,share", [
    # one global key: every call after the warm-up's first
    ("nexmark_q7.sat", 1.0),
    # 111 auctions a call
    ("nexmark_q5.sat", 0.0)])
def test_it_reads_the_share_on_one_key_and_on_many(tiny_bench, workload,
                                                   share):
    manifest, bench_dir = tiny_bench
    _patch(os.path.join(bench_dir, "configs", "nexmark_q7", "config.json"),
           CUT_Q7)
    got = run(manifest, bench_dir, workload)
    assert got[NAME] == pytest.approx(share, abs=0.01)


def test_it_cuts_the_counts_at_the_window(registry):  # noqa: F811
    spans, g, _clock, rec = registry
    c = g.counters_of("q7")
    names = spans.ENGINE_COUNTERS

    def note(ms, one_key, by_key, singly, ignored):
        values = dict.fromkeys(names, 0)
        values.update(walked_one_key=one_key, folded_by_key=by_key,
                      folded_singly=singly, inputs_ignored=ignored)
        c.note(ms * MS, [values[n] for n in names])
    note(500, 0, 100, 0, 0)                 # before the window: the base
    note(1500, 65436, 65536, 0, 0)
    note(2950, 130000, 130972, 100, 0)      # the last inside it
    note(3400, 900000, 900000, 0, 0)        # after it
    assert reader(NAME)(rec) == pytest.approx(130000 / 130972)
    # no tuple handed over between the two notes: nothing
    assert reader(NAME)(dict(rec, _window_of=(5.0, 6.0))) is None


def test_it_reads_nothing_where_the_engine_does_not_count(
        registry):  # noqa: F811
    _spans, g, _clock, rec = registry
    assert reader(NAME)(rec) is None                     # no counters
    # the parent's counters: a series and its readers, no walked_one_key
    g.counters["q7"] = types.SimpleNamespace(
        values={"keys_live": 1, "folded_by_key": 7, "walked_in_lanes": 7},
        between=lambda names, t0, t1: (0,) * len(names),
        folded_between=lambda t0, t1: (1, 0))
    assert reader(NAME)(rec) is None
