"""``key_touches_per_event`` and ``walked_ahead_share`` (PR 33): the
engine's counts of the keys its calls visited, cut at the window."""
import types

import pytest

from benchmarks.tests.test_fold_metrics import (MS, reader,  # noqa: F401
                                                registry)

NAMES = ("key_touches_per_event", "walked_ahead_share")


def test_both_cut_the_counts_at_the_window(registry):
    spans, g, _clock, rec = registry
    c = g.counters_of("q5")
    names = spans.ENGINE_COUNTERS
    at = {n: i for i, n in enumerate(names)}

    def note(ms, touches, ahead):
        values = [0] * len(names)
        values[at["key_touches"]] = touches
        values[at["walked_ahead"]] = ahead
        c.note(ms * MS, values)
    note(500, 100, 0)           # before the window: the base
    note(1500, 300, 150)
    note(2950, 600, 450)        # the last inside it
    note(3400, 9000, 9000)      # after it
    assert c.touched_between(1.0, 3.0) == (500, 450)
    assert reader("key_touches_per_event")(rec) \
        == pytest.approx(500 / rec["events"])
    assert reader("walked_ahead_share")(rec) == pytest.approx(0.9)
    assert reader("key_touches_per_event")(dict(rec, events=0)) is None
    # no key visited inside it
    assert reader("walked_ahead_share")(
        dict(rec, _window_of=(5.0, 6.0))) is None


def test_both_read_nothing_where_the_engine_does_not_count(registry):
    _spans, g, _clock, rec = registry
    for name in NAMES:
        assert reader(name)(rec) is None                 # no counters
    g.counters["q5"] = types.SimpleNamespace(     # the parent's Counters
        values={"keys_live": 3}, folded_between=lambda t0, t1: (1, 0),
        moved_between=lambda t0, t1: {"late_accepted": 0})
    for name in NAMES:
        assert reader(name)(rec) is None
        assert reader(name)(dict(rec, config={"name": "no_such"})) is None


def test_the_manifest_names_them(manifest):
    entries = {m["name"]: m for m in manifest["per_layer"]}
    cells = [w["name"] for w in manifest["workloads"]]
    assert entries["key_touches_per_event"]["workloads"] == cells
    assert entries["walked_ahead_share"]["workloads"] == [
        "nexmark_q5_live.sat", "nexmark_q5_ooo.sat", "nexmark_q5.sat"]
    for name in NAMES:
        assert entries[name]["moves"] == "events_per_s"
        assert entries[name]["source"] == "program_counter"
        assert entries[name]["layer"] \
            == "host operators and native pane fold"
