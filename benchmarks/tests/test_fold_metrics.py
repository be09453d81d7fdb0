"""The two readers of the native pane fold (PR 29): ``fold_ns_per_event``
is the ``fold`` span's self time a handed-over event, without its
``flush`` child; ``fold_by_key_share`` is the engine's two counts cut at
the window, and nothing on a program that does not count them."""
import importlib
import math
import types

import pytest

from benchmarks.harness import program_spans
from benchmarks.tests.test_program_spans import run

MS = 1_000_000


def reader(name):
    return importlib.import_module("benchmarks.metrics." + name).read


@pytest.fixture
def registry(monkeypatch):
    """A span registry entry ``bench_x`` on a clock the test moves, and
    a run record whose window is [1 s, 3 s) of it."""
    spans = program_spans.span_layer()
    clock = types.SimpleNamespace(ns=0)
    monkeypatch.setattr(spans, "_now", lambda: clock.ns)
    g = spans.start_graph("bench_x")
    rec = {"config": {"name": "x"}, "events": 1000, "window_s": 2.0,
           "_window_of": (1.0, 3.0)}
    yield spans, g, clock, rec
    spans.end_graph(g)


def test_fold_ns_per_event_leaves_the_flush_child_out(registry):
    spans, g, clock, rec = registry
    tr = spans.Track("source")
    tr.graph = g
    g.tracks.append(tr)
    other = spans.Track("dispatch")     # a thread without a fold
    other.graph = g
    g.tracks.append(other)
    clock.ns = 1100 * MS
    other.begin("wf/q5/dispatch")
    for i in range(4):
        # a fold of 10 ms a chunk; in the last, 6 of them are a flush
        clock.ns = (1200 + 100 * i) * MS
        tr.begin("wf/q5/fold")
        if i == 3:
            clock.ns += 2 * MS
            tr.begin("wf/q5/flush")
            clock.ns += 6 * MS
            tr.end()
            clock.ns += 2 * MS
        else:
            clock.ns += 10 * MS
        tr.end()
    clock.ns = 2900 * MS
    other.end()
    # outside the window: not counted
    clock.ns = 3500 * MS
    tr.begin("wf/q5/fold")
    clock.ns += 50 * MS
    tr.end()
    clock.ns = 4000 * MS
    # 3 x 10 ms + 4 ms of fold proper over 1000 events
    assert reader("fold_ns_per_event")(rec) == pytest.approx(34e6 / 1000)
    assert reader("fold_ns_per_event")(dict(rec, events=0)) is None


def test_fold_ns_per_event_reads_nothing_without_a_fold(registry):
    spans, g, clock, rec = registry
    tr = spans.Track("source")
    tr.graph = g
    g.tracks.append(tr)
    clock.ns = 1200 * MS
    tr.begin("wf/q5/svc")
    clock.ns += 10 * MS
    tr.end()
    assert reader("fold_ns_per_event")(rec) is None
    assert reader("fold_ns_per_event")(
        dict(rec, config={"name": "no_such_graph"})) is None


def test_fold_by_key_share_cuts_the_counts_at_the_window(registry):
    spans, g, _clock, rec = registry
    c = g.counters_of("q5")
    names = spans.ENGINE_COUNTERS
    at = {n: i for i, n in enumerate(names)}

    def note(ms, by_key, singly):
        values = [0] * len(names)
        values[at["folded_by_key"]] = by_key
        values[at["folded_singly"]] = singly
        c.note(ms * MS, values)
    note(500, 1000, 0)          # before the window: the base
    note(1500, 3000, 500)
    note(2950, 9000, 1000)      # the last inside it
    note(3400, 20000, 20000)    # after it
    assert c.folded_between(1.0, 3.0) == (8000, 1000)
    assert reader("fold_by_key_share")(rec) == pytest.approx(8000 / 9000)
    # nothing noted before the window: counted from the engine's start
    assert c.folded_between(0.0, 3.0) == (9000, 1000)
    # nothing folded inside it
    assert reader("fold_by_key_share")(
        dict(rec, _window_of=(5.0, 6.0))) is None


def test_fold_by_key_share_reads_nothing_where_the_engine_does_not_count(
        registry):
    _spans, g, _clock, rec = registry
    assert reader("fold_by_key_share")(rec) is None       # no counters
    g.counters["q5"] = types.SimpleNamespace(     # the parent's Counters
        values={"keys_live": 3}, live_peak=lambda t0, t1: 3)
    assert reader("fold_by_key_share")(rec) is None
    assert reader("fold_by_key_share")(
        dict(rec, config={"name": "no_such_graph"})) is None


@pytest.mark.parametrize("workload,lo,hi", [
    ("nexmark_q5.sat", 1.0, 1.0),      # 1,024-event chunks in 2,048-id panes
    ("ysb.sat", 1.0, 1.0),
    ("nexmark_q5.paced", 0.3, 0.8),    # 1,000-event chunks: half straddle
])
def test_both_readers_in_a_whole_run(tiny_bench, manifest, workload, lo, hi):
    for name in ("fold_ns_per_event", "fold_by_key_share"):
        entry = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert workload in entry["workloads"]
        assert entry["layer"] == "host operators and native pane fold"
    result, notes, _gen = run(tiny_bench, workload)
    assert result["correct"] is True
    layer = notes["per_layer"]
    assert math.isfinite(layer["fold_ns_per_event"]) \
        and layer["fold_ns_per_event"] > 0
    assert lo <= layer["fold_by_key_share"] <= hi
