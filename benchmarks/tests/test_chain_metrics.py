"""The two readers of the selected batch (PR 31): ``chain_ns_per_event``
is the ``svc`` span a source's chain takes on the source's own thread a
handed-over event; ``gather_avoided_share`` is the share of the columns
that selected batches carried to the window operator and nobody
gathered, and nothing on a program that does not count them."""
import math
import types

import pytest

from benchmarks.tests.test_fold_metrics import MS, reader, registry  # noqa: F401
from benchmarks.tests.test_program_spans import run


def track(spans, g, name):
    tr = spans.Track(name)
    tr.graph = g
    g.tracks.append(tr)
    return tr


def test_chain_ns_per_event_is_the_sources_svc_alone(registry):  # noqa: F811
    spans, g, clock, rec = registry
    src = track(spans, g, "source")
    disp = track(spans, g, "dispatch")     # a svc that is no source's
    clock.ns = 1000 * MS
    src.begin("wf/pipe0/src/body")
    disp.begin("wf/pipe0/sink/svc")
    for i in range(4):
        # a chunk: 5 ms of the generator, then the chain's svc of 12 ms
        # of which 8 are the window engine's fold (a span of its own)
        clock.ns = (1200 + 100 * i) * MS
        src.begin("wf/pipe0/src/svc")
        clock.ns += 2 * MS
        src.begin("wf/pipe0/count/fold")
        clock.ns += 8 * MS
        src.end()
        clock.ns += 2 * MS
        src.end()
    clock.ns = 2900 * MS
    disp.end()
    # outside the window: not counted
    clock.ns = 3500 * MS
    src.begin("wf/pipe0/src/svc")
    clock.ns += 50 * MS
    src.end()
    clock.ns = 4000 * MS
    src.end()
    # 4 x 4 ms of the chain proper over 1000 events; the sink's svc on
    # the other thread (1.8 s) is no source's
    assert reader("chain_ns_per_event")(rec) == pytest.approx(16e6 / 1000)
    assert reader("chain_ns_per_event")(dict(rec, events=0)) is None


def test_chain_ns_per_event_reads_nothing_without_a_chain(registry):  # noqa: F811
    spans, g, clock, rec = registry
    src = track(spans, g, "source")
    clock.ns = 1200 * MS
    src.begin("wf/pipe0/src/body")          # a source with no chain
    clock.ns += 10 * MS
    src.begin("wf/pipe0/count/fold")
    clock.ns += 10 * MS
    src.end()
    src.end()
    other = track(spans, g, "other")
    other.begin("wf/pipe0/map/svc")         # a svc, but not a source's
    clock.ns += 10 * MS
    other.end()
    assert reader("chain_ns_per_event")(rec) is None
    assert reader("chain_ns_per_event")(
        dict(rec, config={"name": "no_such_graph"})) is None


def test_gather_avoided_share_reads_the_operators_counts(registry):  # noqa: F811
    _spans, g, _clock, rec = registry
    assert reader("gather_avoided_share")(rec) is None      # no counters
    c = g.counters_of("count")
    assert reader("gather_avoided_share")(rec) is None      # no selection
    c.selected(5, 1, 21845)
    c.selected(5, 1, 21900)
    assert (c.cols_selected, c.cols_gathered, c.rows_by_selection) \
        == (10, 2, 43745)
    assert reader("gather_avoided_share")(rec) == pytest.approx(0.8)
    g.counters_of("other").selected(5, 5, 10)     # all of them gathered
    assert reader("gather_avoided_share")(rec) == pytest.approx(1 - 7 / 15)


def test_gather_avoided_share_reads_nothing_where_it_is_not_counted(
        registry):  # noqa: F811
    _spans, g, _clock, rec = registry
    g.counters["count"] = types.SimpleNamespace(     # the parent's Counters
        values={"keys_live": 3}, folded_between=lambda t0, t1: (1, 0))
    assert reader("gather_avoided_share")(rec) is None
    assert reader("gather_avoided_share")(
        dict(rec, config={"name": "no_such_graph"})) is None


@pytest.mark.parametrize("workload,avoided", [
    ("ysb.sat", 0.8),              # the join reads `key`, one of five
    ("nexmark_q5_live.sat", None),  # a map and no filter: no selection
])
def test_both_readers_in_a_whole_run(tiny_bench, manifest, workload,
                                     avoided):
    entries = {m["name"]: m for m in manifest["per_layer"]}
    assert workload in entries["chain_ns_per_event"]["workloads"]
    assert (workload in entries["gather_avoided_share"]["workloads"]) \
        == (avoided is not None)
    for name in ("chain_ns_per_event", "gather_avoided_share"):
        assert entries[name]["layer"] \
            == "host operators and native pane fold"
        assert entries[name]["moves"] == "events_per_s"
    result, notes, _gen = run(tiny_bench, workload)
    assert result["correct"] is True
    layer = notes["per_layer"]
    assert math.isfinite(layer["chain_ns_per_event"]) \
        and layer["chain_ns_per_event"] > 0
    if avoided is None:
        assert "gather_avoided_share" not in layer
    else:
        assert layer["gather_avoided_share"] == pytest.approx(avoided)
