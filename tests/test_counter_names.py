"""What the window path counts, by name, as its readers see it: the key
list and order of a window operator's ``Counters`` row in the stats
JSON, of a ``Spans.Launches`` summary and of its slowest launch's row,
and every ``windflow_engine_*`` family on ``/metrics`` with its kind and
help text, in order.  Two graphs on the native lane: a filter ahead of
a keyed count (so the selection's counters are in the row) and a user's
window function (the ``tuples`` engine).  The lists are literals: they
are what the stats JSON and ``/metrics`` held before the counters were
declared in one table, and a declaration that moves a name, a kind or a
help text fails here."""
import json

import numpy as np
import pytest

import windflow_tpu as wf
from windflow_tpu.core.tuples import TupleBatch
from windflow_tpu.operators.basic_ops import Sink
from windflow_tpu.operators.batch_ops import BatchFilter, BatchSource
from windflow_tpu.operators.tpu.farms_tpu import KeyFarmTPU
from windflow_tpu.operators.tpu.win_seq_tpu import WinSeqTPU
from windflow_tpu.runtime.native import native_available
from windflow_tpu.telemetry.metrics import render_openmetrics

pytestmark = pytest.mark.skipif(not native_available(),
                                reason="native library not built")

COUNTERS = [
    "keys_opened", "keys_evicted", "keys_live", "keys_live_peak",
    "windows_fired", "folded_by_key", "folded_singly", "late_accepted",
    "anchors_moved", "inputs_ignored", "key_touches", "walked_ahead",
    "rings_spilled", "panes_staged", "windows_staged", "walked_in_lanes",
    "ingest_ns", "tuple_walk_ns", "key_walk_ns", "stage_ns",
    "panes_shifted", "copy_out_ns", "argmax_ns", "max_raised", "tie_ids",
    "walked_one_key", "tuples_staged"]
SELECTED = ["cols_selected", "cols_gathered", "rows_by_selection"]
SUMMARY = [
    "Operator", "Launches", "Buffers_in", "Fn_elems", "Tile_elems",
    "Fn_rows_dma", "Fn_rows_sorted", "queue_wait", "dispatch",
    "ready_wait", "block", "emit", "pack", "call", "handoff", "Collected",
    "Slowest"]
LAUNCH_ROW = [
    "Seq", "Chunk_seq", "Bytes_in", "Bytes_out", "Buffers_in", "Rows",
    "Fn_elems", "Tile_elems", "Fn_rows_dma", "Fn_rows_sorted", "Picked_s",
    "Collected", "queue_wait", "dispatch", "ready_wait", "block", "emit",
    "pack", "call", "handoff"]
FAMILIES = [
    ("keys_opened", "counter", "key states the window engine created"),
    ("keys_evicted", "counter", "key states the window engine evicted"),
    ("keys_live", "gauge", "key states the window engine holds"),
    ("keys_live_peak", "gauge",
     "most key states the window engine held at once"),
    ("windows_fired", "counter", "windows the engine fired"),
    ("folded_by_key", "counter", "tuples the engine folded with their "
     "key's others of the call in one combine"),
    ("folded_singly", "counter", "tuples the engine folded one by one"),
    ("late_accepted", "counter", "tuples the engine accepted whose stamp "
     "lay behind its stream time when they came"),
    ("anchors_moved", "counter", "times a live key's anchor moved back "
     "for a tuple earlier than the key's first to arrive"),
    ("inputs_ignored", "counter",
     "tuples the engine dropped behind a window that had fired"),
    ("key_touches", "counter",
     "keys the engine's calls visited (a key once a call)"),
    ("walked_ahead", "counter", "of those, the visits of a call that ran "
     "ahead of itself (a table beyond the caches)"),
    ("rings_spilled", "counter",
     "pane rings that left their key state for a block of their own"),
    ("panes_staged", "counter",
     "pane partials the engine's flush copied into launch buffers"),
    ("windows_staged", "counter",
     "windows the engine's flush staged for a launch"),
    ("walked_in_lanes", "counter", "tuples of the engine's calls whose "
     "walk was split over the host lanes"),
    ("walked_one_key", "counter", "tuples of the engine's calls that "
     "carried one key, walked once with no probe"),
    ("tuples_staged", "counter", "tuples a tuples engine's flush copied "
     "into launch buffers for a user's window function"),
    ("cols_selected", "counter",
     "columns the selected batches the operator ingested carried"),
    ("cols_gathered", "counter",
     "columns of selected batches that were gathered on read"),
    ("rows_by_selection", "counter",
     "rows the window store read through a batch's selection"),
    ("ingest_ns", "counter", "nanoseconds inside the engine's batch "
     "calls, less their open and trigger"),
    ("tuple_walk_ns", "counter", "of ingest_ns, the walks that cost by "
     "the tuple (gather, note_late, fold_singly)"),
    ("key_walk_ns", "counter", "of ingest_ns, the walks that cost by the "
     "key (prepare of the keys a call did not open, settle)"),
    ("stage_ns", "counter", "nanoseconds inside the engine's flush, less "
     "its evict (staging the spans, retiring the rings, the rows)"),
    ("panes_shifted", "counter",
     "ring elements the engine's retire() moved down after a staging"),
    ("copy_out_ns", "counter", "nanoseconds copying a flush's columns "
     "out of the engine (perf_counter_ns round the copies, "
     "NativeWindowEngine.flush)"),
    ("argmax_ns", "counter", "of ingest_ns, an argmax engine's own walks "
     "(each key's largest value of a call and the ids at it; the "
     "one-by-one fold), out of what would be tuple_walk_ns; where the "
     "one-key walk took the call, the one-by-one fold alone"),
    ("max_raised", "counter", "times an argmax engine set or raised a "
     "pane's held maximum (and with it its ids)"),
    ("tie_ids", "counter", "ids beyond the first at the maxima of the "
     "windows an argmax engine staged"),
]


def median(gwid, cols, mask):
    import jax.numpy as jnp
    n = mask.sum()
    v = jnp.sort(jnp.where(mask, cols["value"], jnp.inf))
    return ((v[(n - 1) // 2] + v[n // 2]) / 2).astype(jnp.float32)


def report_of(lane):
    """The stats JSON of one small graph: 20 chunks of 500 tuples over 9
    keys, through a keyed count behind a filter (``native``) or a
    user's median (``tuples``), windows of 256 sliding by 128."""
    rng = np.random.RandomState(1)
    sent = {"i": 0}

    def body():
        if sent["i"] >= 20:
            return None
        sent["i"] = i = sent["i"] + 1
        ts = np.arange(i * 500, i * 500 + 500, dtype=np.int64)
        return TupleBatch({"key": rng.randint(0, 9, 500).astype(np.int64),
                           "id": ts, "ts": ts, "value": np.ones(500)})
    g = wf.PipeGraph("counter_names_" + lane, wf.Mode.DEFAULT)
    pipe = g.add_source(BatchSource(body))
    if lane == "native":
        pipe = pipe.chain(BatchFilter(lambda b: b.key % 2 == 0)).add(
            KeyFarmTPU("count", 256, 128, wf.WinType.TB, name="counts",
                       emit_batches=True))
    else:
        pipe = pipe.add(WinSeqTPU(median, 256, 128, wf.WinType.TB,
                                  name="medians", emit_batches=True))
    pipe.add_sink(Sink(lambda b: None))
    g.run()
    return json.loads(g.stats.to_json())


@pytest.mark.parametrize("lane", ["native", "tuples"])
def test_the_counters_launches_and_families_keep_their_names(lane):
    report = report_of(lane)
    assert report["Schema_version"] == 26
    rows = [r["Counters"] for r in report["Spans"]["Operators"]
            if "Counters" in r]
    assert rows
    for row in rows:
        assert list(row) == COUNTERS + (SELECTED if lane == "native"
                                        else [])
    (summary,) = report["Spans"]["Launches"]
    assert summary["Launches"] > 0
    assert list(summary) == SUMMARY
    assert list(summary["Slowest"]) == LAUNCH_ROW
    text = render_openmetrics({"a": {"report": report}})
    said = [line for line in text.splitlines()
            if line.startswith("# ") and "windflow_engine_" in line]
    assert said == [line for name, kind, text_ in FAMILIES
                    for line in (f"# TYPE windflow_engine_{name} {kind}",
                                 f"# HELP windflow_engine_{name} {text_} "
                                 "(span layer)")]

