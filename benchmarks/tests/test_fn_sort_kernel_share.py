"""``fn_sort_kernel_share`` reads the launch records' ``fn_rows_sorted``:
the share of a user's function's rows whose every sort the Pallas
sorting network made, over the launches that ran a user's function, cut
at the window; nothing where no record counts it (the parent's) or no
launch ran a user's function; and the manifest lists it beside the
tile's DMA share."""
import importlib
import types

import pytest

from benchmarks.harness import program_spans

metric = importlib.import_module("benchmarks.metrics.fn_sort_kernel_share")
CELL = "smartgrid_median.sat"


def launches(fields):
    """One emitted launch record a dict of ``fields``, their results on
    the host in the window [10, 11)."""
    spans = program_spans.span_layer()
    g = spans.start_graph("bench_sort_metric")
    ring = g.ring("op")
    for i, f in enumerate(fields):
        t = 10.1 + 0.1 * i
        r = ring.open(1, 64, t - 0.004)
        r.t_picked, r.t_dispatched = t - 0.003, t - 0.002
        r.t_ready_seen = r.t_on_host = t
        r.t_emitted = t + 0.001
        for k, v in f.items():
            setattr(r, k, v)
    return ring


def rec():
    return {"config": {"name": "sort_metric"}, "window_s": 1.0,
            "_window_of": (10.0, 11.0)}


def fn_launch(rows, kernel):
    return {"rows": rows, "fn_elems": 3600 * rows,
            "tile_elems": 4096 * 4096, "fn_rows_dma": rows,
            "fn_rows_sorted": kernel}


@pytest.mark.parametrize("counted,share", [
    ([65_875, 63_750], 1.0),       # every block by the network
    ([65_875 - 4_096, 63_750], (129_625 - 4_096) / 129_625),  # a fallback
    ([0, 0], 0.0)])                # XLA's sort
def test_it_reads_the_rows_the_network_sorted(counted, share):
    launches([fn_launch(n, k) for n, k in zip((65_875, 63_750), counted)])
    assert metric.read(rec()) == pytest.approx(share)


def test_a_builtins_launches_do_not_count():
    launches([fn_launch(4096, 4096), {"rows": 9000}])
    assert metric.read(rec()) == 1.0


def test_no_users_function_reads_nothing():
    launches([{"rows": 9000}, {"rows": 100}])
    assert metric.read(rec()) is None


def test_records_without_the_field_read_nothing(monkeypatch):
    ring = launches([fn_launch(4096, 4096)])
    old = [types.SimpleNamespace(
        t_emitted=r.t_emitted, t_on_host=r.t_on_host, rows=r.rows,
        fn_elems=r.fn_elems, tile_elems=r.tile_elems,
        fn_rows_dma=r.fn_rows_dma) for r in ring.records]
    ring.records.clear()
    ring.records.extend(old)
    assert metric.read(rec()) is None
    # and no span layer at all: nothing, and no raise
    monkeypatch.setattr(program_spans, "span_layer", lambda: None)
    assert metric.read(rec()) is None


def test_the_manifest_lists_it_beside_the_dma_share(manifest):
    entries = {m["name"]: m for m in manifest["per_layer"]}
    m = entries["fn_sort_kernel_share"]
    assert (m["layer"], m["moves"], m["source"], m["better"]) == (
        "XLA programs", "events_per_s", "program_counter", "higher")
    assert m["workloads"] == [CELL] == entries["fn_tile_dma_share"][
        "workloads"]
    assert manifest["per_layer"][-1] is m
