"""``host_buffers_per_launch`` (PR 36) reads the launch records' count of
host arrays handed to the device: 1.0 on a program that packs a launch,
nothing on a program whose records have no such field (the parent's),
cut at the window as ``launch_mean_ms`` cuts."""
import importlib
import types

from benchmarks.harness import program_spans

metric = importlib.import_module("benchmarks.metrics.host_buffers_per_launch")


def launches(stamps, **fields):
    """Launch records as the span layer keeps them, emitted, their
    results on the host at ``stamps``; ``fields`` on every one."""
    spans = program_spans.span_layer()
    g = spans.start_graph("bench_buffers_metric")
    ring = g.ring("op")
    for t in stamps:
        r = ring.open(1, 64, t - 0.004)
        r.t_picked, r.t_dispatched = t - 0.003, t - 0.002
        r.t_ready_seen = r.t_on_host = t
        r.t_emitted = t + 0.001
        for k, v in fields.items():
            setattr(r, k, v)
    return g, ring


def rec(t0, t1):
    return {"config": {"name": "buffers_metric"}, "window_s": t1 - t0,
            "_window_of": (t0, t1)}


def test_one_buffer_a_launch_reads_one():
    launches([10.1, 10.2, 10.3], buffers_in=1)
    assert metric.read(rec(10.0, 11.0)) == 1.0


def test_it_is_cut_at_the_window_as_the_stage_means_are():
    # the two launches outside the window handed over three arrays each
    _g, ring = launches([9.9, 10.1, 10.2, 11.5], buffers_in=1)
    ring.records[0].buffers_in = ring.records[3].buffers_in = 3
    assert metric.read(rec(10.0, 11.0)) == 1.0
    assert metric.read(rec(9.0, 12.0)) == 2.0
    assert program_spans.launch_mean_ms(
        rec(10.0, 11.0), "t_dispatched", "t_picked") is not None
    assert metric.read(rec(20.0, 21.0)) is None       # no launch in it


def test_records_without_the_field_read_nothing(monkeypatch):
    g, ring = launches([10.1, 10.2])
    old = [types.SimpleNamespace(
        t_emitted=r.t_emitted, t_on_host=r.t_on_host, bytes_in=64)
        for r in ring.records]
    ring.records.clear()
    ring.records.extend(old)
    assert metric.read(rec(10.0, 11.0)) is None
    # and no span layer at all: nothing, and no raise
    monkeypatch.setattr(program_spans, "span_layer", lambda: None)
    assert metric.read(rec(10.0, 11.0)) is None
