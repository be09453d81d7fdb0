"""The ``program_span`` metrics (PR 26) read the program's own span
layer: every reader gives a finite number in every cell it lists, the
window is found on the program's clock, idle gaps are named by program
span, and nothing that was under ``benchmarks/`` changed for it."""
import io
import json
import math
import os
import subprocess

import pytest

from benchmarks.harness import program_spans, runner

SEED = 2_147_483_693      # more than 32 signed bits hold
NEW = ("pacing_thread_busy_share", "span_coverage_share",
       "launch_dispatch_mean_ms", "launch_ready_wait_mean_ms",
       "result_emit_mean_ms", "longest_stall_ms")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BASE = "2d8e2475120e3281cece104a5a6457bcca843b75"   # the tree PR 26 began from


def run(tiny_bench, workload, seconds=0.6):
    manifest, bench_dir = tiny_bench
    out, held = io.StringIO(), {}
    result = runner.run_cell(
        manifest, workload, SEED, seconds, False,
        runner.Doors(require_tpu=False, bench_dir=bench_dir, out=out,
                     err=io.StringIO(),
                     tamper=lambda graph, gen, sink: held.update(gen=gen)))
    return result, json.loads(out.getvalue().splitlines()[0]), held["gen"]


@pytest.mark.parametrize("workload", ["nexmark_q5.sat", "ysb.sat",
                                      "nexmark_q5.paced"])
def test_every_new_reader_gives_a_number_where_it_is_listed(tiny_bench,
                                                            workload):
    manifest, _ = tiny_bench
    listed = [m["name"] for m in manifest["per_layer"]
              if m["name"] in NEW and workload in m["workloads"]]
    assert listed and all(
        m["source"] == "program_span" for m in manifest["per_layer"]
        if m["name"] in NEW)
    result, notes, _gen = run(tiny_bench, workload)
    assert result["correct"] is True
    for name in listed:
        assert math.isfinite(notes["per_layer"][name]), name
    assert 0 < notes["per_layer"]["pacing_thread_busy_share"] <= 1.05
    assert notes["per_layer"]["span_coverage_share"] \
        >= notes["per_layer"]["pacing_thread_busy_share"]
    if workload == "nexmark_q5.paced":
        # the stages sum to the round trip the counters give
        assert notes["per_layer"]["launch_dispatch_mean_ms"] \
            + notes["per_layer"]["launch_ready_wait_mean_ms"] \
            == pytest.approx(
                notes["per_layer"]["launch_roundtrip_mean_ms"], rel=0.1)


def test_the_window_is_found_on_the_programs_clock(tiny_bench):
    seconds = 0.6
    result, _notes, gen = run(tiny_bench, "nexmark_q5.sat", seconds)
    rec = {"setup_s": result["metrics"]["setup_s"]["value"],
           "window_s": seconds}
    t_open, t_close = program_spans.window_of(rec)
    assert abs(t_open - gen.t_open) < 0.02
    assert t_close - t_open == pytest.approx(seconds)
    assert program_spans.window_of(rec) == (t_open, t_close)   # kept


def test_a_program_without_the_span_layer_reads_nothing(monkeypatch):
    monkeypatch.setattr(program_spans, "span_layer", lambda: None)
    rec = {"setup_s": 1.0, "window_s": 1.0, "config": {"name": "x"}}
    assert program_spans.pacing_thread(rec) is None
    assert program_spans.launch_mean_ms(rec, "t_on_host", "t_picked") is None
    assert program_spans.longest_stall_ms(rec) is None


def test_gaps_are_named_by_program_span():
    ms = 1e6
    planes = {
        "/device:TPU:0": {"XLA Ops": [
            ("%a = fusion()", 10 * ms, 11 * ms),
            ("%b = fusion()", 40 * ms, 41 * ms),
            ("%c = fusion()", 90 * ms, 91 * ms)]},
        "/host:CPU": {
            "source": [("bench/window_open", 0.0, 0.1 * ms),
                       ("bench/source", 12 * ms, 30 * ms),
                       ("wf/q5/svc", 30 * ms, 39 * ms),
                       ("wf/q5/fold", 31 * ms, 38 * ms),
                       ("bench/window_close", 100 * ms, 100.1 * ms)],
            "dispatch": [("wf/q5/ready_wait", 12 * ms, 39.5 * ms),
                         ("python/ignored", 0.0, 100 * ms)],
        }}
    gaps = program_spans.gaps_by_span(planes)
    # 41..90 ms: nothing open, the span that ended last was ready_wait;
    # 11..40 ms: the middle, 25.5 ms, lies in bench/source and ready_wait
    assert gaps[0] == ["program/after_wf/q5/ready_wait",
                       pytest.approx(0.049)]
    assert gaps[1] == ["bench/source | wf/q5/ready_wait",
                       pytest.approx(0.029)]
    only_bench = program_spans.gaps_by_span(planes, prefixes=("bench/",))
    assert only_bench[1][0] == "bench/source"
    # 11..60 ms: the middle, 35.5 ms, lies in svc and, inside it, fold:
    # the innermost span of a thread's line names it
    inner = program_spans.gaps_by_span(
        {**planes, "/device:TPU:0": {"XLA Ops": [
            ("%a = fusion()", 10 * ms, 11 * ms),
            ("%b = fusion()", 60 * ms, 61 * ms)]}})
    assert inner[0][0] == "wf/q5/fold | wf/q5/ready_wait"


def test_nothing_that_was_under_benchmarks_is_edited():
    """The check ``test_extend.py`` makes in a copy, here on the real
    tree, while PR 26 is the working tree on the commit it began from:
    ``benchmarks/`` shows additions only and ``BENCHMARK.json`` appended
    entries only.  A later PR's tree is not this test's to judge."""
    def git(*args):
        return subprocess.run(["git", "-C", ROOT, *args],
                              capture_output=True, text=True)
    if git("rev-parse", "HEAD").stdout.strip() != BASE:
        pytest.skip("HEAD is not the commit PR 26 began from")
    changed = git("diff", "--name-status", BASE, "--",
                  "benchmarks").stdout.split("\n")
    assert [row for row in changed if row and not row.startswith("A")] == []
    old = json.loads(git("show", BASE + ":BENCHMARK.json").stdout)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        new = json.load(f)
    for key, was in old.items():
        if isinstance(was, list) and was and isinstance(was[0], dict):
            assert new[key][:len(was)] == was, key
        else:
            assert new[key] == was, key
