"""A later PR adds a configuration, a traffic mix and a per-layer metric
as new files and new entries of ``BENCHMARK.json`` and edits no file that
was there: this test does so in a temporary copy and runs the new cell."""
import hashlib
import io
import json
import os

from benchmarks.harness import runner

PIPELINE = '''
"""Q5 with tumbling windows: the wiring and reference of nexmark_q5."""
import os

from benchmarks.harness.runner import load_module

_q5 = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               os.pardir, "nexmark_q5", "pipeline.py"),
                  "q5_for_tumbling")
make_pool, build, reference = _q5.make_pool, _q5.build, _q5.reference
SinkFold, reference_fold = _q5.SinkFold, _q5.reference_fold
launches, device_time_ms = _q5.launches, _q5.device_time_ms
logical_bytes_per_row = _q5.logical_bytes_per_row
'''

METRIC = '''
"""Result rows received in the window per chunk handed over."""


def read(rec):
    return rec["rows"] / rec["chunks"] if rec["chunks"] else None
'''


def digest(root):
    out = {}
    for d, dirs, files in os.walk(root):
        dirs[:] = [x for x in dirs if x not in ("out", "__pycache__")]
        for f in files:
            path = os.path.join(d, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_a_new_cell_is_files_and_entries_only(tiny_bench):
    manifest, bench_dir = tiny_bench
    before = digest(bench_dir)

    conf_dir = os.path.join(bench_dir, "configs", "q5_tumbling")
    os.makedirs(conf_dir)
    with open(os.path.join(bench_dir, "configs", "nexmark_q5",
                           "config.json")) as f:
        cfg = json.load(f)
    cfg.update(name="q5_tumbling", win_events=2048, slide_events=2048)
    with open(os.path.join(conf_dir, "config.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(conf_dir, "pipeline.py"), "w") as f:
        f.write(PIPELINE)
    with open(os.path.join(bench_dir, "traffic", "paced_slow.json"),
              "w") as f:
        # a mix the benchmark does not have: bursts, 0.1 s on, 0.1 s off
        json.dump({"mode": "paced", "phases": [[0.1, 200_000], [0.1, 0]],
                   "chunk_events": 700, "warmup_s": 0.3,
                   "warmup_min_result_batches": 2, "settle_lag_chunks": 1.0,
                   "settle_s": 0.2, "settle_max_s": 2.0}, f)
    with open(os.path.join(bench_dir, "metrics", "rows_per_chunk.py"),
              "w") as f:
        f.write(METRIC)

    manifest = json.loads(json.dumps(manifest))
    manifest["configs"].append({
        "name": "q5_tumbling", "source": cfg["source"],
        "file": "benchmarks/configs/q5_tumbling/config.json",
        "reduced": cfg["reduced"], "why": "tumbling"})
    manifest["workloads"].append({
        "name": "q5_tumbling.paced_slow", "config": "q5_tumbling",
        "traffic": "paced_slow", "chips": 1, "why": "a test's cell"})
    manifest["per_layer"].append({
        "name": "rows_per_chunk", "unit": "rows/chunk", "better": "higher",
        "source": "host_clock", "layer": "whole path",
        "moves": "events_per_s", "workloads": ["q5_tumbling.paced_slow"]})

    out = io.StringIO()
    result = runner.run_cell(
        manifest, "q5_tumbling.paced_slow", 11, 0.6, False,
        runner.Doors(require_tpu=False, bench_dir=bench_dir, out=out,
                     err=io.StringIO()))
    assert result["correct"] is True
    assert result["metrics"]["events_per_s"]["value"] > 90_000
    # the paced-only end-to-end metric is not this cell's: it is not listed
    assert set(result["metrics"]) == {"events_per_s", "setup_s"}
    notes = json.loads(out.getvalue().splitlines()[-1])
    assert notes["per_layer"]["rows_per_chunk"] > 0
    # metrics listed for other cells alone are left out of this one's line
    assert "generator_lag_p95_ms" not in notes["per_layer"]
    after = digest(bench_dir)
    assert {k: v for k, v in after.items() if k in before} == before
    assert set(after) - set(before) == {
        "configs/q5_tumbling/config.json", "configs/q5_tumbling/pipeline.py",
        "traffic/paced_slow.json", "metrics/rows_per_chunk.py"}
