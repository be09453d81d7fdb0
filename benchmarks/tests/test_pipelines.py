"""Each configuration's pipeline against its reference at a tiny size,
through the same ``run_cell`` a chip run takes (only the harness's look
for a chip is skipped), and the check failing where the timed path is
broken underneath or the control stands in the program's place."""
import io

import numpy as np
import pytest

from benchmarks.harness import check, runner

SEED = 2_147_483_659      # more than 32 signed bits hold


def run(tiny_bench, workload, seconds=0.6, tamper=None):
    manifest, bench_dir = tiny_bench
    err = io.StringIO()
    result = runner.run_cell(
        manifest, workload, SEED, seconds, False,
        runner.Doors(require_tpu=False, bench_dir=bench_dir,
                     out=io.StringIO(), err=err, tamper=tamper))
    return result, err.getvalue()


@pytest.mark.parametrize("workload", [
    "nexmark_q5.sat", "ysb.sat", "nexmark_q5.paced",
    "nexmark_q5_mesh4.sat"])
def test_cell_is_correct_and_prints_the_contracts_line(tiny_bench, workload):
    manifest, _ = tiny_bench
    if workload not in [w["name"] for w in manifest["workloads"]]:
        pytest.skip(f"{workload} is not in BENCHMARK.json")
    result, err = run(tiny_bench, workload)
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "compared"]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 100
    want = {m["name"] for m in manifest["end_to_end"]
            if workload in m.get("workloads", [workload])}
    assert set(result["metrics"]) == want
    assert all(v["value"] > 0 for v in result["metrics"].values())
    # the sink's fold is compared where the configuration has one
    folds = {"folds_wrong"} if workload.startswith("nexmark_q5") else set()
    assert set(result["compared"]) == set(check.LIMITS) - {"folds_wrong"} \
        | folds
    # each number compared stands beside its limit, last on stderr
    last = err.strip().splitlines()[-len(result["compared"]) - 1:]
    assert last[-1] == "check correct: True"
    assert all("(limit 0)" in row for row in last[:-1])


def window_logic(graph):
    from windflow_tpu.graph.fuse import iter_logics
    return next(lg for _, lg in iter_logics(graph)
                if hasattr(lg, "launched_batches"))


def alter_an_answer(graph, gen, sink):
    """One count altered where it is produced: as the engine emits it."""
    logic = window_logic(graph)
    real = logic._emit_results
    state = {"n": 0}

    def emit_results(results, *args, **kw):
        state["n"] += 1
        if state["n"] == 3:
            results = np.asarray(results).copy()
            results[0] += 1
        return real(results, *args, **kw)

    logic._emit_results = emit_results


def drop_half_a_chunk(graph, gen, sink):
    """Half of one chunk left out before the window operator sees it."""
    real = gen.make_batch
    state = {"n": 0}

    def make_batch(cols):
        state["n"] += 1
        if state["n"] == 5:
            cols = {k: v[: len(v) // 2] for k, v in cols.items()}
        return real(cols)

    gen.make_batch = make_batch


def lose_a_result_batch(graph, gen, sink):
    """One result batch never reaches the sink."""
    real = sink.__class__.__call__
    state = {"n": 0}

    def call(self, item):
        state["n"] += 1
        if state["n"] != 2:
            real(self, item)

    sink.__class__ = type("LossySink", (sink.__class__,),
                          {"__call__": call})


def repeat_a_result_batch(graph, gen, sink):
    real = sink.__class__.__call__

    def call(self, item):
        real(self, item)
        if item is not None and len(self.batches) == 2:
            real(self, item)

    sink.__class__ = type("EchoSink", (sink.__class__,), {"__call__": call})


def fold_without_the_hot_auction(graph, gen, sink):
    """The sink's fold never sees the hot auction's rows."""
    real = sink.fold.add

    def add(keys, wids, counts):
        cold = np.asarray(keys) != 1100
        real(np.asarray(keys)[cold], np.asarray(wids)[cold],
             np.asarray(counts)[cold])

    sink.fold.add = add


def leave_a_shard_out(graph, gen, sink):
    """The mesh's fourth shard answers nothing: its part of the result
    is left out of what comes back from the chips."""
    logic = window_logic(graph)
    real = logic.engine.compute_kf

    def compute_kf(values, starts, ends):
        out = np.asarray(real(values, starts, ends)).copy()
        out[-1] = 0
        return out

    logic.engine.compute_kf = compute_kf


@pytest.mark.parametrize("workload,fault,number", [
    ("nexmark_q5.sat", alter_an_answer, "rows_wrong"),
    ("nexmark_q5.sat", drop_half_a_chunk, "rows_wrong"),
    ("ysb.sat", drop_half_a_chunk, "events_uncounted"),
    ("ysb.sat", lose_a_result_batch, "rows_missing"),
    ("nexmark_q5.paced", repeat_a_result_batch, "rows_unexpected"),
    ("nexmark_q5.paced", fold_without_the_hot_auction, "folds_wrong"),
    ("nexmark_q5_mesh4.sat", leave_a_shard_out, "rows_wrong")])
def test_a_broken_timed_path_is_not_correct(tiny_bench, workload, fault,
                                            number):
    manifest, _ = tiny_bench
    if workload not in [w["name"] for w in manifest["workloads"]]:
        pytest.skip(f"{workload} is not in BENCHMARK.json")
    result, err = run(tiny_bench, workload, tamper=fault)
    assert result["correct"] is False
    assert result["compared"][number]["value"] > 0
    assert result["failed"] > 0 or number in ("events_uncounted",
                                              "folds_wrong")
    assert "check correct: False" in err


def q5(manifest):
    import json
    import os

    from benchmarks.tests.conftest import ROOT
    conf = next(c for c in manifest["configs"] if c["name"] == "nexmark_q5")
    with open(os.path.join(ROOT, conf["file"])) as f:
        cfg = json.load(f)
    return cfg, runner.load_module(
        os.path.join(ROOT, os.path.dirname(conf["file"]), "pipeline.py"),
        "q5_under_test")


def test_bids_follow_the_generators_law(manifest):
    cfg, q5_pipeline = q5(manifest)
    pool = q5_pipeline.make_pool(dict(cfg, pool_rows=1 << 20), SEED)
    ids, n = np.unique(pool["key"], return_counts=True)
    assert ids.min() == 1000 and ids.max() == 1110 and len(ids) == 111
    share = dict(zip(ids.tolist(), n / n.sum()))
    assert ids[n.argmax()] == 1100
    assert share[1100] == pytest.approx(0.5 + 0.5 / 111, abs=0.003)
    cold = np.array([v for k, v in share.items() if k != 1100])
    assert cold == pytest.approx(0.5 / 111, abs=0.0005)
    ids, n = np.unique(pool["bidder"], return_counts=True)
    assert ids.min() == 1000 and ids.max() == 2010 and len(ids) == 1011
    assert n.max() / n.sum() == pytest.approx(0.75, abs=0.003)
    assert ids[n.argmax()] == 2001
    assert q5_pipeline.n_key_ids(cfg) == 1111


def test_the_sinks_fold_is_the_references_whatever_the_batching(manifest):
    cfg, q5_pipeline = q5(manifest)
    rng = np.random.default_rng(3)
    keys = np.tile(np.arange(1000, 1010), 6)
    wids = np.repeat(np.arange(6), 10)
    counts = rng.integers(1, 50, 60).astype(float)
    counts[13] = counts[17] = 99.0          # a tie in window 1
    want = q5_pipeline.reference_fold((keys, wids, counts))
    assert want[1] == (99.0, frozenset({1003, 1007}))
    order = rng.permutation(60)
    fold = q5_pipeline.SinkFold(cfg)
    for part in np.array_split(order, 7):   # a window's rows in many batches
        fold.add(keys[part], wids[part], counts[part])
    assert fold.result() == want
    assert check.compare_folds(fold.result(), want) == {"folds_wrong": 0}
    short = {w: v for w, v in want.items() if w != 4}
    assert check.compare_folds(short, want) == {"folds_wrong": 1}
    assert check.compare_folds(want, short) == {"folds_wrong": 1}
    assert check.compare_folds({**want, 2: (1.0, frozenset({1}))}, want) \
        == {"folds_wrong": 1}


def test_the_programs_own_failure_counters_decide_too():
    k = np.array([0]), np.array([0]), np.array([5.0])
    numbers = check.compare(k, (*k, 5), 8, 8, {"dead_letters": 1})
    correct, compared = check.verdict(numbers, io.StringIO())
    assert not correct and compared["dead_letters"]["value"] == 1
    assert check.verdict(check.compare(k, (*k, 5), 8, 8, {}),
                         io.StringIO())[0]
