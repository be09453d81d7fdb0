"""Durability-plane tests (docs/RESILIENCE.md "Exactly-once epochs"):
aligned epoch barriers riding the channel planes, atomic manifest
commits, the transactional/idempotent sink contract, epoch-aware
restarts, and the kill-restart-verify chaos proofs -- results bitwise
equal to an uninterrupted run with zero duplicate or lost sink effects
and the conservation ledger balanced across the restart."""
import collections
import json
import os
import pickle
import time

import pytest

import windflow_tpu as wf
from windflow_tpu.core import BasicRecord, DurabilityConfig
from windflow_tpu.core.basic import Pattern, RoutingMode
from windflow_tpu.durability import (EpochStore, EpochTaggedStore,
                                     run_with_epochs)
from windflow_tpu.operators.base import Operator, StageSpec
from windflow_tpu.resilience import FaultPlan
from windflow_tpu.runtime.emitters import StandardEmitter
from windflow_tpu.runtime.node import SourceLoopLogic


# ---------------------------------------------------------------------------
# helpers: an offset-checkpointable record source (exactly-once needs
# sources that rewind -- the same contract ReplaySource/SyntheticSource
# implement) and deterministic oracles
# ---------------------------------------------------------------------------

N_KEYS = 4


def _val(i: int) -> float:
    return float(i % 7)


class _CkptSourceLogic(SourceLoopLogic):
    def __init__(self, n, pace_every=128, pace_s=0.001):
        self.i = 0
        self.n = n
        self.pace_every = pace_every
        self.pace_s = pace_s
        super().__init__(self._step)

    def _step(self, emit):
        i = self.i
        if i >= self.n:
            return False
        if self.pace_every and i % self.pace_every == 0:
            time.sleep(self.pace_s)
        emit(BasicRecord(i % N_KEYS, i // N_KEYS, i, _val(i)))
        self.i = i + 1
        return True

    def state_dict(self):
        return {"i": self.i}

    def load_state(self, st):
        self.i = st["i"]

    def progress_frontier(self):
        return self.i


class CkptSource(Operator):
    """Offset-checkpointable paced source for the chaos suites."""

    def __init__(self, n, name="ckpt_source", pace_every=128,
                 pace_s=0.001):
        super().__init__(name, 1, RoutingMode.NONE, Pattern.SOURCE)
        self.n = n
        self.pace_every = pace_every
        self.pace_s = pace_s

    def stages(self):
        logic = _CkptSourceLogic(self.n, self.pace_every, self.pace_s)
        return [StageSpec(self.name, [logic], StandardEmitter(),
                          self.routing)]


def _acc_oracle(n):
    """Per-key (id, rolling sum) sequences of the accumulator pipeline."""
    out = collections.defaultdict(list)
    sums = collections.defaultdict(float)
    for i in range(n):
        k = i % N_KEYS
        sums[k] += _val(i)
        out[k].append((i // N_KEYS, sums[k]))
    return out


def _per_key(effects):
    got = collections.defaultdict(list)
    for k, tid, v in effects:
        got[k].append((tid, v))
    return got


def _acc_graph(n, tmp, effects, fault_plan=None, interval=0.03,
               pace_every=64, pace_s=0.004, acc_par=2, elastic=None,
               delta=False):
    """source -> keyed map (par 2: multi-producer KEYBY alignment) ->
    keyed accumulator -> transactional sink."""
    def acc(t, a):
        a.value += t.value

    def sink(r):
        if r is not None:
            effects.append((r.key, r.id, r.value))

    cfg = wf.RuntimeConfig(
        durability=DurabilityConfig(epoch_interval_s=interval,
                                    path=os.path.join(tmp, "epochs"),
                                    delta=delta),
        fault_plan=fault_plan)
    g = wf.PipeGraph("dur_acc", wf.Mode.DEFAULT, config=cfg)
    accb = wf.AccumulatorBuilder(acc) \
        .with_initial_value(BasicRecord(value=0.0)) \
        .with_parallelism(acc_par)
    if elastic is not None:
        accb = wf.AccumulatorBuilder(acc) \
            .with_initial_value(BasicRecord(value=0.0)) \
            .with_elasticity(*elastic)
    g.add_source(CkptSource(n, pace_every=pace_every, pace_s=pace_s)) \
        .add(wf.MapBuilder(lambda t: None).with_key_by()
             .with_parallelism(2).build()) \
        .add(accb.build()) \
        .add_sink(wf.SinkBuilder(sink).with_exactly_once().build())
    return g


def _assert_exactly_once(effects, n, graph):
    """Zero duplicate/lost effects, per-key sequences equal the
    uninterrupted oracle, ledger balanced in the (final) run."""
    assert len(effects) == n, (len(effects), n)
    assert len(set(effects)) == len(effects), "duplicate sink effects"
    oracle = _acc_oracle(n)
    got = _per_key(effects)
    assert set(got) == set(oracle)
    for k in oracle:
        assert got[k] == oracle[k], (k, got[k][:4], oracle[k][:4])
    cons = json.loads(graph.stats.to_json())["Conservation"]
    assert cons["Violations_total"] == 0, cons["Violations"]
    assert cons["Edges_balanced"], cons
    # barriers are subtracted from the graph-wide roll-up: the ledger
    # identity holds in stream tuples across the restart
    assert cons["Sources_emitted"] == cons["Sinks_consumed"] \
        + cons["Dead_letters"] + cons["Shed_tuples"], cons


# ---------------------------------------------------------------------------
# manifest store (crash-safe commits, tolerant reads)
# ---------------------------------------------------------------------------

def test_epoch_store_atomic_commit_and_retention(tmp_path):
    store = EpochStore(str(tmp_path / "ep"), retained=2)
    for e in (1, 2, 3):
        path, nbytes = store.commit(e, {"n": pickle.dumps({"x": e})},
                                    {"src": e * 10})
        assert os.path.exists(path) and nbytes > 0
        assert not os.path.exists(path + ".tmp")  # temp renamed away
    # retention keeps only the newest 2
    names = sorted(os.listdir(str(tmp_path / "ep")))
    assert names == ["epoch-000000000002.ckpt", "epoch-000000000003.ckpt"]
    e, payload = store.latest()
    assert e == 3 and payload["offsets"] == {"src": 30}


def test_epoch_store_skips_truncated_manifest(tmp_path):
    """A truncated newest manifest (the crash save_graph used to allow)
    falls back to the previous epoch with a flight event instead of an
    unpickling crash."""
    from windflow_tpu.telemetry import FlightRecorder
    store = EpochStore(str(tmp_path / "ep"), retained=4)
    store.commit(1, {"n": pickle.dumps({"x": 1})}, {})
    store.commit(2, {"n": pickle.dumps({"x": 2})}, {})
    p2 = store.manifest_path(2)
    blob = open(p2, "rb").read()
    with open(p2, "wb") as f:
        f.write(blob[:len(blob) // 2])   # torn mid-write
    flight = FlightRecorder(64)
    e, payload = store.latest(flight=flight)
    assert e == 1 and pickle.loads(payload["states"]["n"]) == {"x": 1}
    evs = [ev for ev in flight.snapshot() if ev["kind"] == "epoch_abort"]
    assert evs and evs[0]["reason"] == "manifest_corrupt"
    assert evs[0]["epoch"] == 2


def test_epoch_store_rejects_foreign_and_newer_schema(tmp_path):
    store = EpochStore(str(tmp_path / "ep"))
    with open(store.manifest_path(1), "wb") as f:
        pickle.dump({"magic": "something-else"}, f)
    with pytest.raises(RuntimeError, match="not a windflow epoch"):
        store.load(1)
    with open(store.manifest_path(2), "wb") as f:
        pickle.dump({"magic": "windflow-epoch-manifest", "schema": 99,
                     "states": {}}, f)
    with pytest.raises(RuntimeError, match="newer than this runtime"):
        store.load(2)


# ---------------------------------------------------------------------------
# snapshot header satellite (utils/checkpoint.py)
# ---------------------------------------------------------------------------

def test_snapshot_header_and_actionable_errors(tmp_path):
    from windflow_tpu.utils.checkpoint import (read_snapshot,
                                               write_snapshot)
    path = str(tmp_path / "s.pkl")
    write_snapshot(path, {"a": {"x": 1}}, epoch=7)
    payload = pickle.load(open(path, "rb"))
    assert payload["magic"] == "windflow-graph-state"
    assert payload["epoch"] == 7
    assert read_snapshot(path) == {"a": {"x": 1}}
    assert not os.path.exists(path + ".tmp")
    # truncation -> actionable error, not an UnpicklingError traceback
    blob = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(blob[:len(blob) // 2])
    with pytest.raises(RuntimeError, match="truncated or corrupt"):
        read_snapshot(path)
    # foreign magic -> actionable
    with open(path, "wb") as f:
        pickle.dump({"magic": "other-tool"}, f)
    with pytest.raises(RuntimeError, match="not a windflow graph"):
        read_snapshot(path)
    # newer schema -> actionable
    with open(path, "wb") as f:
        pickle.dump({"magic": "windflow-graph-state", "schema": 99,
                     "states": {}}, f)
    with pytest.raises(RuntimeError, match="newer than this runtime"):
        read_snapshot(path)
    # legacy header-less state maps still load (tolerant contract)
    with open(path, "wb") as f:
        pickle.dump({"node": {"x": 2}}, f)
    assert read_snapshot(path) == {"node": {"x": 2}}


def test_restore_graph_rejects_truncated_snapshot(tmp_path):
    """End to end through restore_graph: a torn snapshot names the file
    and loads nothing (the pre-atomic failure mode)."""
    from windflow_tpu.utils.checkpoint import restore_graph, save_graph

    def build():
        def acc(t, a):
            a.value += t.value
        state = {"i": 0}

        def src(shipper, ctx):
            if state["i"] >= 10:
                return False
            shipper.push(BasicRecord(0, state["i"], state["i"], 1.0))
            state["i"] += 1
            return True
        g = wf.PipeGraph("hdr")
        g.add_source(wf.SourceBuilder(src).build()) \
            .add(wf.AccumulatorBuilder(acc)
                 .with_initial_value(BasicRecord(value=0.0)).build()) \
            .add_sink(wf.SinkBuilder(lambda r: None).build())
        return g

    g1 = build()
    g1.run()
    path = str(tmp_path / "g.pkl")
    save_graph(g1, path)
    blob = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(blob[:len(blob) - 8])
    with pytest.raises(RuntimeError, match="truncated or corrupt"):
        restore_graph(build(), path)


# ---------------------------------------------------------------------------
# fault-plan epoch actions
# ---------------------------------------------------------------------------

def test_faultplan_epoch_actions_bind_and_fire():
    from windflow_tpu.resilience import InjectedFailure
    plan = FaultPlan(seed=1).crash_at_epoch("acc", 3).torn_commit(5)
    assert plan.torn_commit_epochs == {5}
    nf = plan.for_node("pipe0/acc.0")
    assert nf is not None
    nf.on_epoch(2)  # no-op
    with pytest.raises(InjectedFailure, match="epoch 3"):
        nf.on_epoch(3)
    assert plan.for_node("pipe0/other.0") is None
    with pytest.raises(ValueError):
        plan.crash_at_epoch("x", 0)
    with pytest.raises(ValueError):
        plan.torn_commit(0)


# ---------------------------------------------------------------------------
# barrier aligner unit semantics
# ---------------------------------------------------------------------------

def test_aligner_holds_back_post_barrier_items():
    """Items from producers already past barrier e are parked until the
    alignment completes, then replay in arrival order -- the cut
    separates pre- from post-barrier input exactly."""
    from windflow_tpu.durability.barrier import EpochAligner
    from windflow_tpu.runtime.queues import EpochBarrier

    class _Coord:
        def __init__(self):
            self.snaps = []
            self.acks = []

        def add_snapshot(self, epoch, states):
            self.snaps.append(epoch)

        def sink_ack(self, epoch, name):
            self.acks.append((epoch, name))

    class _Node:
        name = "sink.0"
        outlets = ()
        faults = None
        epoch_barriers_in = 0
        epoch_barriers_out = 0

        class logic:  # stateless, no quiesce/epoch_mark hooks
            pass

        def _emit(self, item):
            raise AssertionError("no emissions expected")

    node = _Node()
    coord = _Coord()
    al = EpochAligner(node, coord, n_producers=2)
    seen = []

    def process(cid, item):
        seen.append((cid, item))

    assert not al.offer(0, "a0", process)     # plain item passes through
    process(0, "a0")
    assert al.offer(0, EpochBarrier(1), process)   # producer 0 aligned
    assert al.busy
    assert al.offer(0, "a1", process)         # held back (0 is aligned)
    assert not al.offer(1, "b0", process)     # producer 1 not yet aligned
    process(1, "b0")
    assert al.offer(1, EpochBarrier(1), process)   # completes the cut
    assert not al.busy
    assert coord.acks == [(1, "sink.0")]
    assert seen == [(0, "a0"), (1, "b0"), (0, "a1")]  # holdback replayed
    assert node.epoch_barriers_in == 2


def test_aligner_final_barrier_unblocks_alignment():
    """A finished producer (final barrier) counts as permanently
    arrived: a finished branch can never stall another's alignment."""
    from windflow_tpu.durability.barrier import EpochAligner
    from windflow_tpu.runtime.queues import EpochBarrier

    class _Coord:
        def __init__(self):
            self.acks = []

        def add_snapshot(self, epoch, states):
            pass

        def sink_ack(self, epoch, name):
            self.acks.append(epoch)

    class _Node:
        name = "sink.0"
        outlets = ()
        faults = None
        epoch_barriers_in = 0
        epoch_barriers_out = 0

        class logic:
            pass

        def _emit(self, item):
            pass

    coord = _Coord()
    al = EpochAligner(_Node(), coord, n_producers=2)
    al.offer(0, EpochBarrier(-1, final=True), lambda c, i: None)
    al.offer(1, EpochBarrier(1), lambda c, i: None)   # completes at once
    al.offer(1, EpochBarrier(2), lambda c, i: None)
    assert coord.acks == [1, 2]


def test_aligner_counts_parked_barrier_once():
    """A next-epoch barrier parked behind an open alignment and then
    replayed left its channel once: ``epoch_barriers_in`` counts it
    once, so the graph-wide ledger (channel gets less barriers) keeps
    every consumed tuple."""
    from windflow_tpu.durability.barrier import EpochAligner
    from windflow_tpu.runtime.queues import EpochBarrier

    class _Coord:
        def __init__(self):
            self.acks = []

        def add_snapshot(self, epoch, states):
            pass

        def sink_ack(self, epoch, name):
            self.acks.append(epoch)

    class _Node:
        name = "sink.0"
        outlets = ()
        faults = None
        epoch_barriers_in = 0
        epoch_barriers_out = 0

        class logic:
            pass

        def _emit(self, item):
            pass

    node = _Node()
    coord = _Coord()
    al = EpochAligner(node, coord, n_producers=2)
    seen = []

    def process(cid, item):
        seen.append((cid, item))

    assert al.offer(0, EpochBarrier(1), process)
    assert al.offer(0, EpochBarrier(2), process)   # parked: 1 still open
    assert al.offer(1, EpochBarrier(1), process)   # cut 1, replay 2
    assert al.offer(1, EpochBarrier(2), process)   # cut 2
    assert coord.acks == [1, 2]
    assert seen == []
    assert node.epoch_barriers_in == 4


# ---------------------------------------------------------------------------
# end-to-end: clean run, exactly-once sinks, metrics/doctor surfaces
# ---------------------------------------------------------------------------

def test_durable_pipeline_clean_run_exactly_once(tmp_path):
    N = 3000
    effects = []
    g = _acc_graph(N, str(tmp_path), effects, interval=0.04,
                   pace_every=128, pace_s=0.002)
    g.run()
    _assert_exactly_once(effects, N, g)
    dur = g.durability
    # >= 2: at least one mid-stream commit plus the graph-level final
    # commit at the clean end (which releases the sink buffers)
    assert dur.commits >= 2 and dur.committed >= 2
    kinds = collections.Counter(e["kind"] for e in g.flight.snapshot())
    assert kinds["epoch_begin"] >= dur.commits - 1  # final has no begin
    assert kinds["epoch_commit"] == dur.commits
    assert kinds["checkpoint_epoch"] == dur.commits
    finals = [e for e in g.flight.snapshot()
              if e["kind"] == "epoch_commit" and e.get("final")]
    assert len(finals) == 1 and finals[0]["effects"] > 0
    # every epoch event carries its epoch id
    for ev in g.flight.snapshot():
        if ev["kind"] in ("epoch_begin", "epoch_commit",
                          "checkpoint_epoch"):
            assert isinstance(ev.get("epoch"), int)
    # manifests on disk + stats/metrics surfaces
    stats = json.loads(g.stats.to_json())
    block = stats["Durability"]
    assert block["Committed_epoch"] == dur.committed
    assert not block["Stalled"]
    from windflow_tpu.telemetry.metrics import render_openmetrics
    text = render_openmetrics({"1": {"report": stats, "active": False}})
    assert "windflow_epoch{" in text
    assert "windflow_epoch_lag_seconds{" in text
    assert "windflow_epoch_commit_seconds{" in text
    # doctor folds the block into the report
    from windflow_tpu.diagnosis.report import build_report, render_text
    rep = build_report(stats)
    assert rep["Durability"]["Committed_epoch"] == dur.committed
    assert "epochs: committed=" in render_text(rep)


def test_doctor_names_stalled_epochs():
    from windflow_tpu.diagnosis.report import build_report, render_text
    stats = {"PipeGraph_name": "g", "Durability": {
        "Committed_epoch": 4, "Epoch_lag_s": 12.5, "Last_commit_s": 0.01,
        "Commits": 4, "Aborts": 0, "Stalled": True}}
    rep = build_report(stats)
    assert "epochs STALLED" in rep["Verdict"]
    assert "committed 4" in rep["Verdict"]
    assert "stalled=True" in render_text(rep)


def test_sink_progress_during_epochs(tmp_path):
    """The non-stop property: the graph keeps emitting THROUGH epochs
    -- sink consumption strictly increases between consecutive commits
    (no graph-wide quiesce on the barrier path)."""
    N = 6000
    effects = []
    g = _acc_graph(N, str(tmp_path), effects, interval=0.05,
                   pace_every=32, pace_s=0.004)
    g.run()
    commits = [e for e in g.flight.snapshot()
               if e["kind"] == "epoch_commit" and "sink_gets" in e]
    assert len(commits) >= 3, commits
    gets = [c["sink_gets"] for c in commits]
    for a, b in zip(gets, gets[1:]):
        assert b > a, ("sink made no progress between commits -- "
                       "the barrier path quiesced the graph", gets)
    _assert_exactly_once(effects, N, g)


def test_live_checkpoint_is_non_stop_under_durability(tmp_path):
    """live_checkpoint with the plane on forces one epoch (no source
    pause) and writes a restore_graph-compatible snapshot."""
    from windflow_tpu.utils.checkpoint import read_snapshot
    N = 20000
    effects = []
    g = _acc_graph(N, str(tmp_path), effects, interval=0.5,
                   pace_every=16, pace_s=0.002)
    g.start()
    deadline = time.monotonic() + 30
    while not effects and time.monotonic() < deadline:
        time.sleep(0.002)
    pre = len(effects)
    path = str(tmp_path / "live.pkl")
    n = g.live_checkpoint(path, timeout=30)
    assert n >= 1
    states = read_snapshot(path)
    assert "pipe0/ckpt_source" in states
    src_off = states["pipe0/ckpt_source"]["i"]
    assert 0 < src_off <= N
    g.wait_end()
    assert len(effects) > pre
    _assert_exactly_once(effects, N, g)
    evs = [e for e in g.flight.snapshot()
           if e["kind"] == "checkpoint_epoch" and e.get("non_stop")]
    assert evs and evs[0]["path"] == path


# ---------------------------------------------------------------------------
# kill-restart-verify chaos: mid-stream crash, barrier-window crash,
# fused-segment crash, torn commit
# ---------------------------------------------------------------------------

def _chaos(tmp_path, plan_for_attempt, n=4000, max_restarts=2):
    effects = []
    attempts = []

    def factory(attempt):
        attempts.append(attempt)
        return _acc_graph(n, str(tmp_path), effects,
                          fault_plan=plan_for_attempt(attempt))

    g = run_with_epochs(factory, max_restarts=max_restarts)
    return g, effects, attempts


def test_chaos_crash_midstream_restarts_exactly_once(tmp_path):
    N = 4000
    g, effects, attempts = _chaos(
        tmp_path,
        lambda a: (FaultPlan(seed=3)
                   .crash_replica("accumulator", at_tuple=1200)
                   if a == 0 else None),
        n=N)
    assert attempts == [0, 1]
    # the paced stream guarantees committed epochs before tuple 1200:
    # the restart resumed from one, not from scratch
    assert getattr(g, "_epoch_restored", None) is not None
    assert g._epoch_restored >= 1
    restores = [e for e in g.flight.snapshot()
                if e["kind"] == "epoch_restore"]
    assert restores and restores[0]["epoch"] == g._epoch_restored
    _assert_exactly_once(effects, N, g)
    # epoch numbering continued across the restart (a reset could let a
    # second failure rewind past already-released effects)
    assert g.durability.committed > g._epoch_restored


def test_chaos_crash_inside_barrier_window(tmp_path):
    """crash_at_epoch: the replica dies mid-cut (aligned, pre-snapshot)
    -- the epoch never commits, the restart resumes from the previous
    one, results stay exactly-once."""
    N = 4000
    g, effects, attempts = _chaos(
        tmp_path,
        lambda a: (FaultPlan(seed=5).crash_at_epoch("accumulator", 2)
                   if a == 0 else None),
        n=N)
    assert attempts == [0, 1]
    assert getattr(g, "_epoch_restored", None) == 1
    _assert_exactly_once(effects, N, g)


def test_chaos_torn_commit_falls_back_previous_epoch(tmp_path):
    """torn_commit: epoch 2's manifest lands truncated at the final
    path and the graph dies; the restart's tolerant reader records the
    damage and falls back to epoch 1."""
    N = 4000
    g, effects, attempts = _chaos(
        tmp_path,
        lambda a: FaultPlan(seed=7).torn_commit(2) if a == 0 else None,
        n=N)
    assert attempts == [0, 1]
    assert getattr(g, "_epoch_restored", None) == 1
    aborts = [e for e in g.flight.snapshot()
              if e["kind"] == "epoch_abort"
              and e.get("reason") == "manifest_corrupt"]
    assert aborts and aborts[0]["epoch"] == 2
    _assert_exactly_once(effects, N, g)
    # the continued numbering moved PAST the torn epoch (re-committing
    # 2 over the damage), and the newest manifest on disk loads clean
    e, payload = EpochStore(
        os.path.join(str(tmp_path), "epochs")).latest()
    assert e is not None and e >= 2 and payload["epoch"] == e


def test_chaos_crash_inside_fused_segment_with_device_engine(tmp_path):
    """The fully-fused lane: source + maps + WinSeqTPU + transactional
    sink fused into one replica; the crash fires on a fused-AWAY
    operator's fault clock; barriers cross the fused segments and the
    async device dispatcher (epoch fence drains in-flight launches).
    Window results after restart equal the uninterrupted run."""
    N, WIN, SLIDE = 6000, 16, 8

    def run(plan_path, fault):
        wins = {}
        counts = collections.Counter()

        def sink(r):
            if r is None:
                return
            wins[(r.key, r.id)] = r.value
            counts[(r.key, r.id)] += 1
        effects_graph = []

        def factory(attempt):
            plan = fault if attempt == 0 else None
            cfg = wf.RuntimeConfig(durability=DurabilityConfig(
                epoch_interval_s=0.03, path=plan_path),
                fault_plan=plan)
            g = wf.PipeGraph("dur_win", wf.Mode.DEFAULT, config=cfg)
            op = wf.WinSeqTPUBuilder("sum") \
                .with_tb_windows(WIN, SLIDE).build()
            g.add_source(CkptSource(N, pace_every=64, pace_s=0.003)) \
                .add(wf.MapBuilder(lambda t: None).build()) \
                .add(op) \
                .add_sink(wf.SinkBuilder(sink).with_exactly_once()
                          .build())
            effects_graph.append(g)
            return g

        g = run_with_epochs(factory, max_restarts=2)
        return g, wins, counts

    # uninterrupted reference (own manifest dir)
    _gr, ref, ref_counts = run(str(tmp_path / "ref"), None)
    assert ref and max(ref_counts.values()) == 1
    # crash on the fused-away map's tuple clock, mid-stream
    plan = FaultPlan(seed=11).crash_replica("map", at_tuple=2500)
    g, wins, counts = run(str(tmp_path / "chaos"), plan)
    assert getattr(g, "_epoch_restored", None) is not None
    assert max(counts.values()) == 1, "duplicate window results"
    assert wins == ref  # bitwise: float sums over identical series


def test_branch_eos_then_crash_releases_no_duplicates(tmp_path):
    """A split graph where one branch ends cleanly BEFORE the other
    branch crashes: the finished branch's sink must not have released
    uncommitted-epoch effects at its own EOS (the restart regenerates
    them -- duplicates).  Release is deferred to the coordinator's
    graph-level final commit."""
    N = 3000
    fast, slow = [], []

    def factory(attempt):
        # the slow branch dies on its LAST tuple -- deterministically
        # after the fast branch's sink reached EOS (it lags ~0.2 ms per
        # tuple behind)
        plan = (FaultPlan(seed=17).crash_replica("slowmap", at_tuple=N)
                if attempt == 0 else None)

        def slow_fn(t):
            time.sleep(0.0002)

        cfg = wf.RuntimeConfig(
            durability=DurabilityConfig(
                epoch_interval_s=0.04,
                path=os.path.join(str(tmp_path), "epochs")),
            fault_plan=plan)
        g = wf.PipeGraph("dur_split", wf.Mode.DEFAULT, config=cfg)
        mp = g.add_source(CkptSource(N, pace_every=64, pace_s=0.002))
        mp = mp.split(lambda t: (0, 1), 2)
        mp.select(0).add_sink(
            wf.SinkBuilder(lambda r: fast.append((r.key, r.id, r.value))
                           if r is not None else None)
            .with_exactly_once().build())
        mp.select(1) \
            .add(wf.MapBuilder(slow_fn).with_name("slowmap").build()) \
            .add_sink(
                wf.SinkBuilder(lambda r: slow.append((r.key, r.id,
                                                      r.value))
                               if r is not None else None)
                .with_exactly_once().build())
        return g

    g = run_with_epochs(factory, max_restarts=2)
    assert getattr(g, "_epoch_restored", None) is not None
    for name, effects in (("fast", fast), ("slow", slow)):
        assert len(effects) == N, (name, len(effects), N)
        assert len(set(effects)) == N, f"{name} branch duplicated effects"


def test_chaos_exhausted_restarts_reraise(tmp_path):
    from windflow_tpu.graph.pipegraph import NodeFailureError
    with pytest.raises(NodeFailureError) as ei:
        _chaos(tmp_path,
               lambda a: FaultPlan(seed=9).crash_replica(
                   "accumulator", at_tuple=100),
               n=2000, max_restarts=1)
    assert len(ei.value.attempt_history) == 2


# ---------------------------------------------------------------------------
# idempotent-by-epoch-id sink variant
# ---------------------------------------------------------------------------

def test_idempotent_sink_with_truncate_on_restore(tmp_path):
    """The idempotent contract: effects apply immediately tagged with
    their epoch; the crashed attempt's uncommitted tail is truncated on
    restore and replayed identically."""
    N = 4000
    store = EpochTaggedStore()

    def factory(attempt):
        plan = (FaultPlan(seed=13).crash_replica("accumulator",
                                                 at_tuple=1200)
                if attempt == 0 else None)

        def acc(t, a):
            a.value += t.value
        cfg = wf.RuntimeConfig(
            durability=DurabilityConfig(
                epoch_interval_s=0.03,
                path=os.path.join(str(tmp_path), "epochs")),
            fault_plan=plan)
        g = wf.PipeGraph("dur_idem", wf.Mode.DEFAULT, config=cfg)
        g.add_source(CkptSource(N, pace_every=64, pace_s=0.004)) \
            .add(wf.MapBuilder(lambda t: None).with_key_by()
                 .with_parallelism(2).build()) \
            .add(wf.AccumulatorBuilder(acc)
                 .with_initial_value(BasicRecord(value=0.0))
                 .with_parallelism(2).build()) \
            .add_sink(wf.SinkBuilder(store)
                      .with_exactly_once("idempotent").build())
        return g

    g = run_with_epochs(
        factory, max_restarts=2,
        on_restore=lambda g_, e, payload: store.truncate_above(e))
    assert getattr(g, "_epoch_restored", None) is not None
    effects = [(r.key, r.id, r.value) for r in store.items()]
    assert len(effects) == N and len(set(effects)) == N
    got, oracle = _per_key(effects), _acc_oracle(N)
    for k in oracle:
        assert sorted(got[k]) == oracle[k]
    # epochs tag monotonically across the restart
    assert store.epochs() == sorted(store.epochs())


def test_idempotent_sink_rejects_plain_callable():
    with pytest.raises(TypeError, match="epoch-keyed writer"):
        g = wf.PipeGraph("bad")
        g.add_source(CkptSource(10)).add_sink(
            wf.SinkBuilder(lambda r: None)
            .with_exactly_once("idempotent").build())
        g.start()


def test_with_exactly_once_validates_mode():
    with pytest.raises(ValueError, match="transactional"):
        wf.SinkBuilder(lambda r: None).with_exactly_once("bogus")


# ---------------------------------------------------------------------------
# epoch x elastic interaction
# ---------------------------------------------------------------------------

def test_epochs_serialize_with_scripted_rescale(tmp_path):
    """A scripted rescale lands between two epochs and a barrier
    cadence keeps firing around it: commits continue on both sides,
    the rewired channel set aligns (new producer counts), and the
    per-key sequences equal the uninterrupted run."""
    N = 12000
    effects = []
    g = _acc_graph(N, str(tmp_path), effects, interval=0.03,
                   pace_every=32, pace_s=0.003, elastic=(1, 3))
    g.start()
    deadline = time.monotonic() + 30
    while not effects and time.monotonic() < deadline:
        time.sleep(0.002)
    before = g.durability.committed
    ev = g.rescale("accumulator", 2)
    assert ev is not None and ev.new_parallelism == 2
    # a barrier arriving during/after the rescale still aligns and
    # commits (the gap released with refreshed producer counts)
    deadline = time.monotonic() + 30
    while g.durability.committed <= before \
            and time.monotonic() < deadline \
            and any(n.is_alive() for n in g._all_nodes()):
        time.sleep(0.005)
    g.wait_end()
    assert g.durability.committed > before, \
        "no epoch committed after the rescale"
    _assert_exactly_once(effects, N, g)
    kinds = [e["kind"] for e in g.flight.snapshot()]
    assert "rescale" in kinds


def test_quiesce_holds_epochs(tmp_path):
    """The legacy quiesce barrier serializes with the epoch plane: it
    drains in-flight epochs first, and no epoch begins while paused."""
    N = 20000
    effects = []
    g = _acc_graph(N, str(tmp_path), effects, interval=0.03,
                   pace_every=32, pace_s=0.002)
    g.start()
    deadline = time.monotonic() + 30
    while g.durability.committed < 1 and time.monotonic() < deadline:
        time.sleep(0.002)
    g.quiesce()
    try:
        with g.durability._cond:
            assert not g.durability._pending  # drained, none in flight
        seq = g.durability.epoch_seq
        time.sleep(0.12)                      # > several intervals
        assert g.durability.epoch_seq == seq  # cadence held
    finally:
        g.resume()
    g.wait_end()
    _assert_exactly_once(effects, N, g)
