"""Audit plane (windflow_tpu/audit/; docs/OBSERVABILITY.md): online
flow-conservation ledger, progress/frontier tracking, keyed-state /
hot-key skew census, and the audit satellites (Queue_high_watermark
export, snapshot rotation, /metrics families).

Chaos coverage (the zero-false-positive contract): a FaultPlan replica
crash, admission shedding and a mid-stream rescale each produce a
ledger that still closes, while a deliberately injected single-tuple
drop/duplication (FaultPlan.drop_put / dup_put) is detected with the
correct edge and count -- online within one audit interval when the
stream keeps flowing, and always at the wait_end closure check.
"""
import json
import os
import time
import threading
import warnings

import numpy as np
import pytest

import windflow_tpu as wf
from windflow_tpu.audit import SpaceSavingSketch
from windflow_tpu.core.basic import RuntimeConfig
from windflow_tpu.core.tuples import TupleBatch
from windflow_tpu.elastic.signals import OperatorSignals
from windflow_tpu.monitoring.monitor import rotate_snapshots
from windflow_tpu.operators.basic_ops import Sink
from windflow_tpu.operators.tpu.win_seq_tpu import WinSeqTPU
from windflow_tpu.resilience import FaultPlan
from windflow_tpu.telemetry import render_openmetrics

WAIT_S = 60


def quiet_run(g):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        g.run()


def record_source(n, n_keys=7, pace_every=0, pace_s=0.01, state=None):
    """Record-plane source; optional pacing keeps the stream alive long
    enough for online audit passes."""
    state = state if state is not None else {}

    def fn(shipper, ctx=None):
        i = state.setdefault("i", 0)
        if i >= n:
            return False
        shipper.push(wf.BasicRecord(i % n_keys, i // n_keys, i, float(i)))
        state["i"] = i + 1
        if pace_every and i % pace_every == 0:
            time.sleep(pace_s)
        return True

    return fn


def fold(t, acc):
    acc.value += t.value


def keyed_graph(n=20_000, *, fault_plan=None, parallelism=2,
                audit_interval_s=0.05, pace_every=0, pace_s=0.01,
                name="audit", n_keys=7, audit=True):
    """source -> KEYBY accumulator(par) -> sink: the smallest graph
    with real channel edges on both routing planes."""
    sunk = []
    cfg = RuntimeConfig(tracing=True, audit=audit,
                        audit_interval_s=audit_interval_s,
                        fault_plan=fault_plan)
    g = wf.PipeGraph(name, wf.Mode.DEFAULT, config=cfg)
    g.add_source(wf.SourceBuilder(
        record_source(n, n_keys=n_keys, pace_every=pace_every,
                      pace_s=pace_s)).build()) \
        .add(wf.AccumulatorBuilder(fold)
             .with_parallelism(parallelism).build()) \
        .add_sink(wf.SinkBuilder(
            lambda r: sunk.append(r) if r is not None else None).build())
    return g, sunk


def conservation(g):
    return json.loads(g.stats.to_json())["Conservation"]


# ---------------------------------------------------------------------------
# ledger: clean runs close on every plane
# ---------------------------------------------------------------------------

def test_ledger_balances_keyed_graph():
    g, sunk = keyed_graph(30_000)
    quiet_run(g)
    assert len(sunk) == 30_000
    assert g.auditor is not None and g.auditor.violations == []
    cons = conservation(g)
    assert cons["Final_check"] is True
    assert cons["Edges_total"] == 3        # 2 accumulator inlets + sink
    assert cons["Edges_balanced"] is True
    for e in cons["Edges"]:
        assert e["sent"] == e["delivered"] == e["enqueued"] \
            == e["dequeued"], e
        assert e["depth"] == 0
    # the graph-wide ledger identity with everything drained
    assert cons["Sources_emitted"] == cons["Sinks_consumed"] == 30_000
    assert cons["In_flight"] == {"channels": 0, "processing": 0,
                                 "device_batches": 0}


def test_ledger_balances_windowed_ingest_feed():
    """Replay source -> WinSeqTPU(sum) -> sink: credited-channel
    proxies and async device batches, the edge kinds beyond plain
    queues."""
    n = 60_000
    ar = np.arange(n, dtype=np.int64)
    trace = TupleBatch({"key": ar % 4, "id": ar // 4, "ts": ar // 4,
                        "value": np.ones(n, np.float64)})
    src = wf.SourceBuilder.from_replay(trace, speedup=None,
                                       chunk=4096).build()
    op = WinSeqTPU("sum", 512, 512, wf.WinType.TB, batch_len=64,
                   emit_batches=True)
    got = []
    cfg = RuntimeConfig(tracing=True, audit_interval_s=0.05,
                        watchdog_timeout_s=WAIT_S)
    g = wf.PipeGraph("audit_win", wf.Mode.DEFAULT, config=cfg)
    g.add_source(src).add(op).add_sink(
        Sink(lambda b: got.append(b) if b is not None else None))
    quiet_run(g)
    assert got                              # windows actually computed
    assert g.auditor.violations == []
    cons = conservation(g)
    assert cons["Edges_balanced"] is True and cons["Edges_total"] >= 1


def test_fully_fused_chain_has_no_edges():
    """LEVEL2 fuses source+map+sink into one replica: no channels, an
    empty (vacuously balanced) ledger, and no violations."""
    sunk = []
    cfg = RuntimeConfig(tracing=True, audit_interval_s=0.05)
    g = wf.PipeGraph("audit_fused", wf.Mode.DEFAULT, config=cfg)
    g.add_source(wf.SourceBuilder(record_source(5_000)).build()) \
        .add(wf.MapBuilder(lambda t: t).build()) \
        .add_sink(wf.SinkBuilder(
            lambda r: sunk.append(r) if r is not None else None).build())
    quiet_run(g)
    assert len(sunk) == 5_000
    assert g.auditor.violations == []
    cons = conservation(g)
    assert cons["Edges_total"] == 0 and cons["Edges_balanced"] is True


def test_audit_off_leaves_hot_path_clean():
    g, sunk = keyed_graph(5_000, audit=False)
    quiet_run(g)
    assert len(sunk) == 5_000
    assert g.auditor is None
    for node in g._all_nodes():
        for o in node.outlets:
            assert o.audit_cells is None
    assert conservation(g) is None


# ---------------------------------------------------------------------------
# injected drop/dup detection (FaultPlan drop_put / dup_put)
# ---------------------------------------------------------------------------

def _run_with_fault(plan, n=4_000, pace_every=100):
    """Paced stream so several audit passes observe the live books."""
    g, sunk = keyed_graph(n, fault_plan=plan, audit_interval_s=0.03,
                          pace_every=pace_every, name="audit_fault")
    quiet_run(g)
    return g, sunk


def test_drop_put_detected_with_edge_and_count():
    g, sunk = _run_with_fault(FaultPlan().drop_put("accumulator.0", 50))
    assert len(sunk) == 3_999              # one tuple truly lost
    v = g.auditor.violations
    assert len(v) == 1, v
    assert v[0]["kind"] == "lost_delivery"
    assert "sink" in v[0]["edge"]          # the edge the tuple vanished on
    assert "accumulator.0" in v[0]["producer"]
    assert v[0]["count"] == 1


def test_dup_put_detected_with_edge_and_count():
    g, sunk = _run_with_fault(FaultPlan().dup_put("accumulator.1", 30))
    assert len(sunk) == 4_001              # one tuple truly duplicated
    v = g.auditor.violations
    assert len(v) == 1, v
    assert v[0]["kind"] == "extra_delivery"
    assert "sink" in v[0]["edge"]
    assert v[0]["count"] == 1


def test_drop_put_detected_online_within_interval():
    """The periodic auditor flags the drop while the stream is still
    flowing -- not only at the wait_end closure check."""
    plan = FaultPlan().drop_put("accumulator.0", 10)
    g, _ = keyed_graph(100_000, fault_plan=plan, audit_interval_s=0.03,
                       pace_every=200, pace_s=0.005, name="audit_live")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        g.start()
        deadline = time.monotonic() + WAIT_S
        try:
            while not g.auditor.violations:
                assert time.monotonic() < deadline, \
                    "no online detection before the stream ended"
                time.sleep(0.01)
            v = g.auditor.violations[0]
            assert v["kind"] == "lost_delivery" and v["count"] == 1
            assert "final" not in v        # flagged by the online pass
        finally:
            g.cancel()
            with pytest.raises(wf.NodeFailureError):
                g.wait_end()


def test_tail_drop_caught_by_final_check():
    """Dropping the LAST delivery leaves nothing flowing afterwards:
    only the wait_end closure check can prove it (and it dumps the
    flight ring as post-mortem evidence)."""
    n = 1_000
    # accumulator emits one record per input; replica 0 owns 4 of 7
    # keys -> its last delivery is its ceil-share of n
    last = sum(1 for i in range(n) if abs(i % 7) % 2 == 0)
    plan = FaultPlan().drop_put("accumulator.0", last)
    g, sunk = keyed_graph(n, fault_plan=plan, parallelism=2,
                          name="audit_tail")
    quiet_run(g)
    assert len(sunk) == n - 1
    v = g.auditor.violations
    assert len(v) == 1 and v[0]["kind"] == "lost_delivery"
    assert v[0].get("final") is True
    assert g.flight.dumped_path and os.path.exists(g.flight.dumped_path)
    kinds = [json.loads(line)["kind"]
             for line in open(g.flight.dumped_path)]
    assert "conservation_violation" in kinds


def test_drop_put_in_fused_segment():
    """LEVEL2 fuses source+map into one head; the put fault binds to
    the LAST segment (map) whose emissions cross the real channel."""
    sunk = []
    plan = FaultPlan().drop_put("map", 25)
    cfg = RuntimeConfig(tracing=True, audit_interval_s=0.05,
                        fault_plan=plan)
    g = wf.PipeGraph("audit_fusedfault", wf.Mode.DEFAULT, config=cfg)
    g.add_source(wf.SourceBuilder(record_source(2_000)).build()) \
        .add(wf.MapBuilder(lambda t: t).build()) \
        .add(wf.AccumulatorBuilder(fold).with_parallelism(2).build()) \
        .add_sink(wf.SinkBuilder(
            lambda r: sunk.append(r) if r is not None else None).build())
    quiet_run(g)
    assert len(sunk) == 1_999
    v = g.auditor.violations
    assert len(v) == 1 and v[0]["kind"] == "lost_delivery"
    assert "accumulator" in v[0]["edge"]


# ---------------------------------------------------------------------------
# chaos: crash / shed / rescale produce ZERO false positives
# ---------------------------------------------------------------------------

def test_drop_put_fires_without_auditor():
    """Put faults act at the Outlet layer with or without the ledger:
    audit=False still loses the tuple (the fault is the ground truth,
    the auditor is the detector)."""
    plan = FaultPlan().drop_put("accumulator.0", 50)
    g, sunk = keyed_graph(2_000, fault_plan=plan, audit=False,
                          name="audit_offfault")
    quiet_run(g)
    assert g.auditor is None
    assert len(sunk) == 1_999              # dropped, silently (no books)


def test_hot_keys_merged_across_upstream_replicas():
    """A KEYBY edge with N upstream replicas carries N sketches; every
    surface must report ONE row per operator (strict OpenMetrics
    parsers reject duplicate series)."""
    sunk = []
    cfg = RuntimeConfig(tracing=True, audit_interval_s=0.05)
    g = wf.PipeGraph("audit_merge", wf.Mode.DEFAULT, config=cfg)
    g.add_source(wf.SourceBuilder(record_source(20_000)).build()) \
        .add(wf.MapBuilder(lambda t: t).with_name("fan")
             .with_parallelism(2).build()) \
        .add(wf.AccumulatorBuilder(fold).with_parallelism(2).build()) \
        .add_sink(wf.SinkBuilder(
            lambda r: sunk.append(r) if r is not None else None).build())
    quiet_run(g)
    assert len(sunk) == 20_000
    # two fan replicas -> two KEYBY sketches feeding one operator
    assert len([1 for op, _sk in g.auditor._sketches
                if "accumulator" in op]) == 2
    report = json.loads(g.stats.to_json())
    ops = [h["operator"] for h in report["Skew"]["Hot_keys"]]
    assert ops.count("pipe0/accumulator") == 1
    text = render_openmetrics({"1": {"report": report, "active": False,
                                     "diagram": ""}})
    shares = [ln for ln in text.splitlines()
              if ln.startswith("windflow_hot_key_share")
              and 'operator="pipe0/accumulator"' in ln]
    assert len(shares) == 1                # no duplicate series


def test_crash_chaos_zero_false_positives():
    plan = FaultPlan().crash_replica("accumulator", at_tuple=500)
    g, _ = keyed_graph(50_000, fault_plan=plan, audit_interval_s=0.02,
                       name="audit_crash")
    with pytest.raises(wf.NodeFailureError):
        quiet_run(g)
    assert g.auditor.violations == []


def test_shed_chaos_zero_false_positives():
    """Admission shedding drops tuples BEFORE the transport edge: the
    ledger closes and the sheds ride the Conservation block."""
    n = 60_000
    ar = np.arange(n, dtype=np.int64)
    trace = TupleBatch({"key": ar % 4, "id": ar // 4, "ts": ar // 4,
                        "value": np.ones(n, np.float64)})
    src = wf.SourceBuilder.from_replay(trace, speedup=None, chunk=512) \
        .with_credits(1024) \
        .with_admission("drop_newest", max_wait_ms=0, seed=11).build()

    def slow_sink(item):
        if item is not None:
            time.sleep(0.005)

    cfg = RuntimeConfig(tracing=True, audit_interval_s=0.05,
                        watchdog_timeout_s=WAIT_S)
    g = wf.PipeGraph("audit_shed", wf.Mode.DEFAULT, config=cfg)
    g.add_source(src).add_sink(Sink(slow_sink))
    quiet_run(g)
    shed = g.dead_letters.count()
    assert shed > 0
    assert g.auditor.violations == []
    cons = conservation(g)
    assert cons["Edges_balanced"] is True
    assert cons["Shed_tuples"] == shed
    assert cons["Dead_letters"] == shed


def test_rescale_chaos_ledger_closes():
    """Mid-stream 1->3->1 rescale: retired replicas' books fold into
    the per-channel retired ledger, so the edges stay balanced."""
    n = 40_000
    state = {}
    sunk = []
    from windflow_tpu.elastic import ElasticityConfig
    cfg = RuntimeConfig(tracing=True, audit_interval_s=0.02,
                        elasticity=ElasticityConfig(enabled=False))
    g = wf.PipeGraph("audit_rescale", wf.Mode.DEFAULT, config=cfg)
    g.add_source(wf.SourceBuilder(
        record_source(n, n_keys=16, pace_every=500, pace_s=0.002,
                      state=state)).build()) \
        .add(wf.AccumulatorBuilder(fold).with_elasticity(1, 4).build()) \
        .add_sink(wf.SinkBuilder(
            lambda r: sunk.append(r) if r is not None else None).build())

    def wait_progress(target):
        deadline = time.monotonic() + WAIT_S
        while state.get("i", 0) < target:
            assert time.monotonic() < deadline
            time.sleep(0.002)

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        g.start()
        wait_progress(n // 3)
        assert g.rescale("accumulator", 3) is not None
        wait_progress(2 * n // 3)
        assert g.rescale("accumulator", 1) is not None
        g.wait_end()
    assert len(sunk) == n
    assert g.auditor.violations == []
    cons = conservation(g)
    assert cons["Edges_balanced"] is True
    assert cons["Sources_emitted"] == cons["Sinks_consumed"] == 40_000


def test_dead_letter_chaos_ledger_closes():
    """svc failures under a dead_letter policy are consumer-side: the
    transport books still balance."""
    sunk = []

    def flaky(t):
        if t.id == 7 and t.key == 3:
            raise ValueError("boom")
        return t

    cfg = RuntimeConfig(tracing=True, audit_interval_s=0.05)
    g = wf.PipeGraph("audit_dl", wf.Mode.DEFAULT, config=cfg)
    g.add_source(wf.SourceBuilder(record_source(5_000)).build()) \
        .add(wf.AccumulatorBuilder(fold).with_parallelism(2).build()) \
        .add(wf.MapBuilder(flaky).with_error_policy("dead_letter")
             .build()) \
        .add_sink(wf.SinkBuilder(
            lambda r: sunk.append(r) if r is not None else None).build())
    quiet_run(g)
    assert g.dead_letters.count() == 1
    assert len(sunk) == 4_999
    assert g.auditor.violations == []
    assert conservation(g)["Edges_balanced"] is True


# ---------------------------------------------------------------------------
# progress / frontier tracking
# ---------------------------------------------------------------------------

def test_frontiers_monotone_and_settle():
    g, _ = keyed_graph(60_000, audit_interval_s=0.02, pace_every=1000,
                       pace_s=0.003, name="audit_frontier")
    samples = []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        g.start()
        deadline = time.monotonic() + WAIT_S
        while any(n.is_alive() for n in g._all_nodes()) \
                and time.monotonic() < deadline:
            fr = {k: v["frontier"]
                  for k, v in g.auditor.tracker.frontiers.items()}
            if fr:
                samples.append(fr)
            time.sleep(0.02)
        g.wait_end()
    # monotone per node across live samples
    for a, b in zip(samples, samples[1:]):
        for k in a.keys() & b.keys():
            assert b[k] >= a[k], (k, a[k], b[k])
    # settled: every node's watermark reached the source frontier and
    # lag reads zero (gauges also land in the stats JSON)
    final = g.auditor.tracker.frontiers
    src_wm = final["pipe0/source"]["frontier"]
    assert src_wm == 60_000
    for name, st in final.items():
        assert st["frontier"] == src_wm, (name, st)
        assert st["lag_ms"] == 0.0
    data = json.loads(g.stats.to_json())
    for op in data["Operators"]:
        for r in op["Replicas"]:
            assert r["Frontier"] == 60_000
            assert r["Frontier_lag_ms"] == 0.0


def test_stalled_frontier_detected():
    """A sink wedged inside svc freezes its frontier while upstream
    advances: the detector fires a frontier_stall flight event, the
    stats flag it, and the stall report carries the frontier rows."""
    release = threading.Event()
    sunk = []

    def sticky(r):
        if r is None:
            return
        if not sunk:
            sunk.append(r)
            release.wait(WAIT_S)     # wedge the first tuple
        else:
            sunk.append(r)

    cfg = RuntimeConfig(tracing=True, audit_interval_s=0.05,
                        frontier_stall_s=0.3)
    g = wf.PipeGraph("audit_stall", wf.Mode.DEFAULT, config=cfg)
    g.add_source(wf.SourceBuilder(record_source(10_000)).build()) \
        .add(wf.AccumulatorBuilder(fold).with_parallelism(2).build()) \
        .add_sink(wf.SinkBuilder(sticky).build())
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        g.start()
        deadline = time.monotonic() + WAIT_S

        def sink_stall():
            return next((e for e in g.flight.snapshot()
                         if e["kind"] == "frontier_stall"
                         and "sink" in e["node"]), None)

        try:
            while sink_stall() is None:
                assert time.monotonic() < deadline, "no stall detected"
                time.sleep(0.02)
            ev = sink_stall()
            assert ev["lag_ms"] >= 300
            assert g.auditor.tracker.frontiers[ev["node"]]["stalled"]
            from windflow_tpu.resilience.watchdog import stall_report
            rows = {r["node"]: r for r in stall_report(g)["nodes"]}
            assert rows[ev["node"]]["frontier_stalled"] is True
        finally:
            release.set()
        g.wait_end()
    assert len(sunk) == 10_000
    assert g.auditor.violations == []


# ---------------------------------------------------------------------------
# keyed-state census + hot-key skew
# ---------------------------------------------------------------------------

def test_census_counts_keys_across_replicas():
    g, _ = keyed_graph(20_000, n_keys=11, name="audit_census")
    quiet_run(g)
    skew = json.loads(g.stats.to_json())["Skew"]
    rows = [r for r in skew["Census"] if "accumulator" in r["replica"]]
    assert len(rows) == 2                   # one per replica
    assert sum(r["keys"] for r in rows) == 11
    assert all(r["bytes_est"] > 0 for r in rows)


def test_hot_key_sketch_identifies_hot_key():
    n = 40_000
    state = {}

    def skewed(shipper):
        i = state.setdefault("i", 0)
        if i >= n:
            return False
        key = 7 if i % 10 else i % 5        # 90% of traffic on key 7
        shipper.push(wf.BasicRecord(key, i, i, 1.0))
        state["i"] = i + 1
        return True

    sunk = []
    cfg = RuntimeConfig(tracing=True, audit_interval_s=0.05)
    g = wf.PipeGraph("audit_skew", wf.Mode.DEFAULT, config=cfg)
    g.add_source(wf.SourceBuilder(skewed).build()) \
        .add(wf.AccumulatorBuilder(fold).with_parallelism(2).build()) \
        .add_sink(wf.SinkBuilder(
            lambda r: sunk.append(r) if r is not None else None).build())
    quiet_run(g)
    skew = json.loads(g.stats.to_json())["Skew"]
    hot = next(h for h in skew["Hot_keys"]
               if "accumulator" in h["operator"])
    assert hot["top"][0][0] == 7
    assert hot["share"] > 0.5
    assert g.auditor.skew_of("pipe0/accumulator") == \
        pytest.approx(hot["share"], abs=1e-9)


def test_space_saving_sketch_bounds_and_merge_error():
    sk = SpaceSavingSketch(4)
    for i in range(1000):
        sk._offer(i % 3, 1)                # heavy keys 0,1,2
    sk._offer("rare", 1)
    assert len(sk.counts) <= 4
    top = sk.top(3)
    assert {row[0] for row in top} >= {0, 1, 2}
    assert 0.2 < sk.top_share() < 0.6      # ~1/3 each, error-corrected


def test_skew_signal_reaches_elastic_load_report():
    n = 30_000
    state = {}

    def skewed(shipper):
        i = state.setdefault("i", 0)
        if i >= n:
            return False
        shipper.push(wf.BasicRecord(3 if i % 10 else i % 4, i, i, 1.0))
        state["i"] = i + 1
        time.sleep(0)                       # keep the stream preemptible
        return True

    from windflow_tpu.elastic import ElasticityConfig
    cfg = RuntimeConfig(tracing=True, audit_interval_s=0.02,
                        elasticity=ElasticityConfig(enabled=False))
    g = wf.PipeGraph("audit_elskew", wf.Mode.DEFAULT, config=cfg)
    g.add_source(wf.SourceBuilder(skewed).build()) \
        .add(wf.AccumulatorBuilder(fold).with_elasticity(1, 4).build()) \
        .add_sink(wf.SinkBuilder(lambda r: None).build())
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        g.start()
        handle = g.elastic["pipe0/accumulator"]
        sig = OperatorSignals(handle)
        sig.sample()                        # priming call
        report = None
        deadline = time.monotonic() + WAIT_S
        while time.monotonic() < deadline:
            time.sleep(0.1)
            report = sig.sample()
            if report is not None and report.skew > 0:
                break
        g.wait_end()
    assert report is not None and report.skew > 0.5


# ---------------------------------------------------------------------------
# satellites: Queue_high_watermark export, /metrics, snapshot rotation
# ---------------------------------------------------------------------------

def test_queue_high_watermark_exported():
    sunk = []

    def slow(r):
        if r is not None:
            sunk.append(r)
            if len(sunk) % 64 == 0:
                time.sleep(0.001)           # let the inlet queue build

    cfg = RuntimeConfig(tracing=True, audit_interval_s=0.05)
    g = wf.PipeGraph("audit_hwm", wf.Mode.DEFAULT, config=cfg)
    g.add_source(wf.SourceBuilder(record_source(30_000)).build()) \
        .add(wf.AccumulatorBuilder(fold).with_parallelism(2).build()) \
        .add_sink(wf.SinkBuilder(slow).build())
    quiet_run(g)
    data = json.loads(g.stats.to_json())
    hwms = [r["Queue_high_watermark"] for op in data["Operators"]
            for r in op["Replicas"] if op["Operator_name"] !=
            "pipe0/source"]
    assert all(isinstance(h, int) for h in hwms)
    assert max(hwms) > 0                    # measured, now exported
    # matches the live channel counters
    chans = {n.name: n.channel.high_watermark
             for n in g._all_nodes() if n.channel is not None}
    assert max(hwms) == max(chans.values())


def test_metrics_render_audit_families():
    g, _ = keyed_graph(10_000, name="audit_metrics")
    quiet_run(g)
    report = json.loads(g.stats.to_json())
    text = render_openmetrics({"1": {"report": report, "active": False,
                                     "diagram": ""}})
    assert "# TYPE windflow_queue_high_watermark gauge" in text
    assert "# TYPE windflow_frontier gauge" in text
    assert "# TYPE windflow_frontier_lag_seconds gauge" in text
    assert "windflow_conservation_violations_total" in text
    assert "windflow_conservation_balanced" in text
    assert "windflow_keyed_state_keys" in text
    assert "windflow_hot_key_share" in text
    # the ledger closed: balanced gauge reads 1, violations 0
    line = next(ln for ln in text.splitlines()
                if ln.startswith("windflow_conservation_balanced"))
    assert line.endswith(" 1")
    line = next(ln for ln in text.splitlines()
                if ln.startswith("windflow_conservation_violations_total"))
    assert line.endswith(" 0")


def test_snapshot_rotation_keeps_last_n(tmp_path):
    d = str(tmp_path)
    for i in range(25):
        p = os.path.join(d, f"{1000 + i}_g_stats.json")
        with open(p, "w") as f:
            f.write("{}")
        os.utime(p, (i, i))                # strictly increasing mtimes
    with open(os.path.join(d, "other_flight.jsonl"), "w") as f:
        f.write("")                        # non-snapshot file: untouched
    rotate_snapshots(d, 16)
    left = sorted(n for n in os.listdir(d) if n.endswith("_stats.json"))
    assert len(left) == 16
    assert left[0] == "1009_g_stats.json"  # oldest 9 pruned
    assert os.path.exists(os.path.join(d, "other_flight.jsonl"))
    rotate_snapshots(d, 0)                 # disabled: no-op
    assert len([n for n in os.listdir(d)
                if n.endswith("_stats.json")]) == 16


def test_snapshot_fallback_rotates(tmp_path, monkeypatch):
    """The dashboard-less fallback prunes old snapshot files when a new
    run starts (configurable keep, default 16)."""
    d = str(tmp_path)
    for i in range(5):
        p = os.path.join(d, f"{100 + i}_old_stats.json")
        with open(p, "w") as f:
            f.write("{}")
        os.utime(p, (i, i))
    sunk = []
    cfg = RuntimeConfig(tracing=True, log_dir=d, snapshot_keep=3,
                        dashboard_port=1)   # unreachable -> fallback
    g = wf.PipeGraph("audit_rot", wf.Mode.DEFAULT, config=cfg)
    g.add_source(wf.SourceBuilder(record_source(2_000)).build()) \
        .add(wf.AccumulatorBuilder(fold).build()) \
        .add_sink(wf.SinkBuilder(
            lambda r: sunk.append(r) if r is not None else None).build())
    quiet_run(g)
    snaps = [n for n in os.listdir(d) if n.endswith("_stats.json")]
    assert len(snaps) <= 3
    assert f"{os.getpid()}_audit_rot_stats.json" in snaps


def test_audit_overhead_results_identical():
    """The audited lane computes the same results as audit=False (the
    overhead bench asserts the same at scale)."""
    g_on, sunk_on = keyed_graph(8_000, name="audit_on")
    quiet_run(g_on)
    g_off, sunk_off = keyed_graph(8_000, audit=False, name="audit_off")
    quiet_run(g_off)
    # sink arrival order races across the two accumulator replicas, but
    # the per-(key, id) snapshots must be identical
    key = sorted((r.key, r.id, r.value) for r in sunk_on)
    assert key == sorted((r.key, r.id, r.value) for r in sunk_off)
    assert g_on.auditor.violations == []
