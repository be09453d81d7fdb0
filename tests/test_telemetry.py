"""Telemetry-plane tests (telemetry/; docs/OBSERVABILITY.md): sampled
end-to-end tracing, log-bucketed latency histograms, the flight
recorder, the OpenMetrics endpoint, the framed dashboard protocol and
the unreachable-dashboard snapshot fallback.
"""
import json
import socket
import struct
import threading
import time
import urllib.request
import warnings

import numpy as np
import pytest

import windflow_tpu as wf
from windflow_tpu.core import BasicRecord, Mode, RuntimeConfig, WinType
from windflow_tpu.core.tuples import TupleBatch
from windflow_tpu.graph.pipegraph import NodeFailureError, StallError
from windflow_tpu.operators.basic_ops import Sink
from windflow_tpu.operators.tpu.win_seq_tpu import (AdaptiveBatcher,
                                                    WinSeqTPU)
from windflow_tpu.resilience import FaultPlan
from windflow_tpu.telemetry import (FlightRecorder, LogHistogram,
                                    TraceContext, TraceSampler,
                                    render_openmetrics, spans)

WAIT_S = 60


def record_source(n, state=None):
    state = state if state is not None else {}

    def fn(shipper, ctx):
        i = state.setdefault("i", 0)
        if i >= n:
            return False
        shipper.push(BasicRecord(i % 4, i // 4, i, float(i)))
        state["i"] = i + 1
        return True

    return fn


def quiet_run(g):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        g.run()


def replay_windowed_graph(tmp_path, n=120_000, sample=2, opt_level=None,
                          tracing=True, port=None):
    """Ingest-fed windowed run: replay source -> WinSeqTPU(sum) ->
    counting sink (the acceptance-criteria shape)."""
    keys = np.arange(n, dtype=np.int64)
    ids = keys // 4
    trace = TupleBatch({"key": keys % 4, "id": ids, "ts": ids,
                        "value": np.ones(n, np.float32)})
    src = wf.SourceBuilder.from_replay(trace, speedup=None, chunk=8192) \
        .with_tracing(sample).build()
    kw = dict(tracing=tracing, log_dir=str(tmp_path),
              latency_target_ms=50.0)
    if opt_level is not None:
        kw["opt_level"] = opt_level
    if port is not None:
        kw["dashboard_port"] = port
    cfg = RuntimeConfig(**kw)
    g = wf.PipeGraph("telem_win", Mode.DEFAULT, cfg)
    op = WinSeqTPU("sum", 128, 64, WinType.TB, batch_len=256,
                   emit_batches=True)
    sums = []

    def sink(b):
        if b is not None and hasattr(b, "cols"):
            sums.append((np.asarray(b.id), np.asarray(b["value"])))

    g.add_source(src).add(op).add_sink(Sink(sink))
    return g, sums


def window_totals(sums):
    ids = np.concatenate([i for i, _v in sums]) if sums else np.empty(0)
    vals = np.concatenate([v for _i, v in sums]) if sums else np.empty(0)
    order = np.argsort(ids, kind="stable")
    return ids[order], vals[order]


# ---------------------------------------------------------------------------
# histograms
# ---------------------------------------------------------------------------

def test_histogram_quantiles_and_bounds():
    h = LogHistogram()
    for v in [10.0] * 90 + [10_000.0] * 9 + [1e6]:
        h.observe(v)
    d = h.to_dict(buckets=True)
    assert d["n"] == 100
    # quantile error bounded by one bucket ratio (2^(1/4) ~ 1.19)
    assert 10.0 <= d["p50_us"] <= 12.0
    assert 10_000.0 <= d["p99_us"] <= 12_000.0
    assert d["max_us"] == 1e6
    assert sum(c for _le, c in d["buckets"]) == 100
    les = [le for le, _c in d["buckets"]]
    assert les == sorted(les)  # monotone boundaries


def test_histogram_merge_equals_combined():
    a, b, both = LogHistogram(), LogHistogram(), LogHistogram()
    rng = np.random.default_rng(7)
    for v in rng.uniform(1, 1e5, 500):
        a.observe(v)
        both.observe(v)
    for v in rng.uniform(1, 1e7, 500):
        b.observe(v)
        both.observe(v)
    m = LogHistogram.merged([a, b, None])
    assert m.counts == both.counts
    assert m.count == both.count == 1000
    assert m.max_us == both.max_us
    assert m.percentile(0.99) == both.percentile(0.99)


def test_sampler_deterministic_period():
    s = TraceSampler(3, "src")
    hits = []
    for i in range(10):
        b = TupleBatch({"key": np.zeros(1, np.int64),
                        "id": np.zeros(1, np.int64),
                        "ts": np.zeros(1, np.int64)})
        s.maybe_attach(b)
        if getattr(b, "trace", None) is not None:
            hits.append(i)
    assert hits == [2, 5, 8]  # every 3rd emission, independent of time
    assert s.started == 3


def test_trace_propagates_through_batch_transforms():
    b = TupleBatch({"key": np.arange(8) % 2, "id": np.arange(8),
                    "ts": np.arange(8), "value": np.ones(8)})
    ctx = TraceContext("src", time.perf_counter())
    b.trace = ctx
    assert b.take(np.array([0, 2, 4])).trace is ctx      # gather
    assert b.take(slice(0, 4)).trace is ctx              # view
    assert b.take(b.key == 1).trace is ctx               # KEYBY mask
    assert b.with_cols(extra=np.zeros(8)).trace is ctx
    plain = TupleBatch({"key": np.zeros(2, np.int64),
                        "id": np.zeros(2, np.int64),
                        "ts": np.zeros(2, np.int64), "value": np.ones(2)})
    assert b.concat(plain).trace is ctx
    assert plain.concat(b).trace is ctx


# ---------------------------------------------------------------------------
# end-to-end tracing: histograms in the stats JSON
# ---------------------------------------------------------------------------

def test_record_chain_latency_histograms(tmp_path):
    cfg = RuntimeConfig(tracing=True, trace_sample=4,
                        log_dir=str(tmp_path))
    g = wf.PipeGraph("telem_rec", Mode.DEFAULT, cfg)
    g.add_source(wf.SourceBuilder(record_source(2000)).build()) \
        .add(wf.MapBuilder(lambda t: None).with_parallelism(2).build()) \
        .add_sink(wf.SinkBuilder(lambda r: None).build())
    quiet_run(g)
    data = json.loads(g.stats.to_json())
    e2e = data["Latency_e2e"]
    assert e2e["n"] > 0
    assert e2e["p50_us"] <= e2e["p95_us"] <= e2e["p99_us"]
    assert e2e["p99_us"] <= max(e2e["max_us"], e2e["p99_us"])
    by_name = {o["Operator_name"]: o for o in data["Operators"]}
    map_op = next(v for k, v in by_name.items() if "map" in k)
    assert map_op["Latency"]["service"]["n"] > 0
    assert map_op["Latency"]["residency"]["n"] > 0
    # recent closed traces carry per-hop stamps ending at the sink
    assert data["Trace_records"]
    hops = data["Trace_records"][-1]["hops"]
    assert any("sink" in h[0] for h in hops)


def test_ingest_windowed_run_latency_surface(tmp_path):
    """Acceptance shape: e2e p50/p99 + per-operator histograms for an
    ingest-fed windowed run, at LEVEL2 (engine fused with the sink)."""
    g, sums = replay_windowed_graph(tmp_path)
    quiet_run(g)
    assert g.fused_nodes, "expected the LEVEL2 engine+sink fusion"
    data = json.loads(g.stats.to_json())
    e2e = data["Latency_e2e"]
    assert e2e["n"] > 0 and e2e["p99_us"] >= e2e["p50_us"] > 0
    assert e2e["buckets"]
    win = next(o for o in data["Operators"]
               if "win_seq_tpu" in o["Operator_name"])
    assert win["Latency"]["service"]["n"] > 0
    assert win["Latency"]["residency"]["n"] > 0
    # per-SEGMENT attribution: a closed trace stamps the fused sink
    # segment under its original name, and the engine's device hop
    names = {h[0] for rec in data["Trace_records"] for h in rec["hops"]}
    assert any("win_seq_tpu" in n for n in names)
    assert any("sink" in n for n in names)
    assert sum(len(v) for _i, v in sums) > 0


def test_sampling_off_is_bitwise_identical(tmp_path):
    """trace_sample=0 keeps the telemetry plane fully out of the item
    path: no histograms in the JSON, and window results bitwise equal
    to a traced run (sampling must never perturb results)."""
    g0, sums0 = replay_windowed_graph(tmp_path, n=60_000, sample=0,
                                      tracing=False)
    quiet_run(g0)
    assert g0.telemetry is None
    g1, sums1 = replay_windowed_graph(tmp_path, n=60_000, sample=2)
    quiet_run(g1)
    assert g1.telemetry is not None and g1.telemetry.closed >= 0
    i0, v0 = window_totals(sums0)
    i1, v1 = window_totals(sums1)
    assert np.array_equal(i0, i1)
    assert np.array_equal(v0, v1)  # bitwise: same lane, same fold order
    data0 = json.loads(g0.stats.to_json())
    assert data0["Latency_e2e"] is None


def test_fused_source_head_traces(tmp_path):
    """A fully-fused linear chain (source+map+sink in ONE node at the
    default LEVEL2) must still sample: the sampler runs in the first
    segment's exit, hops carry the original segment names, and with no
    channel anywhere residency stays empty."""
    cfg = RuntimeConfig(tracing=True, trace_sample=4,
                        log_dir=str(tmp_path))
    g = wf.PipeGraph("telem_fused_head", Mode.DEFAULT, cfg)
    g.add_source(wf.SourceBuilder(record_source(2000)).build()) \
        .add(wf.MapBuilder(lambda t: None).with_name("map").build()) \
        .add_sink(wf.SinkBuilder(lambda r: None).build())
    quiet_run(g)
    assert g.fused_nodes, "expected the LEVEL2 source+map+sink fusion"
    (node,) = g._all_nodes()
    assert node.channel is None and node.logic.trace_sampler is not None
    assert node.logic.trace_sampler.started > 0
    data = json.loads(g.stats.to_json())
    assert data["Latency_e2e"]["n"] == node.logic.trace_sampler.started
    names = {h[0] for rec in data["Trace_records"] for h in rec["hops"]}
    assert any("map" in n for n in names)
    assert any("sink" in n for n in names)
    for op in data["Operators"]:
        assert op["Latency"]["residency"]["n"] == 0, op["Operator_name"]


def test_residency_counts_each_traced_arrival_once(tmp_path):
    """Every traced item crosses the source->engine channel exactly
    once, so the fused consumer's residency count must equal the
    number of traces started (a 2x reads as the consume loop AND the
    first fused segment both observing the same arrival)."""
    g, _sums = replay_windowed_graph(tmp_path, n=120_000, sample=2)
    quiet_run(g)
    assert g.fused_nodes
    started = sum(s.started for s in g.telemetry.samplers)
    assert started > 0
    data = json.loads(g.stats.to_json())
    win = next(o for o in data["Operators"]
               if "win_seq_tpu" in o["Operator_name"])
    assert win["Latency"]["residency"]["n"] == started


def test_with_tracing_override_wins_over_global_zero(tmp_path):
    """A positive per-source with_tracing(N) must enable tracing even
    when RuntimeConfig.trace_sample is 0 (the builder docs promise the
    override wins); global 0 with no override keeps telemetry off."""
    cfg = RuntimeConfig(tracing=True, log_dir=str(tmp_path))
    cfg.trace_sample = 0
    g = wf.PipeGraph("telem_override", Mode.DEFAULT, cfg)
    g.add_source(wf.SourceBuilder(record_source(200))
                 .with_tracing(4).build()) \
        .add_sink(wf.SinkBuilder(lambda r: None).build())
    quiet_run(g)
    assert g.telemetry is not None
    data = json.loads(g.stats.to_json())
    assert data["Latency_e2e"]["n"] > 0
    cfg0 = RuntimeConfig(tracing=True, log_dir=str(tmp_path))
    cfg0.trace_sample = 0
    g0 = wf.PipeGraph("telem_zero", Mode.DEFAULT, cfg0)
    g0.add_source(wf.SourceBuilder(record_source(200)).build()) \
        .add_sink(wf.SinkBuilder(lambda r: None).build())
    quiet_run(g0)
    assert g0.telemetry is None
    assert json.loads(g0.stats.to_json())["Latency_e2e"] is None


def test_with_tracing_builder_validation():
    with pytest.raises(ValueError):
        wf.SourceBuilder(record_source(1)).with_tracing(-1)
    src = wf.SourceBuilder(record_source(1)).with_tracing(7).build()
    assert src.trace_sample == 7


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def test_flight_recorder_ring_bounds():
    fr = FlightRecorder(capacity=4)
    for i in range(10):
        fr.record("ev", i=i)
    evs = fr.snapshot()
    assert len(evs) == 4 and evs[-1]["i"] == 9 and evs[0]["i"] == 6
    off = FlightRecorder(capacity=0)
    off.record("ev")
    assert len(off) == 0 and not off.enabled


def test_flight_dump_on_fault_plan_crash(tmp_path):
    plan = FaultPlan(seed=5).crash_replica("map", at_tuple=20)
    cfg = RuntimeConfig(fault_plan=plan, log_dir=str(tmp_path),
                        cancel_grace_s=1.0)
    g = wf.PipeGraph("telem_crash", config=cfg)
    g.add_source(wf.SourceBuilder(record_source(5000)).build()) \
        .add(wf.MapBuilder(lambda t: None).with_name("map").build()) \
        .add_sink(wf.SinkBuilder(lambda r: None).build())
    with pytest.raises(NodeFailureError):
        quiet_run(g)
    path = g.flight.dumped_path
    assert path is not None
    events = [json.loads(line) for line in open(path)]
    assert any(e["kind"] == "node_failure" for e in events)


def test_flight_kinds_conservation_violation_and_frontier_stall(tmp_path):
    """Audit-plane flight kinds (audit/; docs/OBSERVABILITY.md): a
    seeded drop_put lands a ``conservation_violation`` event, a wedged
    sink lands a ``frontier_stall`` event, and both ride the JSONL
    dump path."""
    # conservation_violation: the wait_end closure check flags the
    # injected drop and dumps the ring as post-mortem evidence
    plan = FaultPlan().drop_put("map", at_put=10)
    cfg = RuntimeConfig(fault_plan=plan, log_dir=str(tmp_path),
                        audit_interval_s=0.05)
    g = wf.PipeGraph("telem_viol", config=cfg)
    g.add_source(wf.SourceBuilder(record_source(200)).build()) \
        .add(wf.MapBuilder(lambda t: t).with_name("map").build()) \
        .add(wf.MapBuilder(lambda t: t).with_name("fan")
             .with_parallelism(2).build()) \
        .add_sink(wf.SinkBuilder(lambda r: None).build())
    quiet_run(g)
    evs = g.flight.snapshot()
    viol = [e for e in evs if e["kind"] == "conservation_violation"]
    assert viol and viol[0]["violation"] == "lost_delivery"
    path = g.flight.dumped_path
    assert path is not None
    dumped = [json.loads(line) for line in open(path)]
    assert any(e["kind"] == "conservation_violation" for e in dumped)

    # frontier_stall: a wedged sink freezes its watermark while the
    # source advances past it
    release = threading.Event()

    def sticky(rec):
        if rec is not None and not release.is_set():
            release.wait(WAIT_S)

    cfg2 = RuntimeConfig(tracing=True, log_dir=str(tmp_path),
                         audit_interval_s=0.05, frontier_stall_s=0.2)
    g2 = wf.PipeGraph("telem_stall", config=cfg2)
    g2.add_source(wf.SourceBuilder(record_source(5000)).build()) \
        .add(wf.MapBuilder(lambda t: t).with_parallelism(2).build()) \
        .add_sink(wf.SinkBuilder(sticky).build())
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        g2.start()
        deadline = time.monotonic() + WAIT_S
        try:
            while not any(e["kind"] == "frontier_stall"
                          for e in g2.flight.snapshot()):
                assert time.monotonic() < deadline, "no stall event"
                time.sleep(0.02)
        finally:
            release.set()
        g2.wait_end()
    g2.flight.dump(str(tmp_path), "telem_stall2")
    dumped = [json.loads(line)
              for line in open(g2.flight.dumped_path)]
    assert any(e["kind"] == "frontier_stall" for e in dumped)


def test_flight_dump_on_watchdog_stall(tmp_path):
    block = threading.Event()  # never set

    def stuck_sink(rec):
        if rec is not None:
            block.wait()

    cfg = RuntimeConfig(watchdog_timeout_s=0.5, cancel_grace_s=0.5,
                        log_dir=str(tmp_path), queue_capacity=8)
    g = wf.PipeGraph("telem_stall", config=cfg)
    g.add_source(wf.SourceBuilder(record_source(10_000)).build()) \
        .add_sink(wf.SinkBuilder(stuck_sink).build())
    box = {}

    def target():
        try:
            g.run()
        except BaseException as e:  # noqa: BLE001 - captured for assert
            box["err"] = e

    t = threading.Thread(target=target, daemon=True)
    t.start()
    t.join(WAIT_S)
    assert not t.is_alive(), "stalled graph failed to cancel"
    assert isinstance(box.get("err"), StallError)
    path = g.flight.dumped_path
    assert path is not None
    events = [json.loads(line) for line in open(path)]
    assert any(e["kind"] == "stall" for e in events)


def test_adaptive_resize_records_flight_event():
    logic = WinSeqTPU("sum", 8, 8, WinType.CB).kwargs  # params only
    from windflow_tpu.operators.tpu.win_seq_tpu import WinSeqTPULogic
    lg = WinSeqTPULogic(win_kind="sum", win_len=8, slide_len=8,
                        win_type=WinType.CB, async_dispatch=False)
    lg.flight = FlightRecorder()
    lg._adaptive = AdaptiveBatcher(256, floor_ms=50.0, patience=2)

    class _Handle:
        def block(self):
            return np.zeros(0)

        def ready(self):
            return True

    for _ in range(2):  # launches near the floor -> x2 after patience
        t_sub = time.perf_counter()
        none = np.empty(0, np.int64)   # a batch of no rows
        lg._finish((_Handle(), (none, none, none), t_sub, 1, 0,
                    lg._launches.open(0, 0, t_sub)), lambda x: None,
                   spans.READY)
    assert lg.batch_len == 512
    assert any(e["kind"] == "batch_resize" and e["new_len"] == 512
               for e in lg.flight.snapshot())
    assert logic["win_len"] == 8  # kwargs untouched by the logic


def test_shed_and_placement_events_recorded(tmp_path):
    # placement events: any graph with a window engine records one per
    # placed replica at start
    g, _sums = replay_windowed_graph(tmp_path, n=30_000)
    quiet_run(g)
    kinds = {e["kind"] for e in g.flight.snapshot()}
    assert "placement" in kinds


# ---------------------------------------------------------------------------
# export surfaces: /metrics + framed dashboard protocol
# ---------------------------------------------------------------------------

def test_openmetrics_renderer_unit():
    apps = {
        1: {"active": True, "report": {
            "PipeGraph_name": 'g"1\\x',
            "Dropped_tuples": 3, "Dead_letter_tuples": 1, "Rescales": 2,
            "Memory_usage_KB": 10,
            "Skew": {"Census": [
                {"replica": "pipe0/map_0", "keys": 5, "bytes_est": 100,
                 "tiers": {"hot": [2, 60], "warm": [2, 30],
                           "cold": [1, 10]},
                 "spills": 4, "spill_bytes": 10}],
                "Hot_keys": []},
            "Latency_e2e": {"n": 3, "sum_us": 600.0,
                            "buckets": [[100.0, 2], [-1.0, 1]]},
            "Operators": [{
                "Operator_name": "pipe0/map", "Parallelism": 2,
                "Replicas": [
                    {"Inputs_received": 5, "Outputs_sent": 5,
                     "Queue_depth": 1},
                    {"Inputs_received": 7, "Outputs_sent": 6,
                     "Queue_depth": 2}],
                "Latency": {"service": {"n": 2, "sum_us": 30.0,
                                        "buckets": [[10.0, 2]]},
                            "residency": {"n": 0, "sum_us": 0.0,
                                          "buckets": []}},
            }],
        }},
    }
    text = render_openmetrics(apps)
    assert text.endswith("# EOF\n")
    assert 'windflow_inputs_total{app="1",graph="g\\"1\\\\x",' \
        'operator="pipe0/map"} 12' in text
    assert 'windflow_queue_depth' in text and "} 3" in text
    # histogram cumulation: +Inf bucket equals the count
    assert 'windflow_e2e_latency_seconds_bucket' in text
    assert 'le="+Inf"} 3' in text
    assert "windflow_e2e_latency_seconds_sum" in text
    assert 'windflow_dropped_tuples_total' in text
    # tiered keyed-state families (state/tiers.py census extras):
    # per-tier byte gauge + spill counter, labelled by replica
    assert 'windflow_keyed_state_bytes{app="1",graph="g\\"1\\\\x",' \
        'replica="pipe0/map_0",tier="hot"} 60' in text
    assert 'tier="cold"} 10' in text
    assert 'windflow_state_spills_total{app="1",graph="g\\"1\\\\x",' \
        'replica="pipe0/map_0"} 4' in text
    # EVERY histogram closes with the mandatory +Inf bucket, even when
    # the sparse buckets already sum to n (histogram_quantile needs it)
    lines = text.splitlines()
    for i, ln in enumerate(lines):
        if "_count{" in ln and "seconds" in ln:
            fam = ln.split("_count{", 1)[0]
            n = ln.rsplit(" ", 1)[1]
            assert f'le="+Inf"}} {n}' in "\n".join(
                b for b in lines[:i] if b.startswith(fam + "_bucket")), ln
    # family-major grouping: every sample line belongs to the most
    # recent # TYPE header's family (strict OpenMetrics parsers reject
    # interleaved families as a clashing name)
    import re

    def base(name):
        return re.sub(r"_(bucket|count|sum|total)$", "", name)

    cur = None
    for ln in lines:
        if ln.startswith("# TYPE"):
            cur = ln.split()[2]
        elif ln.startswith("#"):
            continue
        else:
            name = ln.split("{", 1)[0].split(" ", 1)[0]
            assert base(name) == cur, f"{ln!r} outside family {cur}"


def test_metrics_endpoint_serves_traced_graph(tmp_path):
    from windflow_tpu.monitoring.dashboard import (DashboardServer,
                                                   serve_http)
    dash = DashboardServer(port=0)
    dash.start()
    httpd = serve_http(dash, port=0)
    http_port = httpd.server_address[1]
    try:
        g, _sums = replay_windowed_graph(tmp_path, n=60_000,
                                         port=dash.port)
        quiet_run(g)
        deadline = time.time() + 5
        while True:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{http_port}/metrics",
                    timeout=5) as r:
                ctype = r.headers["Content-Type"]
                text = r.read().decode()
            if "windflow_e2e_latency_seconds_count" in text \
                    or time.time() > deadline:
                break
            time.sleep(0.05)
        assert "openmetrics-text" in ctype
        assert text.endswith("# EOF\n")
        assert "windflow_inputs_total" in text
        assert "windflow_service_time_seconds_bucket" in text
        assert "windflow_e2e_latency_seconds_count" in text
        m = [ln for ln in text.splitlines()
             if ln.startswith("windflow_e2e_latency_seconds_count")]
        assert m and float(m[0].rsplit(" ", 1)[1]) > 0
    finally:
        httpd.shutdown()
        httpd.server_close()
        dash.stop()


class FrameAssertingDashboard(threading.Thread):
    """Satellite: mock TCP dashboard asserting the exact frame shapes
    (register type 0 + SVG, report type 1 + JSON with histogram
    fields, deregister type 2)."""

    def __init__(self):
        super().__init__(daemon=True)
        self.server = socket.create_server(("127.0.0.1", 0))
        self.port = self.server.getsockname()[1]
        self.register_payload = None
        self.reports = []
        self.deregistered = False
        self.errors = []

    def _recv(self, conn, n):
        buf = b""
        while len(buf) < n:
            chunk = conn.recv(n - len(buf))
            if not chunk:
                raise ConnectionError("closed")
            buf += chunk
        return buf

    def run(self):
        try:
            conn, _ = self.server.accept()
            with conn:
                mtype, length = struct.unpack("<ii", self._recv(conn, 8))
                assert mtype == 0, mtype
                assert length > 0
                self.register_payload = self._recv(conn, length).decode()
                conn.sendall(struct.pack("<i", 77))
                while True:
                    try:
                        mtype, app_id, length = struct.unpack(
                            "<iii", self._recv(conn, 12))
                    except ConnectionError:
                        return
                    assert app_id == 77, app_id
                    if mtype == 2:
                        assert length == 0
                        self.deregistered = True
                        return
                    assert mtype == 1, mtype
                    self.reports.append(
                        json.loads(self._recv(conn, length)))
        except BaseException as e:  # surfaced by the test body
            self.errors.append(e)

    def stop(self):
        self.server.close()


def test_dashboard_protocol_framing_and_histogram_fields(tmp_path):
    dash = FrameAssertingDashboard()
    dash.start()
    try:
        g, _sums = replay_windowed_graph(tmp_path, n=60_000,
                                         port=dash.port)
        quiet_run(g)
        dash.join(timeout=10)
        assert not dash.errors, dash.errors
        assert dash.register_payload.lstrip().startswith("<svg")
        assert dash.deregistered
        assert dash.reports
        last = dash.reports[-1]
        assert last["PipeGraph_name"] == "telem_win"
        assert "Latency_e2e" in last
        win = next(o for o in last["Operators"]
                   if "win_seq_tpu" in o["Operator_name"])
        assert "Latency" in win and "service" in win["Latency"]
    finally:
        dash.stop()


def test_unreachable_dashboard_snapshot_fallback(tmp_path):
    """Satellite: MonitoringThread must not silently disable itself --
    it warns once and writes periodic stats-JSON snapshots instead."""
    import windflow_tpu.monitoring.monitor as monitor_mod
    monitor_mod._dash_warned = False  # warn-once is per process
    # grab a port with nothing listening
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    dead_port = s.getsockname()[1]
    s.close()
    cfg = RuntimeConfig(tracing=True, log_dir=str(tmp_path),
                        dashboard_port=dead_port)
    g = wf.PipeGraph("telem_fallback", Mode.DEFAULT, cfg)
    g.add_source(wf.SourceBuilder(record_source(500)).build()) \
        .add_sink(wf.SinkBuilder(lambda r: None).build())
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        g.run()
    assert any("unreachable" in str(w.message) for w in caught)
    snap = tmp_path / f"{__import__('os').getpid()}_telem_fallback_stats.json"
    assert snap.exists(), list(tmp_path.iterdir())
    data = json.loads(snap.read_text())
    assert data["PipeGraph_name"] == "telem_fallback"
    assert data["Operators"]


# ---------------------------------------------------------------------------
# satellites: DOT escaping, bounded controller trace
# ---------------------------------------------------------------------------

def test_graph_to_dot_escapes_operator_names():
    from windflow_tpu.monitoring.monitor import graph_to_dot
    g = wf.PipeGraph('we"ird\\graph')
    g.add_source(wf.SourceBuilder(record_source(1))
                 .with_name('src"quote').build()) \
        .add_sink(wf.SinkBuilder(lambda r: None)
                  .with_name("si\\nk").build())
    dot = graph_to_dot(g)
    assert 'label="src\\"quote"' in dot
    assert 'label="si\\\\nk"' in dot
    assert 'digraph "we\\"ird\\\\graph"' in dot
    # every label attribute's quotes balance after unescaping
    for line in dot.splitlines():
        if "label=" in line:
            body = line.split('label="', 1)[1].rsplit('"', 1)[0]
            unescaped = body.replace('\\\\', '').replace('\\"', '')
            assert '"' not in unescaped and "\\" not in unescaped


def test_graph_to_dot_distinct_ops_never_collide():
    from windflow_tpu.monitoring.monitor import graph_to_dot
    g = wf.PipeGraph("collide")
    g.add_source(wf.SourceBuilder(record_source(1))
                 .with_name("op.1").build()) \
        .add(wf.MapBuilder(lambda t: None).with_name("op-1").build()) \
        .add_sink(wf.SinkBuilder(lambda r: None).with_name("op+1").build())
    dot = graph_to_dot(g)
    ids = [ln.split("[", 1)[0].strip() for ln in dot.splitlines()
           if "label=" in ln]
    assert len(ids) == len(set(ids)) == 3, ids  # sanitized ids unique
    assert 'label="op.1"' in dot and 'label="op-1"' in dot


def test_dashboard_death_mid_run_falls_back_to_snapshots(tmp_path):
    """Satellite hardening: a dashboard that dies AFTER registration
    must not silently end monitoring -- the report loop warns and
    switches to the log-dir snapshot fallback."""
    import windflow_tpu.monitoring.monitor as monitor_mod
    monitor_mod._dash_warned = False

    server = socket.create_server(("127.0.0.1", 0))
    port = server.getsockname()[1]

    def ack_then_die():
        conn, _ = server.accept()
        with conn:
            mtype, length = struct.unpack("<ii", conn.recv(8))
            assert mtype == 0
            left = length
            while left > 0:
                left -= len(conn.recv(min(left, 65536)))
            conn.sendall(struct.pack("<i", 5))
        server.close()  # connection closed: next reports raise OSError

    t = threading.Thread(target=ack_then_die, daemon=True)
    t.start()
    cfg = RuntimeConfig(tracing=True, log_dir=str(tmp_path),
                        dashboard_port=port)
    g = wf.PipeGraph("telem_middeath", Mode.DEFAULT, cfg)

    state = {"i": 0}

    def slow_source(shipper, ctx):
        if state["i"] >= 60:
            return False
        shipper.push(BasicRecord(0, state["i"], state["i"], 1.0))
        state["i"] += 1
        time.sleep(0.05)  # stream for ~3s so reports happen mid-run
        return True

    g.add_source(wf.SourceBuilder(slow_source).build()) \
        .add_sink(wf.SinkBuilder(lambda r: None).build())
    monitor_holder = {}

    def grab_interval():
        # shrink the reporting interval so the dead socket is hit
        # within the run (default is 1 s)
        m = g._monitor
        monitor_holder["m"] = m
        m.interval_s = 0.1

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        g.start()
        grab_interval()
        g.wait_end()
    t.join(timeout=5)
    assert any("unreachable" in str(w.message) for w in caught)
    snap = tmp_path / (f"{__import__('os').getpid()}"
                       f"_telem_middeath_stats.json")
    assert snap.exists(), list(tmp_path.iterdir())
    assert json.loads(snap.read_text())["Operators"]


def test_controller_trace_bounded_in_place():
    from windflow_tpu.ingest.controller import MicrobatchController
    from windflow_tpu.monitoring.stats import StatsRecord
    c = MicrobatchController(latency_target_ms=1.0, adjust_interval_s=0.0)
    for i in range(5000):
        c.trace.append((float(i), i))
    assert len(c.trace) <= 4096
    assert c.trace[-1][1] == 4999       # recent retained, oldest dropped
    assert c.trace_tail(4)[-1][1] == 4999
    rec = StatsRecord("op", "0")
    for i in range(1000):
        rec.controller_trace.append((float(i), i))
    assert len(rec.controller_trace) <= 64
    rec.ingest_batch_size = 8
    d = rec.to_dict()
    assert len(d["Controller_batch_trace"]) <= 32
    assert d["Controller_batch_trace"][-1][1] == 999


def test_to_json_safe_under_concurrent_trace_closures():
    """Sink threads append (ctx, t_end) pairs lock-free while the
    monitoring thread serializes: to_json must snapshot the deque
    atomically (a live iteration raises 'deque mutated')."""
    from windflow_tpu.monitoring.stats import GraphStats
    stats = GraphStats("hammer")
    stats.enable_histograms()
    stop = threading.Event()

    def closer():
        i = 0
        while not stop.is_set():
            stats.add_trace_record(
                (TraceContext("src", float(i)), float(i + 1)))
            i += 1

    t = threading.Thread(target=closer, daemon=True)
    t.start()
    try:
        deadline = time.time() + 1.0
        while time.time() < deadline:
            data = json.loads(stats.to_json())
            assert len(data["Trace_records"]) <= 16
    finally:
        stop.set()
        t.join(timeout=5)
