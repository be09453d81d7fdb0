"""The span layer inside the program (telemetry/spans.py;
docs/OBSERVABILITY.md "Spans"): exact accounting under a scripted clock,
the triad per operator thread, the launch record's stamps, the spans in
a profiler trace, slow spans in the flight recorder, the stats and
/metrics surfaces, and that spans change no output."""
import glob
import json
import threading
import time

import numpy as np
import pytest

import windflow_tpu as wf
from windflow_tpu.core.basic import OptLevel, RuntimeConfig
from windflow_tpu.core.tuples import BasicRecord, TupleBatch
from windflow_tpu.operators.basic_ops import Sink, Source
from windflow_tpu.operators.batch_ops import BatchMap, BatchSource
from windflow_tpu.operators.tpu.farms_tpu import KeyFarmTPU
from windflow_tpu.operators.tpu.win_seq_tpu import WinSeqTPU
from windflow_tpu.runtime.native import native_available
from windflow_tpu.telemetry import FlightRecorder, render_openmetrics, spans

MS = 1_000_000


@pytest.fixture
def clock(monkeypatch):
    """A clock the test moves: ``clock.t`` in nanoseconds."""
    class Clock:
        t = 0

        def __call__(self):
            return self.t

        def tick(self, ns):
            self.t += ns
    c = Clock()
    monkeypatch.setattr(spans, "_now", c)
    return c


def chunks(n_chunks, rows=1000, n_keys=7):
    """A ``BatchSource`` body: ``n_chunks`` chunks with id = ts = index."""
    i = [0]

    def body(ctx=None):
        if i[0] >= n_chunks:
            return None
        ids = np.arange(i[0] * rows, (i[0] + 1) * rows, dtype=np.int64)
        i[0] += 1
        return TupleBatch({"key": ids % n_keys, "id": ids, "ts": ids,
                           "value": np.ones(rows)})
    return body


def cells_of(graph_name):
    return {(tr.thread, c.name): c
            for tr, c in spans.graph(graph_name).cells()}


# -- accounting ------------------------------------------------------------

def test_exact_accounting_under_a_scripted_clock(clock):
    tr = spans.Track("t")
    for work, wait in ((3, 2), (7, 4)):
        tr.begin("wf/op/svc")
        clock.tick(work * MS)
        tr.begin("wf/op/put_wait")
        clock.tick(wait * MS)
        tr.begin("wf/op/keyby")
        clock.tick(1 * MS)
        tr.end()
        tr.end()
        clock.tick(1 * MS)
        tr.end()
        clock.tick(5 * MS)              # under no span
    svc, put, keyby = (tr.cells[n] for n in (
        "wf/op/svc", "wf/op/put_wait", "wf/op/keyby"))
    assert (svc.count, put.count, keyby.count) == (2, 2, 2)
    assert svc.total_ns == (3 + 2 + 1 + 1 + 7 + 4 + 1 + 1) * MS
    assert svc.longest_ns == 13 * MS
    # self time is the span minus what its children cover
    assert svc.self_ns == (3 + 1 + 7 + 1) * MS
    assert put.total_ns == (2 + 1 + 4 + 1) * MS and put.self_ns == 6 * MS
    assert keyby.self_ns == keyby.total_ns == 2 * MS
    assert (keyby.operator, keyby.phase) == ("op", "keyby")
    assert tr.life_ns() == (0, 25 * MS)
    assert not tr.stack


def test_the_timeline_cut_at_two_instants(clock):
    tr = spans.Track("t")
    spent = []                      # (begin, end) of every span, ns
    rng = np.random.default_rng(7)
    for _ in range(400):
        clock.tick(int(rng.integers(1, 30)) * MS)
        a = clock.t
        tr.begin("wf/op/svc")
        clock.tick(int(rng.integers(1, 250)) * MS)   # some over a bucket
        tr.end()
        spent.append((a, clock.t))
    cell = tr.cells["wf/op/svc"]
    assert sum(spans.timeline(cell).values()) == cell.self_ns == sum(
        b - a for a, b in spent)
    for t0, t1 in ((5.05, 21.73), (0.0, 3.0), (40.2, 40.9)):
        want = sum(max(0, min(b, t1 * 1e9) - max(a, t0 * 1e9))
                   for a, b in spent) / 1e9
        got = spans.self_seconds(tr, cell, t0, t1)
        assert abs(got - want) <= spans.BUCKET_NS / 1e9    # one bucket
    # a span that is open now counts up to now
    tr.begin("wf/op/svc")
    clock.tick(40 * MS)
    assert spans.self_seconds(tr, cell) == pytest.approx(
        (cell.self_ns + 40 * MS) / 1e9)
    tr.end()


def test_the_timeline_is_bounded(clock, monkeypatch):
    monkeypatch.setattr(spans, "TIMELINE_BUCKETS", 16)
    tr = spans.Track("t")
    for _ in range(100):
        tr.begin("wf/op/get_wait")
        clock.tick(150 * MS)
        tr.end()
    cell = tr.cells["wf/op/get_wait"]
    assert len(spans.timeline(cell)) <= 17 and len(cell.peaks) <= 16
    assert cell.self_ns == 100 * 150 * MS       # totals keep everything


# -- the triad on a graph --------------------------------------------------

def three_operators(name, sink_fn, n_chunks=60, **cfg):
    g = wf.PipeGraph(name, wf.Mode.DEFAULT, config=RuntimeConfig(
        opt_level=OptLevel.LEVEL0, **cfg))   # LEVEL0: a thread an operator
    g.add_source(BatchSource(chunks(n_chunks))) \
        .add(BatchMap(lambda b: b.with_cols(value=b["value"] * 2),
                      name="doubler")) \
        .add_sink(Sink(sink_fn, name="outlet"))
    return g


def test_the_triad_of_every_operator_thread_sums_to_its_life():
    seen = []

    def slow_sink(item):
        if item is not None:
            time.sleep(0.002)
            seen.append(len(item))
    g = three_operators("spans_triad", slow_sink)
    g.run()
    assert sum(seen) == 60_000
    sg = spans.graph("spans_triad")
    by_thread = {}
    for row in spans.triad(sg):
        if row["operator"] in ("audit", "diagnosis"):
            continue
        by_thread.setdefault(row["thread"], []).append(row)
    assert len(by_thread) == 3
    for thread, rows in by_thread.items():
        spent = sum(r[k] for r in rows
                    for k in ("busy_s", "idle_s", "blocked_s", "body_s"))
        assert spent == pytest.approx(rows[0]["life_s"], rel=0.05), thread
    # the source has no get_wait; its loop is the body, its puts are not
    src = next(r for rows in by_thread.values() for r in rows
               if "batch_source" in r["operator"])
    assert src["idle_s"] == 0.0 and src["body_s"] > 0.0
    assert "body" in src["phases"] and "put_wait" in src["phases"]


def test_a_sink_that_sleeps_makes_its_upstream_read_put_wait():
    def sleepy(item):
        if item is not None:
            time.sleep(0.004)
    g = three_operators("spans_blocked", sleepy, n_chunks=80,
                        queue_capacity=2)
    g.run()
    rows = {r["operator"].rsplit("/", 1)[-1].split(".")[0]: r
            for r in spans.triad(spans.graph("spans_blocked"))}
    doubler, outlet = rows["doubler"], rows["outlet"]
    # the map is blocked on the sink's full channel, not working
    assert doubler["blocked_s"] > 5 * doubler["busy_s"]
    assert doubler["blocked_s"] > 0.5 * doubler["life_s"]
    assert outlet["busy_s"] > 0.8 * outlet["life_s"]    # the sleeper is busy
    assert outlet["blocked_s"] == 0.0


def test_a_keyby_edge_reads_keyby_apart_from_put_wait():
    got = []
    lock = threading.Lock()

    def sink(item):
        if item is not None:
            with lock:
                got.append(len(item))
    g = wf.PipeGraph("spans_keyby", wf.Mode.DEFAULT,
                     config=RuntimeConfig(opt_level=OptLevel.LEVEL0))
    g.add_source(BatchSource(chunks(40))) \
        .add(BatchMap(lambda b: b, parallelism=2, name="keyed",
                      keyed=True)) \
        .add_sink(Sink(sink, name="outlet"))
    g.run()
    assert sum(got) == 40_000
    cells = {c.phase: c for (_t, _n), c in cells_of("spans_keyby").items()
             if "batch_source" in c.operator}
    assert cells["keyby"].count == cells["put_wait"].count == 40
    # the partitioning is a child of the put: the put's self time is
    # the waiting alone
    assert cells["put_wait"].total_ns >= cells["put_wait"].self_ns \
        + cells["keyby"].total_ns - 1
    assert cells["keyby"].self_ns > 0


def test_fused_segments_are_children_of_the_source_loop():
    out = []
    g = wf.PipeGraph("spans_fused", wf.Mode.DEFAULT)     # LEVEL2: one thread
    g.add_source(BatchSource(chunks(30))) \
        .add(BatchMap(lambda b: b, name="ident")) \
        .add_sink(Sink(lambda item: out.append(item), name="outlet"))
    g.run()
    cells = cells_of("spans_fused")
    threads = {t for t, _ in cells if not t.startswith("windflow-")}
    assert len(threads) == 1
    by_name = {n: c for (_t, n), c in cells.items()}
    # ONE span round the source's loop, its body; no span a step
    src = next(c for n, c in by_name.items()
               if n.endswith("batch_source/body"))
    kids = [c for n, c in by_name.items() if c is not src
            and c.operator not in ("audit", "diagnosis")]
    assert {c.phase for c in kids} == {"svc"}
    assert {c.operator.rsplit("/", 1)[-1].split(".")[0] for c in kids} \
        == {"ident", "outlet"}
    # the loop's total covers its children (the segments' own EOS
    # flushes come after it); its self time, the body, is what is left
    assert 0 < src.self_ns < src.total_ns \
        <= src.self_ns + sum(c.self_ns for c in kids)
    assert all(c.count >= 30 for c in kids) and src.count == 1


def test_a_chain_on_a_source_reads_svc_apart_from_the_body():
    def slow_map(b):
        time.sleep(0.002)               # the chained operator's work
        return b
    g = wf.PipeGraph("spans_chain", wf.Mode.DEFAULT)
    pipe = g.add_source(BatchSource(chunks(30)))
    pipe.chain(BatchMap(slow_map, name="chained"))
    pipe.add_sink(Sink(lambda item: None, name="outlet"))
    g.run()
    by_name = {n: c for (_t, n), c in cells_of("spans_chain").items()}
    body = next(c for n, c in by_name.items()
                if n.endswith("batch_source/body"))
    svc = next(c for n, c in by_name.items()
               if n.endswith("batch_source/svc"))
    # the chain runs under the source's name, one svc span a chunk
    assert svc.count == 30 and body.count == 1
    assert svc.self_ns >= 30 * 2 * MS > body.self_ns


def test_a_record_source_reads_no_clock_per_record(monkeypatch):
    reads = [0]
    real = time.perf_counter_ns

    def counting():
        reads[0] += 1
        return real()
    monkeypatch.setattr(spans, "_now", counting)
    n = [0]

    def body(shipper):
        if n[0] >= 20_000:
            return False
        shipper.push(BasicRecord(n[0] % 5, n[0], n[0], 1.0))
        n[0] += 1
        return True
    total = [0]
    g = wf.PipeGraph("spans_records", wf.Mode.DEFAULT)
    g.add_source(Source(body)) \
        .add_sink(Sink(lambda r: total.__setitem__(
            0, total[0] + (r is not None))))
    g.run()
    assert total[0] == 20_000
    assert reads[0] < 20_000 / 4       # blocks of steps, not records


def test_a_graph_of_the_same_name_drops_the_old_cells():
    for n_chunks in (30, 10):
        g = wf.PipeGraph("spans_again", wf.Mode.DEFAULT)
        g.add_source(BatchSource(chunks(n_chunks))) \
            .add_sink(Sink(lambda item: None))
        g.run()
    svc = next(c for (_t, n), c in cells_of("spans_again").items()
               if n.endswith("/svc"))
    assert svc.count == 11      # the second run's alone (10 + its EOS flush)
    assert spans.graph("spans_again").ended


# -- launches --------------------------------------------------------------

def windowed(name, async_dispatch, sink_fn, n_chunks=64, native=True):
    g = wf.PipeGraph(name, wf.Mode.DEFAULT)
    op = WinSeqTPU("sum", 4096, 2048, wf.WinType.TB, batch_len=8,
                   name="win", emit_batches=True,
                   async_dispatch=async_dispatch,
                   # a value_of takes the Python staging lane
                   value_of=None if native else (lambda t: t.value))
    g.add_source(BatchSource(chunks(n_chunks))).add(op) \
        .add_sink(Sink(sink_fn, name="outlet"))
    return g


@pytest.mark.parametrize("async_dispatch,native", [
    (True, True), (False, True), (True, False)],
    ids=["async", "inline", "python_staged"])
def test_the_launch_ring_has_every_launch_in_its_stages(async_dispatch,
                                                        native):
    rows = []
    name = f"spans_launch_{async_dispatch}_{native}"
    g = windowed(name, async_dispatch,
                 lambda item: rows.append(item) if item is not None
                 else None, native=native)
    g.run()
    rep = json.loads(g.stats.to_json())
    rec = next(r for op in rep["Operators"] if "win" in op["Operator_name"]
               for r in op["Replicas"])
    ring = next(iter(spans.graph(name).rings.values()))
    done = ring.finished()
    assert rec["Device_launches"] == len(done) == len(ring.records) > 3
    assert [r.seq for r in done] == list(range(1, len(done) + 1))
    assert all(0 < r.chunk_seq <= 64 for r in done)
    for r in done:
        assert r.t_submitted <= r.t_picked <= r.t_dispatched \
            <= r.t_ready_seen <= r.t_on_host <= r.t_emitted
        assert r.bytes_in > 0 and r.bytes_out > 0
    walls = sum(s["dispatch"] + s["ready_wait"] + s["block"]
                for s in (r.stages_ms() for r in done))
    assert walls == pytest.approx(rec["Device_time_ms"], rel=0.01)
    summary = ring.summary()
    assert summary["Launches"] == len(done)
    assert summary["dispatch"]["max_ms"] >= summary["dispatch"]["mean_ms"]
    # the stages have their spans on the thread that ran them
    phases = {c.phase for c in cells_of(name).values() if "win" in c.operator}
    assert {"dispatch", "block", "emit"} <= phases
    assert ({"fold", "flush"} if native and native_available()
            else {"stage"}) <= phases
    assert ("submit_wait" in phases) == async_dispatch


# -- one clock with the device trace ---------------------------------------

def q5_rows(name, seed=11, n_chunks=48, rows=1024, n_keys=11):
    """A Q5-shaped graph (KEYBY count over sliding windows); returns
    the sink's rows and the numpy recomputation, both sorted."""
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, n_keys, n_chunks * rows).astype(np.int64)
    i = [0]

    def body(ctx=None):
        if i[0] >= n_chunks:
            return None
        lo = i[0] * rows
        i[0] += 1
        ids = np.arange(lo, lo + rows, dtype=np.int64)
        return TupleBatch({"key": keys[lo:lo + rows], "id": ids, "ts": ids,
                           "value": np.ones(rows)})
    got = []
    g = wf.PipeGraph(name, wf.Mode.DEFAULT)
    g.add_source(BatchSource(body)) \
        .add(KeyFarmTPU("count", 4096, 2048, wf.WinType.TB, batch_len=16,
                        name="q5_counts", emit_batches=True)) \
        .add_sink(Sink(lambda b: got.append(
            np.stack([b.key, b.id, b["value"]], 1)) if b is not None
            else None, name="q5_sink"))
    g.run()
    have = np.concatenate(got)
    have = have[np.lexsort((have[:, 1], have[:, 0]))]
    want = []
    n = len(keys)
    for k in range(n_keys):
        ts = np.flatnonzero(keys == k)
        if not len(ts):
            continue
        w = 0
        while w * 2048 <= ts.max():
            want.append((k, w, np.count_nonzero(
                (ts >= w * 2048) & (ts < w * 2048 + 4096))))
            w += 1
    want = np.asarray(sorted(want), np.float64)
    assert n == n_chunks * rows
    return have, want


@pytest.mark.parametrize("session", [False, True], ids=["off", "on"])
def test_spans_change_no_output_and_reach_the_profiler_trace(session,
                                                             tmp_path):
    import jax
    if session:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        assert spans.session_active() is session
        have, want = q5_rows(f"spans_q5_{session}")
    finally:
        if session:
            jax.profiler.stop_trace()
    # row for row the numpy recomputation, with a session on and off
    assert np.array_equal(have, want)
    # without a session no span made an annotation object
    assert not spans.session_active()
    if not session:
        return
    from jax.profiler import ProfileData
    path = sorted(glob.glob(str(
        tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb")))[-1]
    events = [((plane.name, k), ev.name, dict(ev.stats))
              for plane in ProfileData.from_file(path).planes
              if not plane.name.startswith("/device:")
              for k, line in enumerate(plane.lines) for ev in line.events
              if ev.name.startswith("wf/")]
    names = {n for _l, n, _s in events}
    staging = ("fold", "flush") if native_available() else ("stage",)
    for phase in ("svc", "body", "submit_wait", "dispatch", "block",
                  "emit") + staging:
        assert any(n.endswith("/" + phase) for n in names), phase
    # the launch's dispatch annotation carries its sequence number
    launches = sorted(s["launch"] for _l, n, s in events
                      if n.endswith("/dispatch"))
    ring = next(iter(spans.graph("spans_q5_True").rings.values()))
    assert launches == [r.seq for r in ring.records]
    # source loop and dispatcher are two thread lines of the host plane
    assert len({line for line, n, _s in events
                if n.endswith(("/body", "/dispatch"))}) == 2


# -- long spans ------------------------------------------------------------

def test_a_scripted_stall_lands_in_the_flight_recorder(clock):
    flight = FlightRecorder(64)
    g = spans.start_graph("spans_stall", flight)
    a, b, c = (spans.Track(n) for n in ("ingest", "dispatch", "audit"))
    for tr in (a, b, c):
        tr.graph = g
        g.tracks.append(tr)
    clock.t = 10_000 * MS
    b.begin("wf/win/dispatch")          # open when the stall begins
    c.begin("wf/audit/pass")
    clock.tick(5 * MS)
    a.begin("wf/win/svc")
    a.begin("wf/win/submit_wait")       # the stall: 50 ms
    clock.tick(20 * MS)
    c.end()                             # 25 ms: closed, and remembered
    clock.tick(30 * MS)
    a.end()
    a.end()                             # svc, 50 ms: under the 100 ms rule
    b.end()
    slow = [e for e in flight.snapshot() if e["kind"] == "slow_span"]
    assert [e["name"] for e in slow] == ["wf/win/submit_wait"]
    ev = slow[0]
    assert ev["thread"] == "ingest" and ev["ms"] == 50.0
    assert ev["start_s"] == pytest.approx(10.005)
    assert {(o["thread"], o["name"]) for o in ev["others"]} == {
        ("dispatch", "wf/win/dispatch"), ("audit", "wf/audit/pass")}
    assert all(o["open_ms"] == 5.0 for o in ev["others"])
    # a working span over 100 ms is slow too; a body never is, and a
    # cell says so at most once a second
    for name in ("wf/win/fold", "wf/src/body", "wf/win/fold"):
        a.begin("wf/win/svc")       # long only by its child: not recorded
        a.begin(name)
        clock.tick(120 * MS)
        a.end()
        a.end()
    slow = [e for e in flight.snapshot() if e["kind"] == "slow_span"]
    assert [e["name"] for e in slow] == ["wf/win/submit_wait",
                                         "wf/win/fold"]
    assert slow[1]["own_ms"] == slow[1]["ms"] == 120.0
    clock.tick(1000 * MS)
    a.begin("wf/win/fold")
    clock.tick(120 * MS)
    a.end()
    assert flight.snapshot()[-1]["suppressed"] == 1
    assert spans.longest_wait_ms(g, 10.0, 10.1) == 50.0
    # a source's loop is long by many short stretches: never slow
    clock.tick(2000 * MS)
    a.begin("wf/src/svc")
    for _ in range(40):
        clock.tick(5 * MS)
        a.begin("wf/win/flush")
        clock.tick(1 * MS)
        a.end()
    a.end()
    assert flight.snapshot()[-1]["name"] == "wf/win/fold"
    assert a.cells["wf/src/svc"].self_ns == 200 * MS


def test_the_auditor_and_the_diagnosis_tick_have_their_spans():
    def sink(item):
        if item is not None:
            time.sleep(0.01)
    g = wf.PipeGraph("spans_planes", wf.Mode.DEFAULT, config=RuntimeConfig(
        audit_interval_s=0.05, diagnosis_interval_s=0.05))
    g.add_source(BatchSource(chunks(40))).add_sink(Sink(sink))
    g.run()
    cells = {n: c for (_t, n), c in cells_of("spans_planes").items()}
    assert cells["wf/audit/pass"].count >= 2
    assert cells["wf/diagnosis/tick"].count >= 1


# -- where an operator reads it --------------------------------------------

def test_the_stats_json_and_openmetrics_carry_the_spans():
    g = windowed("spans_report", True, lambda item: None)
    g.run()
    rep = json.loads(g.stats.to_json())
    block = rep["Spans"]
    ops = {(r["Operator"], r["Thread"]) for r in block["Operators"]}
    assert any("win" in op for op, _t in ops)
    assert any(t.startswith("winseq-tpu-dispatch:") for _op, t in ops)
    for row in block["Operators"]:
        assert {"Busy_s", "Idle_s", "Blocked_s", "Life_s", "Busy_share",
                "Idle_share", "Blocked_share", "Last_10s"} <= set(row)
        assert row["Busy_share"] + row["Idle_share"] \
            + row["Blocked_share"] <= 1.01
        # every phase's closed spans, counted: how many, the longest one
        assert row["Phases"] and all(
            p["Count"] >= 1 and p["Total_s"] >= p["Self_s"] >= 0
            and p["Longest_ms"] * 1e-3 <= p["Total_s"] + 1e-6
            for p in row["Phases"].values())
    launch = block["Launches"][0]
    assert launch["Launches"] > 0
    # the slowest launch, whole: which one, the chunk that fired it,
    # what it moved
    slowest = launch["Slowest"]
    assert 1 <= slowest["Seq"] <= launch["Launches"]
    assert 1 <= slowest["Chunk_seq"] <= 64
    assert slowest["Bytes_in"] > 0 and slowest["Bytes_out"] > 0
    assert slowest["dispatch"] + slowest["ready_wait"] + slowest["block"] \
        + slowest["emit"] >= launch["emit"]["mean_ms"]
    assert all(launch[s]["max_ms"] >= launch[s]["mean_ms"] >= 0
               for s in spans.STAGES)
    assert "Device_roofline_frac" not in json.dumps(rep)
    text = render_openmetrics({1: {"active": True, "report": rep}})
    for kind in ("busy", "idle", "blocked"):
        fam = f"windflow_operator_{kind}_seconds"
        assert f"# TYPE {fam} counter" in text
        assert any(ln.startswith(fam + "_total{") and "win" in ln
                   for ln in text.splitlines())
    try:
        from prometheus_client.openmetrics import parser
    except ImportError:
        return
    families = {f.name for f in parser.text_string_to_metric_families(text)}
    assert {"windflow_operator_busy_seconds",
            "windflow_operator_idle_seconds",
            "windflow_operator_blocked_seconds"} <= families


# -- the inside of fold and flush (PR 37) ----------------------------------

def test_between_reads_what_the_four_old_readers_read():
    """``Counters.between(names, t0, t1)`` is the one cut of the series;
    ``moved_between``, ``folded_between``, ``staged_between`` and
    ``touched_between`` are callers of it, and the clocks of
    ``ENGINE_CLOCKS`` are cut by it like any count; ``cut`` gives the
    instants of the two notes it read between."""
    c = spans.Counters("op")
    names = spans.ENGINE_COUNTERS
    rng = np.random.default_rng(5)
    level = np.zeros(len(names), np.int64)
    noted = []
    for ms in (40, 130, 990, 1500, 1501, 2950, 3400):
        level = level + rng.integers(0, 1000, len(names))
        c.note(ms * MS, level.tolist())
        noted.append((ms, dict(zip(names, level.tolist()))))

    def moved(name, t0_ms, t1_ms):
        # the last note in the buckets before t0's, the last up to t1's
        lo = [v for ms, v in noted if ms // 100 < t0_ms // 100]
        hi = [v for ms, v in noted if ms // 100 <= t1_ms // 100]
        return (hi[-1][name] if hi else 0) - (lo[-1][name] if lo else 0)

    def notes_at(t0_ms, t1_ms):
        # the instants of those two notes: how wide the cut really is
        lo = [ms for ms, _ in noted if ms // 100 < t0_ms // 100]
        hi = [ms for ms, _ in noted if ms // 100 <= t1_ms // 100]
        if not lo or not hi or hi[-1] <= lo[-1]:
            return None
        return lo[-1] / 1e3, hi[-1] / 1e3
    assert notes_at(1000, 3000) == (0.99, 2.95)
    assert notes_at(0, 3000) is None and notes_at(5000, 6000) is None
    for t0, t1 in ((1000, 3000), (0, 3000), (100, 1550), (5000, 6000)):
        cut = t0 / 1e3, t1 / 1e3
        assert c.cut(*cut) == notes_at(t0, t1)
        by_name = c.moved_between(*cut)
        assert list(by_name) == list(spans.SERIES_COUNTERS)
        assert by_name == {n: moved(n, t0, t1)
                           for n in spans.SERIES_COUNTERS}
        for reader, pair in ((c.folded_between,
                              ("folded_by_key", "folded_singly")),
                             (c.staged_between,
                              ("panes_staged", "windows_staged")),
                             (c.touched_between,
                              ("key_touches", "walked_ahead"))):
            assert reader(*cut) == c.between(pair, *cut) \
                == tuple(by_name[n] for n in pair)
        clocks = tuple(spans.ENGINE_CLOCKS)
        assert c.between(clocks[::-1], *cut) \
            == tuple(by_name[n] for n in clocks[::-1])
    assert c.between((), 1.0, 3.0) == ()


@pytest.mark.parametrize("native", [True, False], ids=["native", "python"])
def test_the_engines_clocks_are_counters_and_no_spans(native):
    """The inside of ``fold`` and ``flush`` reaches the registry's
    series, ``Spans.Operators[].Counters`` (schema 20) and ``/metrics``
    as counters; no span carries a clock's name and the two spans' self
    time still holds what the clocks read; the Python store counts none."""
    if native and not native_available():
        pytest.skip("no native library")
    rows = []
    name = f"spans_clocks_{native}"
    g = windowed(name, True, lambda b: rows.append(b) if b is not None
                 else None, native=native)
    g.run()
    assert rows
    clocks = tuple(spans.ENGINE_CLOCKS)
    cells = cells_of(name)
    assert not {c.phase for c in cells.values()} & (
        {"ingest", "tuple_walk", "key_walk", "copy_out"} | set(clocks))
    rep = json.loads(g.stats.to_json())
    assert rep["Schema_version"] >= 20
    if not native:
        assert not any(c.values[n] for n in clocks
                       for c in spans.graph(name).counters.values())
        assert not any(r["Counters"].get(n) for n in clocks
                       for r in rep["Spans"]["Operators"] if "Counters" in r)
        return
    kept, = spans.graph(name).counters.values()
    row = next(r for r in rep["Spans"]["Operators"] if "Counters" in r)
    assert {n: row["Counters"][n] for n in clocks} \
        == {n: kept.values[n] for n in clocks}
    assert kept.between(clocks, 0.0, 1e12) \
        == tuple(kept.values[n] for n in clocks)
    text = render_openmetrics({1: {"active": True, "report": rep}})
    for n in clocks:
        assert f"windflow_engine_{n}_total{{" in text, n
    v = kept.values
    fold = sum(c.self_ns for c in cells.values() if c.phase == "fold")
    flush = sum(c.self_ns for c in cells.values() if c.phase == "flush")
    assert 0 < v["tuple_walk_ns"] + v["key_walk_ns"] <= v["ingest_ns"] < fold
    assert 0 < v["stage_ns"] and 0 < v["copy_out_ns"]
    assert v["stage_ns"] + v["copy_out_ns"] < flush
    assert v["panes_shifted"] > 0
