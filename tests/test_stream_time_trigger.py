"""The stream-time trigger and key eviction of the device window lanes
(docs/RUNTIME.md "When a window fires").

TB windows on real stamps fire when the *replica's* stream time, the
largest stamp it has ingested over all keys, passes their end plus
``triggering_delay``, not when a later tuple of the same key comes.  A
SEQ replica then owes a row for every (key, window) that holds a tuple of
the key and for no other, and drops a key whose last window has been
staged; a key that comes back is a new key.  The native lane
(native/window_engine.cpp), the Python staging lane (``_TPUKeyState``)
and a plain numpy recomputation must give the same rows.
"""
import json

import numpy as np
import pytest

import windflow_tpu as wf
from windflow_tpu.core import WinType
from windflow_tpu.core.basic import Role
from windflow_tpu.core.tuples import TupleBatch
from windflow_tpu.operators.tpu.win_seq_tpu import WinSeqTPULogic
from windflow_tpu.runtime.native import NativeWindowEngine, native_available
from windflow_tpu.telemetry import spans

WIN, SLIDE, LIFE = 256, 128, 50
LANES = ("native", "python")

pytestmark = pytest.mark.skipif(not native_available(),
                                reason="native library not built")


# -- the plain recomputation -------------------------------------------------

def owed(keys, ts, vals, win, slide, kind="count"):
    """{(key, window): value} for every window that holds a tuple of the
    key: window ``w`` covers ``w*slide <= ts < w*slide + win``."""
    agg = {"count": len, "sum": sum, "max": max, "min": min}[kind]
    held = {}
    for k, t, v in zip(keys.tolist(), ts.tolist(), vals.tolist()):
        w0 = 0 if t < win else (t - win) // slide + 1
        for w in range(w0, t // slide + 1):
            held.setdefault((k, w), []).append(v)
    return {kw: float(agg(vs)) for kw, vs in held.items()}


def churning(n, seed=0, life=LIFE, width=3):
    """A stream on one clock (``ts = i``) whose keys live ``life`` events:
    event i goes to one of the ``width`` keys of its generation."""
    rng = np.random.default_rng(seed)
    ts = np.arange(n, dtype=np.int64)
    keys = ts // life * width + rng.integers(0, width, n)
    vals = rng.integers(1, 100, n).astype(np.float64)
    return keys, ts, vals


# -- driving one replica's logic ---------------------------------------------

class Rows:
    """The sink of a logic driven by hand: every result batch's rows,
    and a refusal of a (key, window) that comes twice."""

    def __init__(self):
        self.rows = {}

    def __call__(self, batch):
        for k, w, v in zip(batch.key.tolist(), batch.id.tolist(),
                           np.asarray(batch["value"]).tolist()):
            assert (k, w) not in self.rows, f"row {(k, w)} came twice"
            self.rows[(k, w)] = v


def make_logic(lane, kind="count", win=WIN, slide=SLIDE, win_type=WinType.TB,
               delay=0, role=Role.SEQ, batch_len=64):
    logic = WinSeqTPULogic(
        kind, win, slide, win_type, batch_len=batch_len,
        triggering_delay=delay, role=role, emit_batches=True,
        async_dispatch=False,
        # a value_of keeps a logic off the native engine
        value_of=(lambda t: t.value) if lane == "python" else None)
    assert (logic._native is not None) == (lane == "native")
    return logic


def feed(logic, out, keys, ts, vals, chunk, lo=0, hi=None):
    hi = len(keys) if hi is None else hi
    for a in range(lo, hi, chunk):
        b = min(a + chunk, hi)
        logic.svc(TupleBatch({"key": keys[a:b], "id": ts[a:b],
                              "ts": ts[a:b], "value": vals[a:b]}), 0, out)


def settle(logic, out):
    """Everything fired so far, launched and emitted; nothing at EOS."""
    logic.flush_chunk(out)
    logic.quiesce(out)


def live_keys(logic):
    return logic._store.snapshot()["keys_live"]


# -- the rule ------------------------------------------------------------------

@pytest.mark.parametrize("chunk", [1000, 4099])
@pytest.mark.parametrize("kind", ["count", "sum", "max"])
@pytest.mark.parametrize("lane", LANES)
def test_lanes_agree_with_numpy_on_churning_keys(lane, kind, chunk):
    keys, ts, vals = churning(20_000, seed=3)
    logic, out = make_logic(lane, kind), Rows()
    feed(logic, out, keys, ts, vals, chunk)
    logic.eos_flush(out)
    assert out.rows == owed(keys, ts, vals, WIN, SLIDE, kind)


@pytest.mark.parametrize("lane", LANES)
def test_quiet_key_fires_before_eos_without_a_tuple_of_its_own(lane):
    # key 7 speaks at ts 0..9 and never again; key 8 carries the stream on
    ts = np.arange(2000, dtype=np.int64)
    keys = np.where(ts < 10, 7, 8)
    vals = np.ones(2000)
    logic, out = make_logic(lane), Rows()
    feed(logic, out, keys, ts, vals, 500)
    settle(logic, out)
    assert out.rows[(7, 0)] == 10.0          # fired by key 8's stamps
    assert {kw for kw in out.rows if kw[0] == 7} == {(7, 0)}
    before = dict(out.rows)
    logic.eos_flush(out)
    assert {kw for kw in out.rows if kw not in before} \
        == {(8, w) for w in range(14, 16)}   # only the EOS-cut tail


@pytest.mark.parametrize("lane", LANES)
def test_triggering_delay_is_honoured(lane):
    delay = 100
    logic, out = make_logic(lane, delay=delay), Rows()
    one = np.ones(1)

    def tup(key, t):
        feed(logic, out, np.array([key]), np.array([t]), one, 1)
        settle(logic, out)

    tup(1, 5)
    tup(2, WIN + delay - 1)                  # one short of end + delay
    assert (1, 0) not in out.rows
    tup(1, 200)                              # 155 behind: inside the delay
    tup(2, WIN + delay)                      # the stream passes end + delay
    assert out.rows[(1, 0)] == 2.0
    tup(1, 250)                              # late: window 0 has fired
    logic.eos_flush(out)
    assert out.rows[(1, 0)] == 2.0
    ignored = logic._store.ignored()
    assert ignored == 1


@pytest.mark.parametrize("lane,n_keys", [("native", 100_000),
                                         ("python", 2_000)])
def test_live_keys_stay_bounded(lane, n_keys):
    """One new key every ``LIFE`` events: the keys a replica holds are
    those first seen in the last window and slide (and the chunk in
    hand), not every key ever seen."""
    n, chunk = n_keys * LIFE, 1000
    keys, ts, vals = churning(n, width=1)
    bound = (WIN + SLIDE + chunk) // LIFE + 2
    logic, out = make_logic(lane), Rows()
    peak = 0
    for a in range(0, n, chunk):
        feed(logic, out, keys, ts, vals, chunk, a, a + chunk)
        logic.flush_chunk(out)
        peak = max(peak, live_keys(logic))
    logic.eos_flush(out)
    assert 0 < peak <= bound, (peak, bound)
    assert len({k for k, _ in out.rows}) == n_keys
    census = logic.keyed_state_census()
    assert census is None or census[0] <= bound


@pytest.mark.parametrize("lane", LANES)
def test_returning_key_is_anchored_anew(lane):
    """Between two lives of a key no empty window is emitted, whether its
    state had been dropped in between or not."""
    ts = np.arange(6000, dtype=np.int64)
    keys = np.where((ts < 10) | ((ts >= 5000) & (ts < 5010)), 7, 8)
    vals = np.ones(6000)
    for chunk in (6000, 250):                # one call, and evicted between
        logic, out = make_logic(lane), Rows()
        for a in range(0, 6000, chunk):
            feed(logic, out, keys, ts, vals, chunk, a, a + chunk)
            logic.flush_chunk(out)
            if chunk == 250 and a == 2000:
                assert live_keys(logic) == 1     # key 7 is gone
        logic.eos_flush(out)
        mine = {kw: v for kw, v in out.rows.items() if kw[0] == 7}
        assert mine == {(7, 0): 10.0, (7, 38): 10.0, (7, 39): 10.0}


@pytest.mark.parametrize("lane", LANES)
def test_cross_key_laggard_is_late_and_counted(lane):
    """The contract: a TB replica wants its input ordered per replica up
    to ``triggering_delay``.  Key A's stamps 0..50, then key B's 0..5000,
    then A's 51..99: A's tail lies behind windows the stream has passed."""
    a1 = np.arange(51, dtype=np.int64)
    b = np.arange(5001, dtype=np.int64)
    a2 = np.arange(51, 100, dtype=np.int64)
    ts = np.concatenate([a1, b, a2])
    keys = np.concatenate([np.full(51, 1), np.full(5001, 2),
                           np.full(49, 1)])
    logic, out = make_logic(lane), Rows()
    feed(logic, out, keys, ts, np.ones(len(ts)), 700)
    logic.eos_flush(out)
    assert out.rows[(1, 0)] == 51.0
    assert {kw for kw in out.rows if kw[0] == 1} == {(1, 0)}
    ignored = logic._store.ignored()
    assert ignored == 49


@pytest.mark.parametrize("lane", LANES)
def test_cb_windows_keep_their_rule(lane):
    """CB windows count a key's own arrivals: a key with few tuples gets
    its (EOS-cut) rows at EOS however far the others have run, and the
    empty rows of the id space are the engine's as before."""
    n = 3000
    idx = np.arange(n, dtype=np.int64)
    keys = np.where(idx % 100 == 0, 5, 6)    # key 5: 30 arrivals
    ids = np.where(keys == 5, idx // 100, idx - idx // 100 - 1)
    logic, out = make_logic(lane, win=64, slide=32, win_type=WinType.CB), \
        Rows()
    feed(logic, out, keys, ids, np.ones(n), 500)
    settle(logic, out)
    assert not [kw for kw in out.rows if kw[0] == 5]
    n6 = int((keys == 6).sum())
    assert {w for k, w in out.rows if k == 6} \
        == set(range((n6 - 64) // 32 + 1))
    logic.eos_flush(out)
    assert {kw: v for kw, v in out.rows.items() if kw[0] == 5} \
        == {(5, 0): 30.0}
    assert live_keys(logic) == 2             # CB keys are never evicted


@pytest.mark.parametrize("cut", [3000, 10_240])
@pytest.mark.parametrize("lane", LANES)
def test_restore_midstream_gives_the_uninterrupted_rows(lane, cut):
    keys, ts, vals = churning(20_000, seed=11)
    whole_logic, whole = make_logic(lane, "sum"), Rows()
    feed(whole_logic, whole, keys, ts, vals, 1000)
    whole_logic.eos_flush(whole)

    first, out = make_logic(lane, "sum"), Rows()
    feed(first, out, keys, ts, vals, 1000, 0, cut)
    first.quiesce(out)
    state = first.state_dict()
    seen = len({int(k) for k in keys[:cut]})
    held = (len(state["keys"]) if lane == "python"
            else first._native.snapshot()["keys_live"])
    assert held < seen / 4                   # the evicted keys are gone
    second = make_logic(lane, "sum")
    second.load_state(state)
    feed(second, out, keys, ts, vals, 1000, cut)
    second.eos_flush(out)
    assert out.rows == whole.rows == owed(keys, ts, vals, WIN, SLIDE, "sum")


@pytest.mark.parametrize("role", [Role.SEQ, Role.PLQ])
def test_dense_roles_number_every_window(role):
    """A PLQ replica's output ids count a key's windows for the next
    stage: it emits the empty ones too and keeps its keys; a SEQ replica
    does neither."""
    ts = np.concatenate([np.arange(10), np.arange(1000, 2000)]) \
        .astype(np.int64)
    keys = np.where(ts < 10, 7, 8)
    keys[-1] = 7                             # key 7 comes back at ts 1999
    got = []
    logic = make_logic("native", role=role)
    logic.svc(TupleBatch({"key": keys, "id": ts, "ts": ts,
                          "value": np.ones(len(ts))}), 0, got.append)
    logic.eos_flush(got.append)
    sevens = sorted(int(w) for b in got
                    for k, w in zip(b.key.tolist(), b.id.tolist()) if k == 7)
    if role == Role.PLQ:
        assert sevens == list(range(16))     # ids 0..15, empties included
        assert live_keys(logic) == 2
    else:
        assert sevens == [0, 14, 15]
        assert live_keys(logic) == 0


# -- the native engine by itself -----------------------------------------------

def drain(eng, rows, max_windows=1 << 20):
    while True:
        r = eng.flush(max_windows)
        if r is None:
            return
        vals, starts, ends, keys, gwids = r[0]["value"], *r[1:5]
        for j in range(len(keys)):
            kw = (int(keys[j]), int(gwids[j]))
            assert kw not in rows
            rows[kw] = float(vals[starts[j]:ends[j]].sum())


@pytest.mark.parametrize("take", [1, 37, 1 << 20])
def test_partial_takes_stage_the_same_rows_and_counters_add_up(take):
    keys, ts, vals = churning(50_000, seed=5)
    eng, rows = NativeWindowEngine(WIN, SLIDE, True, 0, kind="count"), {}
    for a in range(0, len(ts), 1777):
        b = a + 1777
        eng.ingest(keys[a:b], ts[a:b], ts[a:b], vals[a:b])
        if take > 1:
            drain(eng, rows, take)
        s = eng.snapshot()
        assert s["keys_opened"] - s["keys_evicted"] == s["keys_live"]
        assert s["keys_live"] <= s["keys_live_peak"]
    eng.eos()
    drain(eng, rows, take)
    s = eng.snapshot()
    want = owed(keys, ts, vals, WIN, SLIDE)
    assert rows == want
    assert s["windows_fired"] == len(want)
    assert s["keys_opened"] == len(set(keys.tolist()))
    assert s["keys_evicted"] == s["keys_opened"] and s["keys_live"] == 0
    assert s["stream_time"] == len(ts) - 1
    if take > 1:
        # three keys every LIFE events over window, slide and chunk
        assert s["keys_live_peak"] <= 3 * ((WIN + SLIDE + 1777) // LIFE + 2)
        assert s["open_ns"] > 0 and s["trigger_ns"] > 0 and s["evict_ns"] > 0


def test_snapshot_holds_live_keys_only_and_keeps_the_counters():
    keys, ts, vals = churning(30_000, seed=9)
    a = NativeWindowEngine(WIN, SLIDE, True, 0, kind="sum")
    a.ingest(keys[:20_000], ts[:20_000], ts[:20_000], vals[:20_000])
    drain(a, {})
    blob, before = a.serialize(), a.snapshot()
    assert before["keys_evicted"] > 1000
    assert len(blob["native"]) < 64 * 1024   # a few dozen keys, not 1,200
    b = NativeWindowEngine(WIN, SLIDE, True, 0, kind="sum")
    b.deserialize(blob)
    after = b.snapshot()
    for name in ("keys_opened", "keys_evicted", "keys_live",
                 "windows_fired", "stream_time"):
        assert after[name] == before[name], name
    with pytest.raises(ValueError):          # another rule, another blob
        NativeWindowEngine(WIN, SLIDE, True, 0, kind="sum",
                           dense=True).deserialize(blob)


# -- the spans and counters ------------------------------------------------------

def test_account_enters_a_child_without_a_clock():
    tr = spans.Track("t")
    tr.begin("wf/op/fold")
    tr.account("wf/op/open", 2_000)
    tr.account("wf/op/trigger", 3_000)
    tr.end()
    fold, opened = tr.cells["wf/op/fold"], tr.cells["wf/op/open"]
    assert (opened.count, opened.total_ns, opened.self_ns) == (1, 2000, 2000)
    assert tr.cells["wf/op/trigger"].self_ns == 3000
    # the parent's self time is its whole time less its children's
    assert fold.self_ns == max(0, fold.total_ns - 5000)


def test_phases_and_counters_reach_stats_json_and_openmetrics():
    from windflow_tpu.operators.basic_ops import Sink
    from windflow_tpu.operators.batch_ops import BatchSource
    from windflow_tpu.operators.tpu.farms_tpu import KeyFarmTPU
    from windflow_tpu.telemetry.metrics import render_openmetrics
    keys, ts, vals = churning(40_000, seed=1)
    sent = {"i": 0}

    def body(ctx=None):
        a = sent["i"]
        if a >= len(ts):
            return None
        sent["i"] = b = a + 2048
        return TupleBatch({"key": keys[a:b], "id": ts[a:b], "ts": ts[a:b],
                           "value": vals[a:b]})

    rows = Rows()
    g = wf.PipeGraph("stt_spans", wf.Mode.DEFAULT)
    g.add_source(BatchSource(body)).add(
        KeyFarmTPU("count", WIN, SLIDE, WinType.TB, name="churn",
                   emit_batches=True)).add_sink(
        Sink(lambda b: rows(b) if b is not None else None, name="out"))
    g.run()
    assert rows.rows == owed(keys, ts, vals, WIN, SLIDE)
    report = json.loads(g.stats.to_json())
    mine = [r for r in report["Spans"]["Operators"] if "Counters" in r]
    assert mine and all("churn" in r["Operator"] for r in mine)
    c = mine[0]["Counters"]
    assert c["keys_opened"] - c["keys_evicted"] == c["keys_live"]
    assert c["keys_opened"] == len(set(keys.tolist()))
    assert c["windows_fired"] == len(rows.rows)
    assert 0 < c["keys_live_peak"] < 200
    phases = {}
    for r in mine:
        phases.update(r["Phases"])
    for phase in ("fold", "flush", "open", "trigger", "evict"):
        assert phases[phase]["Count"] > 0 and phases[phase]["Self_s"] > 0, \
            phase
    # the registry outlives the graph, and cuts the live count in time
    kept = spans.graph("stt_spans").counters[mine[0]["Operator"]]
    assert kept.values == c
    assert kept.live_peak(0.0, 1e12) == c["keys_live_peak"]
    text = render_openmetrics({"a": {"report": report}})
    for name in ("keys_opened_total", "keys_evicted_total", "keys_live",
                 "keys_live_peak", "windows_fired_total"):
        assert f"windflow_engine_{name}{{" in text, name
