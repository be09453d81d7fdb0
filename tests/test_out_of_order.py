"""A key's anchor can move back (docs/RUNTIME.md 5a, PR 32).

Under the stream rule (TB windows on real stamps) the first tuple of a
key to arrive need not be its earliest in event time: a tuple of a live
key that lies before the window the key fires next, and in no window the
stream has passed, moves the key back, in both stores alike.  A tuple is
dropped only behind a window the stream has passed, and every dropped
tuple is counted in ``inputs_ignored``.  Both stores are driven by hand
through the engine's own calls and held to a brute-force count per (key,
window); a whole ``PipeGraph`` of the shape of the benchmark's
``nexmark_q5_ooo`` is held to the same on seeded random draws.
"""
import json
import pickle

import numpy as np
import pytest

import windflow_tpu as wf
from windflow_tpu.core.basic import Role, WinType
from windflow_tpu.core.tuples import TupleBatch
from windflow_tpu.operators.basic_ops import Sink
from windflow_tpu.operators.batch_ops import BatchMap, BatchSource
from windflow_tpu.operators.tpu.farms_tpu import KeyFarmTPU
from windflow_tpu.operators.tpu.window_store import PyWindowStore
from windflow_tpu.runtime.native import NativeWindowEngine, native_available
from windflow_tpu.telemetry import spans
from windflow_tpu.telemetry.metrics import render_openmetrics

STORES = [pytest.param("native", marks=pytest.mark.skipif(
    not native_available(), reason="native engine unavailable")), "python"]
WIN, SLIDE = 64, 32           # a window is two panes of 32
CLOCK = 1_000_000             # the key whose tuples move the stream time


def make_store(which, delay, kind="count", win=WIN, slide=SLIDE,
               role=Role.SEQ):
    if which == "native":
        return NativeWindowEngine(win, slide, True, delay, kind=kind,
                                  dense=role != Role.SEQ)
    return PyWindowStore(win, slide, WinType.TB, delay, kind=kind, role=role)


def counts_of(store):
    """late_accepted, anchors_moved, inputs_ignored of either store."""
    s = store.snapshot()
    return s["late_accepted"], s["anchors_moved"], s["inputs_ignored"]


def take(store, rows):
    """Everything ready, staged and summed on the host, into ``rows``:
    {(key, window): count}; a row that comes twice is refused."""
    while store.ready():
        cols, starts, ends, keys, gwids, _rts, _kind = store.flush(1 << 20)
        for j in range(len(starts)):
            kw = (int(keys[j]), int(gwids[j]))
            assert kw not in rows, f"row {kw} came twice"
            rows[kw] = float(cols["value"][starts[j]:ends[j]].sum())


def put(store, rows, keys, ts):
    keys, ts = np.asarray(keys, np.int64), np.asarray(ts, np.int64)
    ready = store.ingest(keys, ts, ts, np.ones(len(ts)))
    assert ready == store.ready()
    take(store, rows)


def owed(keys, ts, win=WIN, slide=SLIDE):
    """{(key, window): count} for every window that holds a tuple."""
    want = {}
    for k, t in zip(np.asarray(keys).tolist(), np.asarray(ts).tolist()):
        for w in range(0 if t < win else (t - win) // slide + 1,
                       t // slide + 1):
            want[(k, w)] = want.get((k, w), 0.0) + 1.0
    return want


def mine(rows, key=7):
    return {kw: v for kw, v in rows.items() if kw[0] == key}


# -- the first tuple of a key to arrive is not its earliest -------------------

# first to arrive at 100 (pane 3: windows 2 and 3); the straggler, and
# whether it lies before window 2 (the anchor moves) or not
CASES = {"one_pane_back": (70, 1),        # pane 2: windows 1 and 2
         "two_panes_back": (40, 2),       # pane 1: windows 0 and 1
         "last_id_of_the_window_before": (95, 1),
         "first_id_of_the_anchors_pane": (96, 0),   # windows 2 and 3
         "same_pane_earlier": (97, 0)}


@pytest.mark.parametrize("same_call", [False, True],
                         ids=["next_call", "same_call"])
@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("which", STORES)
def test_a_straggler_before_the_anchor_moves_it_back(which, case, same_call):
    late_ts, moves = CASES[case]
    store, rows = make_store(which, delay=60), {}
    if same_call:
        put(store, rows, [CLOCK, 7, 7], [100, 100, late_ts])
    else:
        put(store, rows, [CLOCK, 7], [100, 100])
        put(store, rows, [7], [late_ts])
    late, moved, ignored = counts_of(store)
    # within one call the anchor is set from the call's earliest tuple
    assert moved == (0 if same_call else min(moves, 1))
    assert (late, ignored) == (1, 0)
    assert not rows                       # nothing has been passed yet
    # the stream passes every window of the key: rows before EOS
    put(store, rows, [CLOCK], [100 + 160 + 60])
    assert mine(rows) == owed([7, 7], [100, late_ts])
    assert len(mine(rows)) == 2 + moves
    store.eos()
    take(store, rows)
    assert mine(rows) == owed([7, 7], [100, late_ts])
    assert store.snapshot()["keys_live"] == 0


@pytest.mark.parametrize("which", STORES)
def test_a_key_moves_back_more_than_once_and_fires_in_window_order(which):
    store, rows = make_store(which, delay=200), {}
    put(store, rows, [CLOCK, 7], [300, 300])      # accept is 96
    for t in (250, 190, 130, 260, 100):
        put(store, rows, [7], [t])
    assert counts_of(store) == (5, 4, 0)  # 260 lies after the anchor
    order = []
    store.eos()
    while store.ready():
        out = store.flush(1 << 20)
        order += [int(w) for k, w in zip(out[3], out[4]) if int(k) == 7]
    assert order == sorted(order) == list(range(2, 10))


# -- a key that is gone, and the boundary --------------------------------------

@pytest.mark.parametrize("which", STORES)
def test_a_straggler_for_an_evicted_key_opens_a_new_one(which):
    store, rows = make_store(which, delay=60), {}
    put(store, rows, [CLOCK, 7], [10, 10])
    put(store, rows, [CLOCK], [150])      # passes window 0 (64 + 60 <= 150)
    assert mine(rows) == {(7, 0): 1.0}
    assert store.snapshot()["keys_live"] == 1      # 7 staged and evicted
    put(store, rows, [7], [70])           # behind the stream, past window 0
    assert counts_of(store) == (1, 0, 0)
    assert store.snapshot()["keys_live"] == 2
    store.eos()
    take(store, rows)                     # (7, 0) a second time is refused
    assert mine(rows) == {(7, 0): 1.0, (7, 1): 1.0, (7, 2): 1.0}


@pytest.mark.parametrize("which", STORES)
def test_a_returning_keys_skipped_windows_are_taken_back(which):
    """A key whose windows have all fired skips the empty ones before its
    next tuple; a straggler into one of them, with the fired windows
    still queued, takes the skip back."""
    store, rows = make_store(which, delay=300), {}
    put(store, rows, [CLOCK, 7], [10, 10])
    one = np.ones(1)
    # passes window 0 of both keys, which stay queued
    assert store.ingest(np.array([CLOCK]), *[np.array([370])] * 2, one) == 2
    # windows 11 and 12; 1 to 10 lie empty and are skipped
    assert store.ingest(np.array([7]), *[np.array([400])] * 2, one) == 2
    put(store, rows, [7], [200])          # windows 5 and 6; accept is 96
    assert counts_of(store) == (1, 1, 0)
    store.eos()
    take(store, rows)
    assert mine(rows) == owed([7, 7, 7], [10, 400, 200])


@pytest.mark.parametrize("which", STORES)
def test_exactly_the_delay_behind_is_kept_and_one_more_is_ignored(which):
    delay = 50
    store, rows = make_store(which, delay=delay), {}
    put(store, rows, [CLOCK, 7], [70, 70])        # windows 1 and 2
    # the stream passes window 1 ([32, 96)) exactly: 96 + 50
    front = 96 + delay
    put(store, rows, [CLOCK], [front])
    assert mine(rows) == {(7, 1): 1.0}
    put(store, rows, [7, 8], [front - delay, front - delay])
    assert counts_of(store) == (2, 0, 0)
    put(store, rows, [7, 8, 9], [front - delay - 1] * 3)
    assert counts_of(store) == (2, 0, 3)
    assert store.ignored() == 3
    store.eos()
    take(store, rows)
    assert mine(rows) == owed([7, 7], [70, 96])
    assert mine(rows, 8) == owed([8], [96])
    assert mine(rows, 9) == {}


@pytest.mark.parametrize("which", STORES)
def test_a_late_tuple_in_the_call_does_not_hide_the_straggler_behind_it(
        which):
    """The call's earliest tuple of the key is late and dropped; the next
    one lies before the key's anchor and still moves it."""
    store, rows = make_store(which, delay=100), {}
    put(store, rows, [CLOCK, 7], [300, 300])       # passes window 4; 7: 8, 9
    put(store, rows, [7, 7, 7], [10, 195, 299])    # accept is 192
    assert counts_of(store) == (2, 1, 1)           # 195: windows 5 and 6
    store.eos()
    take(store, rows)
    assert mine(rows) == owed([7] * 3, [300, 195, 299])


@pytest.mark.parametrize("which", STORES)
def test_eos_fires_what_the_stragglers_are_still_owed(which):
    rng = np.random.RandomState(3)
    n, delay = 4000, 300
    ts = np.arange(n) - np.where(rng.randint(0, 10, n) == 0,
                                 rng.randint(1, delay + 1, n), 0)
    ts = np.maximum(ts, 0)
    keys = ts // 150 * 30 + rng.randint(0, 30, n)
    store, rows = make_store(which, delay=delay), {}
    for a in range(0, n, 250):
        put(store, rows, keys[a:a + 250], ts[a:a + 250])
    before = len(rows)
    store.eos()
    take(store, rows)
    assert rows == owed(keys, ts) and before < len(rows)
    late, moved, ignored = counts_of(store)
    assert ignored == 0 and moved > 0
    front = np.maximum.accumulate(ts)
    assert late == int((ts[1:] < front[:-1]).sum())


# -- both stores, seeded draws ---------------------------------------------------

def disordered(seed, n=6000, delay=300, life=150, share=10, width=40):
    """One bid in ``share`` created up to ``delay`` before it arrives;
    ``width`` keys at a time, each for ``life`` stamps: few bids a key,
    so that a key's first to arrive is often not its earliest."""
    rng = np.random.RandomState(seed)
    back = np.where(rng.randint(0, share, n) == 0,
                    rng.randint(1, delay + 1, n), 0)
    ts = np.maximum(np.arange(n) - back, 0).astype(np.int64)
    keys = (ts // life * width + rng.randint(0, width, n)).astype(np.int64)
    return keys, ts


@pytest.mark.parametrize("kind", ["count", "sum", "max"])
@pytest.mark.parametrize("chunk", [1, 97, 1000])
@pytest.mark.parametrize("which", STORES)
def test_seeded_disorder_against_the_brute_force_count(which, chunk, kind):
    delay = 300
    keys, ts = disordered(chunk + len(kind))
    vals = (ts % 13 + 1).astype(np.float64)
    store = make_store(which, delay, kind)
    got, seen_live = {}, 0

    def drain():
        while store.ready():
            cols, starts, ends, k, w, _r, _e = store.flush(1 << 20)
            for j in range(len(starts)):
                seg = cols["value"][starts[j]:ends[j]]
                kw = (int(k[j]), int(w[j]))
                assert kw not in got
                got[kw] = float(seg.max() if kind == "max" else seg.sum())
    for a in range(0, len(ts), chunk):
        store.ingest(keys[a:a + chunk], ts[a:a + chunk], ts[a:a + chunk],
                     vals[a:a + chunk])
        drain()
        seen_live = max(seen_live, store.snapshot()["keys_live"])
    store.eos()
    drain()
    held = {}
    for k, t, v in zip(keys.tolist(), ts.tolist(), vals.tolist()):
        for w in range(0 if t < WIN else (t - WIN) // SLIDE + 1,
                       t // SLIDE + 1):
            held.setdefault((k, w), []).append(v)
    agg = {"count": len, "sum": sum, "max": max}[kind]
    assert got == {kw: float(agg(vs)) for kw, vs in held.items()}
    late, moved, ignored = counts_of(store)
    assert ignored == 0 and moved > 0 and late > 400
    # state is kept for window + slide + delay, not for ever
    assert seen_live < 40 * (WIN + SLIDE + 2 * delay + chunk) // 150 + 80


@pytest.mark.skipif(not native_available(), reason="native engine unavailable")
@pytest.mark.parametrize("delay,role", [
    (0, Role.SEQ), (120, Role.SEQ), (300, Role.SEQ), (300, Role.PLQ)])
def test_both_stores_count_the_same_under_disorder(delay, role):
    """Also where the disorder exceeds the delay and tuples are late: the
    rows and the three counts agree chunk by chunk, and the live keys
    where nothing was late.  (Two things a late tuple does in the C++
    engine and not in the Python store, as before an anchor could move: a
    key opened for late tuples alone stays until its key comes back, and
    on a dense replica a dropped tuple still opens its key's empty
    windows; ROADMAP D2.)"""
    keys, ts = disordered(delay + 1)
    stores = [make_store(w, delay, role=role) for w in ("native", "python")]
    for a in range(0, len(ts), 211):
        state = []
        for store in stores:
            store.ingest(keys[a:a + 211], ts[a:a + 211], ts[a:a + 211],
                         np.ones(len(ts[a:a + 211])))
            rows = []
            while store.ready():
                cols, starts, ends, k, w, r, _e = store.flush(1 << 20)
                k, ids = store.output_ids(k, w)
                rows += [(int(k[j]), int(w[j]), int(ids[j]), int(r[j]),
                          float(cols["value"][starts[j]:ends[j]].sum()))
                         for j in range(len(starts))]
            state.append((sorted(rows), counts_of(store),
                          None if store.ignored()
                          else store.snapshot()["keys_live"]))
        assert state[0] == state[1], a
    assert (state[0][1][2] > 0) == (delay < 300)


# -- what no stream may lose: the frozen streams of test_fold_by_key -----------

@pytest.mark.skipif(not native_available(), reason="native engine unavailable")
@pytest.mark.parametrize("chunking", [1, 7, 128, 129, 1000, 1 << 30])
@pytest.mark.parametrize("shape", ["disordered", "late"])
@pytest.mark.parametrize("lane", ["tb", "tb_delay", "tb_tumbling",
                                  "tb_odd_pane"])
def test_a_tuple_is_dropped_only_behind_a_window_the_stream_has_passed(
        lane, shape, chunking):
    """The rows are the plain recomputation's over the tuples that were
    not behind a passed window when their call began, and the others are
    all counted: nothing else decides, whatever the chunking."""
    from test_fold_by_key import GOLDEN_N, LANES, cuts_of, drive, stream
    from test_fold_by_key import owed as owed_by
    keys, ts, vals = stream(shape, GOLDEN_N)
    win, slide, _tb, delay = LANES[lane][:4]
    eng, rows, _d, _l = drive(lane, "count", keys, ts, vals,
                              min(chunking, GOLDEN_N))
    keep = np.ones(len(ts), bool)
    lo, front = 0, -1
    for hi in cuts_of(len(ts), min(chunking, GOLDEN_N)):
        t = front - delay - win
        if t >= 0:
            keep[lo:hi] = ts[lo:hi] >= t // slide * slide + win
        front = max(front, int(ts[lo:hi].max()))
        lo = hi
    assert eng.ignored() == int((~keep).sum())
    assert {(k, w): v for k, w, v, _ in rows} \
        == owed_by(keys[keep], ts[keep], vals[keep], win, slide, "count")
    s = eng.snapshot()
    assert s["folded_by_key"] + s["folded_singly"] == int(keep.sum())
    assert s["inputs_ignored"] == eng.ignored()


# -- a snapshot taken after an anchor moved ------------------------------------

@pytest.mark.parametrize("which", STORES)
def test_a_moved_anchor_survives_a_snapshot(which):
    keys, ts = disordered(5, n=3000)
    delay, cut = 300, 1500
    # a key that has just moved back, its new first window not yet fired
    detour = [([CLOCK, 7], [cut + 40, cut + 40]), ([7], [cut - 60])]
    chunks = [(keys[a:a + 100], ts[a:a + 100]) for a in range(0, cut, 100)] \
        + detour \
        + [(keys[a:a + 100], ts[a:a + 100]) for a in range(cut, 3000, 100)]
    at = cut // 100 + len(detour)
    whole, want = make_store(which, delay), {}
    for k, t in chunks:
        put(whole, want, k, t)
    whole.eos()
    take(whole, want)
    first, rows = make_store(which, delay), {}
    for k, t in chunks[:at]:
        put(first, rows, k, t)
    moved = counts_of(first)[1]
    assert moved > 1
    blob = pickle.loads(pickle.dumps(first.serialize()))
    second = make_store(which, delay)
    second.deserialize(blob)
    assert second.snapshot()["keys_live"] == first.snapshot()["keys_live"]
    for k, t in chunks[at:]:
        put(second, rows, k, t)
    second.eos()
    take(second, rows)
    assert rows == want == owed(np.concatenate([c[0] for c in chunks]),
                                np.concatenate([c[1] for c in chunks]))
    assert whole.ignored() == 0


# -- a whole graph of the configuration's shape --------------------------------

def graph_rows(name, keys_by_event, delay_of, n, chunk, d, python_store):
    """``BatchSource`` -> chained ``BatchMap`` (stamp and key from the
    event's creation) -> ``KeyFarmTPU('count', TB, triggering_delay=d)``
    -> ``Sink``: the rows it emits and the window operator's logic."""
    from windflow_tpu.graph.fuse import iter_logics
    sent = {"i": 0}
    lane = np.arange(chunk, dtype=np.int64)

    def body(ctx=None):
        a = sent["i"]
        if a >= n:
            return None
        m = min(chunk, n - a)
        sent["i"] = a + m
        ids = lane[:m] + a
        return TupleBatch({"key": delay_of[a:a + m], "id": ids, "ts": ids,
                           "value": np.ones(m)})

    def created(batch):
        e = np.maximum(batch.id - batch.key, 0)
        return batch.with_cols(ts=e, key=keys_by_event[e])

    rows = {}

    def sink(batch):
        if batch is None:
            return
        for k, w, v in zip(batch.key.tolist(), batch.id.tolist(),
                           np.asarray(batch["value"]).tolist()):
            assert (k, w) not in rows, f"row {(k, w)} came twice"
            rows[(k, w)] = v

    g = wf.PipeGraph(name, wf.Mode.DEFAULT)
    pipe = g.add_source(BatchSource(body))
    pipe.chain(BatchMap(created)).add(
        KeyFarmTPU("count", 4096, 2048, WinType.TB, triggering_delay=d,
                   name="ooo_counts", emit_batches=True,
                   value_of=(lambda t: t.value) if python_store else None))
    pipe.add_sink(Sink(sink, name="ooo_sink"))
    logic = next(lg for _, lg in iter_logics(g)
                 if hasattr(lg, "launched_batches"))
    assert (logic._native is None) == python_store
    g.run()
    return rows, logic, g


@pytest.mark.parametrize("seed", [1, 2_147_483_659])
@pytest.mark.parametrize("python_store", [
    pytest.param(False, marks=pytest.mark.skipif(
        not native_available(), reason="native engine unavailable")), True],
    ids=["native", "python"])
def test_a_graph_of_the_configurations_shape_counts_every_bid(python_store,
                                                              seed):
    n, d, chunk = (40_000, 1228, 128) if python_store \
        else (200_000, 1228, 256)
    rng = np.random.default_rng(seed)
    delay_of = np.where(rng.integers(0, 10, n) == 0,
                        rng.integers(1, d + 1, n), 0).astype(np.int64)
    # an auction takes bids for some 37 ids, as the generator's does
    keys_by_event = (np.arange(n) * 3 // 46
                     + rng.integers(-100, 11, n)).astype(np.int64) + 1000
    rows, logic, g = graph_rows(f"ooo_{seed}_{python_store}", keys_by_event,
                                delay_of, n, chunk, d, python_store)
    e = np.maximum(np.arange(n) - delay_of, 0)
    assert rows == owed(keys_by_event[e], e, 4096, 2048)
    snap = logic._store.snapshot()
    # the same chunks into a store driven by hand move as many anchors
    by_hand = make_store("python" if python_store else "native", d,
                         win=4096, slide=2048)
    for a in range(0, n, chunk):
        put(by_hand, {}, keys_by_event[e[a:a + chunk]], e[a:a + chunk])
    assert snap["inputs_ignored"] == 0
    assert snap["anchors_moved"] == counts_of(by_hand)[1] \
        > (-1 if python_store else 0)
    front = np.maximum.accumulate(e)
    assert snap["late_accepted"] == int((e[1:] < front[:-1]).sum())
    assert 0.09 < snap["late_accepted"] / n < 0.11
    if python_store:
        return
    # the three counts in the series, the stats JSON and /metrics
    report = json.loads(g.stats.to_json())
    mine_ = [r for r in report["Spans"]["Operators"] if "Counters" in r]
    c = mine_[0]["Counters"]
    assert {n_: c[n_] for n_ in ("late_accepted", "anchors_moved",
                                 "inputs_ignored")} \
        == {n_: snap[n_] for n_ in ("late_accepted", "anchors_moved",
                                    "inputs_ignored")}
    kept = spans.graph(g.name).counters[mine_[0]["Operator"]]
    moved = kept.moved_between(0.0, 1e12)
    assert moved["late_accepted"] == c["late_accepted"]
    assert moved["anchors_moved"] == c["anchors_moved"]
    assert moved["inputs_ignored"] == 0
    assert kept.folded_between(0.0, 1e12) \
        == (c["folded_by_key"], c["folded_singly"])
    assert c["folded_by_key"] + c["folded_singly"] == n
    text = render_openmetrics({"a": {"report": report}})
    for name in ("late_accepted_total", "anchors_moved_total",
                 "inputs_ignored_total"):
        assert f"windflow_engine_{name}{{" in text, name


@pytest.mark.skipif(not native_available(), reason="native engine unavailable")
def test_ignored_tuples_reach_the_series_and_the_metrics_page():
    """A stream whose disorder exceeds the operator's delay: the dropped
    tuples are in the counter series, the stats JSON and /metrics."""
    n, d = 60_000, 200
    rng = np.random.default_rng(5)
    delay_of = np.where(rng.integers(0, 10, n) == 0,
                        rng.integers(1, 8 * 4096, n), 0).astype(np.int64)
    keys_by_event = (np.arange(n) // 40).astype(np.int64)
    rows, logic, g = graph_rows("ooo_ignored", keys_by_event, delay_of, n,
                                1024, d, False)
    snap = logic._store.snapshot()
    assert snap["inputs_ignored"] > 100
    folded = snap["folded_by_key"] + snap["folded_singly"]
    assert folded + snap["inputs_ignored"] == n
    report = json.loads(g.stats.to_json())
    c = next(r["Counters"] for r in report["Spans"]["Operators"]
             if "Counters" in r)
    assert c["inputs_ignored"] == snap["inputs_ignored"]
    kept = next(iter(spans.graph(g.name).counters.values()))
    assert kept.moved_between(0.0, 1e12)["inputs_ignored"] \
        == snap["inputs_ignored"]
    text = render_openmetrics({"a": {"report": report}})
    line = next(ln for ln in text.splitlines()
                if ln.startswith("windflow_engine_inputs_ignored_total{"))
    assert int(line.rsplit(" ", 1)[1]) == snap["inputs_ignored"]
