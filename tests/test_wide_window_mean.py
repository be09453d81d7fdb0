"""SUM and MEAN over windows of many panes, through an ordinary graph, are
exact wherever the window's own sum is.

The wide-window combine (``ops/window_compute._block_sum_program``) adds a
window's own elements and nobody else's, so a result does not depend on
the length of the launch's flat buffer or on the window's place in it.
What it replaced took ``c[end] - c[start]`` of ONE float32 running sum
over the whole buffer: once that sum passes 2**24 (a few hundred keys'
panes of a thousand each) every later window is off in its low digits.
The streams here carry whole numbers, so every window's sum is exact in
float32 and the comparison is ``==``; the buffers are long enough that
the single running sum is not.  ``KeyFarmTPU`` over the native engine
(pane partials, ``mean_panes``) and over ``PyWindowStore`` (pane partials
for SUM, the keys' own tuples for MEAN), at 2, 33, 360 and 3,600 panes a
window; time-based, count-based and hopping windows.
"""
import numpy as np
import pytest

import windflow_tpu as wf
from windflow_tpu.core.basic import WinType
from windflow_tpu.core.tuples import TupleBatch
from windflow_tpu.operators.basic_ops import Sink
from windflow_tpu.operators.batch_ops import BatchSource
from windflow_tpu.operators.tpu.farms_tpu import KeyFarmTPU
from windflow_tpu.runtime.native import native_available

CHUNK = 4096
SEED = 2_147_483_659
EXACT_BELOW = 1 << 24
# panes a window -> keys: enough of them that a launch's flat buffer
# (keys x (panes a window + slides a chunk) partials of 1,500 on average)
# adds up to far more than 2**24 from 33 panes on
KEYS = {2: 300, 33: 300, 360: 100, 3600: 40}
STORES = [pytest.param("native", marks=pytest.mark.skipif(
    not native_available(), reason="native engine unavailable")), "python"]


def stream(n_keys, n, seed, lane="tb", whole=True):
    """(keys, stamps, values): ``tb``: a key drawn for every event, the
    stamp the event's index; ``cb``: keys in turn, the stamp a key's own
    arrival count.  Values are whole numbers 0..3000, or uniform floats."""
    rng = np.random.default_rng([seed, n_keys, n])
    i = np.arange(n, dtype=np.int64)
    keys, ts = (rng.integers(0, n_keys, n), i) if lane == "tb" \
        else (i % n_keys, i // n_keys)
    vals = rng.integers(0, 3001, n).astype(np.float64) if whole \
        else rng.random(n)
    return keys.astype(np.int64), ts, vals


def owed(keys, ts, vals, win, slide, kind):
    """{(key, window): value} for every window that holds a tuple of the
    key, plainly: the tuples binned by (pane, key) in float64, a window
    the difference of the running sums of its panes; a mean is the
    float32 quotient of sum and count."""
    pane = int(np.gcd(win, slide))
    n_keys, n_panes = int(keys.max()) + 1, int(ts.max()) // pane + 1
    code = ts // pane * n_keys + keys
    if win < slide:               # a hopping window's gap belongs to none
        held = ts % slide < win
        code, vals = code[held], vals[held]
    cnt = np.bincount(code, minlength=n_panes * n_keys)
    tot = np.bincount(code, weights=vals, minlength=n_panes * n_keys)
    run_c, run_s = (np.concatenate([np.zeros((1, n_keys)), np.cumsum(
        a.reshape(n_panes, n_keys), axis=0)]) for a in (cnt, tot))
    lo = np.arange(0, n_panes, slide // pane)
    hi = np.minimum(lo + win // pane, n_panes)
    w_c, w_s = run_c[hi] - run_c[lo], run_s[hi] - run_s[lo]
    wids, ks = np.nonzero(w_c)
    value = w_s[wids, ks] if kind == "sum" else (
        w_s[wids, ks].astype(np.float32)
        / w_c[wids, ks].astype(np.float32)).astype(np.float64)
    return dict(zip(zip(ks.tolist(), wids.tolist()), value.tolist())), \
        float(w_s.max())


def graph_rows(name, kind, win, slide, win_type, cols, store):
    """``BatchSource`` -> ``KeyFarmTPU(kind)`` -> ``Sink``: the rows."""
    keys, ts, vals = cols
    sent = {"i": 0}

    def body(ctx=None):
        a = sent["i"]
        if a >= len(keys):
            return None
        sent["i"] = b = min(a + CHUNK, len(keys))
        ids = np.arange(a, b, dtype=np.int64)
        cb = win_type == WinType.CB
        return TupleBatch({"key": keys[a:b], "id": ts[a:b] if cb else ids,
                           "ts": ids if cb else ts[a:b],
                           "value": vals[a:b]})

    got = []

    def sink(batch):
        if batch is not None:
            got.append((batch.key, batch.id, np.asarray(batch["value"])))

    g = wf.PipeGraph(name, wf.Mode.DEFAULT)
    pipe = g.add_source(BatchSource(body))
    pipe.add(KeyFarmTPU(kind, win, slide, win_type, name="wide",
                        emit_batches=True))
    pipe.add_sink(Sink(sink, name="wide_sink"))
    logics = [seg.logic for n in g._all_nodes()
              for seg in getattr(n.logic, "segments", [n])
              if hasattr(seg.logic, "_svc_batch")]
    assert logics
    for logic in logics:
        if store == "python":
            logic._native = None
        assert (logic._native is None) == (store == "python")
    g.run()
    ks, ws, vs = (np.concatenate([b[j] for b in got]) for j in range(3))
    rows = dict(zip(zip(ks.tolist(), ws.tolist()), vs.tolist()))
    assert len(rows) == len(ks), "a window came twice"
    return rows


def exact(name, kind, panes, store, lane="tb", hop=0):
    """One graph run against the recomputation, row for row, ``==``."""
    n_keys = KEYS[panes]
    pane = n_keys if lane == "tb" else 4
    win, slide = panes * pane, (1 + hop * panes) * pane
    n = (panes + 45) * (n_keys if lane == "tb" else 4 * n_keys)
    cols = stream(n_keys, n, SEED, lane)
    want, largest = owed(*cols, win, slide, kind)
    assert largest < EXACT_BELOW, "the stream was to keep sums exact"
    rows = graph_rows(name, kind, win, slide,
                      WinType.TB if lane == "tb" else WinType.CB, cols,
                      store)
    wrong = sum(rows.get(kw) != v for kw, v in want.items())
    assert (len(rows), wrong) == (len(want), 0), \
        f"{wrong} of {len(want)} rows wrong, {len(rows)} received"
    return len(rows)


@pytest.mark.parametrize("panes", [2, 33, 360, 3600])
@pytest.mark.parametrize("store", STORES)
@pytest.mark.parametrize("kind", ["mean", "sum"])
def test_time_based_windows_are_exact_at_every_width(kind, store, panes):
    n_rows = exact(f"wide_tb_{kind}_{store}_{panes}", kind, panes, store)
    # nearly every key fires every slide: about a row an event
    assert n_rows > 0.8 * (panes + 45) * KEYS[panes]


@pytest.mark.parametrize("panes", [33, 360])
@pytest.mark.parametrize("store", STORES)
@pytest.mark.parametrize("kind", ["mean", "sum"])
def test_count_based_windows_share_the_combine(kind, store, panes):
    exact(f"wide_cb_{kind}_{store}_{panes}", kind, panes, store, lane="cb")


@pytest.mark.parametrize("panes", [33, 360])
@pytest.mark.parametrize("kind", ["mean", "sum"])
@pytest.mark.skipif(not native_available(),
                    reason="native engine unavailable")
def test_hopping_windows_share_the_combine(kind, panes):
    """``slide = win + a pane``: a window is still ``panes`` partials wide
    and a launch holds a key's windows over one span."""
    exact(f"wide_hop_{kind}_{panes}", kind, panes, "native", hop=1)


@pytest.mark.parametrize("store", STORES)
def test_sums_that_are_not_whole_numbers_stay_within_their_own_rounding(
        store):
    """Uniform floats in [0, 1): nothing is exact, and the bound is the
    window's own.  Each element is rounded to float32 once (2**-24
    relative) and the 3,600 partials of a window are added in rows of
    128, a depth of some 14 additions: under 15 * 2**-24 = 9e-7 of the
    window's sum, whatever lies before it in the buffer.  The single
    running sum's error was 2**-24 of the BUFFER's sum (40 keys x 3,630
    panes: 40 times a window's), per window."""
    panes, n_keys = 3600, KEYS[3600]
    cols = stream(n_keys, (panes + 45) * n_keys, SEED, whole=False)
    want, _ = owed(*cols, panes * n_keys, n_keys, "mean")
    rows = graph_rows(f"wide_float_{store}", "mean", panes * n_keys, n_keys,
                      WinType.TB, cols, store)
    assert rows.keys() == want.keys()
    err = max(abs(rows[kw] - v) / v for kw, v in want.items())
    assert err < 1e-6, err


def test_the_blocked_sum_takes_a_window_wherever_it_lies():
    """The program alone: windows of every length up to 20,000 anywhere
    in a buffer whose running sum passes 2**24 many times over, empty
    windows among them, against int64 arithmetic."""
    from windflow_tpu.ops.window_compute import (WindowComputeEngine,
                                                 _block_levels)
    assert [_block_levels(w) for w in (64, 128, 256, 16384, 32768)] \
        == [1, 1, 2, 2, 3]
    rng = np.random.default_rng(SEED)
    for t, w in ((5000, 40), (2048, 129), (40_000, 128), (300_000, 3600),
                 (70_000, 20_000)):
        vals = rng.integers(0, min(4000, EXACT_BELOW // w), t)
        cnts = rng.integers(0, 3, t)
        lens = rng.integers(0, w + 1, 3000)
        lens[0] = w
        starts = np.where(lens > 0, rng.integers(0, t - 1, 3000), 0)
        ends = np.where(lens > 0, np.minimum(starts + lens, t), 0)
        run_v, run_c = (np.r_[0, np.cumsum(a)] for a in (vals, cnts))
        s, c = run_v[ends] - run_v[starts], run_c[ends] - run_c[starts]
        for kind, want in (
                ("sum", s.astype(np.float32)),
                ("mean", s.astype(np.float32)
                 / np.maximum(ends - starts, 1).astype(np.float32)),
                ("mean_panes", s.astype(np.float32)
                 / np.maximum(c, 1).astype(np.float32))):
            got = WindowComputeEngine(kind).compute(
                {"value": vals.astype(np.float64),
                 "count": cnts.astype(np.float64)},
                starts, ends, np.arange(3000)).block()
            assert (got == want).all(), (kind, t, w)


@pytest.mark.parametrize("store", STORES)
def test_what_a_flush_staged_reaches_the_series_the_stats_and_the_metrics(
        store):
    """``panes_staged`` and ``windows_staged`` where the engine's other
    counts are: its snapshot, the span registry's series (cut at two
    instants by ``staged_between``), ``Spans.Operators[].Counters`` and
    ``/metrics``.  The Python store counts them in its snapshot (the
    elements of the flat buffers it made) and has no series."""
    import json

    from windflow_tpu.graph.fuse import iter_logics
    from windflow_tpu.telemetry import spans
    from windflow_tpu.telemetry.metrics import render_openmetrics
    panes, n_keys = 33, KEYS[33]
    cols = stream(n_keys, (panes + 45) * n_keys, SEED)
    sent = {"i": 0}

    def body(ctx=None):
        a = sent["i"]
        if a >= len(cols[0]):
            return None
        sent["i"] = b = min(a + CHUNK, len(cols[0]))
        return TupleBatch({"key": cols[0][a:b], "id": cols[1][a:b],
                           "ts": cols[1][a:b], "value": cols[2][a:b]})

    rows = []
    g = wf.PipeGraph(f"staged_{store}", wf.Mode.DEFAULT)
    g.add_source(BatchSource(body)).add(
        KeyFarmTPU("mean", panes * n_keys, n_keys, WinType.TB, name="wide",
                   emit_batches=True)
    ).add_sink(Sink(lambda b: rows.append(len(b)) if b is not None
                    else None, name="sink"))
    logic = next(lg for _, lg in iter_logics(g)
                 if hasattr(lg, "launched_batches"))
    if store == "python":
        logic._native = None
    g.run()
    snap = logic._store.snapshot()
    assert snap["windows_staged"] == sum(rows) > 10_000
    report = json.loads(g.stats.to_json())
    assert report["Schema_version"] >= 18
    counted = [r for r in report["Spans"]["Operators"] if "Counters" in r]
    names = ("panes_staged", "windows_staged")
    if store == "python":
        # a key's tuples once a launch, however many of its windows fire
        assert snap["panes_staged"] > snap["windows_staged"]
        assert not any(r["Counters"].get(n) for r in counted for n in names)
        return
    # a launch stages a key's span once: 33 panes and one more a window
    # after the first, so between one pane a row and 33
    assert 1 < snap["panes_staged"] / snap["windows_staged"] < panes
    c = counted[0]["Counters"]
    assert {n: c[n] for n in names} == {n: snap[n] for n in names}
    kept = spans.graph(g.name).counters[counted[0]["Operator"]]
    assert kept.staged_between(0.0, 1e12) \
        == (snap["panes_staged"], snap["windows_staged"])
    text = render_openmetrics({"a": {"report": report}})
    for n in names:
        assert f"windflow_engine_{n}_total{{" in text, n
