"""The window operator's two stores behind one set of calls (PR 30).

``WinSeqTPULogic`` stages from ``runtime/native.NativeWindowEngine`` or
from ``operators/tpu/window_store.PyWindowStore``.  Here both are driven
by hand through the same calls on the same stream and must stage the
same windows after the same chunk, each key's in the same order (across
the keys of one chunk the C++ engine fires in tuple order and the Python
store key by key, as they always have); each must continue a stream from
its own snapshot; the operator must name its store at one place; the
Python store must launch when and as large as it did at the parent
commit; and a snapshot the parent wrote must load.
"""
import os
import pickle

import numpy as np
import pytest

from windflow_tpu.core.basic import Role, WinType
from windflow_tpu.core.tuples import TupleBatch
from windflow_tpu.operators.tpu.win_seq_tpu import WinSeqTPULogic
from windflow_tpu.operators.tpu.window_store import PyWindowStore
from windflow_tpu.runtime.native import NativeWindowEngine, native_available

needs_native = pytest.mark.skipif(not native_available(),
                                  reason="native engine unavailable")
GOLDEN = os.path.join(os.path.dirname(__file__), "golden")

WIN, SLIDE = 64, 32          # pane 32: both stores stage pane partials
N, CHUNK = 1500, 50          # a chunk straddles a pane edge (50 !| 32)


def stream(win_type):
    """Three keys on one clock; key 2 goes quiet after 300 events."""
    i = np.arange(N, dtype=np.int64)
    keys = np.where(i < 300, i % 3, i % 2)
    vals = ((i * 7) % 11).astype(np.float64)
    if win_type == WinType.TB:
        return keys, i, i, vals
    ids = np.zeros(N, np.int64)      # CB: a key's own arrival count
    for k in range(3):
        m = keys == k
        ids[m] = np.arange(int(m.sum()))
    return keys, ids, i, vals


def make_store(which, kind, win_type, role):
    if which == "native":
        return NativeWindowEngine(WIN, SLIDE, win_type == WinType.TB, 0,
                                  renumber=False, kind=kind,
                                  dense=role != Role.SEQ)
    return PyWindowStore(WIN, SLIDE, win_type, 0, renumber=False, kind=kind,
                         role=role)


REDUCE = {"sum": np.sum, "count": np.sum, "max": np.max, "min": np.min}


def take(store, kind, rows):
    """Everything ready, staged and reduced on the host: one row a
    window, (key, window id, output id, result stamp, value)."""
    while store.ready():
        cols, starts, ends, keys, gwids, rts, engine_kind = \
            store.flush(1 << 20)
        assert engine_kind == ("sum" if kind == "count" else None)
        keys, ids = store.output_ids(keys, gwids)
        for j in range(len(starts)):
            seg = cols["value"][starts[j]:ends[j]]
            v = float(REDUCE[kind](seg)) if len(seg) else 0.0
            rows.append((int(keys[j]), int(gwids[j]), int(ids[j]),
                         int(rts[j]), v if np.isfinite(v) else None))


def drive(store, kind, win_type, lo=0, hi=N, rows=None, through=None,
          dtype=np.float64, late=False):
    """``through``: every chunk is handed over as a selected batch holds
    it (PR 31): base columns three times as long with the chunk's rows
    scattered over them in order, the rows' places as ``sel``, and the
    columns named in ``through`` compact.  ``late``: one row in 40 is
    stamped 200 back, behind its key's fired windows."""
    keys, ids, ts, vals = stream(win_type)
    vals = vals.astype(dtype)
    if late:
        back = np.arange(N) % 40 == 39
        ids = np.where(back, np.maximum(ids - 200, 0), ids)
    rows = [] if rows is None else rows
    rng = np.random.RandomState(lo + 1)
    for a in range(lo, hi, CHUNK):
        b = min(a + CHUNK, hi)
        cols = {"keys": keys[a:b], "ids": ids[a:b], "ts": ts[a:b],
                "vals": vals[a:b]}
        if through is None:
            ready = store.ingest(*cols.values())
        else:
            n = b - a
            sel = np.sort(rng.choice(3 * n, n, replace=False))
            for name, col in cols.items():
                if name not in through:
                    base = np.full(3 * n, -9, col.dtype)
                    base[sel] = col
                    cols[name] = base
            ready = store.ingest(*cols.values(), sel)
        assert ready == store.ready()
        take(store, kind, rows)
    return rows


# -- (i) conformance -----------------------------------------------------------

@needs_native
@pytest.mark.parametrize("role", [Role.SEQ, Role.PLQ], ids=["seq", "plq"])
@pytest.mark.parametrize("win_type", [WinType.CB, WinType.TB],
                         ids=["cb", "tb"])
@pytest.mark.parametrize("kind", ["sum", "count", "max", "min"])
def test_both_stores_stage_the_same_windows_after_the_same_chunk(
        kind, win_type, role):
    got = {}
    for which in ("native", "python"):
        store = make_store(which, kind, win_type, role)
        rows, marks = [], []
        for a in range(0, N, CHUNK):
            drive(store, kind, win_type, a, a + CHUNK, rows)
            marks.append(len(rows))
        store.eos()
        take(store, kind, rows)
        for k in range(3):        # a key's windows fire in their order
            mine = [r[1] for r in rows if r[0] == k]
            assert mine == sorted(mine)
        got[which] = (rows, marks, store.ignored(),
                      store.snapshot()["keys_live"])
    assert got["native"][1:] == got["python"][1:]
    rows, marks = got["python"][:2]
    other = got["native"][0]
    for lo, hi in zip([0] + marks, marks + [len(rows)]):
        assert sorted(rows[lo:hi]) == sorted(other[lo:hi])   # chunk by chunk
    before_eos = marks[-1]
    assert 0 < before_eos < len(rows)
    quiet = [r for r in rows[:before_eos] if r[0] == 2]
    if win_type == WinType.TB:
        # the quiet key's rows came when the stream passed them
        assert quiet and quiet == [r for r in rows if r[0] == 2]
    if role == Role.PLQ:          # a key's output ids count its windows
        for k in range(3):
            ids = [r[2] for r in rows if r[0] == k]
            assert ids == list(range(len(ids)))
    else:
        assert all(r[1] == r[2] for r in rows)


@pytest.mark.parametrize("dtype", [np.float64, np.float32],
                         ids=["f64", "f32"])
@pytest.mark.parametrize("through", [(), ("keys",), ("vals", "ts")],
                         ids=["bare", "joined", "mapped"])
@pytest.mark.parametrize("win_type", [WinType.CB, WinType.TB],
                         ids=["cb", "tb"])
@pytest.mark.parametrize("kind", ["sum", "count", "max"])
@pytest.mark.parametrize("which", [
    pytest.param("native", marks=needs_native), "python"])
def test_through_a_selection_a_store_stages_what_the_gathered_columns_do(
        which, kind, win_type, through, dtype):
    """``ingest(..., sel)`` on either store: the same windows after the
    same chunk (a chunk of 50 straddles the pane edge at 32: SUM and CB
    fold one by one, TB COUNT and MAX by key where a chunk lies in one
    pane), the same late rows counted as ignored, the same keys live."""
    got = []
    for thr in (None, through):
        store = make_store(which, kind, win_type, Role.SEQ)
        rows, marks = [], []
        for a in range(0, N, CHUNK):
            drive(store, kind, win_type, a, a + CHUNK, rows, through=thr,
                  dtype=dtype, late=True)
            marks.append(len(rows))
        store.eos()
        take(store, kind, rows)
        got.append((rows, marks, store.ignored(),
                    store.snapshot()["keys_live"]))
    assert got[0] == got[1]
    assert got[0][2] > 0 and len(got[0][0]) > 40      # late rows; windows


@pytest.mark.parametrize("which", [
    pytest.param("native", marks=needs_native), "python"])
def test_a_selection_that_leaves_its_base_is_refused_before_anything_folds(
        which):
    """The rows come from a batch that checked them; handed in from
    elsewhere they are outside input to native code."""
    keys, ids, ts, vals = stream(WinType.TB)
    store = make_store(which, "count", WinType.TB, Role.SEQ)
    for bad in ([0, 5, 100], [-1, 3, 4], [2, 1 << 40, 3]):
        with pytest.raises(IndexError):
            store.ingest(keys[:100], ids[:100], ts[:100], vals[:100],
                         np.array(bad, np.int64))
    assert store.snapshot()["keys_live"] == 0 and store.ready() == 0
    # a base column shorter than the others bounds the rows
    with pytest.raises(IndexError):
        store.ingest(keys[:100], ids[:50], ts[:100], vals[:100],
                     np.array([1, 60], np.int64))
    # every column compact: the rows are not followed at all
    assert store.ingest(keys[:3], ids[:3], ts[:3], vals[:3],
                        np.array([7, 8, 900], np.int64)) == 0
    assert store.snapshot()["keys_live"] == 3


@pytest.mark.parametrize("win,slide", [(64, 32), (12, 4)],
                         ids=["panes", "values"])
@pytest.mark.parametrize("which", [
    pytest.param("native", marks=needs_native), "python"])
def test_a_partial_flush_keeps_what_the_windows_left_behind_need(
        which, win, slide):
    """``flush(max_windows)`` below the ready count stages the oldest and
    leaves the rest whole: a key's data is cut only below the first
    window still queued for it, also when more arrives in between."""
    def store():
        if which == "native":
            return NativeWindowEngine(win, slide, False, 0, kind="sum")
        return PyWindowStore(win, slide, WinType.CB, 0, kind="sum")

    n, more = 2000, 400
    i = np.arange(n + more, dtype=np.int64)
    keys, ids, vals = i % 2, i // 2, ((i * 5) % 13).astype(np.float64)

    def rows(take_at_most):
        st, got = store(), {}
        for lo, hi in ((0, n), (n, n + more)):
            st.ingest(keys[lo:hi], ids[lo:hi], i[lo:hi], vals[lo:hi])
            out = st.flush(take_at_most)       # one take a chunk: the
            while out is not None:             # second finds a backlog
                cols, starts, ends, ks, gwids = out[:5]
                assert len(starts) <= take_at_most
                for j in range(len(starts)):
                    assert (int(ks[j]), int(gwids[j])) not in got
                    got[int(ks[j]), int(gwids[j])] = float(
                        cols["value"][starts[j]:ends[j]].sum())
                out = st.flush(take_at_most) if hi == n + more else None
        assert st.ready() == 0
        return got

    whole = rows(1 << 20)
    for (k, w), v in whole.items():            # and whole is right
        m = (keys == k) & (ids >= w * slide) & (ids < w * slide + win)
        assert v == vals[m].sum()
    assert len(whole) > 70 and rows(7) == whole


# -- (ii) a store continues from its own snapshot -----------------------------

@pytest.mark.parametrize("which", [
    pytest.param("native", marks=needs_native), "python"])
def test_a_store_continues_identically_from_its_snapshot(which):
    kind, win_type = "sum", WinType.TB
    whole = make_store(which, kind, win_type, Role.SEQ)
    want = drive(whole, kind, win_type)
    whole.eos()
    take(whole, kind, want)
    first = make_store(which, kind, win_type, Role.SEQ)
    rows = drive(first, kind, win_type, 0, 700)
    blob = first.serialize()
    second = make_store(which, kind, win_type, Role.SEQ)
    second.deserialize(pickle.loads(pickle.dumps(blob)))
    assert second.snapshot()["keys_live"] == first.snapshot()["keys_live"]
    drive(second, kind, win_type, 700, N, rows)
    second.eos()
    take(second, kind, rows)
    assert rows == want


# -- (iii) the operator names its store at one place --------------------------

def feed(logic, out, win_type=WinType.TB, lo=0, hi=N):
    keys, ids, ts, vals = stream(win_type)
    for a in range(lo, hi, CHUNK):
        b = min(a + CHUNK, hi)
        logic.svc(TupleBatch({"key": keys[a:b], "id": ids[a:b],
                              "ts": ts[a:b], "value": vals[a:b]}), 0, out)


class Rows:
    def __init__(self):
        self.rows = {}

    def __call__(self, batch):
        for k, w, v in zip(batch.key.tolist(), batch.id.tolist(),
                           batch["value"].tolist()):
            assert (k, w) not in self.rows
            self.rows[(k, w)] = v


def make_logic(**kw):
    return WinSeqTPULogic("sum", WIN, SLIDE, WinType.TB, batch_len=8,
                          emit_batches=True, async_dispatch=False, **kw)


def rows_of(logic):
    out = Rows()
    feed(logic, out)
    logic.eos_flush(out)
    return out.rows


@needs_native
def test_setting_native_to_none_before_the_first_chunk_takes_the_python_store():
    ref = make_logic()
    assert ref._store is ref._native
    want = rows_of(ref)
    logic = make_logic()
    logic._native = None
    assert logic._store is logic._py
    assert rows_of(logic) == want
    assert ref._py.snapshot()["keys_live"] == 0      # never touched
    assert ref._py.ready() == 0 and logic._py._stream_time == N - 1


class DropsAWindow:
    """The engine, with the last window of its third flush gone: the
    shape of benchmarks/tests/test_nexmark_q5_live.py's EvictsEarly."""

    def __init__(self, engine):
        self.engine, self.n = engine, 0

    def __getattr__(self, name):
        return getattr(self.engine, name)

    def flush(self, max_windows):
        out = self.engine.flush(max_windows)
        self.n += 1
        if out is not None and self.n == 3:
            self.gone = (int(out[3][-1]), int(out[4][-1]))
            out = tuple(a if j == 0 or j > 5 else a[:-1]
                        for j, a in enumerate(out))
        return out


@needs_native
def test_a_wrapper_round_the_native_engine_is_what_the_operator_stages_from():
    want = rows_of(make_logic())
    logic = make_logic()
    logic._native = wrapper = DropsAWindow(logic._native)
    got = rows_of(logic)
    assert wrapper.n >= 3
    assert set(want) - set(got) == {wrapper.gone}
    assert all(got[kw] == want[kw] for kw in got)


# -- the Python store launches when and as large as at the parent -------------

def test_python_store_launches_at_batch_len_from_inside_the_ingest():
    """Two chunks; the first fires 21 windows of two keys and fills
    ``batch_len`` five times inside the one ingest.  Sizes and counts are
    those the parent commit (10529d7) gave on this stream."""
    logic = WinSeqTPULogic("sum", 16, 8, WinType.CB, batch_len=4,
                           emit_batches=True, async_dispatch=False,
                           max_batch_delay_ms=1e9,
                           value_of=lambda t: t.value)
    assert logic._native is None
    sizes, real = [], logic._submit

    def submit(cols, starts, *args, **kw):
        sizes.append(len(starts))
        return real(cols, starts, *args, **kw)

    logic._submit = submit
    out, after_chunk = [], []
    for lo, hi in ((0, 200), (200, 260)):
        i = np.arange(lo, hi, dtype=np.int64)
        logic.svc(TupleBatch({"key": i % 2, "id": i // 2, "ts": i,
                              "value": np.ones(len(i))}), 0, out.append)
        after_chunk.append(len(sizes))
    logic.eos_flush(out.append)
    assert sizes == [4, 4, 4, 4, 4, 4, 4, 4, 2]
    assert after_chunk == [5, 7]
    assert logic.launched_batches == 9
    assert sum(len(b) for b in out) == sum(sizes)


def python_lane(**kw):
    kw.setdefault("batch_len", 8192)
    logic = WinSeqTPULogic("sum", 64, 32, WinType.CB, emit_batches=True,
                           async_dispatch=False, max_batch_delay_ms=1e9,
                           value_of=lambda t: t.value, **kw)
    assert logic._native is None
    sizes, real = [], logic._submit

    def submit(cols, starts, *args, **kw):
        sizes.append(len(starts))
        return real(cols, starts, *args, **kw)

    logic._submit = submit
    return logic, sizes


def one_key(lo, hi):
    i = np.arange(lo, hi, dtype=np.int64)
    return TupleBatch({"key": np.zeros(len(i), np.int64), "id": i, "ts": i,
                       "value": ((i * 3) % 17).astype(np.float64)})


def check_one_key(out, n_events):
    """Every window of ``one_key(0, n_events)``, once and right."""
    got = {}
    for b in out:
        for w, v in zip(b.id.tolist(), b["value"].tolist()):
            assert w not in got
            got[w] = v
    assert sorted(got) == list(range((n_events - 1) // 32 + 1))
    vals = ((np.arange(n_events) * 3) % 17).astype(np.float64)
    for w, v in got.items():
        assert v == vals[w * 32:w * 32 + 64].sum(), w


def test_a_batch_len_halved_under_a_queue_sends_the_queue_whole():
    """The AdaptiveBatcher halves ``batch_len`` from the dispatcher
    thread.  With 4,998 windows queued below 8,192, the next window fired
    after the halving finds the batch full: the Python store's launch
    takes everything queued, as at the parent commit, and every value is
    right."""
    logic, sizes = python_lane()
    out = []
    logic.svc(one_key(0, 5000 * 32), 0, out.append)
    assert sizes == [] and logic._store.ready() == 4998
    logic.batch_len = 4096
    logic.svc(one_key(5000 * 32, 5000 * 32 + 40), 0, out.append)
    assert sizes == [4999] and logic._store.ready() == 1
    logic.eos_flush(out.append)
    check_one_key(out, 5000 * 32 + 40)


def test_a_capped_launch_leaves_the_rest_of_the_queue_right():
    """An idle tick stages at most max(batch_len, 4096) windows.  What a
    device step's hold let gather beyond that goes with the next launch,
    over data the first must not have cut."""
    logic, sizes = python_lane(batch_len=8)
    out = []
    logic.chunk_hold = True
    logic.svc(one_key(0, 5000 * 32), 0, out.append)
    logic.chunk_hold = False
    assert sizes == [] and logic._store.ready() == 4998
    logic.max_batch_delay_ms = 0.0
    logic.idle_tick(out.append)
    logic.max_batch_delay_ms = 1e9
    assert sizes == [4096] and logic._store.ready() == 902
    logic.svc(one_key(5000 * 32, 5000 * 32 + 40), 0, out.append)
    logic.eos_flush(out.append)
    assert sizes == [4096, 903, 3]
    check_one_key(out, 5000 * 32 + 40)


# -- what the parent commit did, and nothing pinned: launch accounting, -------
# -- the gauges, the record plane ----------------------------------------------

def record(key, tid, value=1.0):
    from windflow_tpu.core.tuples import BasicRecord
    return BasicRecord(key, tid, tid, value)


def test_python_store_counts_kept_tuples_and_a_record_meets_no_buffer_bound():
    logic, sizes = python_lane(max_buffer_elems=100)
    out = []
    for i in range(200):                   # fires five windows, on the
        logic.svc(record(0, i), 0, out.append)      # record plane
    assert logic._store.ready() == 5
    assert sizes == [] and logic._buffered_since_launch == 0
    # a chunk: 60 tuples kept, 40 behind the fired frontier
    i = np.concatenate([np.arange(100, 140), np.arange(200, 260)])
    chunk = TupleBatch({"key": np.zeros(100, np.int64), "id": i, "ts": i,
                        "value": np.ones(100)})
    logic.svc(chunk, 0, out.append)
    assert sizes == [] and logic._buffered_since_launch == 60
    assert logic._store.ignored() == 40
    logic.svc(record(0, 260), 0, out.append)       # still no bound
    assert sizes == []
    i = np.arange(261, 301)
    logic.svc(TupleBatch({"key": np.zeros(40, np.int64), "id": i, "ts": i,
                          "value": np.ones(40)}), 0, out.append)
    assert sizes == [8] and logic._buffered_since_launch == 0


def test_a_renumbered_record_carries_its_new_id():
    logic = WinSeqTPULogic("sum", 4, 2, WinType.CB, renumbering=True,
                           async_dispatch=False,
                           value_of=lambda t: t.value)
    assert logic._native is None
    recs = [record("a", 100 + 7 * i) for i in range(6)]
    for r in recs:
        logic.svc(r, 0, lambda item: None)
    assert [r.id for r in recs] == list(range(6))


@pytest.mark.parametrize("lane", [
    pytest.param("native", marks=needs_native), "python"])
def test_the_gauges_read_what_they_read_at_the_parent(lane):
    """``Inputs_ignored`` and the audit's ``staging`` are the Python
    store's (0 on the native lane, whose late tuples are among its
    counters); an empty Python store has a census row, an empty native
    engine none."""
    import types
    logic = WinSeqTPULogic(
        "sum", 64, 32, WinType.CB, batch_len=4, async_dispatch=False,
        emit_batches=True, max_batch_delay_ms=1e9,
        value_of=(lambda t: t.value) if lane == "python" else None)
    assert (logic._native is not None) == (lane == "native")
    logic.stats = types.SimpleNamespace(
        operator_name="w", num_launches=0, bytes_to_device=0,
        inputs_ignored=0, bytes_from_device=0, device_time_ms=0.0)
    assert logic.keyed_state_census() == ((0, 0) if lane == "python"
                                          else None)
    out = []
    logic.svc(one_key(0, 200), 0, out.append)       # five windows fire
    logic.svc(one_key(50, 250), 0, out.append)      # 142 tuples late
    assert logic._store.ignored() == 142
    assert logic.stats.num_launches == 1
    assert logic._store.ready() == (2 if lane == "python" else 1)
    assert logic.audit_in_flight()["staging"] == (
        2 if lane == "python" else 0)
    assert logic.flush_chunk(out.append) == 1
    assert logic.stats.inputs_ignored == (142 if lane == "python" else 0)
    assert logic.keyed_state_census()[0] == 1
    logic.eos_flush(out.append)


# -- a snapshot the parent wrote loads ----------------------------------------

@pytest.mark.parametrize("lane", [
    pytest.param("native", marks=needs_native), "python"])
def test_a_snapshot_written_by_the_parent_commit_loads(lane):
    """``tests/golden/winseq_state_pr29_<lane>.pkl``: ``state_dict()`` of a
    logic at 10529d7 (PR 29), settled after 1,000 of the 2,000 events
    below (``sum``, TB 64/32, ``batch_len`` 8, chunks of 250).  The
    Python lane's names ``win_seq_tpu._TPUKeyState`` with the two slots
    of the resident lane that went."""
    n, cut, chunk = 2000, 1000, 250
    i = np.arange(n, dtype=np.int64)
    keys, vals = i % 5, (i % 7).astype(np.float64)

    def logic():
        lg = WinSeqTPULogic(
            "sum", WIN, SLIDE, WinType.TB, batch_len=8, emit_batches=True,
            async_dispatch=False,
            value_of=(lambda t: t.value) if lane == "python" else None)
        assert (lg._native is not None) == (lane == "native")
        return lg

    def run(lg, out, lo, hi):
        for a in range(lo, hi, chunk):
            b = a + chunk
            lg.svc(TupleBatch({"key": keys[a:b], "id": i[a:b], "ts": i[a:b],
                               "value": vals[a:b]}), 0, out)

    whole, before = logic(), Rows()
    run(whole, before, 0, cut)
    whole.flush_chunk(before)
    whole.quiesce(before)
    after = Rows()
    run(whole, after, cut, n)
    whole.eos_flush(after)
    with open(os.path.join(GOLDEN, f"winseq_state_pr29_{lane}.pkl"),
              "rb") as f:
        state = pickle.load(f)
    restored, got = logic(), Rows()
    restored.load_state(state)
    assert restored._store.snapshot()["keys_live"] == 5
    run(restored, got, cut, n)
    restored.eos_flush(got)
    assert got.rows == after.rows and len(got.rows) > 100
    assert not set(got.rows) & set(before.rows)
    # and a snapshot of one lane is still refused by the other
    other = WinSeqTPULogic(
        "sum", WIN, SLIDE, WinType.TB,
        value_of=None if lane == "python" else (lambda t: t.value))
    if (other._native is not None) != (lane == "native"):
        with pytest.raises(RuntimeError, match="snapshot came from"):
            other.load_state(state)


# -- a key's state in one place (PR 33): the two stores still agree -----------

from test_native_runtime import (KS_LAWS, KS_SLIDE, KS_WIN,  # noqa: E402
                                 churn_stream, q5_law)


def store_of(which, kind, win, slide, win_type, delay=0):
    if which == "native":
        return NativeWindowEngine(win, slide, win_type == WinType.TB, delay,
                                  kind=kind)
    return PyWindowStore(win, slide, win_type, delay, kind=kind,
                         role=Role.SEQ)


def staged_by(store, kind, keys, ts, vals, chunk):
    """Rows (as ``take`` makes them), how many there were after each
    chunk, and the store's own counts after the last."""
    rows, marks = [], []
    for a in range(0, len(keys), chunk):
        b = a + chunk
        store.ingest(keys[a:b], ts[a:b], ts[a:b], vals[a:b])
        take(store, kind, rows)
        marks.append((len(rows), store.snapshot()["keys_live"]))
    store.eos()
    take(store, kind, rows)
    s = store.snapshot()
    return sorted(rows), marks, (s["late_accepted"], s["anchors_moved"],
                                 s["inputs_ignored"], store.ignored())


@needs_native
@pytest.mark.parametrize("law", list(KS_LAWS))
def test_both_stores_count_the_q5_laws_alike(law):
    """3,000 keys live, born and evicted, one bid in ten a straggler
    (``ooo``): the same rows after the same chunk, the same keys live,
    the same disorder met."""
    keys, ts, vals = q5_law(120_000, KS_LAWS[law])
    got = [staged_by(store_of(which, "count", KS_WIN, KS_SLIDE, WinType.TB,
                              KS_LAWS[law]), "count", keys, ts, vals, 4096)
           for which in ("native", "python")]
    assert got[0] == got[1]
    assert len(got[0][0]) > 10_000 and max(m[1] for m in got[0][1]) > 2000
    assert (got[0][2][0] > 10_000) == (law == "ooo")


@needs_native
@pytest.mark.parametrize("win_type", [WinType.CB, WinType.TB],
                         ids=["cb", "tb"])
@pytest.mark.parametrize("kind", ["sum", "count", "max", "min"])
@pytest.mark.parametrize("ppw", [16, 64])
def test_both_stores_stage_a_long_ring_alike(ppw, kind, win_type):
    """Windows of 16 and 64 panes: the native engine's ring leaves the
    key state; late rows (one in 97, 700 stamps back) and keys that die
    among them."""
    keys, ts, vals = churn_stream("late", 2000)
    got = [staged_by(store_of(which, kind, ppw * 16, 16, win_type), kind,
                     keys, ts, vals, 129) for which in ("native", "python")]
    assert got[0] == got[1]
    assert len(got[0][0]) > 200
