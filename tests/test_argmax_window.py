"""The ``argmax`` window kind (PR 40): a window's rows are the tuples at
its largest value -- key = the tuple's id (on TB too, where the stamp
places it), id = the window, value = the value -- one row a tuple, in id
order.

Both stores (the C++ engine and ``PyWindowStore``) against a plain numpy
argmax a window, on seeded streams whose values come from a few, so that
most windows tie: time- and count-based, tumbling and sliding over 2, 33
and 360 panes, the window the stream's end cuts short included.  The
slice walk on the lanes gives the one walk's rows; a snapshot continues
the stream; values above 2**24 come back exact, and the same stream with
its values sent through float32 does not (the control); the operator
carries the group key in a column of its own, through a ``PipeGraph``;
a TB row names its tuple by id where several tuples share a stamp (the
batch and record planes, the lanes); the host lane and the composites
that route by key refuse the kind."""
import numpy as np
import pytest

import windflow_tpu as wf
from windflow_tpu.core.basic import WinType
from windflow_tpu.core.tuples import TupleBatch
from windflow_tpu.operators.basic_ops import Sink
from windflow_tpu.operators.batch_ops import BatchSource
from windflow_tpu.operators.tpu.farms_tpu import KeyFarmTPU, WinFarmTPU
from windflow_tpu.operators.tpu.win_seq_tpu import WinSeqTPU
from windflow_tpu.operators.tpu.window_store import PyWindowStore
from windflow_tpu.ops import argmax as am
from windflow_tpu.ops.host_compute import HostComputeEngine
from windflow_tpu.ops.window_compute import WindowComputeEngine
from windflow_tpu.runtime.native import (NativeWindowEngine, get_lib,
                                         native_available)

needs_native = pytest.mark.skipif(not native_available(),
                                  reason="native engine unavailable")
STORES = ["native", "python"]
# (win, slide): tumbling, and sliding over 2, 33 and 360 panes
SHAPES = [(64, 64), (64, 32), (33 * 4, 4), (360 * 2, 2)]


def stream(seed, n, n_keys, win_type, n_values=4, lo=0):
    """keys, ids (TB: one clock; CB: a key's own arrival count) and
    values drawn from ``n_values`` whole numbers from ``lo``."""
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, n_keys, n).astype(np.int64)
    if win_type == WinType.TB:
        ids = np.arange(n, dtype=np.int64)
    else:
        ids = np.zeros(n, np.int64)
        for k in range(n_keys):
            mine = keys == k
            ids[mine] = np.arange(int(mine.sum()))
    vals = (lo + rng.integers(0, n_values, n)).astype(np.float64)
    return keys, ids, vals


def reference(keys, ids, vals, win, slide, stamps=None):
    """Every (id, window, value, key) owed: per key and window that holds
    a tuple of the key, the ids at the window's largest value, sorted.
    ``stamps`` (TB): what places a tuple in its windows, where it is not
    its id."""
    out = []
    place = ids if stamps is None else stamps
    for k in np.unique(keys):
        mine = keys == k
        kid, kv, kp = ids[mine], vals[mine], place[mine]
        for w in range(int(kp.max()) // slide + 1):
            inside = (kp >= w * slide) & (kp < w * slide + win)
            if inside.any():
                top = kv[inside].max()
                for t in np.sort(kid[inside][kv[inside] == top]):
                    out.append((int(t), w, float(top), int(k)))
    return sorted(out)


def make(which, win, slide, win_type):
    if which == "native":
        if not native_available():
            pytest.skip("native engine unavailable")
        return NativeWindowEngine(win, slide, win_type == WinType.TB, 0,
                                  kind="argmax")
    return PyWindowStore(win, slide, win_type, 0, kind="argmax")


def drive(store, keys, ids, vals, chunk, engine=None, lo=0, hi=None,
          rows=None, stamps=None):
    """Chunks of the stream through the store's calls, each ready window
    staged, launched and turned into rows as the operator does (a TB
    store places a tuple by ``stamps`` where given, and takes its id in
    the stamp's place)."""
    place = ids if stamps is None else stamps
    engine = engine or WindowComputeEngine("argmax")
    rows = [] if rows is None else rows
    hi = len(keys) if hi is None else hi

    def take():
        while store.ready():
            out = store.flush(1 << 20)
            cols, starts, ends, d_keys, gwids = out[:5]
            assert out[6] is None and len(out) == 8
            res = engine.compute(cols, starts, ends, gwids).block()
            tids, win_of, values = am.rows(res, out[7])
            rows.extend((int(t), int(gwids[w]), float(v), int(d_keys[w]))
                        for t, w, v in zip(tids, win_of, values))
    for a in range(lo, hi, chunk):
        b = min(a + chunk, hi)
        store.ingest(keys[a:b], place[a:b], ids[a:b], vals[a:b])
        take()
    if hi == len(keys):
        store.eos()
        take()
    return rows


@pytest.mark.parametrize("win,slide", SHAPES,
                         ids=["tumbling", "2panes", "33panes", "360panes"])
@pytest.mark.parametrize("win_type", [WinType.TB, WinType.CB],
                         ids=["tb", "cb"])
@pytest.mark.parametrize("which", STORES)
def test_both_stores_give_every_tuple_at_each_windows_maximum(
        which, win_type, win, slide):
    """Three keys, values from four: most windows tie.  2,005 events
    (the last window is cut at EOS), in chunks that straddle panes."""
    keys, ids, vals = stream(17, 2005, 3, win_type)
    want = reference(keys, ids, vals, win, slide)
    assert len(want) > len({(w, k) for _, w, _, k in want})   # ties
    got = drive(make(which, win, slide, win_type), keys, ids, vals, 77)
    assert sorted(got) == want
    # in id order inside each window
    firsts = {}
    for t, w, _, k in got:
        assert t >= firsts.get((w, k), t)
        firsts[(w, k)] = t


def shared_stamps(seed, n, per):
    """``per`` tuples a stamp, each with an id of its own that does not
    follow the stamps (a permutation of 0..n-1)."""
    return (np.arange(n, dtype=np.int64) // per,
            np.random.default_rng(seed).permutation(n).astype(np.int64))


@pytest.mark.parametrize("win,slide", SHAPES[:3],
                         ids=["tumbling", "2panes", "33panes"])
@pytest.mark.parametrize("which", STORES)
def test_a_tb_row_is_keyed_by_the_tuples_id_not_its_stamp(which, win,
                                                           slide):
    """Three tuples a stamp, their ids out of stamp order, values from
    three: each window's rows are the ids of its tuples at the maximum,
    in id order, never the stamps."""
    n = 2005
    keys, _, vals = stream(23, n, 3, WinType.TB, n_values=3)
    stamps, ids = shared_stamps(23, n, 3)
    want = reference(keys, ids, vals, win, slide, stamps)
    assert len(want) > len({(w, k) for _, w, _, k in want})   # ties
    got = drive(make(which, win, slide, WinType.TB), keys, ids, vals, 77,
                stamps=stamps)
    assert sorted(got) == want
    firsts = {}
    for t, w, _, k in got:
        assert t >= firsts.get((w, k), t)
        firsts[(w, k)] = t


@needs_native
def test_the_lanes_carry_a_tb_tuples_own_id():
    """Calls long enough to split, four tuples a stamp with ids out of
    stamp order: the lanes off and on give the same rows, keyed by id."""
    n = 200_003
    keys, _, vals = stream(31, n, 2, WinType.TB, n_values=3)
    stamps, ids = shared_stamps(31, n, 4)
    got = {}
    for mode in (0, 1):
        eng = NativeWindowEngine(1 << 12, 1 << 11, True, 0, kind="argmax")
        get_lib().wfn_engine_lanes_forced(eng.ptr, mode)
        got[mode] = drive(eng, keys, ids, vals, 50_000, stamps=stamps)
    assert got[0] == got[1]
    assert sorted(got[0]) == reference(keys, ids, vals, 1 << 12, 1 << 11,
                                       stamps)


@pytest.mark.parametrize("which", STORES)
def test_one_global_key_with_a_long_tail(which):
    """Q7's shape at a CPU's size: one key, tumbling windows, Beam's
    log-uniform prices in cents, a few bids planted at the top."""
    rng = np.random.default_rng(7)
    n, win = 50_000, 4096
    vals = np.round(10 ** (6 * rng.random(n)) * 100)
    vals[rng.integers(0, n, 40)] = 99_999_999.0
    keys, ids = np.zeros(n, np.int64), np.arange(n, dtype=np.int64)
    want = reference(keys, ids, vals, win, win)
    assert sorted(drive(make(which, win, win, WinType.TB), keys, ids, vals,
                        4096)) == want


@needs_native
@pytest.mark.parametrize("n_keys", [1, 5])
def test_the_walk_in_slices_gives_the_one_walks_rows(n_keys):
    """Calls long enough to split, the lanes forced off and on: the same
    rows, ties in the same order, and every tuple of the forced calls
    walked in slices -- but on one key, where every call after the first
    is the one-key walk's, which the lanes' rule does not split."""
    keys, ids, vals = stream(3, 200_003, n_keys, WinType.TB, n_values=3)
    got = {}
    for mode in (0, 1):
        eng = NativeWindowEngine(1 << 14, 1 << 13, True, 0, kind="argmax")
        get_lib().wfn_engine_lanes_forced(eng.ptr, mode)
        got[mode] = drive(eng, keys, ids, vals, 50_000)
        s = eng.snapshot()
        one_key = len(keys) - 50_000 if n_keys == 1 else 0
        assert s["walked_one_key"] == one_key
        assert s["walked_in_lanes"] == (0 if mode == 0
                                        else len(keys) - one_key)
    assert got[0] == got[1]
    assert sorted(got[0]) == reference(keys, ids, vals, 1 << 14, 1 << 13)


@needs_native
def test_the_engine_counts_raises_ties_and_its_own_walks():
    """``max_raised`` moves as a pane's maximum rises; ``tie_ids`` is the
    rows beyond one a window; ``argmax_ns`` lies inside ``ingest_ns``
    beside the other walks; no other kind moves them."""
    keys, ids, vals = stream(5, 20_000, 1, WinType.TB, n_values=2)
    eng = NativeWindowEngine(256, 256, True, 0, kind="argmax")
    rows = drive(eng, keys, ids, vals, 1000)
    s = eng.snapshot()
    n_windows = s["windows_staged"]
    assert len(rows) == n_windows + s["tie_ids"] and s["tie_ids"] > 0
    assert n_windows <= s["max_raised"] <= len(keys)
    assert 0 < s["argmax_ns"] and s["argmax_ns"] + s["tuple_walk_ns"] \
        + s["key_walk_ns"] <= s["ingest_ns"]
    other = NativeWindowEngine(256, 256, True, 0, kind="max")
    other.ingest(keys, ids, ids, vals)
    assert [other.snapshot()[n] for n in ("argmax_ns", "max_raised",
                                          "tie_ids")] == [0, 0, 0]


@pytest.mark.parametrize("which", STORES)
def test_a_slice_of_the_synthetic_law_folds_as_its_columns_do(which):
    """``synth_ingest`` (a ``SynthChunk`` reaching the operator): the
    slice's columns, whatever the store."""
    from windflow_tpu.core.tuples import SynthChunk
    b = SynthChunk(0, 3000, 3, 5, 1.0, 0.0).materialize()
    keys, ids, vals = b.key, b.id, b["value"]
    store = make(which, 64, 32, WinType.TB)
    for a in range(0, 3000, 700):
        store.synth_ingest(a, min(700, 3000 - a), 3, 5)
    rows = drive(store, keys, ids, vals, 3000, lo=3000)
    assert sorted(rows) == reference(keys, ids, vals, 64, 32)


@pytest.mark.parametrize("which", STORES)
def test_a_store_continues_identically_from_its_snapshot(which):
    keys, ids, vals = stream(11, 4000, 4, WinType.TB, n_values=3)
    store = make(which, 64, 16, WinType.TB)
    rows = drive(store, keys, ids, vals, 100, hi=2000)
    again = make(which, 64, 16, WinType.TB)
    again.deserialize(store.serialize())
    if which == "native":
        assert again.serialize()["native"] == store.serialize()["native"]
    drive(again, keys, ids, vals, 100, lo=2000, rows=rows)
    assert sorted(rows) == reference(keys, ids, vals, 64, 16)


def test_values_above_2_24_come_back_exact_and_through_float32_do_not(
        monkeypatch):
    """The device compares int32: a price of 99,999,999 cents comes back
    as it went.  The control: the same launch with its values sent through
    float32 first gives wrong values, about seven windows in eight at
    this magnitude (float32's spacing is 8 there)."""
    rng = np.random.default_rng(2)
    n, win = 64 * 400, 64
    vals = (1 << 24) + rng.integers(0, 80_000_000, n).astype(np.float64)
    keys, ids = np.zeros(n, np.int64), np.arange(n, dtype=np.int64)
    want = reference(keys, ids, vals, win, win)
    got = drive(PyWindowStore(win, win, WinType.TB, kind="argmax"), keys,
                ids, vals, 640)
    assert sorted(got) == want
    assert max(v for _, _, v, _ in got) > 1 << 24
    exact = am.int32_values

    def through_float32(values):
        return exact(np.asarray(values, np.float32).astype(np.float64))
    monkeypatch.setattr(am, "int32_values", through_float32)
    got = sorted(drive(PyWindowStore(win, win, WinType.TB, kind="argmax"),
                       keys, ids, vals, 640))
    wrong = sum(g[2] != w[2] for g, w in zip(got, want))
    assert got != want and 0.75 * len(want) < wrong < len(want)


@pytest.mark.parametrize("bad", [0.5, float(1 << 31), float("nan")])
def test_a_value_the_device_cannot_hold_is_refused(bad):
    with pytest.raises(ValueError, match="whole numbers"):
        am.int32_values(np.array([1.0, bad]))
    assert am.int32_values(np.array([-np.inf, 5.0])).tolist() == [
        am.INT32_NEUTRAL, 5]


def test_the_device_lane_gives_each_windows_first_element_at_its_max():
    """Extents of every length over a section with empty elements and
    ties, against numpy; the host lane refuses the kind."""
    rng = np.random.default_rng(4)
    v = rng.integers(0, 5, 300).astype(np.float64)
    v[:7] = -np.inf
    starts = rng.integers(0, 290, 200)
    ends = np.minimum(starts + rng.integers(0, 40, 200), 300)
    dev = WindowComputeEngine("argmax").compute({"value": v}, starts, ends,
                                                starts).block()
    assert dev.dtype == np.int32 and dev.shape == (2, 200)
    for b, (s, e) in enumerate(zip(starts, ends)):
        if e > s and (v[s:e] > -np.inf).any():
            k = int(np.argmax(v[s:e]))
            assert (dev[0, b], dev[1, b]) == (v[s + k], k)
        else:
            assert dev[0, b] == am.INT32_NEUTRAL
    with pytest.raises(ValueError, match="argmax stay on the device"):
        HostComputeEngine("argmax")


def run_graph(name, n, win, chunk=1000, n_keys=2, per=None, **kw):
    keys, ids, vals = stream(9, n, n_keys, WinType.TB, n_values=5,
                             lo=(1 << 24) + 1)
    stamps = ids
    if per:      # ``per`` tuples a stamp, ids out of stamp order
        stamps, ids = shared_stamps(9, n, per)
    chunks = iter([TupleBatch({"key": keys[a:a + chunk],
                               "id": ids[a:a + chunk],
                               "ts": stamps[a:a + chunk],
                               "value": vals[a:a + chunk]})
                   for a in range(0, n, chunk)])
    out = []
    g = wf.PipeGraph(name, wf.Mode.DEFAULT)
    g.add_source(BatchSource(lambda: next(chunks, None))).add(
        WinSeqTPU("argmax", win, win, WinType.TB, name="top",
                  emit_batches=True, **kw)).add_sink(
        Sink(lambda b: out.append(b) if b is not None else None,
             name="out"))
    g.run()
    got = sorted((int(t), int(w), float(v), int(k)) for b in out
                 for t, w, v, k in zip(b.key, b.id, b["value"], b["group"]))
    return got, reference(keys, ids, vals, win, win, stamps)


def test_through_a_graph_the_group_key_rides_in_its_own_column():
    got, want = run_graph("argmax_device", 12_345, 512)
    assert got == want and {k for *_, k in got} == {0, 1}


def test_through_a_graph_a_tb_row_carries_the_tuples_id():
    got, want = run_graph("argmax_ids", 12_345, 128, per=4)
    assert got == want


class Rec:
    def __init__(self, key, tid, ts):
        self.key, self.tid, self.ts = key, tid, ts

    def get_control_fields(self):
        return self.key, self.tid, self.ts

    def set_control_fields(self, key, tid, ts):
        self.key, self.tid, self.ts = key, tid, ts


@pytest.mark.parametrize("which", STORES)
def test_a_record_carries_its_id_into_a_tb_row(which):
    """The record plane: one tuple a call, three a stamp, ids out of
    stamp order."""
    n, win = 600, 32
    keys, _, vals = stream(41, n, 2, WinType.TB, n_values=3)
    stamps, ids = shared_stamps(41, n, 3)
    store = make(which, win, win, WinType.TB)
    for k, i, t, v in zip(keys.tolist(), ids.tolist(), stamps.tolist(),
                          vals.tolist()):
        store.ingest_record(Rec(k, i, t), v)
    got = drive(store, keys, ids, vals, n, lo=n, stamps=stamps)
    assert sorted(got) == reference(keys, ids, vals, win, win, stamps)


def test_the_host_lane_refuses_the_kind():
    with pytest.raises(ValueError, match="argmax stay on the device"):
        WinSeqTPU("argmax", 64, 64, WinType.TB, emit_batches=True,
                  placement="host").stages()


def test_the_composites_that_route_by_key_refuse_the_kind():
    with pytest.raises(ValueError, match="emit_batches"):
        WinSeqTPU("argmax", 64, 64, WinType.TB).stages()
    with pytest.raises(ValueError, match="argmax"):
        WinFarmTPU("argmax", 64, 64, WinType.TB, parallelism=2).stages()
    ok = KeyFarmTPU("argmax", 64, 64, WinType.TB, emit_batches=True)
    assert ok.stages()


# -- the one-key walk -------------------------------------------------------

@needs_native
@pytest.mark.parametrize("chunk", [50, 129, 1000],
                         ids=["in_a_pane", "straddles", "long"])
@pytest.mark.parametrize("shape", ["inorder", "late", "stray_first",
                                   "stray_mid", "stray_last"])
@pytest.mark.parametrize("win_type", [WinType.TB, WinType.CB],
                         ids=["tb", "cb"])
def test_the_one_key_walk_keeps_the_ids_at_the_maximum(win_type, shape,
                                                       chunk):
    """One key, values from five (ties at the maximum inside a call and
    across calls), a TB tuple's own id apart from its stamp: per call the
    general walks' partials, slot_of, counts and staged ids, snapshots
    byte for byte, from the first call on (which the rule never admits);
    a stray key at the first, a middle or the last tuple of a call gives
    the call back to them.  The rows are the recomputation's."""
    from test_native_runtime import one_key_owed_moves, one_key_pair, \
        one_key_stream
    n = 3000
    cuts = list(range(chunk, n, chunk)) + [n]
    keys, stamps, vals = one_key_stream(shape, cuts)
    ids = stamps if win_type == WinType.CB else \
        stamps * 3 + np.random.default_rng(1).integers(0, 3, n)
    lane = (128, 64, win_type == WinType.TB, 40, False, False)
    eng, moved = one_key_pair(lane, "argmax", keys, stamps, vals, cuts,
                              tss=ids)
    assert moved == one_key_owed_moves(keys, cuts, "argmax",
                                       win_type == WinType.TB)
    assert moved[0] == 0
    if not shape.startswith("stray"):
        assert (sum(moved) > 0) == (win_type == WinType.TB)
    if shape == "late" or win_type == WinType.CB:
        return     # late tuples drop; CB windows count a key's own ids
    got = drive(make("native", 128, 64, win_type), keys, ids, vals, chunk,
                stamps=stamps)
    want = reference(keys, ids, vals, 128, 64, stamps)
    assert len(want) > len({(w, k) for _, w, _, k in want})   # ties
    assert sorted(got) == want


@needs_native
@pytest.mark.parametrize("through", [(), ("ids", "ts"), ("keys", "vals")],
                         ids=["all_through", "ids_compact", "keys_compact"])
def test_the_one_key_walk_reads_an_argmax_call_through_its_selection(
        through):
    """A selected batch on one key, late tuples among it: the general
    walks' state and bytes, and the calls the rule admits walked once."""
    from test_native_runtime import one_key_owed_moves, one_key_pair, \
        one_key_stream
    cuts = list(range(129, 3000, 129)) + [3000]
    keys, stamps, vals = one_key_stream("late", cuts)
    ids = stamps * 3 + np.random.default_rng(2).integers(0, 3, 3000)
    _eng, moved = one_key_pair((128, 64, True, 40, False, False), "argmax",
                               keys, stamps, vals, cuts, tss=ids,
                               through=through)
    assert moved == one_key_owed_moves(keys, cuts, "argmax", True)


@needs_native
def test_one_global_key_is_walked_once_a_call():
    """Q7's shape: after the first call every call is the one-key walk's,
    ``argmax_ns`` reads the one-by-one fold alone (a call that straddles
    a window's edge), and the rows are the recomputation's."""
    rng = np.random.default_rng(7)
    n, win, chunk = 60_000, 4096, 1000
    vals = np.round(10 ** (6 * rng.random(n)) * 100)
    vals[rng.integers(0, n, 40)] = 99_999_999.0
    keys, ids = np.zeros(n, np.int64), np.arange(n, dtype=np.int64)
    eng = make("native", win, win, WinType.TB)
    rows = drive(eng, keys, ids, vals, chunk)
    assert sorted(rows) == reference(keys, ids, vals, win, win)
    s = eng.snapshot()
    assert s["walked_one_key"] == n - chunk
    assert s["walked_in_lanes"] == 0
    assert s["argmax_ns"] + s["tuple_walk_ns"] + s["key_walk_ns"] \
        <= s["ingest_ns"]
