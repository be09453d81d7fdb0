"""The native pane fold is by key (native/window_engine.cpp "THE FOLD IS
BY KEY"): a call's tuples are counted per key in one walk, and a key
whose tuples of the call all lie in one pane at or above its acceptance
boundary goes into the pane with one combine; the others fold one by one.

What that may never change: a flushed window, an ``ignored`` count, a
key's life, the order windows fire in.  Checked three ways: one stream
under many chunkings against a plain recomputation (in-order streams,
where the rows do not depend on the chunking); every lane, kind and
stream shape against the digest of what the engine of the commit before
the by-key fold staged, byte for byte and in firing order (``GOLDEN``);
and the two counters the fold keeps, which add up to the tuples folded.
"""
import hashlib
import json

import numpy as np
import pytest

import windflow_tpu as wf
from windflow_tpu.core import WinType
from windflow_tpu.core.tuples import TupleBatch
from windflow_tpu.operators.basic_ops import Sink
from windflow_tpu.operators.batch_ops import BatchSource
from windflow_tpu.operators.tpu.farms_tpu import KeyFarmTPU
from windflow_tpu.runtime.native import NativeWindowEngine, native_available
from windflow_tpu.telemetry import spans
from windflow_tpu.telemetry.metrics import render_openmetrics

pytestmark = pytest.mark.skipif(not native_available(),
                                reason="native library not built")

KINDS = ("count", "sum", "max", "min", "mean")
# lane -> (win, slide, is_tb, delay, renumber, dense)
LANES = {
    "tb": (256, 128, True, 0, False, False),
    "tb_delay": (256, 128, True, 40, False, False),
    "tb_dense": (256, 128, True, 0, False, True),
    "tb_tumbling": (192, 192, True, 0, False, False),
    "tb_odd_pane": (300, 100, True, 0, False, False),   # pane 100: a division
    "cb": (256, 128, False, 0, False, False),
    "renumbered": (256, 128, True, 0, True, False),
    "hopping": (96, 160, True, 0, False, False),
    "cb_hopping": (96, 160, False, 0, False, False),
}


# -- streams (a frozen generator: the digests below depend on the bits) ------

def stream(shape, n, seed=7):
    """keys, ids (= ts) and values of ``n`` events on one clock: half the
    events on a hot key of the current generation, half on the 12 keys
    round it; a generation lives 700 events, so keys are born, die and
    are evicted.  ``disordered`` shuffles stamps inside blocks of 40;
    ``late`` throws one event in 97 some 700 stamps back, below its
    key's acceptance boundary or into a dead key."""
    rng = np.random.RandomState(seed)
    ts = np.arange(n, dtype=np.int64)
    gen = ts // 700
    keys = np.where(rng.randint(0, 2, n) > 0, gen * 12,
                    gen * 12 + rng.randint(0, 12, n)).astype(np.int64)
    vals = rng.randint(1, 1000, n).astype(np.float64)
    if shape == "disordered":
        ts = ts + rng.randint(0, 40, n)
    elif shape == "late":
        back = rng.randint(0, 97, n) == 0
        ts = np.where(back, np.maximum(ts - 700, 0), ts)
    else:
        assert shape == "inorder"
    return keys, ts, vals


def cuts_of(n, chunking):
    """Where a chunking cuts [0, n): a chunk size, or the cuts given."""
    if isinstance(chunking, int):
        return list(range(chunking, n, chunking)) + [n]
    return [c for c in chunking if c < n] + [n]


# -- driving an engine by hand -----------------------------------------------

def scattered(rng, cols, compact=()):
    """A chunk's columns as a selected batch hands them over (PR 31):
    each column's rows scattered, in order, over a base column some
    three times as long whose other rows hold what no stream does, and
    the rows' places.  Columns named in ``compact`` stay as they are: a
    map laid them over the selection."""
    n = len(cols["keys"])
    sel = np.sort(rng.choice(3 * n + 2, n, replace=False)).astype(np.int64)
    out = {}
    for name, col in cols.items():
        if name in compact:
            out[name] = col
            continue
        base = np.full(3 * n + 2, -(1 << 40), col.dtype)
        base[sel] = col
        out[name] = base
    return out, sel


def drive(lane, kind, keys, ts, vals, chunking, look_at=None, through=None):
    """Feed the stream (to a lane of ``LANES``, or one given whole) in
    chunks, staging whatever is ready after each and at EOS.  Returns the engine, every staged window in firing order
    as (key, window, value, result ts), a digest of every byte staged,
    and ``keys_live`` after the chunk that ends at ``look_at``.
    ``through`` feeds every chunk through a selection
    (:func:`scattered`): the names of the columns that stay compact."""
    win, slide, is_tb, delay, renumber, dense = LANES.get(lane, lane)
    eng = NativeWindowEngine(win, slide, is_tb, delay, renumber=renumber,
                             kind=kind, dense=dense)
    rows, digest, live_at = [], hashlib.sha256(), None

    def take():
        while True:
            out = eng.flush(1 << 30)
            if out is None:
                return
            cols, starts, ends, d_keys, gwids, rts, _ = out
            for a in (cols["value"], *out[1:6], *list(cols.values())[1:]):
                digest.update(np.ascontiguousarray(a).tobytes())
            pv = cols["value"]
            for i in range(len(d_keys)):
                panes = pv[starts[i]:ends[i]]
                if kind in ("count", "sum"):
                    v = panes.sum()
                elif kind == "mean":
                    c = cols["count"][starts[i]:ends[i]].sum()
                    v = panes.sum() / c if c else 0.0
                else:
                    v = (panes.max() if kind == "max" else panes.min()) \
                        if len(panes) else 0.0
                rows.append((int(d_keys[i]), int(gwids[i]), float(v),
                             int(rts[i])))
    lo = 0
    rng = np.random.RandomState(len(keys))
    for hi in cuts_of(len(keys), chunking):
        if through is None:
            ready = eng.ingest(keys[lo:hi], ts[lo:hi], ts[lo:hi],
                               vals[lo:hi])
        else:
            c, sel = scattered(rng, {"keys": keys[lo:hi], "ids": ts[lo:hi],
                                     "ts": ts[lo:hi], "vals": vals[lo:hi]},
                               through)
            ready = eng.ingest(c["keys"], c["ids"], c["ts"], c["vals"], sel)
        if ready:
            take()
        if hi == look_at:
            take()
            live_at = eng.snapshot()["keys_live"]
        lo = hi
    eng.eos()
    take()
    return eng, rows, digest.hexdigest(), live_at


def owed(keys, ts, vals, win, slide, kind):
    """{(key, window): value} for every window that holds a tuple of the
    key (``w*slide <= ts < w*slide + win``): what a SEQ replica on TB
    windows owes, however the stream was cut."""
    agg = {"count": len, "sum": sum, "max": max, "min": min,
           "mean": lambda vs: sum(vs) / len(vs)}[kind]
    held = {}
    for k, t, v in zip(keys.tolist(), ts.tolist(), vals.tolist()):
        w0 = 0 if t < win else (t - win) // slide + 1
        for w in range(w0, t // slide + 1):
            held.setdefault((k, w), []).append(v)
    return {kw: float(agg(vs)) for kw, vs in held.items()}


# -- one stream, many chunkings, against the plain recomputation -------------

N_SMALL = 6000
# pane = gcd(256, 128) = 128: cuts on a pane edge, beside one, chunks of
# one event, chunks that straddle, chunks that hold whole panes
SMALL_CHUNKINGS = (1, 7, 127, 128, 129, 1000, [3000], [2944, 3000],
                   [2943, 2945, 3000], N_SMALL)


@pytest.mark.parametrize("kind", KINDS)
def test_in_order_rows_do_not_depend_on_the_chunking(kind):
    keys, ts, vals = stream("inorder", N_SMALL)
    win, slide = LANES["tb"][:2]
    want = owed(keys, ts, vals, win, slide, kind)
    seen = {}
    for chunking in SMALL_CHUNKINGS:
        eng, rows, _digest, live = drive("tb", kind, keys, ts, vals,
                                         chunking, look_at=3000)
        got = {(k, w): v for k, w, v, _rts in rows}
        assert len(got) == len(rows), chunking         # no window twice
        assert got == pytest.approx(want), chunking
        assert all(rts == w * slide + win - 1 for _k, w, _v, rts in rows)
        # a key's windows fire in order
        last = {}
        for k, w, _v, _rts in rows:
            assert last.get(k, -1) < w, (chunking, k, w)
            last[k] = w
        s = eng.snapshot()
        assert eng.ignored() == 0
        assert s["keys_live"] == 0 and s["windows_fired"] == len(rows)
        assert s["folded_by_key"] + s["folded_singly"] == N_SMALL
        if 3000 in cuts_of(N_SMALL, chunking):
            seen[str(chunking)] = live
    # the keys held once 3,000 events are in and staged: the same
    # however they came
    assert len(seen) >= 5 and len(set(seen.values())) == 1, seen


def test_large_chunks_on_and_off_the_pane_edge():
    """The cells' own chunks: 65,536 events divide a pane of 2^18 ids,
    50,000 do not (one chunk in 5.2 straddles an edge).  Same rows, and
    the counters say which fold ran."""
    n, win, slide = 1 << 20, 1 << 19, 1 << 18
    rng = np.random.RandomState(3)
    ts = np.arange(n, dtype=np.int64)
    keys = np.where(rng.randint(0, 2, n) > 0, 100,
                    rng.randint(0, 111, n)).astype(np.int64)
    vals = np.ones(n)
    want = None
    share = {}
    for chunk in (65_536, 50_000, 200_000):
        eng, staged, _d, _l = drive((win, slide, True, 0, False, False),
                                    "count", keys, ts, vals, chunk)
        rows = {(k, w): v for k, w, v, _rts in staged}
        if want is None:
            want = rows
            for w in range(n // slide):
                hot = ((keys == 100) & (ts >= w * slide)
                       & (ts < w * slide + win)).sum()
                assert rows[(100, w)] == hot
        assert rows == want, chunk
        s = eng.snapshot()
        assert s["folded_by_key"] + s["folded_singly"] == n
        share[chunk] = s["folded_by_key"] / n
    assert share[65_536] == 1.0
    assert 0.7 < share[50_000] < 0.9
    assert share[200_000] < 0.6             # three chunks of six straddle


def test_a_key_that_dies_and_comes_back_inside_one_call():
    """Key 5 bids, falls silent for four windows while key 9 carries the
    stream on, and bids again: fed event by event it is evicted and
    opened anew, fed in one call it is one key whose tuples straddle
    panes.  The rows are the same, and none is an empty window."""
    win, slide = LANES["tb"][:2]
    ts = np.arange(2000, dtype=np.int64)
    keys = np.where((ts < 60) | (ts >= 1500), 5, 9).astype(np.int64)
    keys[::3] = 9
    vals = (ts % 17 + 1).astype(np.float64)
    for kind in ("count", "max", "sum"):
        want = owed(keys, ts, vals, win, slide, kind)
        opened = {}
        for chunking in (1, 2000, [60, 1500], [1499, 1501]):
            eng, rows, _d, _l = drive("tb", kind, keys, ts, vals, chunking)
            assert {(k, w): v for k, w, v, _ in rows} \
                == pytest.approx(want), (kind, chunking)
            opened[str(chunking)] = eng.snapshot()["keys_opened"]
        assert opened["1"] == 3 and opened["2000"] == 2    # 5, 9, 5 again


def test_a_late_tuple_in_an_otherwise_one_pane_chunk():
    """A chunk whose tuples lie in one pane, but for one of key 3 that
    lies below the key's acceptance boundary: key 3 folds one by one and
    the late tuple is counted; the chunk's other keys fold by key."""
    win, slide = LANES["tb"][:2]
    eng = NativeWindowEngine(win, slide, True, 0, kind="count")
    ts = np.arange(1024, dtype=np.int64)
    keys = (ts % 4).astype(np.int64)
    eng.ingest(keys, ts, ts, np.ones(1024))
    assert eng.flush(1 << 30) is not None          # windows 0..5 fired
    before = eng.snapshot()
    ts2 = np.arange(1024, 1100, dtype=np.int64)
    keys2 = (ts2 % 4).astype(np.int64)
    ts2[10] = 5                                    # key 2's, long fired
    eng.ingest(keys2, ts2, ts2, np.ones(76))
    s = eng.snapshot()
    assert eng.ignored() == 1
    mine = int((keys2 == keys2[10]).sum())
    assert s["folded_singly"] - before["folded_singly"] == mine - 1
    assert s["folded_by_key"] - before["folded_by_key"] == 76 - mine
    eng.eos()
    cols, starts, ends, d_keys, gwids, _rts, _ = eng.flush(1 << 30)
    pv = cols["value"]
    got = {(int(k), int(w)): pv[a:b].sum()
           for k, w, a, b in zip(d_keys, gwids, starts, ends)}
    kept = np.r_[ts, np.delete(ts2, 10)]
    kept_keys = np.r_[keys, np.delete(keys2, 10)]
    for (k, w), v in got.items():
        if w >= 6:
            assert v == ((kept_keys == k) & (kept >= w * slide)
                         & (kept < w * slide + win)).sum(), (k, w)


# -- every lane against the engine before the by-key fold --------------------

# sha256 of every byte the engine of commit 359ec96 (the per-tuple fold)
# staged, in firing order, then `ignored`, keys opened and windows
# fired; regenerate with `python tests/test_fold_by_key.py` in a checkout
# of the engine to be trusted.  Every in-order digest is that commit's.
# Twenty others are PR 32's, where that engine lost tuples: under
# `tb_delay/*/disordered` it anchored a key at its first tuple to arrive
# and never emitted the window an earlier straggler opened (483 rows of
# the 485 owed: tests/test_out_of_order.py holds the engine to the plain
# recomputation there); under `cb/*/late` and `cb_hopping/*` it dropped
# the tuples below a key's anchor without counting them (the same staged
# bytes, `ignored` two to three higher: every tuple is now folded,
# counted as ignored, or in a hopping gap).
GOLDEN_CHUNKINGS = {"c1": 1, "c7": 7, "c128": 128, "c129": 129,
                    "c1000": 1000, "whole": 1 << 30}
GOLDEN_N = 4000


def golden_cases():
    for lane in LANES:
        for kind in KINDS:
            for shape in ("inorder", "disordered", "late"):
                yield lane, kind, shape


def digest_of(lane, kind, shape):
    keys, ts, vals = stream(shape, GOLDEN_N)
    h = hashlib.sha256()
    for name, chunking in GOLDEN_CHUNKINGS.items():
        eng, rows, digest, _live = drive(lane, kind, keys, ts, vals,
                                         min(chunking, GOLDEN_N))
        s = eng.snapshot()
        h.update(f"{name}:{digest}:{eng.ignored()}:{s['keys_opened']}:"
                 f"{s['windows_fired']}:{len(rows)};".encode())
    return h.hexdigest()[:16]


GOLDEN = json.loads("""
{
"tb/count/inorder": "e3dbed7976a8ad09",
"tb/count/disordered": "75ce8daf80533078",
"tb/count/late": "fda7fa5b44738edc",
"tb/sum/inorder": "0b695dd64acd1c6e",
"tb/sum/disordered": "f3e49326c73ea22d",
"tb/sum/late": "d7fc6cdb328c9e26",
"tb/max/inorder": "b00c507e8c5602c4",
"tb/max/disordered": "ed7f205b88bb9060",
"tb/max/late": "b9a45c00cddf881f",
"tb/min/inorder": "16356e743ef75cee",
"tb/min/disordered": "7e5bdd259a7811b6",
"tb/min/late": "f2ced32b7a0d1ea2",
"tb/mean/inorder": "ec8c4b1c79497fb8",
"tb/mean/disordered": "723dbec41b6eddf2",
"tb/mean/late": "157248bc205a36bd",
"tb_delay/count/inorder": "b776b9f4c1528e1d",
"tb_delay/count/disordered": "02f45928b0dadfb0",
"tb_delay/count/late": "e4ce709b23b8bbe0",
"tb_delay/sum/inorder": "3f996067650e73f7",
"tb_delay/sum/disordered": "84d86a48f0efeba2",
"tb_delay/sum/late": "fa5c5921e688eeed",
"tb_delay/max/inorder": "9c057798a76344dc",
"tb_delay/max/disordered": "c41fbca023e3db2f",
"tb_delay/max/late": "fa9f718d8071fc67",
"tb_delay/min/inorder": "6cdf1a0cd416dc7e",
"tb_delay/min/disordered": "9faea69dbefe1579",
"tb_delay/min/late": "eddb69632a5121dd",
"tb_delay/mean/inorder": "e97622603f5a4e7d",
"tb_delay/mean/disordered": "ae49aa0dfc774829",
"tb_delay/mean/late": "2c51d4e90d7c6111",
"tb_dense/count/inorder": "e3dbed7976a8ad09",
"tb_dense/count/disordered": "e732d1412fc5517b",
"tb_dense/count/late": "49a956d564636578",
"tb_dense/sum/inorder": "0b695dd64acd1c6e",
"tb_dense/sum/disordered": "8e54b6ce7b0ae7e5",
"tb_dense/sum/late": "5be0cdc1f79e793b",
"tb_dense/max/inorder": "b00c507e8c5602c4",
"tb_dense/max/disordered": "3ed00030102b6638",
"tb_dense/max/late": "e53e4702b43df713",
"tb_dense/min/inorder": "16356e743ef75cee",
"tb_dense/min/disordered": "303a156e9b5c6b6d",
"tb_dense/min/late": "ac9268648ad05471",
"tb_dense/mean/inorder": "ec8c4b1c79497fb8",
"tb_dense/mean/disordered": "ba74d3dd6d39e0bf",
"tb_dense/mean/late": "3e9b14c623ab5106",
"tb_tumbling/count/inorder": "df52422da03c3825",
"tb_tumbling/count/disordered": "b24789282720624f",
"tb_tumbling/count/late": "cbf500240e2db70c",
"tb_tumbling/sum/inorder": "5e1343c1645c98f5",
"tb_tumbling/sum/disordered": "d105e88c561f3f97",
"tb_tumbling/sum/late": "33870f498153c819",
"tb_tumbling/max/inorder": "de87c3e688fc1326",
"tb_tumbling/max/disordered": "0edb85606356514a",
"tb_tumbling/max/late": "488e3d021a04ff14",
"tb_tumbling/min/inorder": "b2bfa052d61d519f",
"tb_tumbling/min/disordered": "9e9a34a147fefc6d",
"tb_tumbling/min/late": "480cb71726464824",
"tb_tumbling/mean/inorder": "97b4748965e6cbc7",
"tb_tumbling/mean/disordered": "58e14d0ac68261e7",
"tb_tumbling/mean/late": "b43095c992b48e45",
"tb_odd_pane/count/inorder": "e83c28586b8fbdf4",
"tb_odd_pane/count/disordered": "bb5905d7f1f8c9d4",
"tb_odd_pane/count/late": "667dce0538bdf6ab",
"tb_odd_pane/sum/inorder": "081f7601a663c652",
"tb_odd_pane/sum/disordered": "733aee16b1e8292c",
"tb_odd_pane/sum/late": "04d4260159b39e88",
"tb_odd_pane/max/inorder": "e90e54ece24c0f40",
"tb_odd_pane/max/disordered": "ebdd5b38b4ab4077",
"tb_odd_pane/max/late": "3725addca5fee261",
"tb_odd_pane/min/inorder": "0ebde3b3e655e952",
"tb_odd_pane/min/disordered": "5946199cb5dd6deb",
"tb_odd_pane/min/late": "3d6225d51a2e2b87",
"tb_odd_pane/mean/inorder": "6dcb7c8cfb4d7ba9",
"tb_odd_pane/mean/disordered": "11a5dcf3b32e2b23",
"tb_odd_pane/mean/late": "321e83c8041a40ec",
"cb/count/inorder": "5c4c2d61c5306646",
"cb/count/disordered": "aae7270e297f9753",
"cb/count/late": "6d98436695348d1f",
"cb/sum/inorder": "fcb33dfce0292884",
"cb/sum/disordered": "9614c623682a87ff",
"cb/sum/late": "df85bb34375a66f3",
"cb/max/inorder": "735a1746fc062297",
"cb/max/disordered": "04e6bdd537d3e9e4",
"cb/max/late": "1c70454c84db5067",
"cb/min/inorder": "539c0895558e1a15",
"cb/min/disordered": "b0bbdce691cd0a0f",
"cb/min/late": "38625dba3adf948d",
"cb/mean/inorder": "7bf1e4616ec20bf7",
"cb/mean/disordered": "df3e7caccc74fa44",
"cb/mean/late": "551c4eacc1df2803",
"renumbered/count/inorder": "1393f02dbc11bb4d",
"renumbered/count/disordered": "1393f02dbc11bb4d",
"renumbered/count/late": "1393f02dbc11bb4d",
"renumbered/sum/inorder": "26b6e6892a0dad57",
"renumbered/sum/disordered": "26b6e6892a0dad57",
"renumbered/sum/late": "26b6e6892a0dad57",
"renumbered/max/inorder": "8aea29f281ca44f6",
"renumbered/max/disordered": "8aea29f281ca44f6",
"renumbered/max/late": "8aea29f281ca44f6",
"renumbered/min/inorder": "1742542be6ad98be",
"renumbered/min/disordered": "1742542be6ad98be",
"renumbered/min/late": "1742542be6ad98be",
"renumbered/mean/inorder": "f7943d375e482692",
"renumbered/mean/disordered": "f7943d375e482692",
"renumbered/mean/late": "f7943d375e482692",
"hopping/count/inorder": "f3030058beed0f81",
"hopping/count/disordered": "f02080cb1a2ebeae",
"hopping/count/late": "b96f77ea2eae33eb",
"hopping/sum/inorder": "9ad3271bc1577aac",
"hopping/sum/disordered": "91215d3850607d58",
"hopping/sum/late": "aaf092189c7c036f",
"hopping/max/inorder": "e63a29203383954e",
"hopping/max/disordered": "07d5ec899a5eef70",
"hopping/max/late": "6e951580171932d0",
"hopping/min/inorder": "e851d8efa1f281f9",
"hopping/min/disordered": "b2ee78a5c57ec559",
"hopping/min/late": "8292c620ce255589",
"hopping/mean/inorder": "01254c1196631f65",
"hopping/mean/disordered": "aa0fb9075eefc454",
"hopping/mean/late": "554092ede20e6b64",
"cb_hopping/count/inorder": "fb3c04e2629d402f",
"cb_hopping/count/disordered": "8d0131823f8fb3f2",
"cb_hopping/count/late": "1e7862279eb2eef6",
"cb_hopping/sum/inorder": "25b42fd15035ebcc",
"cb_hopping/sum/disordered": "37c09da1aff5ca8e",
"cb_hopping/sum/late": "8fe18a8176b80a93",
"cb_hopping/max/inorder": "a025e941bb394550",
"cb_hopping/max/disordered": "dcc1f97da721cfb2",
"cb_hopping/max/late": "dd795928af6f7597",
"cb_hopping/min/inorder": "470ee2305dec46e7",
"cb_hopping/min/disordered": "d1724c6438a3ef13",
"cb_hopping/min/late": "3ac8e761f967b6eb",
"cb_hopping/mean/inorder": "1beeae14f19b9a97",
"cb_hopping/mean/disordered": "63b2b8b2ffbf840b",
"cb_hopping/mean/late": "77306df222a8797b"
}
""")


@pytest.mark.parametrize("lane,kind,shape", list(golden_cases()))
def test_every_lane_stages_what_the_per_tuple_fold_staged(lane, kind, shape):
    assert digest_of(lane, kind, shape) == GOLDEN[f"{lane}/{kind}/{shape}"]


# -- the two counters ---------------------------------------------------------

def test_the_counters_add_up_and_say_which_fold_ran():
    keys, ts, vals = stream("late", N_SMALL)
    for lane, by_key in (("tb", True), ("cb", False), ("renumbered", False),
                         ("hopping", False)):
        for kind in ("count", "max", "sum"):
            eng, _rows, _d, _l = drive(lane, kind, keys, ts, vals, 128)
            s = eng.snapshot()
            folded = s["folded_by_key"] + s["folded_singly"]
            if lane != "hopping":
                # nothing vanishes uncounted: below the ring of a CB key
                # that keeps its anchor is ignored too
                assert folded == N_SMALL - eng.ignored(), (lane, kind)
            else:
                # a hopping gap's tuples belong to no window: neither
                # folded nor owed
                assert 0 < folded < N_SMALL - eng.ignored(), (lane, kind)
            if by_key and kind != "sum":
                assert s["folded_by_key"] > 0.5 * folded, (lane, kind)
            else:
                assert s["folded_by_key"] == 0, (lane, kind)
    # aligned chunks of an in-order stream: all by key; straddling: not
    keys, ts, vals = stream("inorder", N_SMALL)
    eng, _rows, _d, _l = drive("tb", "count", keys, ts, vals, 128)
    s = eng.snapshot()
    assert s["folded_by_key"] == N_SMALL and s["folded_singly"] == 0
    eng, _rows, _d, _l = drive("tb", "count", keys, ts, vals, 100)
    s = eng.snapshot()
    assert s["folded_singly"] > 0 and s["folded_by_key"] > 0
    assert s["folded_by_key"] + s["folded_singly"] == N_SMALL


def test_the_counters_reach_the_stats_json_and_the_metrics_page():
    keys, ts, vals = stream("inorder", 20_000)
    chunks = [TupleBatch({"key": keys[i:i + 500], "id": ts[i:i + 500],
                          "ts": ts[i:i + 500], "value": vals[i:i + 500]})
              for i in range(0, len(keys), 500)]     # 500 straddles 128
    it = iter(chunks)
    g = wf.PipeGraph("fold_counters", wf.Mode.DEFAULT)
    g.add_source(BatchSource(lambda: next(it, None))).add(
        KeyFarmTPU("count", 256, 128, WinType.TB, name="counts",
                   emit_batches=True)).add_sink(
        Sink(lambda b: None, name="out"))
    g.run()
    report = json.loads(g.stats.to_json())
    mine = [r for r in report["Spans"]["Operators"] if "Counters" in r]
    assert mine and all("counts" in r["Operator"] for r in mine)
    c = mine[0]["Counters"]
    assert c["folded_by_key"] + c["folded_singly"] == len(keys)
    assert c["folded_singly"] > 0 and c["folded_by_key"] > 0
    kept = spans.graph("fold_counters").counters[mine[0]["Operator"]]
    assert kept.values == c
    assert kept.folded_between(0.0, 1e12) \
        == (c["folded_by_key"], c["folded_singly"])
    text = render_openmetrics({"a": {"report": report}})
    for name in ("folded_by_key_total", "folded_singly_total"):
        assert f"windflow_engine_{name}{{" in text, name


# -- the engine through a selection (PR 31) -----------------------------------

# what a selected batch leaves compact: nothing (a filter alone), the
# keys (a join laid them over it), the values, all but the ids
COMPACT = {"none": (), "keys": ("keys",), "vals": ("vals",),
           "all_but_ids": ("keys", "ts", "vals")}


def selection_cases():
    for lane in LANES:
        for kind in KINDS:
            yield lane, kind, "late"     # late rows are counted as ignored
    for kind in KINDS:
        yield "tb", kind, "disordered"
        yield "cb", kind, "inorder"


@pytest.mark.parametrize("lane,kind,shape", list(selection_cases()),
                         ids=lambda v: str(v))
def test_through_a_selection_the_engine_stages_the_same_bytes(lane, kind,
                                                               shape):
    """``ingest(..., sel)`` reads rows ``sel[j]`` of the base columns in
    its two walks: what it stages, counts as ignored, opens and fires is
    what ``ingest`` of the gathered columns does, byte for byte, under
    every chunking (one event a call, chunks that straddle a pane edge,
    the whole stream) on every lane: the digest is ``GOLDEN``'s."""
    keys, ts, vals = stream(shape, GOLDEN_N)
    which = list(COMPACT)[(len(lane) + len(kind)) % len(COMPACT)]
    for compact in {"none", which}:
        for f32 in (False, True):
            if f32 and compact != "none":
                continue
            h = hashlib.sha256()
            for name, chunking in GOLDEN_CHUNKINGS.items():
                eng, rows, digest, _live = drive(
                    lane, kind, keys, ts,
                    vals.astype(np.float32) if f32 else vals,
                    min(chunking, GOLDEN_N), through=COMPACT[compact])
                s = eng.snapshot()
                h.update(f"{name}:{digest}:{eng.ignored()}:"
                         f"{s['keys_opened']}:{s['windows_fired']}:"
                         f"{len(rows)};".encode())
            # values are whole numbers under 1000: exact in f32 too
            assert h.hexdigest()[:16] == GOLDEN[f"{lane}/{kind}/{shape}"], \
                (compact, f32)


def test_a_selection_folds_by_key_where_the_gathered_chunk_would():
    """The by-key lane and the one-by-one walk both read through the
    selection: the two counts and the late rows are the gathered
    chunk's."""
    keys, ts, vals = stream("late", N_SMALL)
    for lane, kind in (("tb", "count"), ("tb", "max"), ("tb", "sum"),
                       ("cb", "count"), ("hopping", "min")):
        for chunking in (128, 100):
            plain, rows, digest, _l = drive(lane, kind, keys, ts, vals,
                                            chunking)
            through, rows_t, digest_t, _l = drive(lane, kind, keys, ts, vals,
                                                  chunking, through=())
            assert rows_t == rows and digest_t == digest
            assert through.ignored() == plain.ignored() > 0
            counts = [{k: v for k, v in e.snapshot().items()
                       if not k.endswith("_ns")} for e in (through, plain)]
            assert counts[0] == counts[1], (lane, kind)
            assert counts[0]["folded_by_key"] + counts[0]["folded_singly"] > 0


def test_a_selection_of_a_column_that_needs_converting():
    """A base value column that is not a float in a row (whole numbers, a
    strided view) is gathered first and converted after: its rows alone."""
    keys, ts, _vals = stream("inorder", 2000)
    vals = (ts % 13 + 1)
    want = drive("tb", "sum", keys, ts, vals.astype(np.float64), 500)[1]
    eng = NativeWindowEngine(*LANES["tb"][:4], kind="sum")
    rng = np.random.RandomState(1)
    got = []
    for lo in range(0, 2000, 500):
        c, sel = scattered(rng, {"keys": keys[lo:lo + 500],
                                 "ids": ts[lo:lo + 500],
                                 "ts": ts[lo:lo + 500],
                                 "vals": vals[lo:lo + 500]})
        wide = np.zeros(2 * len(c["ids"]), np.int64)
        wide[::2] = c["ids"]
        eng.ingest(c["keys"].astype(np.int32), wide[::2], c["ts"],
                   c["vals"], sel)
    eng.eos()
    out = eng.flush(1 << 30)
    cols, starts, ends, d_keys, gwids, rts, _ = out
    got = [(int(k), int(w), float(cols["value"][a:b].sum()), int(r))
           for k, w, a, b, r in zip(d_keys, gwids, starts, ends, rts)]
    assert sorted(got) == sorted(want)


if __name__ == "__main__":
    print(json.dumps({f"{lane}/{kind}/{shape}": digest_of(lane, kind, shape)
                      for lane, kind, shape in golden_cases()}, indent=0))
