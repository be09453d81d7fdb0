"""Native C++ runtime tests: channel semantics, columnar kernels, and
full-graph runs over native channels."""
import threading

import numpy as np
import pytest

from windflow_tpu.runtime.native import (NativeChannel, native_available,
                                         pane_reduce)

pytestmark = pytest.mark.skipif(not native_available(),
                                reason="g++ toolchain unavailable")


class TestNativeChannel:
    def test_fifo_and_eos(self):
        ch = NativeChannel(16)
        p0 = ch.register_producer()
        p1 = ch.register_producer()
        ch.put(p0, "a")
        ch.put(p1, "b")
        ch.close(p0)
        ch.put(p1, "c")
        ch.close(p1)
        got = [ch.get() for _ in range(3)]
        assert [g[1] for g in got] == ["a", "b", "c"]
        assert got[0][0] == p0 and got[1][0] == p1
        assert ch.get() is None  # all producers closed

    def test_objects_survive_gc(self):
        import gc
        ch = NativeChannel(8)
        p = ch.register_producer()
        obj = {"payload": list(range(100))}
        ch.put(p, obj)
        del obj
        gc.collect()
        _, back = ch.get()
        assert back["payload"][-1] == 99

    def test_blocking_backpressure(self):
        ch = NativeChannel(2)
        p = ch.register_producer()
        ch.put(p, 1)
        ch.put(p, 2)
        done = threading.Event()

        def producer():
            ch.put(p, 3)  # blocks until a slot frees
            done.set()

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        assert not done.wait(0.1)
        assert ch.get()[1] == 1
        assert done.wait(1.0)

    def test_cross_thread_stream(self):
        ch = NativeChannel(64)
        p = ch.register_producer()
        n = 5000
        out = []

        def consumer():
            while True:
                got = ch.get()
                if got is None:
                    return
                out.append(got[1])

        t = threading.Thread(target=consumer, daemon=True)
        t.start()
        for i in range(n):
            ch.put(p, i)
        ch.close(p)
        t.join(timeout=10)
        assert out == list(range(n))


class TestNativeKernels:
    def test_pane_sum_matches_numpy(self):
        rng = np.random.default_rng(0)
        vals = rng.normal(size=1000)
        pos = np.sort(rng.integers(0, 1000, 33))
        pos[0], pos[-1] = 0, 1000
        out = pane_reduce(vals, pos, "sum")
        cs = np.concatenate([[0], np.cumsum(vals)])
        np.testing.assert_allclose(out, cs[pos[1:]] - cs[pos[:-1]],
                                   rtol=1e-12)

    def test_pane_max_min_empty_panes(self):
        vals = np.array([3.0, 1.0, 4.0, 1.0, 5.0])
        pos = np.array([0, 2, 2, 5])  # middle pane empty
        out_max = pane_reduce(vals, pos, "max")
        assert out_max[0] == 3.0
        assert out_max[1] == -np.inf
        assert out_max[2] == 5.0
        out_min = pane_reduce(vals, pos, "min")
        assert out_min[1] == np.inf


def test_full_graph_over_native_channels():
    import windflow_tpu as wf
    from windflow_tpu.core import BasicRecord, Mode, RuntimeConfig
    from windflow_tpu.runtime.queues import make_channel

    cfg = RuntimeConfig(use_native_runtime=True)
    assert type(make_channel(cfg)).__name__ == "NativeChannel"
    state = {}
    total = {"v": 0.0}
    lock = threading.Lock()

    def src(shipper, ctx):
        i = state.setdefault("i", 0)
        if i >= 200:
            return False
        shipper.push(BasicRecord(i % 3, i // 3, i, float(i)))
        state["i"] = i + 1
        return True

    def snk(rec):
        if rec is not None:
            with lock:
                total["v"] += rec.value

    g = wf.PipeGraph("native", Mode.DEFAULT, cfg)
    g.add_source(wf.SourceBuilder(src).build()) \
        .add(wf.MapBuilder(lambda t: None).with_parallelism(2).build()) \
        .add_sink(wf.SinkBuilder(snk).build())
    g.run()
    assert total["v"] == sum(range(200))


def test_engine_int64_min_key():
    """INT64_MIN is a valid tuple key: it must not collide with the
    hash table's empty-slot sentinel (window_engine.cpp dense_of)."""
    import numpy as np
    from windflow_tpu.runtime.native import NativeWindowEngine

    eng = NativeWindowEngine(8, 4, False, 0)
    kmin = np.iinfo(np.int64).min
    keys = np.array([kmin, 5] * 40, np.int64)
    ids = np.arange(80, dtype=np.int64) // 2
    eng.ingest(keys, ids, ids, np.ones(80))
    eng.eos()
    got = {}
    while True:
        out = eng.flush(1000)
        if out is None:
            break
        vals, starts, ends, d_keys, gwids = out[0]["value"], *out[1:5]
        for i in range(len(d_keys)):
            got.setdefault(int(d_keys[i]), []).append(
                vals[starts[i]:ends[i]].sum())
    assert set(got) == {kmin, 5}
    assert got[kmin][0] == 8.0 and got[5][0] == 8.0


def test_engine_partial_flush_keeps_queued_window_data():
    """A flush smaller than the ready count must not evict tuples still
    needed by fired-but-unstaged windows (window_engine.cpp eviction)."""
    import numpy as np
    from windflow_tpu.runtime.native import NativeWindowEngine

    eng = NativeWindowEngine(4, 2, True, 0)
    n = 100
    eng.ingest(np.zeros(n, np.int64), np.arange(n), np.arange(n),
               np.ones(n))
    assert eng.ready() == 48
    seen = 0
    while True:
        out = eng.flush(10)
        if out is None:
            break
        vals, starts, ends, _keys, gwids = out[0]["value"], *out[1:5]
        for i in range(len(gwids)):
            assert vals[starts[i]:ends[i]].sum() == 4.0, int(gwids[i])
            seen += 1
    assert seen == 48


def test_engine_gapped_window_stages_empty_extent():
    """A fired window whose extent contains no tuples (gapped id space)
    must stage start==end so the device combine emits the masked
    neutral 0 -- matching the Python/XLA path -- instead of the
    +-inf pane fill (window_engine.cpp flush staging)."""
    from windflow_tpu.runtime.native import NativeWindowEngine

    for kind in ("max", "min", "sum"):
        eng = NativeWindowEngine(4, 4, False, 0, kind=kind)
        # key 0: ids 0..3 (window 0 full), then a gap to ids 12..15
        # (window 3 full); windows 1 and 2 have no tuples in extent
        ids = np.array([0, 1, 2, 3, 12, 13, 14, 15], np.int64)
        eng.ingest(np.zeros(8, np.int64), ids, ids,
                   np.full(8, 7.0))
        eng.eos()
        got = {}
        while True:
            out = eng.flush(1000)
            if out is None:
                break
            vals, starts, ends, d_keys, gwids = out[0]["value"], *out[1:5]
            for i in range(len(gwids)):
                w = int(gwids[i])
                seg = vals[starts[i]:ends[i]]
                if len(seg) == 0:
                    got[w] = 0.0  # empty extent -> masked neutral
                elif kind == "max":
                    got[w] = seg.max()
                elif kind == "min":
                    got[w] = seg.min()
                else:
                    got[w] = seg.sum()
        assert got[1] == 0.0 and got[2] == 0.0, (kind, got)
        assert np.isfinite(list(got.values())).all(), (kind, got)
        full = 7.0 if kind in ("max", "min") else 28.0
        assert got[0] == full and got[3] == full, (kind, got)


def test_engine_renumber_hopping_gap_after_eviction():
    """Renumber lane + hopping windows (win < slide): after a flush
    evicts up to next_fire*slide, subsequent arrivals land BELOW the
    pane ring base (they belong to no window) and must be skipped, not
    folded at a negative ring index (window_engine.cpp ingest)."""
    from windflow_tpu.runtime.native import NativeWindowEngine

    eng = NativeWindowEngine(2, 10, False, 0, renumber=True)
    ids = np.arange(4, dtype=np.int64)
    eng.ingest(np.zeros(4, np.int64), ids, ids, np.ones(4))
    assert eng.ready() == 1  # window 0 = arrivals [0, 2)
    out = eng.flush(10)      # evicts panes below next_fire*slide = 10
    assert out is not None and len(out[4]) == 1
    # arrivals 4..9 sit in the gap below the evicted frontier; 10..11
    # fill window 1 exactly
    n = 8
    ids2 = np.arange(4, 4 + n, dtype=np.int64)
    eng.ingest(np.zeros(n, np.int64), ids2, ids2, np.full(n, 3.0))
    eng.eos()
    out = eng.flush(10)
    vals, starts, ends, _keys, gwids = out[0]["value"], *out[1:5]
    assert list(gwids) == [1]
    assert vals[starts[0]:ends[0]].sum() == 6.0  # arrivals 10, 11 only


@pytest.mark.parametrize("win,slide,kind,start,delay,vscale,voff", [
    (32, 16, "sum", 0, 0, 1.0, 0.0),    # sliding
    (16, 16, "max", 0, 0, 1.0, 0.0),    # tumbling
    (8, 24, "sum", 0, 0, 1.0, 0.0),     # hopping (gap ids dropped)
    (1, 1, "sum", 0, 0, 1.0, 0.0),      # degenerate single-id windows
    (32, 16, "sum", 30_000, 0, 1.0, 0.0),   # mid-stream start: anchor
    (32, 16, "sum", 0, 40, 1.0, 0.0),       # TB triggering delay
    (16, 8, "min", 0, 0, -2.5, 7.0),        # value law scale/offset
])
def test_engine_synth_ingest_matches_array_ingest(win, slide, kind,
                                                  start, delay, vscale,
                                                  voff):
    """The fused generate+fold lane must stage bit-identical windows to
    ingesting the same synthetic law as materialized arrays, across
    chunk splits, geometries, kinds, anchored mid-stream starts,
    triggering delay, and the value law's scale/offset."""
    from windflow_tpu.runtime.native import NativeWindowEngine

    N, K, VMOD = 40_000, 7, 97

    def drain(eng, out):
        while True:
            r = eng.flush(1 << 20)
            if r is None:
                return
            vals, starts, ends, keys, gwids = r[0]["value"], *r[1:5]
            agg_of = {"sum": np.sum, "max": np.max, "min": np.min}[kind]
            for b in range(len(starts)):
                seg = vals[starts[b]:ends[b]]
                out[(keys[b], gwids[b])] = (agg_of(seg) if len(seg)
                                            else 0.0)

    # reference: array ingest of the same law over events
    # [start, start + N)
    idx = start + np.arange(N, dtype=np.int64)
    keys = idx % K
    ids = idx // K
    vals = (idx % VMOD).astype(np.float64) * vscale + voff
    ref_eng = NativeWindowEngine(win, slide, True, delay, False, kind)
    ref = {}
    for lo in range(0, N, 7_000):
        hi = min(lo + 7_000, N)
        ref_eng.ingest(keys[lo:hi], ids[lo:hi], ids[lo:hi], vals[lo:hi])
        drain(ref_eng, ref)
    ref_eng.eos()
    drain(ref_eng, ref)

    # fused lane: uneven chunk boundaries exercise the per-key ranges
    eng = NativeWindowEngine(win, slide, True, delay, False, kind)
    got = {}
    for lo in range(start, start + N, 9_999):
        eng.synth_ingest(lo, min(9_999, start + N - lo), K, VMOD,
                         vscale, voff)
        drain(eng, got)
    eng.eos()
    drain(eng, got)
    assert got.keys() == ref.keys() and len(got) > 50
    for k in got:
        assert got[k] == ref[k], (k, got[k], ref[k])
    assert eng.ignored() == ref_eng.ignored()


def test_engine_deserialize_rejects_huge_length_field():
    """A corrupted checkpoint blob with an enormous vector-length field
    must fail cleanly, not overflow the bounds check into a multi-GB
    resize (window_engine.cpp get_vec)."""
    from windflow_tpu.runtime.native import NativeWindowEngine

    e1 = NativeWindowEngine(32, 16, True)
    e1.ingest(np.zeros(10, np.int64), np.arange(10, dtype=np.int64),
              np.arange(10, dtype=np.int64), np.ones(10))
    blob = bytearray(e1.serialize()["native"])
    import struct
    # parse the WFN4 snapshot framing (window_engine.cpp serialize()):
    # the 15-i64 header (magic,win,slide,delay,tb,rn,dense,kind,
    # stream_time,fired_upto,keys_opened,keys_evicted,keys_live_peak,
    # windows_fired,nkeys) and the first live key's 8 fixed i64s
    # (key,next_fire,anchor,opened_max,max_id,pane_base,arrivals,
    # staged_upto), then walk the four per-key vectors
    # (pacc,pcnt,plid,plts) by their length headers and corrupt the
    # first non-empty one
    off = 15 * 8 + 8 * 8
    corrupted = False
    for _ in range(4):
        n = struct.unpack_from("<q", blob, off)[0]
        assert 0 <= n <= 32  # framing sanity: a plausible ring length
        if n > 0:
            struct.pack_into("<q", blob, off, 1 << 61)
            corrupted = True
            break
        off += 8 + n * 8
    assert corrupted  # 10 ingested values: the pane ring is non-empty
    e2 = NativeWindowEngine(32, 16, True)
    with pytest.raises(ValueError):
        e2.deserialize({"native": bytes(blob)})


def test_engine_deserialize_corruption_fuzz():
    """Random bit flips and truncations of a checkpoint blob must
    always either load or raise a Python exception -- never crash the
    process (the C++ get_vec bounds checks are the only thing between a
    corrupted length field and a wild resize/read)."""
    import random

    from windflow_tpu.runtime.native import NativeWindowEngine
    eng = NativeWindowEngine(64, 32, False, 0)
    ids = np.arange(5000, dtype=np.int64)
    eng.ingest(ids % 8, ids // 8, ids // 8, np.ones(5000))
    blob = eng.serialize()["native"]
    # control: the pristine blob must load, or the fuzz is vacuous
    NativeWindowEngine(64, 32, False, 0).deserialize({"native": blob})
    rnd = random.Random(0)
    for _trial in range(200):
        b = bytearray(blob)
        for _ in range(rnd.randint(1, 8)):
            b[rnd.randrange(len(b))] ^= 1 << rnd.randrange(8)
        e2 = NativeWindowEngine(64, 32, False, 0)
        try:
            e2.deserialize({"native": bytes(b)})
        except Exception:
            pass  # clean rejection is a pass; only a crash fails
    for cut in range(0, len(blob), max(1, len(blob) // 40)):
        e2 = NativeWindowEngine(64, 32, False, 0)
        try:
            e2.deserialize({"native": bytes(blob[:cut])})
        except Exception:
            pass


# -- a key's state in one place (PR 33) ---------------------------------------
#
# The engine's key states moved (native/window_engine.cpp "A KEY STATE IN ONE
# PLACE": a flat pool, a hot line, a short ring inside the key state, walks
# that run ahead of themselves on a big table).  None of that may change a
# staged byte, a count or a snapshot: every case below was pinned at the
# commit before the move (1d0498c, PR 32), as a digest of everything that
# engine staged in firing order (``test_fold_by_key.drive``) or as the bytes
# of its snapshot, in ``tests/golden/key_state_pr32.json``; regenerate with
# ``python tests/test_native_runtime.py`` in a checkout of the engine to be
# trusted.

import base64  # noqa: E402
import functools  # noqa: E402
import hashlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402

from test_fold_by_key import drive as drive_lane  # noqa: E402
from test_fold_by_key import stream as churn_stream  # noqa: E402

from windflow_tpu.runtime.native import (NativeWindowEngine,  # noqa: E402
                                         get_lib)

KS_GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "golden", "key_state_pr32.json")
KS_WIN, KS_SLIDE = 32768, 16384     # 3,000 keys live: the table outgrows
KS_DELAY = KS_WIN * 3 // 10         # the bound under which no call runs ahead
KS_LAWS = {"live": 0, "ooo": KS_DELAY}


def q5_law(n, delay=0, seed=3):
    """``nexmark_q5_live``'s law at the tests' size, and with ``delay``
    ``nexmark_q5_ooo``'s: arrival ``i`` is the bid created at ``e = i -
    d`` (``d`` 0 nine times in ten, else uniform over 1..delay) on the
    hot auction of its hundred or one of the 111 round the newest; an
    auction lives some 1,700 bids.  Returns keys, stamps, values."""
    rng = np.random.RandomState(seed)
    i = np.arange(n, dtype=np.int64)
    d = np.where(rng.rand(n) < 0.1, rng.randint(1, delay + 1, n), 0) \
        if delay else 0
    e = np.maximum(i - d, 0)
    last = e * 3 // 46
    cold = np.maximum(last - 100, 0) + rng.randint(0, 111, n)
    keys = np.where(rng.rand(n) < 0.5, last // 100 * 100, cold) + 1000
    return keys.astype(np.int64), e, rng.randint(1, 1000, n).astype(float)


def plain_counts(keys, ts, win, slide):
    """{(key, window): count} for every window that holds a tuple of the
    key (``win`` a multiple of ``slide``): the plain recomputation."""
    want = {}
    pane = ts // slide
    for back in range(win // slide):
        w = pane - back
        ok = w >= 0
        kw, n = np.unique(np.stack([keys[ok], w[ok]]), axis=1,
                          return_counts=True)
        for k, x, c in zip(kw[0].tolist(), kw[1].tolist(), n.tolist()):
            want[(k, x)] = want.get((k, x), 0.0) + c
    return want


def ks_lane(law, dense=False):
    return (KS_WIN, KS_SLIDE, True, KS_LAWS[law], False, dense)


def ks_cuts(case, n):
    """Where the calls end.  ``grows_mid_call``: calls of 4,096 until
    the table has outgrown the bound, then one of 100,000 that opens
    6,500 keys, so the table grows in the middle of a call that runs
    ahead; ``crosses_between_calls``: calls of 1,024, so the table
    crosses the bound between two calls with hundreds either side."""
    if case == "grows_mid_call":
        head = list(range(4096, 40961, 4096))
        return head + [140960] + list(range(145056, n, 4096)) + [n]
    assert case == "crosses_between_calls"
    return list(range(1024, n, 1024)) + [n]


def ks_digest(eng, rows, digest):
    s = eng.snapshot()
    return hashlib.sha256(
        f"{digest}:{eng.ignored()}:{s['keys_opened']}:{s['keys_evicted']}:"
        f"{s['windows_fired']}:{s['late_accepted']}:{s['anchors_moved']}:"
        f"{s['folded_by_key']}:{s['folded_singly']}:{len(rows)}"
        .encode()).hexdigest()[:16]


def ks_q5(law, kind, chunking, through=None, dense=False, n=200_000):
    keys, ts, vals = q5_law(n, KS_LAWS[law])
    if isinstance(chunking, str):
        chunking = ks_cuts(chunking, n)
    eng, rows, digest, _ = drive_lane(ks_lane(law, dense), kind, keys, ts,
                                      vals, chunking, through=through)
    return eng, rows, ks_digest(eng, rows, digest)


def ks_long_ring(ppw, kind, tb):
    """A ring of 16 or 64 panes a window (``slide`` 16), far longer than
    a key state holds: three stream shapes under three chunkings."""
    h = hashlib.sha256()
    for shape in ("inorder", "disordered", "late"):
        keys, ts, vals = churn_stream(shape, 3000)
        for chunking in (7, 129, 1 << 30):
            eng, rows, digest, _ = drive_lane(
                (ppw * 16, 16, tb, 40 if tb else 0, False, False), kind,
                keys, ts, vals, min(chunking, 3000))
            h.update(ks_digest(eng, rows, digest).encode())
    return h.hexdigest()[:16]


def ks_reuse(kind):
    """Slots evicted and reused by keys whose rings are of the other
    sort: calls of 400 stamps (a key's tuples of one call span 12 panes
    of 32: its ring leaves the key state) and calls of 20 (it stays) by
    turns, over keys that live 700 stamps."""
    keys, ts, vals = churn_stream("inorder", 9000)
    cuts, at, wide = [], 0, True
    while at < 9000:
        end = min(at + 1500, 9000)
        cuts += list(range(at + (400 if wide else 20), end,
                           400 if wide else 20)) + [end]
        at, wide = end, not wide
    eng, rows, digest, _ = drive_lane((64, 32, True, 0, False, False), kind,
                                      keys, ts, vals, cuts)
    return eng, rows, ks_digest(eng, rows, digest)


def ks_moved_back(kind="count"):
    """A ring that leaves the key state *because* ``move_back`` grew it
    at the front: key 7's first bid to arrive is its latest (pane 12),
    the stragglers of three later calls lie 2, 4 and 9 panes before it,
    none behind a window the stream has passed (``delay`` 320)."""
    clock = 1_000_000
    calls = [([clock, 7], [400, 400]), ([7], [330]), ([7, 7], [200, 210]),
             ([7], [100]), ([clock, 7], [460, 420]), ([clock], [2000])]
    keys = np.concatenate([np.asarray(k, np.int64) for k, _ in calls])
    ts = np.concatenate([np.asarray(t, np.int64) for _, t in calls])
    cuts = np.cumsum([len(k) for k, _ in calls]).tolist()
    eng, rows, digest, _ = drive_lane(
        (64, 32, True, 320, False, False), kind, keys, ts,
        np.arange(1.0, len(ts) + 1), cuts)
    return eng, rows, ks_digest(eng, rows, digest), keys, ts


def ks_synth(kind, dense):
    """``synth_ingest`` over 5,000 keys (a table of 65,536 records)."""
    eng = NativeWindowEngine(64, 32, True, 0, kind=kind, dense=dense)
    digest, n_rows = hashlib.sha256(), 0
    for lo in range(0, 1_000_000, 99_999):
        eng.synth_ingest(lo, min(99_999, 1_000_000 - lo), 5000, 97, 0.5, 1.0)
        if lo > 800_000:
            eng.eos()
        while True:
            out = eng.flush(1 << 30)
            if out is None:
                break
            n_rows += len(out[1])
            for a in (out[0]["value"], *out[1:6]):
                digest.update(np.ascontiguousarray(a).tobytes())
    return ks_digest(eng, range(n_rows), digest.hexdigest())


# name -> (lane, kind, stream, events before the snapshot, flush cap):
# what a snapshot has to carry on each lane, fired windows still queued
# (a partial take) and rings inside and outside the key state among it
KS_SNAPSHOTS = {
    "tb_count_ooo": ((256, 128, True, 40, False, False), "count",
                     "disordered", 1900, 5),
    "tb_mean_long_ring": ((1024, 16, True, 0, False, False), "mean",
                          "inorder", 1500, 1 << 30),
    "tb_dense_max": ((256, 128, True, 0, False, True), "max", "late",
                     2100, 3),
    "cb_sum": ((256, 128, False, 0, False, False), "sum", "inorder", 1700,
               1 << 30),
    "renumbered_min": ((96, 32, True, 0, True, False), "min", "inorder",
                       1300, 2),
}
KS_SNAP_N, KS_SNAP_CHUNK = 3000, 130


def ks_snap_engine(name):
    (win, slide, tb, delay, renum, dense), kind = KS_SNAPSHOTS[name][:2]
    return NativeWindowEngine(win, slide, tb, delay, renumber=renum,
                              kind=kind, dense=dense)


def ks_snap_feed(eng, name, lo, hi, rows):
    """Events [lo, hi) of the snapshot's stream in calls of 130, at most
    the case's cap of windows staged after each (everything, at EOS), each
    window into ``rows`` with its staged panes (a restored engine lists
    its keys in the snapshot's order, so windows of one firing may come
    in another order than from the engine that never stopped)."""
    _lane, _kind, shape, _cut, flush_cap = KS_SNAPSHOTS[name]
    keys, ts, vals = churn_stream(shape, KS_SNAP_N)

    def take(cap):
        out = eng.flush(cap)
        if out is None:
            return False
        cols, starts, ends, d_keys, gwids, rts = out[:6]
        for j in range(len(starts)):
            rows.append((int(d_keys[j]), int(gwids[j]), int(rts[j])) + tuple(
                c[starts[j]:ends[j]].tobytes() for c in cols.values()))
        return True
    for a in range(lo, hi, KS_SNAP_CHUNK):
        b = min(a + KS_SNAP_CHUNK, hi)
        eng.ingest(keys[a:b], ts[a:b], ts[a:b], vals[a:b])
        take(flush_cap)
    if hi == KS_SNAP_N:
        eng.eos()
        while take(1 << 30):
            pass


def ks_rows_digest(rows):
    assert len(set(r[:2] for r in rows)) == len(rows)
    return hashlib.sha256(repr(sorted(rows)).encode()).hexdigest()[:16]


def ks_snapshot(name):
    """The snapshot's bytes mid-stream, and the digest of what the
    engine staged from there to the end of the stream."""
    cut = KS_SNAPSHOTS[name][3]
    eng, after = ks_snap_engine(name), []
    ks_snap_feed(eng, name, 0, cut, [])
    blob = eng.serialize()["native"]
    ks_snap_feed(eng, name, cut, KS_SNAP_N, after)
    return blob, ks_rows_digest(after)


def ks_cases():
    """name -> a function that answers the case's digest."""
    cases = {}
    for law in KS_LAWS:
        for kind in ("count", "max", "sum"):
            for chunking in (4096, 65536):
                cases[f"q5/{law}/{kind}/c{chunking}"] = (
                    lambda law=law, kind=kind, c=chunking:
                    ks_q5(law, kind, c)[2])
        for case in ("grows_mid_call", "crosses_between_calls"):
            cases[f"q5/{law}/count/{case}"] = (
                lambda law=law, case=case: ks_q5(law, "count", case)[2])
        cases[f"q5/{law}/count/dense"] = (
            lambda law=law: ks_q5(law, "count", 4096, dense=True)[2])
    for ppw in (16, 64):
        for kind in ("count", "max", "sum", "mean"):
            for tb in (True, False):
                cases[f"ring/{ppw}/{kind}/{'tb' if tb else 'cb'}"] = (
                    lambda ppw=ppw, kind=kind, tb=tb:
                    ks_long_ring(ppw, kind, tb))
    for kind in ("count", "sum"):
        cases[f"reuse/{kind}"] = lambda kind=kind: ks_reuse(kind)[2]
        for dense in (False, True):
            cases[f"synth/{kind}/{'dense' if dense else 'sparse'}"] = (
                lambda kind=kind, dense=dense: ks_synth(kind, dense))
    cases["moved_back/count"] = lambda: ks_moved_back()[2]
    return cases


def ks_golden():
    with open(KS_GOLDEN) as f:
        return json.load(f)




@pytest.mark.parametrize("case", list(ks_cases()))
def test_the_engine_stages_what_the_parent_engine_staged(case):
    """The live and the ooo law at the tests' size, by key and one by
    one, the table growing inside a call that runs ahead and crossing
    the bound between two; rings of 16 and 64 panes a window on every
    lane that keeps one; slots reused by keys whose ring is of the
    other sort; a ring ``move_back`` pushed out of its key state;
    ``synth_ingest`` and ``dense`` engines: the parent's bytes."""
    assert ks_cases()[case]() == ks_golden()["digests"][case]


@pytest.mark.parametrize("through", [(), ("ids", "ts"), ("keys", "vals")],
                         ids=["all_through", "ids_compact", "ids_through"])
@pytest.mark.parametrize("law", list(KS_LAWS))
def test_through_a_selection_a_big_table_stages_the_same(law, through):
    """``ingest(..., sel)`` where the walk runs ahead: the ids read
    through the rows or compact, the staged bytes are the plain call's."""
    assert ks_q5(law, "count", 4096, through=through)[2] \
        == ks_golden()["digests"][f"q5/{law}/count/c4096"]


@pytest.mark.parametrize("law", list(KS_LAWS))
def test_the_q5_laws_count_every_bid_in_every_window(law):
    """Against the plain recomputation, stragglers included."""
    keys, ts, _vals = q5_law(200_000, KS_LAWS[law])
    eng, rows, _digest = ks_q5(law, "count", 4096)
    got = {(k, w): v for k, w, v, _rts in rows}
    assert len(got) == len(rows) and eng.ignored() == 0
    assert got == plain_counts(keys, ts, KS_WIN, KS_SLIDE)
    s = eng.snapshot()
    assert s["keys_live_peak"] > 2000 and s["rings_spilled"] == 0
    # every call but the first few ran ahead
    assert 0.9 * s["key_touches"] < s["walked_ahead"] < s["key_touches"]


@pytest.mark.parametrize("case", ["grows_mid_call", "crosses_between_calls"])
@pytest.mark.parametrize("law", list(KS_LAWS))
def test_a_call_runs_ahead_where_the_table_has_outgrown_the_bound(law, case):
    """What decides is the table's size as the call begins (16,384
    records: 1,023 live keys made it grow): before that no call runs
    ahead, after it every call does, the one in which the table grows
    again included; the rows either side are the plain recomputation's
    (``test_the_engine_stages_what_the_parent_engine_staged`` holds the
    same calls to the parent's bytes)."""
    n = 200_000
    keys, ts, vals = q5_law(n, KS_LAWS[law])
    eng = NativeWindowEngine(*ks_lane(law)[:4], kind="count")
    got, lo, was, crossed, grew = {}, 0, eng.snapshot(), None, 0

    def drain():
        while (out := eng.flush(1 << 30)) is not None:
            for k, w, a, b in zip(out[3].tolist(), out[4].tolist(),
                                  out[1].tolist(), out[2].tolist()):
                got[(k, w)] = got.get((k, w), 0) + out[0]["value"][a:b].sum()
    for i, hi in enumerate(ks_cuts(case, n)):
        eng.ingest(keys[lo:hi], ts[lo:hi], ts[lo:hi], vals[lo:hi])
        s = eng.snapshot()
        touched = s["key_touches"] - was["key_touches"]
        ahead = s["walked_ahead"] - was["walked_ahead"]
        assert touched == len(np.unique(keys[lo:hi]))
        lo = hi
        if was["keys_live"] < 1023:
            assert ahead == 0 and crossed is None
        else:
            assert ahead == touched > 0
            crossed = i if crossed is None else crossed
            grew += was["keys_live"] < 4095 <= s["keys_live"]
        was = s
        drain()
    eng.eos()
    drain()
    assert crossed is not None and crossed > 0
    assert grew == (case == "grows_mid_call")
    assert got == plain_counts(keys, ts, KS_WIN, KS_SLIDE)
    assert eng.ignored() == 0 and eng.snapshot()["rings_spilled"] == 0


@pytest.mark.parametrize("case,spilled", [
    ("ring16_tb", "all"), ("ring64_cb", "all"), ("cb", "all"),
    ("reuse", "some"), ("tb", "none"), ("renumbered", "none")])
def test_a_ring_leaves_its_key_state_where_it_outgrows_it(case, spilled):
    """``rings_spilled``: every ring of a long window and of a CB lane
    (its two extra lanes have no room inline), some where slots are
    reused by keys of either sort, none on a TB lane of two or three
    panes a window."""
    if case == "reuse":
        eng = ks_reuse("count")[0]
    else:
        lane = {"ring16_tb": (256, 16, True, 40, False, False),
                "ring64_cb": (1024, 16, False, 0, False, False),
                "cb": (256, 128, False, 0, False, False),
                "tb": (256, 128, True, 0, False, False),
                "renumbered": (256, 128, True, 0, True, False)}[case]
        eng = drive_lane(lane, "sum", *churn_stream("inorder", 3000), 129)[0]
    s = eng.snapshot()
    assert s["keys_opened"] > 50 and s["walked_ahead"] == 0
    assert {"all": s["rings_spilled"] == s["keys_opened"],
            "some": 0 < s["rings_spilled"] < s["keys_opened"],
            "none": s["rings_spilled"] == 0}[spilled]


def test_a_ring_move_back_pushed_out_of_its_key_state_keeps_its_panes():
    eng, rows, _digest, keys, ts = ks_moved_back()
    got = {(k, w): v for k, w, v, _rts in rows if k == 7}
    mine = keys == 7
    assert got == plain_counts(keys[mine], ts[mine], 64, 32)
    s = eng.snapshot()
    assert eng.ignored() == 0 and s["anchors_moved"] == 3
    # key 7's, and the clock key's: its stamps jump 48 panes
    assert s["rings_spilled"] == 2


@pytest.mark.parametrize("name", list(KS_SNAPSHOTS))
def test_a_snapshot_is_the_parent_engines_byte_for_byte(name):
    """The same stream leaves the same snapshot as at the parent commit
    (so the parent's library restores this one's), and the parent's
    bytes restore here: the engine stages from them what the engine
    that never stopped did, and writes them out again unchanged."""
    gold = ks_golden()["snapshots"][name]
    theirs = base64.b64decode(gold["blob"])
    mine, after = ks_snapshot(name)
    assert mine == theirs and after == gold["after"]
    eng, rows = ks_snap_engine(name), []
    eng.deserialize({"native": theirs})
    assert eng.serialize()["native"] == theirs
    ks_snap_feed(eng, name, KS_SNAPSHOTS[name][3], KS_SNAP_N, rows)
    assert ks_rows_digest(rows) == gold["after"] and len(rows) > 30


@pytest.mark.parametrize("python_store", [False, True],
                         ids=["native", "python"])
def test_the_three_counts_reach_the_series_the_stats_and_the_metrics_page(
        python_store):
    """``key_touches``, ``walked_ahead``, ``rings_spilled`` where the
    engine's other counts are: the span registry's series (cut at two
    instants by ``touched_between``), ``Spans.Operators[].Counters`` and
    ``/metrics``; the Python store visits no key state and reports none."""
    import windflow_tpu as wf
    from windflow_tpu.core import WinType
    from windflow_tpu.core.tuples import TupleBatch
    from windflow_tpu.graph.fuse import iter_logics
    from windflow_tpu.operators.basic_ops import Sink
    from windflow_tpu.operators.batch_ops import BatchSource
    from windflow_tpu.operators.tpu.farms_tpu import KeyFarmTPU
    from windflow_tpu.telemetry import spans
    from windflow_tpu.telemetry.metrics import render_openmetrics
    n, chunk = (30_000, 2048) if python_store else (200_000, 4096)
    keys, ts, vals = q5_law(n, KS_DELAY)
    sent, rows = {"i": 0}, {}

    def body(ctx=None):
        a = sent["i"]
        if a >= n:
            return None
        sent["i"] = b = min(a + chunk, n)
        return TupleBatch({"key": keys[a:b], "id": ts[a:b], "ts": ts[a:b],
                           "value": vals[a:b]})

    def sink(batch):
        if batch is not None:
            for k, w, v in zip(batch.key.tolist(), batch.id.tolist(),
                               np.asarray(batch["value"]).tolist()):
                rows[(k, w)] = v
    g = wf.PipeGraph(f"key_state_{python_store}", wf.Mode.DEFAULT)
    g.add_source(BatchSource(body)).add(
        KeyFarmTPU("count", KS_WIN, KS_SLIDE, WinType.TB,
                   triggering_delay=KS_DELAY, name="counts",
                   emit_batches=True,
                   value_of=(lambda t: t.value) if python_store else None)
    ).add_sink(Sink(sink, name="sink"))
    logic = next(lg for _, lg in iter_logics(g)
                 if hasattr(lg, "launched_batches"))
    assert (logic._native is None) == python_store
    g.run()
    assert rows == plain_counts(keys, ts, KS_WIN, KS_SLIDE)
    report = json.loads(g.stats.to_json())
    assert report["Schema_version"] >= 17
    counted = [r for r in report["Spans"]["Operators"] if "Counters" in r]
    names = ("key_touches", "walked_ahead", "rings_spilled")
    if python_store:
        assert not any(r["Counters"].get(n_) for r in counted
                       for n_ in names)
        assert all(c.touched_between(0.0, 1e12) == (0, 0)
                   for c in spans.graph(g.name).counters.values())
        return
    c, snap = counted[0]["Counters"], logic._store.snapshot()
    assert {n_: c[n_] for n_ in names} == {n_: snap[n_] for n_ in names}
    assert c["rings_spilled"] == 0
    assert 0.9 * c["key_touches"] < c["walked_ahead"] < c["key_touches"]
    assert c["key_touches"] == sum(
        len(np.unique(keys[a:a + chunk])) for a in range(0, n, chunk))
    kept = spans.graph(g.name).counters[counted[0]["Operator"]]
    assert kept.touched_between(0.0, 1e12) \
        == (c["key_touches"], c["walked_ahead"])
    text = render_openmetrics({"a": {"report": report}})
    for n_ in names:
        assert f"windflow_engine_{n_}_total{{" in text, n_


# -- the engine's clocks of the inside of fold and flush (PR 37) ------------

PARENT_STATS = ("open_ns", "trigger_ns", "evict_ns", "keys_opened",
                "keys_evicted", "keys_live", "keys_live_peak",
                "windows_fired", "folded_by_key", "folded_singly",
                "late_accepted", "anchors_moved", "inputs_ignored",
                "stream_time", "key_touches", "walked_ahead",
                "rings_spilled", "panes_staged", "windows_staged")
CLOCKS = ("ingest_ns", "tuple_walk_ns", "key_walk_ns", "stage_ns",
          "panes_shifted", "copy_out_ns")
ARGMAX_STATS = ("argmax_ns", "max_raised", "tie_ids")


@pytest.mark.parametrize("case,want", [
    ("moved_back", [2, 2, 0, 2, 15, 9, 0, 5, 3, 0, 2000, 8, 0, 2, 22, 15]),
    ("q5_ooo", [3908, 3908, 0, 2931, 6998, 58278, 1722, 5903, 2, 0, 59999,
                6819, 5155, 0, 13208, 6998])])
def test_the_first_nineteen_stats_keep_their_order_and_values(case, want):
    """The nineteen stats the engine had before its clocks came, read by
    name, hold on a fixed stream the counts the engine without the clocks
    read (by position 3..18); the clocks and an argmax engine's own are
    declared in the order the tests name them."""
    from windflow_tpu.telemetry import spans
    assert tuple(spans.ENGINE_CLOCKS) == CLOCKS
    assert tuple(spans.ENGINE_ARGMAX) == ARGMAX_STATS
    eng = ks_moved_back()[0] if case == "moved_back" \
        else ks_q5("ooo", "count", 4096, n=60_000)[0]
    slots = [NativeWindowEngine.STATS.index(n) for n in PARENT_STATS[3:]]
    stats = list(eng.stats())
    assert [stats[i] for i in slots] == want
    assert [eng.snapshot()[n] for n in PARENT_STATS[3:]] == want


def stat_feed(eng, keys, ids, vals, chunk, ts=None):
    """``keys`` in calls of ``chunk``, what fired staged after each call
    but the last."""
    ts = ids if ts is None else ts
    for lo in range(0, len(keys), chunk):
        hi = lo + chunk
        eng.ingest(keys[lo:hi], ids[lo:hi], ts[lo:hi], vals[lo:hi])
        if hi < len(keys):
            while eng.flush(1 << 30) is not None:
                pass
    return eng


@functools.lru_cache(maxsize=1)
def stat_runs():
    """Five engines after known streams, each read once: a Q5-shaped
    count with its generator's delay and the lanes forced on
    (``count``), a ring that left its key state (``moved_back``), one
    key with late tuples (``one_key_late``), an argmax over three keys
    with ties, and a user's function's ``tuples`` engine.  Run ->
    (engine, ``stats()`` as a list, ``snapshot()``)."""
    keys, ts, vals = q5_law(60_000, KS_DELAY)
    count = NativeWindowEngine(KS_WIN, KS_SLIDE, True, KS_DELAY,
                               kind="count")
    get_lib().wfn_engine_lanes_forced(count.ptr, 1)
    stat_feed(count, keys, ts, vals, 4096)
    keys, ts, vals = one_key_stream("late", [20_000])
    late = stat_feed(NativeWindowEngine(256, 128, True, 0, kind="count"),
                     keys, ts, vals, 1000)
    rng = np.random.RandomState(5)
    keys = rng.randint(0, 3, 20_000).astype(np.int64)
    ids = np.arange(20_000, dtype=np.int64)
    vals = rng.randint(1, 3, 20_000).astype(np.float64)
    argmax = stat_feed(NativeWindowEngine(256, 256, True, 0, kind="argmax"),
                       keys, ids, vals, 1000)
    tuples = stat_feed(NativeWindowEngine(40, 1, True, 5, kind="tuples"),
                       keys, ids, vals, 500, ts=ids // 17)
    engines = {"count": count, "moved_back": ks_moved_back()[0],
               "one_key_late": late, "argmax": argmax, "tuples": tuples}
    return {run: (eng, list(eng.stats()), eng.snapshot())
            for run, eng in engines.items()}


def inside_ingest(s, _eng):
    return 0 < s["tuple_walk_ns"] + s["key_walk_ns"] + s["argmax_ns"] \
        <= s["ingest_ns"]


# each engine stat: the run that moves it, and what it holds there (a
# count, or a test of the snapshot and the engine for a clock)
STAT_WANT = {
    "keys_opened": ("count", 3908), "keys_evicted": ("count", 979),
    "keys_live": ("count", 2929), "keys_live_peak": ("count", 2931),
    "windows_fired": ("count", 4353), "folded_by_key": ("count", 58278),
    "folded_singly": ("count", 1722), "late_accepted": ("count", 5903),
    "anchors_moved": ("moved_back", 3),
    "inputs_ignored": ("one_key_late", 186),
    "stream_time": ("count", 59999), "key_touches": ("count", 6819),
    "walked_ahead": ("count", 5155), "rings_spilled": ("moved_back", 2),
    "panes_staged": ("count", 4258), "windows_staged": ("count", 2129),
    "walked_in_lanes": ("count", 16384),
    "ingest_ns": ("count", lambda s, _e: s["ingest_ns"]
                  > s["tuple_walk_ns"] + s["key_walk_ns"]),
    "tuple_walk_ns": ("count", inside_ingest),
    "key_walk_ns": ("count", inside_ingest),
    "stage_ns": ("count", lambda s, _e: s["stage_ns"] > 0),
    "panes_shifted": ("count", 3450),
    "copy_out_ns": ("count", lambda s, e: s["copy_out_ns"]
                    == e.copy_out_ns > 0),
    "argmax_ns": ("argmax", lambda s, _e: s["argmax_ns"] > 0
                  and inside_ingest(s, _e)),
    "max_raised": ("argmax", 359), "tie_ids": ("argmax", 9347),
    "walked_one_key": ("one_key_late", 19_000),
    "tuples_staged": ("tuples", 20976),
}


def _stat_ids():
    from windflow_tpu.telemetry import spans
    return [s.name for s in spans.ENGINE_STATS]


@pytest.mark.parametrize("name", _stat_ids())
def test_each_engine_stat_holds_its_count_in_its_slot_and_on_the_metrics_page(
        name):
    """One case an entry of ``spans.ENGINE_STATS``: the entry's slot of
    ``NativeWindowEngine.STATS`` in the buffer ``stats()`` refills holds
    what ``snapshot()`` reads by name, and that is what the engine
    counted on a known stream (0 on the runs that do not move it); a
    count is on ``/metrics`` under its kind and help text, with that
    value, and the stream time is not."""
    from windflow_tpu.telemetry import spans
    from windflow_tpu.telemetry.metrics import render_openmetrics
    stat, = (s for s in spans.ENGINE_STATS if s.name == name)
    run, want = STAT_WANT[name]
    runs = stat_runs()
    eng, stats, snap = runs[run]
    assert stats[NativeWindowEngine.STATS.index(name)] == snap[name]
    assert want(snap, eng) if callable(want) else snap[name] == want
    if name in ("argmax_ns", "max_raised", "tie_ids", "tuples_staged"):
        assert runs["count"][2][name] == 0
    counted = spans.Counters("op")
    counted.note(0, [snap[n] for n in spans.ENGINE_COUNTERS])
    report = {"PipeGraph_name": "g", "Spans": {"Operators": [
        {"Operator": "op", "Counters": dict(counted.values)}]}}
    text = render_openmetrics({"a": {"report": report}})
    metric = f"windflow_engine_{name}"
    if not stat.kind:
        assert name not in counted.values and metric not in text
        return
    assert f"# TYPE {metric} {stat.kind}\n# HELP {metric} {stat.help} " \
        "(span layer)\n" in text
    total = "_total" if stat.kind == "counter" else ""
    assert f'{metric}{total}{{operator="op",app="a",graph="g"}} ' \
        f"{snap[name]}\n" in text


def test_each_clock_grows_only_in_the_call_it_times():
    """0 before a call; ``ingest_ns`` and its two walks move in a batch
    call and not in a flush, ``stage_ns`` and ``copy_out_ns`` in a flush
    and not in a batch call; the walks lie inside ``ingest_ns`` and that,
    with ``open`` and ``trigger``, inside the wall time round the calls;
    the record-at-a-time lane (``synth_ingest``) has no clock."""
    import time
    keys, ts, vals = q5_law(120_000, KS_DELAY)
    eng = NativeWindowEngine(KS_WIN, KS_SLIDE, True, KS_DELAY, kind="count")
    assert not any(eng.snapshot()[n] for n in CLOCKS)
    wall = 0
    for lo in range(0, len(keys), 4096):
        before = eng.snapshot()
        t0 = time.perf_counter_ns()
        ready = eng.ingest(keys[lo:lo + 4096], ts[lo:lo + 4096],
                           ts[lo:lo + 4096], vals[lo:lo + 4096])
        wall += time.perf_counter_ns() - t0
        mid = eng.snapshot()
        for n in ("ingest_ns", "tuple_walk_ns", "key_walk_ns"):
            assert mid[n] > before[n], n
        for n in ("stage_ns", "copy_out_ns", "panes_shifted", "evict_ns"):
            assert mid[n] == before[n], n
        if not ready:
            continue
        t0 = time.perf_counter_ns()
        out = eng.flush(1 << 30)
        flush_wall = time.perf_counter_ns() - t0
        after = eng.snapshot()
        assert out is not None
        assert after["stage_ns"] > mid["stage_ns"]
        assert after["copy_out_ns"] > mid["copy_out_ns"]
        assert after["stage_ns"] - mid["stage_ns"] \
            + after["copy_out_ns"] - mid["copy_out_ns"] \
            + after["evict_ns"] - mid["evict_ns"] <= flush_wall
        for n in ("ingest_ns", "tuple_walk_ns", "key_walk_ns", "open_ns",
                  "trigger_ns"):
            assert after[n] == mid[n], n
    s = eng.snapshot()
    assert s["windows_staged"] > 1000 and s["panes_shifted"] > 0
    assert 0 < s["tuple_walk_ns"] + s["key_walk_ns"] <= s["ingest_ns"]
    assert s["ingest_ns"] + s["open_ns"] + s["trigger_ns"] <= wall
    # the wrapper's own clock: an attribute, mirrored into its slot of
    # the buffer the engine refills
    slot = NativeWindowEngine.STATS.index("copy_out_ns")
    assert eng.stats()[slot] == eng.stats()[slot] == eng.copy_out_ns \
        == s["copy_out_ns"] > 0
    assert eng.flush(1 << 30) is None       # nothing ready: no clock read
    assert eng.snapshot()["stage_ns"] == s["stage_ns"]
    synth = NativeWindowEngine(64, 32, True, 0, kind="sum")
    synth.synth_ingest(0, 10_000, 5)
    assert synth.snapshot()["windows_fired"] > 0
    assert not any(synth.snapshot()[n] for n in CLOCKS[:3])


def test_panes_shifted_is_the_ring_elements_retire_moved_down():
    """Two keys, windows of four panes of 10 sliding by one, no delay.
    Key 1 has a tuple in every pane 0..7, key 2 in panes 2..7 (its
    anchor is window 0 too: panes 2 and 3 lie in it); a ring grown to
    hold pane 7 is ``7 + 1 + (7 // 2 + 8) = 19`` elements long with its
    headroom (``grow_ring``).  The tuple at 79 makes the stream pass
    windows 0..3; the flush stages them for both keys, each key fires
    window 4 next, so its ring drops panes 0..3 and moves the 15
    elements behind them down.  The second firing (a tuple at 119 passes
    windows 4..7) finds key 1's ring at pane 4, 15 long, pane 11 inside
    it: it drops 4..7 and moves 11 down; key 2 has nothing opened and is
    evicted, which moves nothing."""
    eng = NativeWindowEngine(40, 10, True, 0, kind="count")

    def ingest(keys, ts):
        ts = np.asarray(ts, np.int64)
        return eng.ingest(np.asarray(keys, np.int64), ts, ts,
                          np.ones(len(ts)))
    ready = ingest([1] * 8 + [2] * 6,
                   [5, 15, 25, 35, 45, 55, 65, 79, 25, 35, 45, 55, 65, 75])
    assert ready == 8 and eng.snapshot()["panes_shifted"] == 0
    assert len(eng.flush(1 << 30)[1]) == 8
    assert eng.snapshot()["panes_shifted"] == 15 + 15
    assert ingest([1] * 4, [85, 95, 105, 119]) == 8   # 4..7 of both keys
    assert len(eng.flush(1 << 30)[1]) == 8
    s = eng.snapshot()
    assert s["panes_shifted"] == 15 + 15 + 11
    assert (s["keys_evicted"], s["keys_live"]) == (1, 1)


def test_the_clocks_are_no_part_of_the_state():
    """A snapshot restores the engine's counts and none of its clocks:
    the serialized bytes are the parent's (the golden snapshots above),
    and an engine restored from them starts its clocks at 0."""
    name = next(iter(KS_SNAPSHOTS))
    eng, rows = ks_snap_engine(name), []
    ks_snap_feed(eng, name, 0, KS_SNAPSHOTS[name][3], rows)
    before = eng.snapshot()
    assert before["ingest_ns"] > 0 and before["keys_opened"] > 0
    twin = ks_snap_engine(name)
    twin.deserialize(eng.serialize())
    after = twin.snapshot()
    assert after["keys_opened"] == before["keys_opened"]
    assert after["keys_live"] == before["keys_live"]
    assert not any(after[n] for n in CLOCKS)
    assert twin.serialize()["native"] == eng.serialize()["native"]


# -- the tuple walk in slices, on the host lanes (PR 39) ---------------------

import ctypes  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402

from test_fold_by_key import scattered  # noqa: E402

from windflow_tpu.runtime.native import get_lib  # noqa: E402

LANES_FROM = 49152                   # Engine::LANES_FROM
SPLIT_CHUNKS = (LANES_FROM - 1, LANES_FROM, LANES_FROM + 1, 50_000)


def lanes_info():
    """(helper threads of this process's pool, their CPU ns, the lanes
    the rule gives this process, the shortest call it splits)."""
    out = (ctypes.c_longlong * 4)()
    get_lib().wfn_lanes_info(out)
    return list(out)


def last_call(eng, n):
    """What the engine's last call of ``n`` tuples left in its per-call
    arrays (``wfn_engine_last_call``): per key in the order of first
    touch (key, count, lo, hi, the extreme's bits, pool slot), slot_of,
    the keys opened."""
    lib = get_lib()
    need = -lib.wfn_engine_last_call(eng.ptr, n, None, 0)
    buf = (ctypes.c_longlong * need)()
    assert lib.wfn_engine_last_call(eng.ptr, n, buf, need) == need
    got = np.frombuffer(buf, np.int64)
    nd = int(got[0])
    keys = got[1:1 + 6 * nd].reshape(nd, 6)
    return keys, got[1 + 6 * nd:1 + 6 * nd + n], got[2 + 6 * nd + n:]


def split_stream(name):
    """(lane, kind, keys, ids, values, chunks through a selection): a
    Q5-shaped stream (111 keys, one hot); a YSB-shaped one, a view in
    three of 100 campaigns' events through a selection; late events
    (``note_late`` marks them, fold_singly drops or folds them); a MEAN,
    which folds one by one; keys opened inside every call (a generation
    of 13 keys every 700 events) under a MAX."""
    rng = np.random.RandomState(11)
    n = 4 * 50_000 + 17
    if name in ("q5", "mean_singly", "ysb_selected"):
        keys = np.where(rng.rand(n) < 0.5, 7, rng.randint(0, 111, n))
        if name == "ysb_selected":
            keys = rng.randint(0, 1000, n) // 10
        ts = np.arange(n, dtype=np.int64)
        vals = rng.randint(1, 1000, n).astype(np.float64)
        kind = "mean" if name == "mean_singly" else "count"
        return ((1 << 15, 1 << 14, True, 0, False, False), kind,
                keys.astype(np.int64), ts, vals, name == "ysb_selected")
    shape, kind = {"late": ("late", "count"),
                   "opens_mid_call": ("inorder", "max")}[name]
    keys, ts, vals = churn_stream(shape, n)
    return (256, 128, True, 40, False, False), kind, keys, ts, vals, False


def split_drive(name, chunk, mode):
    """The stream in calls of ``chunk`` under the lanes' rule held to
    never (0), always (1) or left to decide (-1): per call what
    :func:`last_call` reads and the snapshot without its clocks, and
    every staged byte."""
    lane, kind, keys, ids, vals, selected = split_stream(name)
    eng = NativeWindowEngine(*lane[:4], renumber=lane[4], kind=kind,
                             dense=lane[5])
    get_lib().wfn_engine_lanes_forced(eng.ptr, mode)
    rng = np.random.RandomState(5)
    calls, staged, sizes = [], hashlib.sha256(), []

    def take():
        while (out := eng.flush(1 << 30)) is not None:
            for a in (*out[0].values(), *out[1:6]):
                staged.update(np.ascontiguousarray(a).tobytes())
    for lo in range(0, len(keys), chunk):
        hi = min(lo + chunk, len(keys))
        if selected:
            c, sel = scattered(rng, {"keys": keys[lo:hi], "ids": ids[lo:hi],
                                     "ts": ids[lo:hi], "vals": vals[lo:hi]})
            eng.ingest(c["keys"], c["ids"], c["ts"], c["vals"], sel)
        else:
            eng.ingest(keys[lo:hi], ids[lo:hi], ids[lo:hi], vals[lo:hi])
        parts, slots, opened = last_call(eng, hi - lo)
        snap = {k: v for k, v in eng.snapshot().items()
                if k not in CLOCKS and not k.endswith("_ns")
                and k != "walked_in_lanes"}
        calls.append((parts.tobytes(), slots.tobytes(), opened.tobytes(),
                      snap))
        sizes.append(hi - lo)
        take()
    eng.eos()
    take()
    assert eng.snapshot()["walked_ahead"] == 0   # the table fits, always
    return calls, staged.hexdigest(), eng.snapshot()["walked_in_lanes"], \
        sizes


@pytest.mark.parametrize("chunk", SPLIT_CHUNKS)
@pytest.mark.parametrize("name", ["q5", "ysb_selected", "late",
                                  "mean_singly", "opens_mid_call"])
def test_a_walk_in_slices_leaves_what_the_one_walk_leaves(name, chunk):
    """Lanes forced off, forced on, and left to the rule, on calls just
    under, at and over the shortest the rule splits: every call's
    partials, their first-touch order and pool slots, slot_of (late
    marks included), the keys opened, the snapshot, and every byte
    staged are the one walk's; ``walked_in_lanes`` counts the tuples of
    the calls split, which under the rule are some of those at or over
    ``LANES_FROM`` (the ones that came within ``LANES_GAP_NS`` of the
    last: this test's Python between two calls may take longer) where
    the process may have helpers."""
    assert lanes_info()[3] == LANES_FROM
    one, on, rule = (split_drive(name, chunk, mode) for mode in (0, 1, -1))
    for got in (on, rule):
        assert got[1] == one[1]
        for c, (a, b) in enumerate(zip(got[0], one[0])):
            assert a == b, (name, chunk, c)
    sizes = one[3]
    assert one[2] == 0
    assert on[2] == sum(sizes)
    assert rule[2] <= (sum(n for n in sizes[1:] if n >= LANES_FROM)
                       if lanes_info()[2] > 1 else 0)


LANES_SCRIPT = r"""
import ctypes, hashlib, json, os, sys, time
import numpy as np
from windflow_tpu.runtime.native import NativeWindowEngine, get_lib
lib = get_lib()

def info():
    out = (ctypes.c_longlong * 4)()
    lib.wfn_lanes_info(out)
    return list(out)

def tasks():
    return len(os.listdir("/proc/self/task"))

def run(chunk, mode, n=200_000):
    rng = np.random.RandomState(1)
    keys = np.where(rng.rand(n) < .5, 7, rng.randint(0, 111, n))
    keys = keys.astype(np.int64)
    ts = np.arange(n, dtype=np.int64)
    eng = NativeWindowEngine(1 << 15, 1 << 14, True, 0, kind="count")
    lib.wfn_engine_lanes_forced(eng.ptr, mode)
    h = hashlib.sha256()
    for a in range(0, n, chunk):
        eng.ingest(keys[a:a + chunk], ts[a:a + chunk], ts[a:a + chunk],
                   np.ones(len(keys[a:a + chunk])))
        while (out := eng.flush(1 << 30)) is not None:
            for c in (out[0]["value"], *out[1:6]):
                h.update(np.ascontiguousarray(c).tobytes())
    return h.hexdigest(), eng.snapshot()["walked_in_lanes"]

got = {"lanes": info()[2], "tasks0": tasks(), "info0": info()}
got["short"] = run(4096, -1)     # shorter than any call the rule splits
got["tasks1"], got["info1"] = tasks(), info()
got["long"] = run(65536, 1)      # every call split (under the rule, a
#                                  call that follows the last by over a
#                                  millisecond would walk alone)
got["tasks2"], got["info2"] = tasks(), info()
if sys.argv[1] == "asleep":
    time.sleep(0.05)
    c0 = info()[1]
    time.sleep(0.2)
    got["asleep_ns"] = info()[1] - c0
elif sys.argv[1] == "fork":
    pid = os.fork()
    if pid == 0:
        os._exit(0 if run(65536, 1) == got["long"] else 3)
    for _ in range(600):
        done, status = os.waitpid(pid, os.WNOHANG)
        if done:
            got["child"] = os.waitstatus_to_exitcode(status)
            break
        time.sleep(0.1)
    else:
        os.kill(pid, 9)
        got["child"] = "hung"
got["at"] = time.monotonic()
print(json.dumps(got), flush=True)
"""


def lanes_script(what):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=root, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", LANES_SCRIPT, what], env=env,
                       capture_output=True, text=True, timeout=120)
    ended = time.monotonic()
    assert p.returncode == 0, p.stderr[-2000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    if got["lanes"] < 2:
        pytest.skip("the process may run on fewer than three cores")
    return got, ended


def test_no_lane_thread_exists_before_a_call_that_engages_them():
    """Calls shorter than ``LANES_FROM`` start no thread; the first call
    that engages the lanes starts the pool's helpers, and only them."""
    got, _ = lanes_script("threads")
    assert got["info0"][0] == got["info1"][0] == 0
    assert got["tasks1"] == got["tasks0"]
    assert got["short"][1] == 0 and got["long"][1] == 200_000
    helpers = got["info2"][0]
    assert helpers == got["lanes"] - 1
    assert got["tasks2"] == got["tasks0"] + helpers


def test_lanes_without_a_call_sleep():
    """After a call, the helpers spin some microseconds and sleep: 200 ms
    of no call cost them (nearly) no CPU."""
    got, _ = lanes_script("asleep")
    assert got["info2"][1] > 0
    assert got["asleep_ns"] < 2_000_000


def test_a_forked_child_walks_with_lanes_of_its_own():
    """A child forked with the parent's lanes alive never waits on them:
    its calls complete with the parent's answer."""
    got, _ = lanes_script("fork")
    assert got["child"] == 0


def test_the_interpreter_exits_promptly_with_lanes_alive():
    """The helpers are stopped and joined at exit: the process ends as
    soon after its last line as a process without them."""
    got, ended = lanes_script("exit")
    assert got["info2"][0] > 0
    assert ended - got["at"] < 5.0



# -- the one-key walk -------------------------------------------------------
#
# A call whose tuples all carry one key, after a call that touched one key,
# on a lane that folds by key, is walked once with no probe (docs/RUNTIME.md
# 5b).  Its twin here is the same engine fed the same calls with an empty
# call in front of each: an empty call changes no row, no count and no byte
# of a snapshot, and leaves the engine's last call touching no key, so every
# call of the twin takes the general walks.

import dataclasses  # noqa: E402

from test_fold_by_key import LANES as FOLD_LANES  # noqa: E402
from test_fold_by_key import owed  # noqa: E402

ONE_KEY_LANES = ("tb", "tb_tumbling", "tb_delay", "tb_odd_pane", "tb_dense",
                 "cb")
ONE_KEY_SHAPES = ("inorder", "late", "stray_first", "stray_mid",
                  "stray_last")
ONE_KEY_N = 3000


def one_key_stream(shape, cuts, seed=13):
    """One key (5) on one clock, values from five whole numbers (ties at
    every extreme); ``late`` sends one tuple in 23 some 300 stamps back
    (behind the stream time: accepted where its window has not fired,
    dropped where it has); ``stray_*`` puts a tuple of key 6 at the
    first, middle or last tuple of every third call."""
    n = cuts[-1]
    rng = np.random.RandomState(seed)
    ts = np.arange(n, dtype=np.int64)
    keys = np.full(n, 5, np.int64)
    vals = rng.randint(1, 6, n).astype(np.float64)
    if shape == "late":
        back = rng.randint(0, 23, n) == 0
        ts = np.where(back, np.maximum(ts - 300, 0), ts)
    elif shape.startswith("stray_"):
        lo = 0
        for i, hi in enumerate(cuts):
            if i % 3 == 1:
                at = {"first": lo, "mid": (lo + hi) // 2,
                      "last": hi - 1}[shape[len("stray_"):]]
                keys[at] = 6
            lo = hi
    else:
        assert shape == "inorder"
    return keys, ts, vals


def one_key_digest(h, item):
    """Every array of what a flush handed over, into ``h``."""
    if isinstance(item, dict):
        for v in item.values():
            one_key_digest(h, v)
    elif isinstance(item, (tuple, list)):
        for v in item:
            one_key_digest(h, v)
    elif isinstance(item, np.ndarray):
        h.update(np.ascontiguousarray(item).tobytes())
    elif dataclasses.is_dataclass(item):
        one_key_digest(h, [getattr(item, f.name)
                           for f in dataclasses.fields(item)])
    else:
        h.update(repr(item).encode())


def one_key_pair(lane, kind, keys, ids, vals, cuts, tss=None, through=None,
                 snap_every=4):
    """The calls ``cuts`` make of the stream into an engine and into its
    twin (an empty call in front of each: the general walks), every ready
    window staged after each call and at EOS.  Per call: what
    :func:`last_call` reads, the counts (clocks and ``walked_one_key``
    aside) and every staged byte are the twin's; every ``snap_every``
    calls and at the end, the snapshot's bytes.  Returns the engine and,
    per call, how far its ``walked_one_key`` moved.  ``tss``: the column
    handed over as stamps (an argmax engine's tuple ids on TB)."""
    win, slide, is_tb, delay, renumber, dense = FOLD_LANES.get(lane, lane)
    tss = ids if tss is None else tss
    engs = [NativeWindowEngine(win, slide, is_tb, delay, renumber=renumber,
                               kind=kind, dense=dense) for _ in range(2)]
    empty = np.empty(0, np.int64)
    rng = np.random.RandomState(len(keys))
    hashes = [hashlib.sha256(), hashlib.sha256()]
    moved, lo = [], 0
    aside = set(CLOCKS) | {"walked_one_key"}

    def take(i):
        while (out := engs[i].flush(1 << 30)) is not None:
            one_key_digest(hashes[i], out)
    for c, hi in enumerate(cuts):
        cols = {"keys": keys[lo:hi], "ids": ids[lo:hi], "ts": tss[lo:hi],
                "vals": vals[lo:hi]}
        sel = None
        if through is not None:
            cols, sel = scattered(rng, cols, through)
        before = engs[0].snapshot()["walked_one_key"]
        engs[1].ingest(empty, empty, empty, np.empty(0))
        for eng in engs:
            eng.ingest(cols["keys"], cols["ids"], cols["ts"], cols["vals"],
                       sel)
        moved.append(engs[0].snapshot()["walked_one_key"] - before)
        got = [last_call(eng, hi - lo) for eng in engs]
        for a, b in zip(*got):
            assert a.tobytes() == b.tobytes(), (lane, kind, c)
        snaps = [{k: v for k, v in eng.snapshot().items()
                  if k not in aside and not k.endswith("_ns")}
                 for eng in engs]
        assert snaps[0] == snaps[1], (lane, kind, c)
        for i in (0, 1):
            take(i)
        assert hashes[0].hexdigest() == hashes[1].hexdigest(), (lane, kind, c)
        if c % snap_every == snap_every - 1:
            assert engs[0].serialize() == engs[1].serialize(), (lane, kind, c)
        lo = hi
    for i in (0, 1):
        engs[i].eos()
        take(i)
    assert hashes[0].hexdigest() == hashes[1].hexdigest()
    assert engs[0].serialize() == engs[1].serialize()
    assert engs[1].snapshot()["walked_one_key"] == 0
    return engs[0], moved


def one_key_owed_moves(keys, cuts, kind, is_tb):
    """What the rule admits: a call of one key after a call of one key, on
    a lane that folds by key (TB; COUNT, MAX, MIN, ARGMAX), all its
    tuples; else nothing."""
    by_key = is_tb and kind in ("count", "max", "min", "argmax")
    want, lo, before = [], 0, 0
    for hi in cuts:
        now = len(np.unique(keys[lo:hi]))
        want.append(hi - lo if by_key and before == 1 and now == 1 else 0)
        before, lo = now, hi
    return want


@pytest.mark.parametrize("chunk", [64, 129], ids=["in_a_pane", "straddles"])
@pytest.mark.parametrize("shape", ONE_KEY_SHAPES)
@pytest.mark.parametrize("lane", ONE_KEY_LANES)
@pytest.mark.parametrize("kind", ["count", "max", "min"])
def test_the_one_key_walk_leaves_what_the_general_walk_leaves(kind, lane,
                                                              shape, chunk):
    """COUNT, MAX and MIN on time- and count-based lanes: calls inside a
    pane (folded by key) and calls that straddle a pane edge (one by
    one), in order and with late tuples, and a stray key at the first, a
    middle and the last tuple of a call (the walk gives up): per call the
    partials, slot_of, the counts and every staged byte the general
    walk's, snapshots byte for byte; ``walked_one_key`` moves by a call's
    tuples where the rule admits the call and not otherwise (never the
    first call, never a count-based lane); the rows are the plain
    recomputation's."""
    cuts = list(range(chunk, ONE_KEY_N, chunk)) + [ONE_KEY_N]
    keys, ts, vals = one_key_stream(shape, cuts)
    eng, moved = one_key_pair(lane, kind, keys, ts, vals, cuts)
    is_tb = FOLD_LANES[lane][2]
    assert moved == one_key_owed_moves(keys, cuts, kind, is_tb)
    assert moved[0] == 0
    assert (sum(moved) > ONE_KEY_N // 4) == is_tb
    if not is_tb or FOLD_LANES[lane][5] or shape == "late":
        return     # CB windows count a key's own ids, a dense lane emits
        #            empty windows too, late tuples drop
    win, slide = FOLD_LANES[lane][:2]
    _eng, rows, _digest, _ = drive_lane(lane, kind, keys, ts, vals, cuts)
    want = owed(keys, ts, vals, win, slide, kind)
    got = {(k, w): v for k, w, v, _rts in rows}
    assert len(got) == len(rows) and got == want


@pytest.mark.parametrize("through", [(), ("ids", "ts"), ("keys", "vals")],
                         ids=["all_through", "ids_compact", "keys_compact"])
@pytest.mark.parametrize("kind", ["count", "max", "min"])
def test_through_a_selection_the_one_key_walk_leaves_the_same(kind, through):
    """A selected batch (its rows read through the selection, or compact
    columns laid over it), late tuples among them: the general walk's
    state and bytes, and the one-key walk takes the calls it takes on the
    plain columns."""
    cuts = list(range(129, ONE_KEY_N, 129)) + [ONE_KEY_N]
    keys, ts, vals = one_key_stream("late", cuts)
    _eng, moved = one_key_pair("tb_delay", kind, keys, ts, vals, cuts,
                               through=through)
    assert moved == one_key_owed_moves(keys, cuts, kind, True)


def test_one_key_calls_of_one_tuple_and_the_counter_in_a_graph():
    """A record at a time (calls of one tuple: every one after the first
    on the one-key walk), then ``walked_one_key`` where the engine's other
    counts are: the span registry's series, ``Spans.Operators[].Counters``
    and ``/metrics``."""
    import windflow_tpu as wf
    from windflow_tpu.core import WinType
    from windflow_tpu.core.tuples import TupleBatch
    from windflow_tpu.operators.basic_ops import Sink
    from windflow_tpu.operators.batch_ops import BatchSource
    from windflow_tpu.operators.tpu.win_seq_tpu import WinSeqTPU
    from windflow_tpu.telemetry import spans
    from windflow_tpu.telemetry.metrics import render_openmetrics
    keys, ts, vals = one_key_stream("inorder", [400])
    _eng, moved = one_key_pair("tb", "count", keys, ts, vals,
                               list(range(1, 401)))
    assert moved == [0] + [1] * 399
    n, chunk = 40_000, 1000
    keys, ts, vals = one_key_stream("inorder", [n])
    sent, rows = {"i": 0}, {}

    def body(ctx=None):
        a = sent["i"]
        if a >= n:
            return None
        sent["i"] = b = min(a + chunk, n)
        return TupleBatch({"key": keys[a:b], "id": ts[a:b], "ts": ts[a:b],
                           "value": vals[a:b]})

    def sink(batch):
        if batch is not None:
            for k, w, v in zip(batch.key.tolist(), batch.id.tolist(),
                               np.asarray(batch["value"]).tolist()):
                rows[(k, w)] = v
    g = wf.PipeGraph("one_key_counter", wf.Mode.DEFAULT)
    g.add_source(BatchSource(body)).add(
        WinSeqTPU("max", 4096, 4096, WinType.TB, name="top",
                  emit_batches=True)).add_sink(Sink(sink, name="sink"))
    g.run()
    assert rows == owed(keys, ts, vals, 4096, 4096, "max")
    report = json.loads(g.stats.to_json())
    assert report["Schema_version"] >= 23
    counted = [r for r in report["Spans"]["Operators"] if "Counters" in r]
    c = counted[0]["Counters"]
    # every call but the first, whose engine had no last call
    assert c["walked_one_key"] == n - chunk
    kept = spans.graph(g.name).counters[counted[0]["Operator"]]
    assert kept.between(("walked_one_key",), 0.0, 1e12) == (n - chunk,)
    text = render_openmetrics({"a": {"report": report}})
    assert "windflow_engine_walked_one_key_total{" in text


if __name__ == "__main__":
    gold = {"digests": {n: f() for n, f in ks_cases().items()},
            "snapshots": {}}
    for name in KS_SNAPSHOTS:
        blob, after = ks_snapshot(name)
        gold["snapshots"][name] = {
            "blob": base64.b64encode(blob).decode(), "after": after}
    with open(KS_GOLDEN, "w") as f:
        json.dump(gold, f, indent=1)
    print(f"{len(gold['digests'])} digests, {len(gold['snapshots'])} "
          f"snapshots -> {KS_GOLDEN}")
