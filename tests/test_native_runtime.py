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
import hashlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402

from test_fold_by_key import drive as drive_lane  # noqa: E402
from test_fold_by_key import stream as churn_stream  # noqa: E402

from windflow_tpu.runtime.native import NativeWindowEngine  # noqa: E402

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


@pytest.mark.parametrize("case,want", [
    ("moved_back", [2, 2, 0, 2, 15, 9, 0, 5, 3, 0, 2000, 8, 0, 2, 22, 15]),
    ("q5_ooo", [3908, 3908, 0, 2931, 6998, 58278, 1722, 5903, 2, 0, 59999,
                6819, 5155, 0, 13208, 6998])])
def test_the_first_nineteen_stats_keep_their_order_and_values(case, want):
    """``_account_churn`` slices ``STATS`` by position: the new clocks
    come behind the nineteen, whose counts on a fixed stream are the
    ones the engine of the commit before read (PR 37's parent, by
    position 3..18)."""
    from windflow_tpu.telemetry import spans
    assert NativeWindowEngine.STATS[:19] == PARENT_STATS
    assert NativeWindowEngine.STATS[19:] == CLOCKS == tuple(
        spans.ENGINE_CLOCKS)
    eng = ks_moved_back()[0] if case == "moved_back" \
        else ks_q5("ooo", "count", 4096, n=60_000)[0]
    assert list(eng.stats())[3:19] == want
    assert [eng.snapshot()[n] for n in PARENT_STATS[3:]] == want


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
    # the wrapper's own clock: an attribute, mirrored into the slot of
    # the buffer behind the twenty-four the engine refills
    assert NativeWindowEngine.STATS.index("copy_out_ns") == 24
    assert eng.stats()[24] == eng.stats()[24] == eng.copy_out_ns \
        == s["copy_out_ns"] > 0
    assert list(eng.stats())[19:24] == [s[n] for n in CLOCKS[:5]]
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
