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
