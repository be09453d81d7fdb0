"""A filtered batch carries its selection (core/tuples.py, PR 31).

``TupleBatch.take`` of a mask or of scattered indices copies no column:
the batch holds the base columns and the rows, and a column is gathered
when it is read.  What that may never change is what a reader sees: here
every accessor of a selected batch is held to what the eager gather of
the commit before gave (``eager_take`` below is that commit's ``take``),
the native mask-to-rows pass to ``np.nonzero``, and a pooled base buffer
to the pool's own rule: not lent again while a batch that reads it lives.
"""
import copy
import pickle

import numpy as np
import pytest

import windflow_tpu as wf
from windflow_tpu.core.basic import OptLevel, RuntimeConfig
from windflow_tpu.core.tuples import BasicRecord, ColumnPool, TupleBatch
from windflow_tpu.operators.basic_ops import Sink
from windflow_tpu.operators.batch_ops import (BatchFilter, BatchMap,
                                              BatchSource)
from windflow_tpu.operators.tpu.farms_tpu import KeyFarmTPU
from windflow_tpu.runtime import native
from windflow_tpu.telemetry import spans

N = 1000


def batch(n=N, seed=3, strided=False):
    rng = np.random.RandomState(seed)
    m = 2 * n if strided else n
    cols = {"key": rng.randint(0, 50, m).astype(np.int64),
            "id": np.arange(m, dtype=np.int64),
            "ts": np.arange(m, dtype=np.int64) * 3,
            "value": rng.rand(m),
            "event_type": rng.randint(0, 3, m).astype(np.int64)}
    if strided:     # every column a strided view of a buffer twice as long
        cols = {k: v[::2] for k, v in cols.items()}
    return TupleBatch(cols)


def eager_take(cols, idx):
    """``TupleBatch.take`` as the commit before had it, on a dict of
    columns: the rows gathered from every column at once."""
    if isinstance(idx, slice):
        return {k: v[idx] for k, v in cols.items()}
    idx = np.asarray(idx)
    if idx.dtype == np.bool_:
        idx = np.nonzero(idx)[0]
    elif idx.size == 0:
        idx = idx.astype(np.intp)
    return {k: np.take(v, idx, axis=0) for k, v in cols.items()}


def same(got, want):
    assert list(got) == list(want)
    for k in want:
        assert got[k].dtype == want[k].dtype, k
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)


def mask_of(shape, n=N, dtype=np.bool_):
    m = np.zeros(n, np.bool_)
    if shape == "full":
        m[:] = True
    elif shape == "run":
        m[n // 4: n // 2] = True
    elif shape == "third":
        m = np.random.RandomState(11).randint(0, 3, n) == 0
    elif shape == "one":
        m[n // 3] = True
    elif shape == "two_apart":
        m[[5, n - 5]] = True
    else:
        assert shape == "empty"
    return m.astype(dtype)


MASKS = ("empty", "full", "run", "third", "one", "two_apart")
INDEXES = {
    "none": [],
    "scattered": [7, 3, 900, 3, 12],
    "ascending": [1, 5, 6, 100, 999],
    "run": list(range(40, 90)),
    "negative": [-1, 0, -N, 17],
    "int32": np.array([9, 2, 4], np.int32),
    "uint8": np.array([1, 0, 1, 1, 200], np.uint8),   # indices, not a mask
    "permutation": np.random.RandomState(5).permutation(N),
    "longer": np.r_[np.arange(N), np.arange(N)],
}


# -- every accessor against the eager result ---------------------------------

def check_accessors(got, want, trace=None):
    """``got`` (a batch, selected or not) reads as the columns ``want``
    in every way a batch can be read.  Accessors that do not gather come
    first, ``cols`` (which compacts) last."""
    n = len(want["key"])
    assert len(got) == n
    assert got.names() == list(want)
    assert got.payload_names() == [c for c in want
                                   if c not in TupleBatch.CONTROL]
    assert f"n={n}" in repr(got) and "event_type" in repr(got)
    assert getattr(got, "trace", None) is trace
    np.testing.assert_array_equal(got.ts, want["ts"])
    np.testing.assert_array_equal(got["value"], want["value"])
    assert got["value"] is got["value"]          # gathered once, kept
    with pytest.raises(KeyError):
        got["no_such_column"]
    # with_cols lays a compact column over it and carries the rest on
    laid = got.with_cols(key=want["key"] + 1, extra=np.arange(n))
    assert len(laid) == n and getattr(laid, "trace", None) is trace
    np.testing.assert_array_equal(laid.key, want["key"] + 1)
    np.testing.assert_array_equal(laid.id, want["id"])
    same(laid.cols, {**want, "key": want["key"] + 1, "extra": np.arange(n)})
    with pytest.raises(ValueError):
        got.with_cols(value=np.zeros(n + 1))
    # concat, both ways round
    other = TupleBatch({k: v[:3] for k, v in want.items()})
    same(got.concat(other).cols,
         {k: np.concatenate([v, v[:3]]) for k, v in want.items()})
    same(other.concat(got).cols,
         {k: np.concatenate([v[:3], v]) for k, v in want.items()})
    recs = list(got.records())
    assert len(recs) == n
    for r, k, i, t, v in zip(recs[:5], want["key"], want["id"], want["ts"],
                             want["value"]):
        assert isinstance(r, BasicRecord)
        assert (r.key, r.id, r.ts, r.value) == (k, i, t, v)
    np.testing.assert_array_equal(got.key, want["key"])
    np.testing.assert_array_equal(got.id, want["id"])
    same(got.cols, want)
    assert got.selection is None                 # `cols` compacted it
    same(TupleBatch.empty_like(got).cols, {k: v[:0] for k, v in want.items()})


@pytest.mark.parametrize("strided", [False, True], ids=["plain", "strided"])
@pytest.mark.parametrize("dtype", [np.bool_], ids=["bool"])
@pytest.mark.parametrize("shape", MASKS)
def test_a_mask_take_reads_as_the_eager_gather(shape, dtype, strided):
    native.get_lib()        # the native pass where there is a library
    b = batch(strided=strided)
    mask = mask_of(shape, dtype=dtype)
    check_accessors(b.take(mask), eager_take(b.cols, mask))
    # a mask that is itself a strided view
    wide = np.zeros(2 * N, np.bool_)
    wide[::2] = mask
    check_accessors(b.take(wide[::2]), eager_take(b.cols, mask))


@pytest.mark.parametrize("name", list(INDEXES))
def test_an_index_take_reads_as_the_eager_gather(name):
    b = batch()
    idx = INDEXES[name]
    check_accessors(b.take(idx), eager_take(b.cols, idx))


@pytest.mark.parametrize("name", ["slice", "step", "empty"])
def test_a_slice_stays_a_view(name):
    b = batch()
    sl = {"slice": slice(10, 500), "step": slice(3, 900, 7),
          "empty": slice(5, 5)}[name]
    got = b.take(sl)
    assert got.selection is None
    assert all(np.shares_memory(got[k], b[k]) or len(got) == 0
               for k in b.names())
    check_accessors(got, eager_take(b.cols, sl))


def test_a_contiguous_run_is_a_view_and_no_selection():
    b = batch()
    for idx in (mask_of("run"), mask_of("full"), INDEXES["run"]):
        got = b.take(idx)
        assert got.selection is None and np.shares_memory(got.key, b.key)


def test_what_does_not_shorten_is_gathered_at_once():
    """A selection is shorter than its base: that is how a reader handed
    columns of both kinds tells them apart (``held``)."""
    b = batch()
    for name in ("permutation", "longer"):
        assert b.take(INDEXES[name]).selection is None
    one = TupleBatch({k: v[:1] for k, v in b.cols.items()})
    assert one.take(np.array([True])).selection is None
    sel = b.take(mask_of("third"))
    assert sel.selection is not None and len(sel.selection) < N


def test_bad_indices_are_refused_as_numpy_refuses_them():
    b = batch()
    for bad in ([N], [-N - 1], [0, 5000]):
        with pytest.raises(IndexError):
            b.take(bad)
    with pytest.raises(IndexError):
        b.take(np.ones(N + 1, np.bool_))
    with pytest.raises(TypeError):
        b.take(np.array([1.5, 2.0]))


# -- reads gather one column, once -------------------------------------------

def test_a_read_gathers_its_column_and_no_other():
    b = batch()
    mask = mask_of("third")
    got = b.take(mask)
    rows = np.nonzero(mask)[0]
    np.testing.assert_array_equal(got.selection, rows)
    assert got.selection_counts() == (5, 0)
    assert got.held("key") is b.key and got.held("value") is b["value"]
    key = got.key
    assert got.selection_counts() == (5, 1) and got.key is key
    assert got.held("key") is key                  # compact once read
    joined = got.with_cols(key=key * 2)
    # the selection is one object for the lineage: a read through either
    # batch is there for both, and counted once
    np.testing.assert_array_equal(joined.ts, b.ts[rows])
    assert joined.selection_counts() == (5, 2) == got.selection_counts()
    assert joined.held("ts") is got.ts
    assert joined.held("key") is joined.key and len(joined.held("key")) \
        == len(rows)
    assert joined.held("id") is b.id               # still the base column
    assert joined.names() == ["key", "id", "ts", "value", "event_type"]
    with pytest.raises(KeyError):
        joined.held("no_such_column")
    with pytest.raises(KeyError):
        b.held("no_such_column")
    assert b.selection is None and b.selection_counts() == (0, 0)
    assert b.held("key") is b.key


def test_compact_is_in_place_and_keeps_what_was_read():
    b = batch()
    got = b.take(mask_of("third"))
    key = got.key
    assert got.compact() is got and got.selection is None
    assert got.key is key and got.compact() is got
    same(got.cols, eager_take(b.cols, mask_of("third")))
    pool = ColumnPool()
    pooled = b.take(mask_of("third")).compact(pool)
    same(pooled.cols, eager_take(b.cols, mask_of("third")))
    assert pool.stats()["misses"] == 5
    # a pool at the take is a partitioner's: gathered at once (KEYBY)
    part = b.take(mask_of("third"), pool)
    assert part.selection is None and pool.stats()["buffers"] == 10


# -- a take of a take --------------------------------------------------------

@pytest.mark.parametrize("second", ["mask", "index", "slice", "run", "all"])
@pytest.mark.parametrize("joined", [False, True], ids=["bare", "joined"])
def test_a_take_of_a_take_composes(second, joined):
    b = batch()
    first = mask_of("third")
    want = eager_take(b.cols, first)
    got = b.take(first)
    if joined:      # a compact column laid over the first selection
        new_key = want["key"] * 10
        got = got.with_cols(key=new_key)
        want = {**want, "key": new_key}
    n1 = len(got)
    idx = {"mask": np.random.RandomState(2).randint(0, 2, n1) == 1,
           "index": [n1 - 1, 0, 17, 17],
           "slice": slice(4, 90),
           "run": list(range(10, 60)),
           "all": np.ones(n1, np.bool_)}[second]
    twice = got.take(idx)
    if second in ("mask", "index"):
        assert twice.selection is not None
        assert twice.held("ts") is b.ts            # still the first base
    check_accessors(twice, eager_take(want, idx))
    # the first selection is none the worse for it
    same(got.cols, want)


# -- the riding trace --------------------------------------------------------

def test_the_trace_rides_on_every_derived_batch():
    b = batch()
    b.trace = trace = object()
    mask = mask_of("third")
    sel = b.take(mask)
    check_accessors(b.take(mask), eager_take(b.cols, mask), trace=trace)
    assert sel.take([1, 5, 2]).trace is trace
    assert sel.take(slice(0, 4)).trace is trace
    assert b.take(INDEXES["run"]).trace is trace
    assert sel.with_cols(key=sel.key).trace is trace
    plain = TupleBatch({k: v[:2] for k, v in b.cols.items()})
    assert plain.concat(sel).trace is trace and sel.concat(plain).trace \
        is trace
    assert not hasattr(batch().take(mask), "trace")


# -- leaving the process -----------------------------------------------------

@pytest.mark.parametrize("how", ["pickle", "deepcopy", "wire"])
def test_a_selected_batch_leaves_the_process_compact(how):
    b = batch()
    mask = mask_of("third")
    sel = b.take(mask).with_cols(key=b.key[mask] + 7)
    sel.trace = "ctx"
    want = {**eager_take(b.cols, mask), "key": b.key[mask] + 7}
    if how == "pickle":
        blob = pickle.dumps(sel)
        assert len(blob) < 0.6 * len(pickle.dumps(b))   # no base chunk in it
        back = pickle.loads(blob)
        assert back.trace == "ctx"
    elif how == "deepcopy":
        back = copy.deepcopy(sel)
        assert back.trace == "ctx"
    else:
        from windflow_tpu.distributed import wire
        back = wire.decode_batch(wire.encode_batch_payload(sel))
    assert back.selection is None
    same(back.cols, want)
    plain = pickle.loads(pickle.dumps(b))
    same(plain.cols, b.cols)
    assert not hasattr(plain, "trace")


# -- the native pass ---------------------------------------------------------

needs_native = pytest.mark.skipif(not native.native_available(),
                                  reason="native library not built")


@needs_native
@pytest.mark.parametrize("dtype", [np.bool_, np.uint8], ids=["bool", "uint8"])
@pytest.mark.parametrize("n", [1, 2, 7, 64, 1000, 65_536, 1 << 17])
def test_the_native_compaction_is_np_nonzero(n, dtype):
    rng = np.random.RandomState(n % 1000)
    pool = ColumnPool()

    def take(rows):
        return pool.take(rows, np.int64)
    for density in (0.0, 1 / 3, 0.5, 1.0):
        mask = (rng.rand(n) < density).astype(dtype)
        if dtype == np.uint8 and n > 2:
            mask[mask > 0] = rng.randint(1, 256, int((mask > 0).sum()))
        rows = native.mask_to_rows(mask, take)
        want = np.nonzero(mask)[0]
        assert rows.dtype == np.int64 == want.dtype
        np.testing.assert_array_equal(rows, want)
        del rows
    # one buffer served them all: each answer was dropped before the next
    assert pool.stats()["buffers"] == 1
    # not one byte a row in a row: numpy's to answer
    assert native.mask_to_rows(np.ones(n, np.int64), take) is None
    if n > 1:       # (a view of one row is in a row)
        assert native.mask_to_rows(np.ones(2 * n, np.bool_)[::2],
                                   take) is None


@needs_native
def test_the_rows_buffer_is_lent_again_only_when_the_selection_is_gone():
    from windflow_tpu.core import tuples
    native.get_lib()
    b = batch()
    mask = mask_of("third")
    first = b.take(mask)
    buf = first.selection.base
    assert buf is not None and len(buf) == 1 << 10   # the pool's: 2^k rows
    second = b.take(mask)
    assert second.selection.base is not buf          # the first still reads it
    np.testing.assert_array_equal(first.selection, second.selection)
    buf = id(buf)
    del first
    third = b.take(~mask)
    assert id(third.selection.base) == buf           # free again: lent again
    np.testing.assert_array_equal(second.key, b.key[mask])
    assert tuples._ROWS_POOL.max_per_bucket == 4


def test_without_the_library_the_rows_are_numpys(monkeypatch):
    monkeypatch.setattr(native, "_lib", False)   # as WINDFLOW_NATIVE=0 leaves it
    b = batch()
    mask = mask_of("third")
    got = b.take(mask)
    assert got.selection.base is None or len(got.selection.base) != 1 << 10
    check_accessors(got, eager_take(b.cols, mask))


# -- a pooled base column is not lent again under a selected batch -----------

def test_a_pooled_base_buffer_is_not_lent_while_a_selected_batch_reads_it():
    pool = ColumnPool()

    def chunk(seed):
        rng = np.random.RandomState(seed)
        cols = {}
        for name, dt in (("key", np.int64), ("id", np.int64),
                         ("ts", np.int64), ("value", np.float64)):
            col = pool.take(N, dt)
            col[:] = rng.randint(0, 100, N)
            cols[name] = col
        return TupleBatch(cols)
    b = chunk(1)
    mask = mask_of("third")
    want = eager_take({k: v.copy() for k, v in b.cols.items()}, mask)
    sel = b.take(mask)
    del b                        # the selected batch alone holds the base
    assert pool.stats()["buffers"] == 4
    other = chunk(2)             # must not be handed the same buffers
    assert pool.stats()["buffers"] == 8 and pool.stats()["hits"] == 0
    same(sel.cols, want)         # read after the pool was asked again
    del sel, other
    chunk(3)
    assert pool.stats()["hits"] == 4     # all free once the batches died


# -- a YSB-shaped graph, row for row -----------------------------------------

G_EVENTS, G_CHUNK, G_WIN, G_ADS, G_CAMPAIGNS = 40_000, 1000, 4096, 60, 6


def ysb_graph(name, config, parallelism=1, python_store=False):
    """filter views -> join ad to campaign -> tumbling count a campaign,
    on the benchmark's law (event i has ts = i): the rows the sink got,
    the rows owed, and every window operator replica's counters."""
    rng = np.random.RandomState(17)
    ads = rng.randint(0, G_ADS, G_EVENTS).astype(np.int64)
    kinds = rng.randint(0, 3, G_EVENTS).astype(np.int64)
    campaign_of_ad = (rng.permutation(G_ADS) // (G_ADS // G_CAMPAIGNS)) \
        .astype(np.int64)
    seen_full, seen_key = [], []

    def body():
        i = body.i
        if i >= G_EVENTS:
            return None
        body.i = i + G_CHUNK
        ts = np.arange(i, i + G_CHUNK, dtype=np.int64)
        return TupleBatch({"key": ads[i:i + G_CHUNK], "id": ts, "ts": ts,
                           "value": np.ones(G_CHUNK),
                           "event_type": kinds[i:i + G_CHUNK]})
    body.i = 0

    def views_only(batch):
        seen_full.append(len(batch))           # the full batch, as ever
        return batch["event_type"] == 0

    def join_campaign(batch):
        seen_key.append(len(batch.key))        # a key column of survivors
        return batch.with_cols(key=campaign_of_ad[batch.key])

    got = {}

    def sink(b):
        if b is not None:
            for k, w, v in zip(b.key.tolist(), b.id.tolist(),
                               b["value"].tolist()):
                assert (k, w) not in got
                got[(k, w)] = v
    g = wf.PipeGraph(name, wf.Mode.DEFAULT, config=config)
    pipe = g.add_source(BatchSource(body))
    pipe.chain(BatchFilter(views_only)).chain(BatchMap(join_campaign)).add(
        KeyFarmTPU("count", G_WIN, G_WIN, wf.WinType.TB, batch_len=64,
                   parallelism=parallelism, coalesce=parallelism == 1,
                   name="campaign_count", emit_batches=True))
    pipe.add_sink(Sink(sink, name="count_sink"))
    logics = [seg.logic for n in g._all_nodes()
              for seg in getattr(n.logic, "segments", [n])
              if hasattr(seg.logic, "_svc_batch")]
    if python_store:
        for logic in logics:
            logic._native = None
    g.run()
    views = kinds == 0
    camp = campaign_of_ad[ads]
    owed = {}
    for w in range((G_EVENTS + G_WIN - 1) // G_WIN):
        in_w = views[w * G_WIN:(w + 1) * G_WIN]
        counts = np.bincount(camp[w * G_WIN:(w + 1) * G_WIN][in_w],
                             minlength=G_CAMPAIGNS)
        owed.update({(k, w): float(c) for k, c in enumerate(counts) if c})
    assert seen_full == [G_CHUNK] * (G_EVENTS // G_CHUNK)
    assert sum(seen_key) == int(views.sum())
    counters = [c for op, c in spans.graph(name).counters.items()
                if "campaign_count" in op]
    return got, owed, int(views.sum()), counters, logics


@needs_native
def test_fused_the_selection_reaches_the_engine():
    got, owed, survivors, counters, _ = ysb_graph(
        "ysb_sel_fused", RuntimeConfig(opt_level=OptLevel.LEVEL2))
    assert got == owed and len(owed) > 50
    assert len(counters) == 1
    c = counters[0]
    chunks = G_EVENTS // G_CHUNK
    # five columns a chunk carried, one of them (`key`, for the join)
    # gathered; every survivor read by the engine through its selection
    assert (c.cols_selected, c.cols_gathered, c.rows_by_selection) \
        == (5 * chunks, chunks, survivors)
    assert c.selected_totals() == {"cols_selected": 5 * chunks,
                                    "cols_gathered": chunks,
                                    "rows_by_selection": survivors}
    assert c.values["folded_by_key"] + c.values["folded_singly"] \
        == survivors


@needs_native
@pytest.mark.parametrize("shape", ["level0", "keyby_two"])
def test_across_a_queue_the_batch_is_compact(shape):
    """At LEVEL0 the chain's batch crosses a queue to the window
    operator, under a farm of two it is partitioned by key first: the
    operator sees ordinary batches, and the same rows come out."""
    got, owed, survivors, counters, logics = ysb_graph(
        "ysb_sel_" + shape, RuntimeConfig(
            opt_level=OptLevel.LEVEL0 if shape == "level0"
            else OptLevel.LEVEL2),
        parallelism=2 if shape == "keyby_two" else 1)
    assert got == owed
    assert len(logics) == (2 if shape == "keyby_two" else 1)
    assert counters
    for c in counters:
        assert (c.cols_selected, c.cols_gathered, c.rows_by_selection) \
            == (0, 0, 0)
    assert sum(c.values["folded_by_key"] + c.values["folded_singly"]
               for c in counters) == survivors


def test_the_python_store_takes_the_selection_too():
    got, owed, survivors, _counters, logics = ysb_graph(
        "ysb_sel_python", RuntimeConfig(opt_level=OptLevel.LEVEL2),
        python_store=True)
    assert got == owed
    (logic,) = logics
    assert logic._store is logic._py
    c = logic._counters
    chunks = G_EVENTS // G_CHUNK
    assert (c.cols_selected, c.cols_gathered, c.rows_by_selection) \
        == (5 * chunks, chunks, survivors)


def test_the_selection_counters_reach_the_stats_json_and_the_metrics_page():
    import json
    from windflow_tpu.telemetry.metrics import render_openmetrics
    if not native.native_available():
        pytest.skip("native library not built")
    name = "ysb_sel_stats"
    rng = np.random.RandomState(1)

    def body():
        if body.i >= 20:
            return None
        body.i += 1
        ts = np.arange(body.i * 500, body.i * 500 + 500, dtype=np.int64)
        return TupleBatch({"key": rng.randint(0, 9, 500).astype(np.int64),
                           "id": ts, "ts": ts, "value": np.ones(500)})
    body.i = 0
    g = wf.PipeGraph(name, wf.Mode.DEFAULT)
    g.add_source(BatchSource(body)).chain(
        BatchFilter(lambda b: b.key % 2 == 0)).add(
        KeyFarmTPU("count", 256, 128, wf.WinType.TB, name="counts",
                   emit_batches=True)).add_sink(Sink(lambda b: None))
    g.run()
    report = json.loads(g.stats.to_json())
    rows = [r["Counters"] for r in report["Spans"]["Operators"]
            if "Counters" in r]
    assert rows and rows[0]["cols_selected"] == 4 * 20
    assert rows[0]["cols_gathered"] == 0   # the engine read all four in place
    assert rows[0]["rows_by_selection"] \
        == rows[0]["folded_by_key"] + rows[0]["folded_singly"]
    text = render_openmetrics({"a": {"report": report}})
    for counter in ("cols_selected", "cols_gathered", "rows_by_selection"):
        assert f"windflow_engine_{counter}_total{{" in text


# -- columns of other shapes ---------------------------------------------------

def test_columns_numpy_must_gather_still_read_right():
    """Two-dimensional, 4-byte and strided columns and int32 rows: the
    same batch as the eager gather, with a pool at the boundary too."""
    native.get_lib()
    n = 200
    cols = {"key": np.arange(n, dtype=np.int64), "id": np.arange(n),
            "ts": np.arange(2 * n, dtype=np.int64)[::2],
            "value": np.arange(n, dtype=np.float32),
            "vec": np.arange(3 * n, dtype=np.float64).reshape(n, 3)}
    b = TupleBatch(cols)
    for idx in (np.arange(n) % 3 == 0, np.array([5, 1, 77], np.int32)):
        check = b.take(idx)
        assert check.selection is not None
        same(check.cols, eager_take(cols, idx))
        pool = ColumnPool()
        same(b.take(idx).compact(pool).cols, eager_take(cols, idx))
