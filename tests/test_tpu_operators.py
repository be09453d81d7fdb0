"""Tests for the device-batched (TPU) window operators.

Mirror of tests/mp_tests_gpu (SURVEY.md §4): identical fixtures to the
CPU tests, device engines, varying batch lengths, aggregate oracle.
Runs on the JAX CPU backend in CI (conftest.py); the same programs
compile for TPU unchanged.
"""
import threading

import numpy as np
import pytest

import windflow_tpu as wf
from windflow_tpu.core import BasicRecord, Mode, WinType
from windflow_tpu.ops.window_compute import WindowComputeEngine
from windflow_tpu.ops.flatfat_jax import FlatFATJax


def ordered_source(n_keys, per_key):
    state = {}

    def fn(shipper, ctx):
        i = state.setdefault("i", 0)
        if i >= n_keys * per_key:
            return False
        key = i % n_keys
        tid = i // n_keys
        shipper.push(BasicRecord(key, tid, tid, float(tid)))
        state["i"] = i + 1
        return True

    return fn


class Collector:
    def __init__(self):
        self.lock = threading.Lock()
        self.results = []

    def __call__(self, rec):
        if rec is not None:
            with self.lock:
                self.results.append((rec.key, rec.id, rec.value))

    def by_key(self):
        out = {}
        for k, g, v in self.results:
            out.setdefault(k, {})[g] = v
        return out


def oracle(per_key, win, slide, agg=sum):
    out = {}
    g = 0
    while g * slide < per_key:
        vals = [float(v) for v in range(per_key)
                if g * slide <= v < g * slide + win]
        out[g] = float(agg(vals)) if vals else 0.0
        g += 1
    return out


def run_graph(op, n_keys=3, per_key=48, mode=Mode.DEFAULT):
    coll = Collector()
    g = wf.PipeGraph("t", mode)
    g.add_source(wf.SourceBuilder(ordered_source(n_keys, per_key)).build()) \
        .add(op).add_sink(wf.SinkBuilder(coll).build())
    g.run()
    return coll


class TestWindowComputeEngine:
    def test_scan_sum(self):
        eng = WindowComputeEngine("sum")
        vals = np.arange(20, dtype=np.float64)
        starts = np.array([0, 5, 10])
        ends = np.array([5, 10, 20])
        out = eng.compute({"value": vals}, starts, ends,
                          np.arange(3)).block()
        np.testing.assert_allclose(out, [10, 35, 145])

    def test_sparse_table_max(self):
        rng = np.random.default_rng(0)
        vals = rng.normal(size=100)
        starts = np.array([0, 10, 50, 93])
        ends = np.array([7, 30, 82, 100])
        eng = WindowComputeEngine("max")
        out = eng.compute({"value": vals}, starts, ends,
                          np.arange(4)).block()
        expect = [vals[s:e].max() for s, e in zip(starts, ends)]
        np.testing.assert_allclose(out, expect, rtol=1e-6)

    def test_custom_fn(self):
        import jax.numpy as jnp

        def fn(gwid, cols, mask):
            v = jnp.where(mask, cols["value"], 0.0)
            return jnp.sum(v * v)

        vals = np.arange(10, dtype=np.float64)
        eng = WindowComputeEngine(fn)
        out = eng.compute({"value": vals}, np.array([0, 4]),
                          np.array([4, 10]), np.arange(2)).block()
        np.testing.assert_allclose(out, [sum(v * v for v in range(4)),
                                         sum(v * v for v in range(4, 10))])

    def test_ffat_kind(self):
        import jax.numpy as jnp
        eng = WindowComputeEngine(("ffat", jnp.add, 0.0))
        vals = np.arange(32, dtype=np.float64)
        starts = np.array([0, 8, 3])
        ends = np.array([8, 32, 5])
        out = eng.compute({"value": vals}, starts, ends,
                          np.arange(3)).block()
        np.testing.assert_allclose(out, [28, 468, 7])


class TestFlatFATJax:
    def test_build_query(self):
        import jax.numpy as jnp
        f = FlatFATJax(jnp.add, 0.0, 16, dtype=np.float64)
        f.build(np.arange(16, dtype=np.float64))
        out = f.query_ranges(np.array([0, 4, 15]), np.array([16, 8, 16]))
        np.testing.assert_allclose(out, [120, 22, 15])

    def test_update(self):
        import jax.numpy as jnp
        f = FlatFATJax(jnp.maximum, -np.inf, 8, dtype=np.float64)
        f.build(np.arange(8, dtype=np.float64))
        f.update(np.array([0, 3]), np.array([100.0, -5.0]))
        out = f.query_ranges(np.array([0, 2]), np.array([8, 4]))
        np.testing.assert_allclose(out, [100.0, 2.0])

    def test_randomized_min_queries(self):
        import jax.numpy as jnp
        rng = np.random.default_rng(3)
        vals = rng.normal(size=64)
        f = FlatFATJax(jnp.minimum, np.inf, 64, dtype=np.float64)
        f.build(vals)
        starts = rng.integers(0, 60, size=20)
        ends = starts + rng.integers(1, 4, size=20)
        out = f.query_ranges(starts, ends)
        expect = [vals[s:e].min() for s, e in zip(starts, ends)]
        np.testing.assert_allclose(out, expect, rtol=1e-6)


@pytest.mark.parametrize("win,slide", [(8, 8), (12, 4)])
@pytest.mark.parametrize("batch", [1, 7, 64, 1024])
@pytest.mark.parametrize("win_type", [WinType.CB, WinType.TB])
def test_win_seq_tpu_matches_oracle(win, slide, batch, win_type):
    b = wf.WinSeqTPUBuilder("sum").with_batch(batch)
    b = (b.with_cb_windows(win, slide) if win_type == WinType.CB
         else b.with_tb_windows(win, slide))
    coll = run_graph(b.build())
    expect = oracle(48, win, slide)
    assert coll.by_key() == {k: expect for k in range(3)}


@pytest.mark.parametrize("kind,agg", [("max", max), ("min", min),
                                      ("count", len)])
def test_win_seq_tpu_builtin_kinds(kind, agg):
    b = wf.WinSeqTPUBuilder(kind).with_batch(16).with_tb_windows(12, 4)
    coll = run_graph(b.build())
    expect = oracle(48, 12, 4, agg=agg)
    assert coll.by_key() == {k: expect for k in range(3)}


@pytest.mark.parametrize("native_panes", [True, False])
@pytest.mark.parametrize("kind,agg", [("max", max), ("min", min),
                                      ("sum", sum)])
def test_win_seq_tpu_pane_path_with_retained_tail(kind, agg, native_panes,
                                                  monkeypatch):
    """Pane pre-reduction (pane = gcd >= 16) with launches that happen
    while later tuples are already retained: the last pane of a batch
    must not absorb tuples beyond its window edge (reduceat's final
    segment runs to the end of the array).  Covers both the native
    pane_reduce helper and the numpy fallback."""
    if not native_panes:
        from windflow_tpu.runtime import native as native_mod
        monkeypatch.setattr(native_mod, "pane_reduce",
                            lambda *a, **k: None)
    b = wf.WinSeqTPUBuilder(kind).with_batch(2).with_tb_windows(64, 32)
    coll = run_graph(b.build(), n_keys=2, per_key=400)
    expect = oracle(400, 64, 32, agg=agg)
    got = coll.by_key()
    assert set(got) == {0, 1}
    for k in got:
        assert got[k] == pytest.approx(expect, rel=1e-5)


@pytest.mark.parametrize("coalesce", [True, False])
@pytest.mark.parametrize("par", [1, 3])
@pytest.mark.parametrize("win_type", [WinType.CB, WinType.TB])
def test_key_farm_tpu(par, win_type, coalesce):
    """Both lowerings must agree: the coalesced single engine (default)
    and the literal N-replica farm with hash-partitioned keys."""
    b = wf.KeyFarmTPUBuilder("sum").with_parallelism(par).with_batch(8) \
        .with_coalesce(coalesce)
    b = (b.with_cb_windows(12, 4) if win_type == WinType.CB
         else b.with_tb_windows(12, 4))
    op = b.build()
    coll = run_graph(op, n_keys=5)
    n_reps = len(op.stages()[0].replicas)
    assert n_reps == (1 if coalesce else par)
    expect = oracle(48, 12, 4)
    assert coll.by_key() == {k: expect for k in range(5)}


@pytest.mark.parametrize("par", [2, 4])
@pytest.mark.parametrize("win_type", [WinType.CB, WinType.TB])
def test_win_farm_tpu(par, win_type):
    b = wf.WinFarmTPUBuilder("sum").with_parallelism(par).with_batch(4)
    b = (b.with_cb_windows(12, 4) if win_type == WinType.CB
         else b.with_tb_windows(12, 4))
    mode = Mode.DETERMINISTIC if win_type == WinType.CB else Mode.DEFAULT
    coll = run_graph(b.build(), mode=mode)
    expect = oracle(48, 12, 4)
    assert coll.by_key() == {k: expect for k in range(3)}


@pytest.mark.parametrize("plq_on_tpu", [True, False])
def test_pane_farm_tpu(plq_on_tpu):
    def host_comb(gwid, iterable, result):
        result.value = sum(t.value for t in iterable)

    if plq_on_tpu:
        b = wf.PaneFarmTPUBuilder("sum", host_comb, plq_on_tpu=True)
    else:
        b = wf.PaneFarmTPUBuilder(host_comb, "sum", plq_on_tpu=False)
    coll = run_graph(b.with_parallelism(2, 1).with_batch(8)
                     .with_tb_windows(12, 4).build())
    expect = oracle(48, 12, 4)
    got = coll.by_key()
    for k in range(3):
        assert got[k] == expect, (k, got[k])


@pytest.mark.parametrize("opt_level", [wf.OptLevel.LEVEL0,
                                       wf.OptLevel.LEVEL2])
@pytest.mark.parametrize("kind,agg", [("sum", sum), ("max", max),
                                      ("min", min)])
def test_pane_farm_tpu_columnar_wlq(kind, agg, opt_level):
    """A builtin-name host WLQ takes the columnar pane->window combine;
    results must equal both the oracle and the callable-WLQ path
    (which stays on the per-record engine)."""
    def host_comb(gwid, iterable, result):
        result.value = agg(t.value for t in iterable)

    results = {}
    for wlq in (kind, host_comb):
        b = wf.PaneFarmTPUBuilder(kind, wlq).with_parallelism(1, 1) \
            .with_batch(8).with_tb_windows(12, 4)
        b.opt_level = opt_level
        op = b.build()
        assert op._wlq_columnar == isinstance(wlq, str)
        coll = run_graph(op)
        results[isinstance(wlq, str)] = coll.by_key()
    expect = oracle(48, 12, 4, agg=agg)
    for columnar, got in results.items():
        for k in range(3):
            assert got[k] == pytest.approx(expect, rel=1e-9), \
                (columnar, k, got[k])


def test_pane_farm_tpu_columnar_wlq_batch_output_and_par():
    """Columnar WLQ with keyed parallelism and TupleBatch output."""
    sink_batches = []
    lock = threading.Lock()

    class BatchSink:
        def __call__(self, item):
            from windflow_tpu.core.tuples import TupleBatch
            if item is None:
                return
            with lock:
                if isinstance(item, TupleBatch):
                    for i in range(len(item)):
                        sink_batches.append((int(item.key[i]),
                                             int(item.id[i]),
                                             float(item["value"][i])))
                else:
                    sink_batches.append((item.key, item.id, item.value))

    b = wf.PaneFarmTPUBuilder("sum", "sum").with_parallelism(1, 2) \
        .with_batch(8).with_tb_windows(12, 4).with_batch_output()
    g = wf.PipeGraph("pcb", Mode.DEFAULT)
    g.add_source(wf.SourceBuilder(ordered_source(4, 48)).build()) \
        .add(b.build()).add_sink(wf.SinkBuilder(BatchSink()).build())
    g.run()
    got = {}
    for k, w, v in sink_batches:
        got.setdefault(k, {})[w] = v
    expect = oracle(48, 12, 4)
    assert set(got) == set(range(4))
    for k in got:
        assert got[k] == pytest.approx(expect, rel=1e-9)


@pytest.mark.parametrize("target", ["winseq_tpu", "batch_map",
                                    "kf_tpu_par3"])
def test_chunked_synth_source_any_consumer(target):
    """SynthChunk descriptors must be transparent at every
    columnar-plane boundary: chunk-aware device engines fold them
    natively; every other batch consumer (transforms, multi-replica
    keyed farms behind routing emitters) sees materialized batches
    with identical content.  (Record-plane host operators don't consume
    TupleBatch either -- plane adapters are explicit by design.)"""
    from windflow_tpu.operators.batch_ops import BatchMap
    from windflow_tpu.operators.synth import SyntheticSource

    def build_ops(g):
        if target == "winseq_tpu":
            return [wf.WinSeqTPUBuilder("sum").with_batch(16)
                    .with_tb_windows(12, 4).build()]
        if target == "batch_map":
            # a chunk landing on a plain batch transform materializes
            return [BatchMap(lambda b: b),
                    wf.WinSeqTPUBuilder("sum").with_batch(16)
                    .with_tb_windows(12, 4).build()]
        return [wf.KeyFarmTPUBuilder("sum").with_parallelism(3)
                .with_coalesce(False).with_batch(16)
                .with_tb_windows(12, 4).build()]

    results = {}
    for chunked in (False, True):
        coll = Collector()
        g = wf.PipeGraph("chunks", Mode.DEFAULT)
        mp = g.add_source(SyntheticSource(6_000, 5, batch=700,
                                          chunked=chunked))
        for op in build_ops(g):
            mp = mp.add(op)
        mp.add_sink(wf.SinkBuilder(coll).build())
        g.run()
        results[chunked] = coll.by_key()
    assert results[True] == results[False]
    assert len(results[True]) == 5


def test_nested_pane_farm_builtin_wlq_falls_back_to_record_engine():
    """Nested copies carry non-identity configs (striped/offset window
    ids) the columnar WLQ cannot reproduce; a builtin-name WLQ must
    fall back to the stock per-record engine there and match the
    callable-WLQ nesting exactly."""
    from windflow_tpu.operators.nesting import _clone_inner

    def host_comb(gwid, it, res):
        res.value = sum(t.value for t in it)

    results = {}
    for wlq in ("sum", host_comb):
        inner = wf.PaneFarmTPUBuilder("sum", wlq) \
            .with_parallelism(2, 1).with_tb_windows(12, 4).build()
        if isinstance(wlq, str):
            assert inner._wlq_columnar  # identity config: columnar ok
            copy = _clone_inner(inner, 1, 2, 4, 8)
            assert not copy._wlq_columnar  # nested: falls back
        op = wf.WinFarmTPUBuilder(inner).with_parallelism(2).build()
        coll = run_graph(op)
        results[isinstance(wlq, str)] = coll.by_key()
    expect = oracle(48, 12, 4)
    for columnar, got in results.items():
        for k in range(3):
            assert got[k] == pytest.approx(expect, rel=1e-9), \
                (columnar, k, got[k])


def test_pane_farm_tpu_rejects_unsupported_builtin_wlq():
    with pytest.raises(ValueError, match="builtin"):
        wf.PaneFarmTPUBuilder("count", "count") \
            .with_tb_windows(12, 4).build()


def test_pane_combine_logic_out_of_order_and_checkpoint():
    """Pane ids arriving out of order park until the gap fills; a
    snapshot taken mid-stream resumes exactly."""
    import pickle
    from windflow_tpu.operators.tpu.pane_combine import PaneCombineLogic

    def feed(lg, seq, out):
        for pid, v in seq:
            r = BasicRecord(7, pid, pid, v)
            lg.svc(r, 0, out.append)

    ref_lg, ref_out = PaneCombineLogic("sum", 3, 1), []
    feed(ref_lg, [(i, float(i)) for i in range(8)], ref_out)
    ref_lg.eos_flush(ref_out.append)

    lg, out = PaneCombineLogic("sum", 3, 1), []
    feed(lg, [(0, 0.0), (2, 2.0), (3, 3.0), (1, 1.0)], out)  # 1 late
    blob = pickle.dumps(lg.state_dict())
    lg2, out2 = PaneCombineLogic("sum", 3, 1), []
    lg2.load_state(pickle.loads(blob))
    feed(lg2, [(i, float(i)) for i in range(4, 8)], out2)
    lg2.eos_flush(out2.append)

    def collect(rs):
        return {(r.key, r.id): r.value for r in rs}
    assert collect(ref_out) == collect(out + out2)
    assert len(ref_out) == 8  # 6 complete + 2 EOS partials


@pytest.mark.parametrize("map_on_tpu", [True, False])
def test_win_mapreduce_tpu(map_on_tpu):
    def host_fn(gwid, iterable, result):
        result.value = sum(t.value for t in iterable)

    if map_on_tpu:
        b = wf.WinMapReduceTPUBuilder("sum", host_fn, map_on_tpu=True)
    else:
        b = wf.WinMapReduceTPUBuilder(host_fn, "sum", map_on_tpu=False)
    coll = run_graph(b.with_parallelism(3, 1).with_batch(8)
                     .with_tb_windows(12, 4).build())
    expect = oracle(48, 12, 4)
    got = coll.by_key()
    for k in range(3):
        assert got[k] == expect, (k, got[k])


@pytest.mark.parametrize("coalesce", [True, False])
@pytest.mark.parametrize("win_type", [WinType.CB, WinType.TB])
def test_key_ffat_tpu(win_type, coalesce):
    import jax.numpy as jnp
    b = wf.KeyFFATTPUBuilder(lambda t: t.value, (jnp.add, 0.0)) \
        .with_parallelism(2).with_batch(8).with_coalesce(coalesce)
    b = (b.with_cb_windows(12, 4) if win_type == WinType.CB
         else b.with_tb_windows(12, 4))
    coll = run_graph(b.build(), n_keys=4)
    expect = oracle(48, 12, 4)
    assert coll.by_key() == {k: expect for k in range(4)}


def test_win_seqffat_tpu_builtin():
    b = wf.WinSeqFFATTPUBuilder(lambda t: t.value, "max") \
        .with_batch(16).with_tb_windows(10, 5)
    coll = run_graph(b.build())
    expect = oracle(48, 10, 5, agg=max)
    assert coll.by_key() == {k: expect for k in range(3)}


class TestPallasKernels:
    def test_window_sums_matches_numpy(self):
        from windflow_tpu.ops.pallas.window_sum import window_sums
        rng = np.random.default_rng(1)
        vals = rng.normal(size=5000).astype(np.float32)
        starts = np.sort(rng.integers(0, 4000, 20)).astype(np.int32)
        ends = (starts + rng.integers(1, 900, 20)).astype(np.int32)
        out = window_sums(vals, starts, ends)
        expect = [vals[s:e].sum() for s, e in zip(starts, ends)]
        np.testing.assert_allclose(out, expect, rtol=1e-3)

    def test_window_sums_empty_and_single(self):
        from windflow_tpu.ops.pallas.window_sum import window_sums
        vals = np.arange(300, dtype=np.float32)
        out = window_sums(vals, np.array([5, 10, 0]), np.array([5, 11, 300]))
        np.testing.assert_allclose(out, [0.0, 10.0, vals.sum()], rtol=1e-4)


def test_cb_eos_result_timestamps_full_graph():
    """EOS-flushed CB windows must carry the last-extent-tuple ts, on
    both the native renumbered lane and the Python fallback path
    (regression: the Python eos_flush hardcoded rts=0)."""
    from windflow_tpu.core.tuples import TupleBatch
    from windflow_tpu.operators.batch_ops import BatchSource
    from windflow_tpu.operators.tpu.win_seq_tpu import (WinSeqTPU,
                                                        WinSeqTPULogic)

    win, slide, n, n_keys = 64, 32, 20_000, 4
    keys = np.arange(n, dtype=np.int64) % n_keys
    ids = np.arange(n, dtype=np.int64) // n_keys
    ts = ids * 7 + 3
    vals = np.ones(n)
    max_id = int(ids.max())

    for force_python in (False, True):
        batches = [TupleBatch({"key": keys[i:i + 4096], "id": ids[i:i + 4096],
                               "ts": ts[i:i + 4096],
                               "value": vals[i:i + 4096]})
                   for i in range(0, n, 4096)]
        it = iter(batches)
        got = {}
        lock = threading.Lock()

        def sink(item):
            if item is None:
                return
            with lock:
                for i in range(len(item)):
                    got[(int(item.key[i]), int(item.id[i]))] = int(item.ts[i])

        g = wf.PipeGraph("t", Mode.DEFAULT)
        op = WinSeqTPU("sum", win, slide, WinType.CB, batch_len=64,
                       emit_batches=True)
        g.add_source(BatchSource(lambda ctx: next(it, None))) \
            .add(op).add_sink(wf.SinkBuilder(sink).build())
        if force_python:
            for node in g._all_nodes():
                if isinstance(node.logic, WinSeqTPULogic):
                    node.logic._native = None
        g.run()
        assert got, "no windows emitted"
        for (k, wid), rts in got.items():
            last_id = min(wid * slide + win - 1, max_id)
            assert rts == last_id * 7 + 3, \
                (force_python, k, wid, rts, last_id * 7 + 3)


def test_native_engine_renumber_mode_matches_explicit_ids():
    """Renumber mode (implicit arrival-order ids) must stage the same
    windows as explicit dense ids."""
    from windflow_tpu.runtime.native import (NativeWindowEngine,
                                             native_available)
    if not native_available():
        pytest.skip("native runtime unavailable")
    rng = np.random.default_rng(7)
    n, n_keys = 30_000, 5
    keys = rng.integers(0, n_keys, n).astype(np.int64)
    # per-key arrival-order ids (what renumbering computes)
    ids = np.zeros(n, np.int64)
    counters = {}
    for i, k in enumerate(keys):
        ids[i] = counters.get(int(k), 0)
        counters[int(k)] = ids[i] + 1
    ts = np.arange(n, dtype=np.int64)
    vals = rng.random(n)

    def collect(renumber):
        eng = NativeWindowEngine(48, 16, False, 0, renumber=renumber)
        out = {}

        def take(o):
            if o is None:
                return
            cols, st_, en_, dk, dg, dr, _ = o
            v = cols["value"]
            for i in range(len(dk)):
                s, e = int(st_[i]), int(en_[i])
                out[(int(dk[i]), int(dg[i]))] = (round(float(v[s:e].sum()), 6),
                                                 int(dr[i]))
            return

        for i in range(0, n, 4096):
            # renumber mode ignores the id column entirely
            bogus = np.zeros(min(4096, n - i), np.int64) if renumber \
                else ids[i:i + 4096]
            if eng.ingest(keys[i:i + 4096], bogus, ts[i:i + 4096],
                          vals[i:i + 4096]) >= 64:
                take(eng.flush(1 << 16))
        eng.eos()
        while eng.ready():
            take(eng.flush(1 << 16))
        return out

    a = collect(renumber=True)
    b = collect(renumber=False)
    assert a == b and len(a) > 100


class TestPallasFlatFATQuery:
    """ops/pallas/flatfat_query.py vs the XLA query (flatfat_jax.py)."""

    def _check(self, comb, neutral, n_leaves, B, seed=0):
        import jax.numpy as jnp  # noqa: F401  (combine fns traced)
        from windflow_tpu.ops.pallas.flatfat_query import flatfat_query_ranges
        rng = np.random.default_rng(seed)
        f = FlatFATJax(comb, neutral, n_leaves)
        f.build(rng.normal(size=n_leaves).astype(np.float32))
        starts = rng.integers(0, n_leaves - 1, B)
        ends = np.minimum(starts + rng.integers(1, n_leaves // 2 + 2, B),
                          n_leaves)
        want = f.query_ranges(starts, ends)
        got = flatfat_query_ranges(np.asarray(f.tree), starts, ends,
                                   comb, neutral)
        np.testing.assert_allclose(got, want, rtol=1e-4)

    def test_sum(self):
        import jax.numpy as jnp
        self._check(jnp.add, 0.0, 256, 64)

    def test_max_min(self):
        import jax.numpy as jnp
        self._check(jnp.maximum, -np.inf, 1024, 128, seed=1)
        self._check(jnp.minimum, np.inf, 64, 16, seed=2)

    def test_non_commutative_order(self):
        def left_weighted(a, b):
            return a * 0.5 + b
        self._check(left_weighted, 0.0, 128, 32, seed=3)

    def test_engine_pallas_path_matches_xla(self, monkeypatch):
        """WindowComputeEngine ffat kind through the pallas query gate."""
        import jax.numpy as jnp
        from windflow_tpu.ops import window_compute as wc
        monkeypatch.setenv("WINDFLOW_PALLAS_FFAT", "1")
        rng = np.random.default_rng(4)
        T, B = 500, 40
        vals = rng.normal(size=T)
        starts = rng.integers(0, T - 1, B)
        ends = np.minimum(starts + rng.integers(1, 80, B), T)
        gwids = np.arange(B, dtype=np.int64)
        eng = wc.WindowComputeEngine(("ffat", jnp.maximum, -np.inf))
        got = eng.compute({"value": vals}, starts, ends, gwids).block()
        monkeypatch.setenv("WINDFLOW_PALLAS_FFAT", "0")
        eng2 = wc.WindowComputeEngine(("ffat", jnp.maximum, -np.inf))
        want = eng2.compute({"value": vals}, starts, ends, gwids).block()
        np.testing.assert_allclose(got, want, rtol=1e-4)


@pytest.mark.parametrize("kind,agg", [("sum", np.sum), ("count", len),
                                      ("max", np.max), ("min", np.min)])
def test_native_engine_builtin_kinds_ground_truth(kind, agg):
    """All builtin kinds through the native columnar engine vs numpy
    (the C++ pane partials must use the kind's own reduction/neutral)."""
    from windflow_tpu.core.tuples import TupleBatch
    from windflow_tpu.operators.tpu.win_seq_tpu import WinSeqTPULogic
    from windflow_tpu.runtime.native import native_available
    if not native_available():
        pytest.skip("native runtime unavailable")
    rng = np.random.default_rng(5)
    n, n_keys, win, slide = 20_000, 4, 96, 32
    keys = np.arange(n, dtype=np.int64) % n_keys
    ids = np.arange(n, dtype=np.int64) // n_keys
    vals = rng.normal(size=n)
    logic = WinSeqTPULogic(kind, win, slide, WinType.TB, batch_len=128,
                           emit_batches=True)
    assert logic._native is not None
    ems = []
    for i in range(0, n, 4096):
        logic.svc(TupleBatch({"key": keys[i:i + 4096], "id": ids[i:i + 4096],
                              "ts": ids[i:i + 4096],
                              "value": vals[i:i + 4096]}), 0, ems.append)
    logic.eos_flush(ems.append)
    got = {}
    for b in ems:
        for i in range(len(b)):
            got[(int(b.key[i]), int(b.id[i]))] = float(b["value"][i])
    for k in range(n_keys):
        kv = vals[keys == k]
        lwid = 0
        while lwid * slide <= len(kv) - 1:
            seg = kv[lwid * slide: lwid * slide + win]
            want = float(agg(seg))
            assert (k, lwid) in got
            assert abs(got[(k, lwid)] - want) <= 1e-3 * max(1, abs(want)), \
                (kind, k, lwid, got[(k, lwid)], want)
            lwid += 1


class TestResidentFFAT:
    """rebuild=False mode: HBM-resident per-key forest, incremental
    scatter updates (win_seqffat_gpu.hpp:150)."""

    def _run(self, combine, win, slide, per_key=200, n_keys=3):
        b = wf.WinSeqFFATTPUBuilder(lambda t: t.value, combine) \
            .with_cb_windows(win, slide).with_rebuild(False)
        coll = run_graph(b.build(), n_keys=n_keys, per_key=per_key)
        return coll.by_key()

    def test_max_sliding(self):
        got = self._run("max", 24, 8)
        expect = oracle(200, 24, 8, agg=max)
        assert got == {k: expect for k in range(3)}

    def test_sum_overlapping(self):
        got = self._run("sum", 20, 4)
        expect = oracle(200, 20, 4)
        for k in range(3):
            assert got[k].keys() == expect.keys()
            for w in expect:
                assert abs(got[k][w] - expect[w]) <= 1e-3 * max(
                    1, abs(expect[w]))

    def test_custom_combine(self):
        import jax.numpy as jnp
        b = wf.WinSeqFFATTPUBuilder(
            lambda t: t.value, (jnp.minimum, float("inf"))) \
            .with_cb_windows(12, 12).with_rebuild(False)
        coll = run_graph(b.build())
        expect = oracle(48, 12, 12, agg=min)
        assert coll.by_key() == {k: expect for k in range(3)}

    @pytest.mark.parametrize("combine,agg", [("sum", sum), ("max", max)])
    def test_tb_resident(self, combine, agg):
        """TB windows on the resident forest: ring eviction keyed on the
        timestamp proof (win_seqffat_gpu.hpp:444-...)."""
        b = wf.WinSeqFFATTPUBuilder(lambda t: t.value, combine) \
            .with_tb_windows(24, 8).with_rebuild(False)
        coll = run_graph(b.build(), n_keys=3, per_key=200)
        got = coll.by_key()
        expect = oracle(200, 24, 8, agg=agg)
        for k in range(3):
            assert got[k].keys() == expect.keys(), k
            for w in expect:
                assert abs(got[k][w] - expect[w]) <= 1e-3 * max(
                    1, abs(expect[w])), (k, w)

    def test_tb_resident_ring_growth_on_dense_span(self):
        """A TB window span holding more tuples than the initial ring
        capacity forces leaf growth (re-scatter), not data loss: ts
        advance by 1 per 8 tuples, so win=16 spans ~128 tuples while
        the initial capacity is sized for win+slide+headroom ts only
        ... the logic is constructed directly with a small ring."""
        import jax.numpy as jnp
        from windflow_tpu.core import WinType
        from windflow_tpu.operators.tpu.ffat_resident import \
            WinSeqFFATResidentLogic

        lg = WinSeqFFATResidentLogic(
            lambda t: t.value, jnp.add, 0.0, 16, 8, win_type=WinType.TB)
        lg._chunk_headroom = 32
        lg.capacity = 64  # force a tiny ring
        from windflow_tpu.ops.flatfat_jax import BatchedFlatFAT
        lg.forest = BatchedFlatFAT(jnp.add, 0.0, 2, 64)
        out = []
        n = 1024  # ts = i // 8: 128 tuples per 16-ts window > 64 ring
        for i in range(n):
            lg.svc(BasicRecord(0, i, i // 8, 1.0), 0, out.append)
        lg.eos_flush(out.append)
        assert lg.capacity > 64  # the ring grew
        got = {r.get_control_fields()[1]: r.value for r in out}
        max_ts = (n - 1) // 8
        w = 0
        while w * 8 <= max_ts:
            lo, hi = w * 8, w * 8 + 16
            want = sum(1.0 for i in range(n) if lo <= i // 8 < hi)
            assert got[w] == want, (w, got[w], want)
            w += 1

    def test_tb_resident_sparse_ts_gaps(self):
        """Sparse timestamps: empty windows between bursts emit the
        masked 0, and window extents resolve by ts binary search."""
        import jax.numpy as jnp
        from windflow_tpu.core import WinType
        from windflow_tpu.operators.tpu.ffat_resident import \
            WinSeqFFATResidentLogic

        lg = WinSeqFFATResidentLogic(
            lambda t: t.value, jnp.add, 0.0, 8, 8, win_type=WinType.TB)
        out = []
        for ts in [0, 1, 2, 50, 51, 90]:
            lg.svc(BasicRecord(0, ts, ts, float(ts)), 0, out.append)
        lg.eos_flush(out.append)
        got = {r.get_control_fields()[1]: r.value for r in out}
        assert got[0] == 3.0        # ts 0,1,2
        assert got[6] == 101.0      # ts 50,51 in [48,56)
        assert got[11] == 90.0      # ts 90 in [88,96)
        for w, v in got.items():
            if w not in (0, 6, 11):
                assert v == 0.0, (w, v)

    def test_tb_resident_rejects_out_of_order(self):
        import jax.numpy as jnp
        from windflow_tpu.core import WinType
        from windflow_tpu.operators.tpu.ffat_resident import \
            WinSeqFFATResidentLogic

        lg = WinSeqFFATResidentLogic(
            lambda t: t.value, jnp.add, 0.0, 8, 4, win_type=WinType.TB)
        lg.svc(BasicRecord(0, 0, 10, 1.0), 0, lambda x: None)
        with pytest.raises(ValueError, match="in-order"):
            lg.svc(BasicRecord(0, 1, 3, 1.0), 0, lambda x: None)

    def test_many_keys_grow_forest(self):
        """Key count beyond the initial forest capacity forces growth."""
        b = wf.WinSeqFFATTPUBuilder(lambda t: t.value, "sum") \
            .with_cb_windows(8, 8).with_rebuild(False)
        coll = run_graph(b.build(), n_keys=40, per_key=16)
        got = coll.by_key()
        expect = oracle(16, 8, 8)
        assert len(got) == 40
        for k in range(40):
            for w in expect:
                assert abs(got[k][w] - expect[w]) <= 1e-3

    def test_checkpoint_roundtrip(self):
        import pickle
        from windflow_tpu.operators.tpu.ffat_resident import \
            WinSeqFFATResidentLogic
        import jax.numpy as jnp
        mk = lambda: WinSeqFFATResidentLogic(
            lambda t: t.value, jnp.add, 0.0, 16, 8)
        a, out = mk(), []
        for i in range(60):
            a.svc(BasicRecord(i % 2, i // 2, i // 2, float(i)), 0,
                  out.append)
        blob = pickle.dumps(a.state_dict())
        b, out2 = mk(), []
        b.load_state(pickle.loads(blob))
        ref, out3 = mk(), []
        for i in range(120):
            ref.svc(BasicRecord(i % 2, i // 2, i // 2, float(i)), 0,
                    out3.append)
        for i in range(60, 120):
            b.svc(BasicRecord(i % 2, i // 2, i // 2, float(i)), 0,
                  out2.append)
        ref.eos_flush(out3.append)
        b.eos_flush(out2.append)
        want = {(r.key, r.id): r.value for r in out3}
        got = {(r.key, r.id): r.value for r in out + out2}
        assert want.keys() == got.keys()
        for k in want:
            assert abs(want[k] - got[k]) <= 1e-3 * max(1, abs(want[k]))

    def test_window_fires_on_completing_tuple(self):
        """Liveness: the tuple that completes a window must fire it
        immediately, not the next one (record-at-a-time path)."""
        from windflow_tpu.operators.tpu.ffat_resident import \
            WinSeqFFATResidentLogic
        import jax.numpy as jnp
        lg = WinSeqFFATResidentLogic(lambda t: t.value, jnp.add, 0.0, 16, 8)
        out = []
        for i in range(16):
            lg.svc(BasicRecord(0, i, i * 3, float(i)), 0, out.append)
        assert len(out) == 1 and out[0].value == sum(range(16))
        # CB result ts = last tuple in extent
        assert out[0].ts == 15 * 3

    def test_restore_into_smaller_default_instance(self):
        """Restoring a snapshot must pin the forest to the snapshot's
        row count so new keys never alias checkpointed rows."""
        import pickle
        from windflow_tpu.operators.tpu.ffat_resident import \
            WinSeqFFATResidentLogic
        import jax.numpy as jnp
        a = WinSeqFFATResidentLogic(lambda t: t.value, jnp.add, 0.0, 8, 8,
                                    initial_keys=2)
        out = []
        for i in range(4 * 8):  # 4 keys -> forest grows past 2 rows
            a.svc(BasicRecord(i % 4, i // 4, 0, 1.0), 0, out.append)
        blob = pickle.dumps(a.state_dict())
        b = WinSeqFFATResidentLogic(lambda t: t.value, jnp.add, 0.0, 8, 8)
        b.load_state(pickle.loads(blob))
        out2 = []
        for i in range(6 * 8):  # two NEW keys (4, 5) post-restore
            b.svc(BasicRecord(i % 6, i // 6, 0, 2.0), 0, out2.append)
        by_key = {}
        for r in out2:
            by_key.setdefault(r.key, []).append(r.value)
        # new keys' windows must hold only their own values (8 x 2.0)
        assert by_key[4] == [16.0] and by_key[5] == [16.0]


def test_idle_tick_launches_on_stalled_stream():
    """A source that stalls mid-stream must not withhold fired windows:
    the node's timed gets drive WinSeqTPULogic.idle_tick, which
    launches staged/ready windows once the rate-limit allows."""
    import threading
    import time
    import numpy as np
    import windflow_tpu as wf
    from windflow_tpu.core import Mode, WinType
    from windflow_tpu.core.tuples import TupleBatch
    from windflow_tpu.operators.basic_ops import Sink
    from windflow_tpu.operators.batch_ops import BatchSource
    from windflow_tpu.operators.tpu.win_seq_tpu import WinSeqTPU

    gate = threading.Event()
    state = {"phase": 0}

    def batch(lo):
        idx = lo + np.arange(4096)
        return TupleBatch({"key": idx % 2, "id": idx // 2,
                           "ts": idx // 2, "value": np.ones(4096)})

    def source(ctx):
        ph = state["phase"]
        state["phase"] = ph + 1
        if ph == 0:
            # fires 14 windows/key; launches at svc (rate limit idle)
            # and stamps _last_launch_t
            return batch(0)
        if ph == 1:
            # fires 16 more windows/key, arriving within the rate
            # limit: they stage but can NOT launch at svc -- only an
            # idle tick can deliver them during the stall
            return batch(4096)
        gate.wait(30)
        return None

    count = {"n": 0}
    lock = threading.Lock()

    def sink(item):
        if item is None:
            return
        with lock:
            count["n"] += 1

    g = wf.PipeGraph("stall", Mode.DEFAULT)
    # batch_len high so the size trigger can NOT fire; only the time
    # trigger (via idle ticks) can launch during the stall
    op = WinSeqTPU("sum", 256, 128, WinType.TB, batch_len=1 << 16,
                   max_batch_delay_ms=20.0)
    g.add_source(BatchSource(source, 1)).add(op).add_sink(Sink(sink))
    g.start()
    # all 60 fired windows (30/key up to id 4095) must arrive DURING
    # the stall, before the source is released
    deadline = time.monotonic() + 20
    while count["n"] < 60 and time.monotonic() < deadline:
        time.sleep(0.01)
    stalled_count = count["n"]
    gate.set()
    g.wait_end()
    assert stalled_count >= 60, \
        f"only {stalled_count} windows emitted during the stall"


def test_pallas_winsum_engine_path(monkeypatch):
    """WINDFLOW_PALLAS_WINSUM=1 routes builtin sum batches through the
    hand-scheduled Pallas kernel (interpret mode off TPU) with results
    identical to the XLA paths."""
    monkeypatch.setenv("WINDFLOW_PALLAS_WINSUM", "1")
    eng = WindowComputeEngine("sum")
    rng = np.random.default_rng(2)
    vals = rng.random(5000).astype(np.float64)
    starts = np.sort(rng.integers(0, 4000, 16)).astype(np.int64)
    ends = starts + rng.integers(1, 900, 16)
    out = eng.compute({"value": vals}, starts, ends,
                      np.arange(16)).block()
    expect = [vals[s:e].sum() for s, e in zip(starts, ends)]
    np.testing.assert_allclose(out, expect, rtol=1e-3)


def test_with_max_buffer_builder_knob():
    """withMaxBuffer reaches every device-engine replica, including the
    PLQ replicas of nested Pane_Farm copies."""
    import windflow_tpu as wf
    from windflow_tpu.core import WinType

    op = wf.PaneFarmTPUBuilder("sum", lambda g, it, r: None) \
        .with_parallelism(2, 1).withTBWindows(64, 4) \
        .withMaxBuffer(1 << 20).build()
    assert op.max_buffer_elems == 1 << 20
    for st in op.stages():
        for rep in st.replicas:
            if hasattr(rep, "max_buffer_elems"):
                assert rep.max_buffer_elems == 1 << 20
    nested = wf.WinFarmTPUBuilder(
        wf.PaneFarmTPUBuilder("sum", lambda g, it, r: None)
        .with_parallelism(1, 1).withTBWindows(64, 4)
        .withMaxBuffer(1 << 20).build()).with_parallelism(2).build()
    for st in nested.stages():
        for rep in st.replicas:
            if hasattr(rep, "max_buffer_elems"):
                assert rep.max_buffer_elems == 1 << 20
    seq = wf.WinSeqTPUBuilder("sum").withCBWindows(64, 16) \
        .with_max_buffer(123456).build()
    assert seq.kwargs["max_buffer_elems"] == 123456
    # ... and on every other TPU builder, including WLQ-on-device
    others = [
        wf.WinFarmTPUBuilder("sum").withTBWindows(64, 4)
            .withParallelism(3),
        wf.WinMapReduceTPUBuilder("sum", lambda g, it, r: None)
            .withTBWindows(64, 4).withParallelism(2, 1),
        wf.WinSeqFFATTPUBuilder(lambda t, r: None, "sum")
            .withTBWindows(64, 4),
        wf.KeyFFATTPUBuilder(lambda t, r: None, "sum")
            .withTBWindows(64, 4).withParallelism(2),
        wf.PaneFarmTPUBuilder("sum", lambda g, it, r: None,
                              plq_on_tpu=False)
            .withTBWindows(64, 4).withParallelism(1, 1),
    ]
    for b in others:
        op2 = b.withMaxBuffer(1 << 20).build()
        carriers = [rep for st in op2.stages() for rep in st.replicas
                    if hasattr(rep, "max_buffer_elems")]
        assert carriers, type(op2).__name__
        assert all(r.max_buffer_elems == 1 << 20 for r in carriers), \
            type(op2).__name__
