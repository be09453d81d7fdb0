"""The mp_tests matrix: full pipelines in the reference test style.

Replicates the structure of tests/mp_tests_cpu + mp_tests_gpu
(SURVEY.md §4): a pipeline prefix source -> filter -> flatmap -> map
before the window operator, every window operator x CB/TB x
DEFAULT/DETERMINISTIC/PROBABILISTIC (the _oop/_prob variants) x string
keys (_string variants), with randomized parallelisms and the
global-aggregate determinism oracle.
"""
import random
import threading
import zlib

import pytest

import windflow_tpu as wf
from windflow_tpu.core import BasicRecord, Mode, WinType
from windflow_tpu.utils.synthetic import (ordered_keyed_stream,
                                          pareto_ooo_stream)

N_KEYS, PER_KEY = 4, 60
WIN, SLIDE = 10, 5


class SumSink:
    def __init__(self):
        self.lock = threading.Lock()
        self.total = 0.0
        self.count = 0

    def __call__(self, rec):
        if rec is not None:
            with self.lock:
                self.total += rec.value
                self.count += 1


def sum_win(gwid, it, result):
    result.value = sum(t.value for t in it)


def prefix_ops(rnd):
    """source -> filter(pass-all) -> flatmap(x1) -> map(identity) with
    randomized parallelisms (test_mp_* pipeline prefix)."""

    def keep(t):
        return True

    def fm(t, shipper):
        shipper.push(t)

    def ident(t):
        pass

    return (wf.FilterBuilder(keep).with_parallelism(rnd.randint(1, 3)).build(),
            wf.FlatMapBuilder(fm).with_parallelism(rnd.randint(1, 3)).build(),
            wf.MapBuilder(ident).with_parallelism(rnd.randint(1, 3)).build())


def build_window_op(kind, win_type, par, win=None, slide=None):
    win = WIN if win is None else win
    slide = SLIDE if slide is None else slide
    if kind == "wf":
        b = wf.WinFarmBuilder(sum_win).with_parallelism(par)
    elif kind == "kf":
        b = wf.KeyFarmBuilder(sum_win).with_parallelism(par)
    elif kind == "kff":
        b = wf.KeyFFATBuilder(lambda t, r: setattr(r, "value", t.value),
                              lambda a, c, o: setattr(o, "value",
                                                      a.value + c.value)) \
            .with_parallelism(par)
    elif kind == "pf":
        b = wf.PaneFarmBuilder(sum_win, sum_win) \
            .with_parallelism(par, max(1, par - 1))
    elif kind == "wmr":
        b = wf.WinMapReduceBuilder(sum_win, sum_win) \
            .with_parallelism(max(2, par), 1)
    elif kind == "kf_tpu":
        b = wf.KeyFarmTPUBuilder("sum").with_parallelism(par)
    elif kind == "kff_tpu":
        b = wf.KeyFFATTPUBuilder(lambda t: t.value, "sum") \
            .with_parallelism(par)
    elif kind == "kf+pf":
        inner = wf.PaneFarmBuilder(sum_win, sum_win).with_parallelism(2, 1) \
            .with_tb_windows(win, slide).build() if win_type == WinType.TB \
            else wf.PaneFarmBuilder(sum_win, sum_win).with_parallelism(2, 1) \
            .with_cb_windows(win, slide).build()
        return wf.KeyFarmBuilder(inner).with_parallelism(par).build()
    elif kind == "wf+pf":
        inner = _with_wins(wf.PaneFarmBuilder(sum_win, sum_win)
                           .with_parallelism(2, 1), win_type, win, slide).build()
        return wf.WinFarmBuilder(inner).with_parallelism(par).build()
    elif kind == "wf+wmr":
        inner = _with_wins(wf.WinMapReduceBuilder(sum_win, sum_win)
                           .with_parallelism(2, 1), win_type, win, slide).build()
        return wf.WinFarmBuilder(inner).with_parallelism(par).build()
    elif kind == "kf+wmr":
        inner = _with_wins(wf.WinMapReduceBuilder(sum_win, sum_win)
                           .with_parallelism(2, 1), win_type, win, slide).build()
        return wf.KeyFarmBuilder(inner).with_parallelism(par).build()
    # device-side complex nesting (win_farm_gpu.hpp:73-76,
    # key_farm_gpu.hpp:254): the inner device stage runs builtin 'sum'
    elif kind == "wf+pf_tpu":
        inner = _with_wins(wf.PaneFarmTPUBuilder("sum", sum_win)
                           .with_parallelism(2, 1), win_type, win, slide).build()
        return wf.WinFarmTPUBuilder(inner).with_parallelism(par).build()
    elif kind == "kf+pf_tpu":
        inner = _with_wins(wf.PaneFarmTPUBuilder("sum", sum_win)
                           .with_parallelism(2, 1), win_type, win, slide).build()
        return wf.KeyFarmTPUBuilder(inner).with_parallelism(par).build()
    elif kind == "wf+wmr_tpu":
        inner = _with_wins(wf.WinMapReduceTPUBuilder("sum", sum_win)
                           .with_parallelism(2, 1), win_type, win, slide).build()
        return wf.WinFarmTPUBuilder(inner).with_parallelism(par).build()
    elif kind == "kf+wmr_tpu":
        inner = _with_wins(wf.WinMapReduceTPUBuilder("sum", sum_win)
                           .with_parallelism(2, 1), win_type, win, slide).build()
        return wf.KeyFarmTPUBuilder(inner).with_parallelism(par).build()
    else:
        raise ValueError(kind)
    return _with_wins(b, win_type, win, slide).build()


def _with_wins(builder, win_type, win=None, slide=None):
    win = WIN if win is None else win
    slide = SLIDE if slide is None else slide
    return (builder.with_tb_windows(win, slide) if win_type == WinType.TB
            else builder.with_cb_windows(win, slide))


def expected_total(per_key, n_keys, win, slide):
    """Sum over all keys of all window sums with EOS flush."""
    total = 0.0
    g = 0
    while g * slide < per_key:
        total += sum(v for v in range(per_key)
                     if g * slide <= v < g * slide + win)
        g += 1
    return total * n_keys


@pytest.mark.parametrize("kind", ["wf", "kf", "kff", "pf", "wmr",
                                  "kf+pf", "wf+pf", "wf+wmr", "kf+wmr",
                                  "wf+pf_tpu", "kf+pf_tpu",
                                  "wf+wmr_tpu", "kf+wmr_tpu"])
@pytest.mark.parametrize("win_type", [WinType.CB, WinType.TB])
def test_matrix_randomized_parallelism(kind, win_type):
    """The core oracle: R randomized repetitions with different random
    parallelisms (mp_tests style, test_mp_gpu_kff_cb.cpp:81-95, which
    draws 1..9), totals must match each other and the sequential
    expectation.  Streams run long enough (96 windows/key) that even a
    parallelism-9 farm gives every worker >= 10 windows, crossing
    archive-purge and renumber boundaries on each."""
    # the parallel prefix destroys per-key order, so the matrix runs in
    # DETERMINISTIC mode (ordering collectors); the DEFAULT-mode
    # renumbering path has its own dedicated test below with tumbling
    # windows, whose totals are arrival-order invariant.
    mode = Mode.DETERMINISTIC
    per_key = 480
    # WF(PF) copies run with private slide = SLIDE * outer_par, and
    # Pane_Farm requires slide < win (pane_farm.hpp:170-173) -- the
    # pf-in-WF kinds get a window wide enough to stay valid at
    # parallelism 9
    win = 50 if kind in ("wf+pf", "wf+pf_tpu") else WIN
    totals = []
    for trial in range(3):
        # crc32, not hash(): PYTHONHASHSEED randomizes hash() per run,
        # which once let a routing bug hide behind a lucky
        # parallelism=1 draw
        rnd = random.Random(100 * trial + zlib.crc32(kind.encode()) % 50)
        sink = SumSink()
        g = wf.PipeGraph("mp", mode)
        fil, fm, mp_ = prefix_ops(rnd)
        # trial 0 always runs the outer farm at parallelism >= 2 so
        # nesting arithmetic is exercised every run
        op = build_window_op(kind, win_type,
                             rnd.randint(2, 9) if trial == 0
                             else rnd.randint(1, 9), win)
        pipe = g.add_source(wf.SourceBuilder(
            ordered_keyed_stream(N_KEYS, per_key)).build())
        if mode == Mode.DEFAULT:
            pipe.chain(fil).chain(fm).chain(mp_)
        else:
            pipe.add(fil).add(fm).add(mp_)
        pipe.add(op).add_sink(wf.SinkBuilder(sink).build())
        g.run()
        totals.append(sink.total)
    assert totals[0] == totals[1] == totals[2] == \
        expected_total(per_key, N_KEYS, win, SLIDE)


@pytest.mark.parametrize("kind", ["kf", "kff", "wf", "pf", "wmr",
                                  "kf_tpu", "kff_tpu"])
def test_string_keys(kind):
    """_string variants: non-integral keys through hash routing, for
    every window operator family incl. the device engines (the
    reference's *_string tests; device record lanes intern non-integral
    keys into a reserved id range and restore them on results).  CB
    kinds renumber arrival-dense ids in DEFAULT mode; the multicast
    kinds run TB windows over the stream's own timestamps."""
    sink = SumSink()
    g = wf.PipeGraph("mp", Mode.DEFAULT)
    cb = kind in ("kf", "kff", "kf_tpu", "kff_tpu")
    src = pareto_ooo_stream(N_KEYS, PER_KEY, jitter=1, key_type="str")
    op = build_window_op(kind, WinType.CB if cb else WinType.TB, 3)
    g.add_source(wf.SourceBuilder(src).build()) \
        .add(op).add_sink(wf.SinkBuilder(sink).build())
    g.run()
    if cb:
        assert sink.total == expected_total(PER_KEY, N_KEYS, WIN, SLIDE)
    else:
        assert sink.total == expected_sum_of_events(src.events, WIN, SLIDE)


def interleaved_batch_source(N, BS, NK, value_fn, stride=2):
    """Batch-source body where replica r emits every ``stride``-th
    batch of a shared [0, N) timeline (round-robin keys, dense per-key
    ids) -- the columnar-plane fixture shared by the ordering-mode and
    soak tests."""
    import numpy as np
    from windflow_tpu.core.tuples import TupleBatch

    state = {}

    def source(ctx):
        ridx = ctx.get_replica_index()
        st = state.setdefault(ridx, {"b": ridx})
        base = st["b"] * BS
        if base >= N:
            return None
        n = min(BS, N - base)
        idx = base + np.arange(n)
        st["b"] += stride
        ids = idx // NK
        return TupleBatch({"key": idx % NK, "id": ids, "ts": ids,
                           "value": value_fn(ids)})

    return source


def collect_dropped(g):
    """Dropped-record control fields from every K-slack collector,
    split into the two independent drop planes: window-stage collectors
    drop late SOURCE tuples; the sink collector drops late window
    RESULTS (cross-replica result disorder)."""
    dropped_src, dropped_res = [], []
    for node in g._all_nodes():
        dr = getattr(node.logic, "dropped_records", None)
        if dr is None:
            continue
        (dropped_res if "sink" in node.name else dropped_src).extend(dr)
    return dropped_src, dropped_res


def test_probabilistic_mode_out_of_order():
    """_prob variants: K-slack collectors on an out-of-order stream.
    Exact accounting oracle: every source tuple is either emitted
    in-order by a K-slack collector or recorded as dropped — the sink
    total must equal the window sums over exactly the surviving events,
    and the graph's central drop counter must match the collectors'
    dropped-record lists (kslack_node.hpp:193-200 drop rule)."""
    sink = SumSink()
    g = wf.PipeGraph("prob", Mode.PROBABILISTIC)
    src = pareto_ooo_stream(N_KEYS, PER_KEY, jitter=4)
    op = wf.KeyFarmBuilder(sum_win).with_parallelism(3) \
        .with_tb_windows(50, 25).build()
    g.add_source(wf.SourceBuilder(src).build()) \
        .add(op).add_sink(wf.SinkBuilder(sink).build())
    g.run()
    assert sink.count > 0
    dropped_src, dropped_res = collect_dropped(g)
    assert g.get_num_dropped_tuples() == len(dropped_src) + len(dropped_res)
    dropped_ids = {(k, tid) for k, tid, _ts in dropped_src}
    assert len(dropped_ids) == len(dropped_src)  # no tuple dropped twice
    surviving = [e for e in src.events if (e[0], e[1]) not in dropped_ids]
    assert len(surviving) + len(dropped_src) == len(src.events)
    wins = window_sums_of_events(surviving, 50, 25)
    expect = (sum(wins.values())
              - sum(wins[(k, gw)] for k, gw, _ts in dropped_res))
    assert sink.total == expect


def window_sums_of_events(events, win, slide):
    """Per-(key, gwid) window sums with EOS flush of opened windows."""
    per_key = {}
    for k, tid, ts in events:
        per_key.setdefault(k, []).append((ts, float(tid)))
    wins = {}
    for k, recs in per_key.items():
        max_ts = max(ts for ts, _ in recs)
        g = 0
        while g * slide <= max_ts:
            wins[(k, g)] = sum(v for ts, v in recs
                               if g * slide <= ts < g * slide + win)
            g += 1
    return wins


def expected_sum_of_events(events, win, slide):
    return sum(window_sums_of_events(events, win, slide).values())


@pytest.mark.parametrize("kind", ["kf", "wf", "pf", "wmr", "kf_tpu"])
def test_triggering_delay_absorbs_disorder_exact(kind):
    """A triggering delay covering the source's maximum disorder makes
    TB windows exact on an out-of-order stream (the DELAYED state,
    window.hpp:114): windows hold their fire until the delay passes, so
    stragglers still land inside their windows -- the reference's _oop
    variants, across every operator family."""
    def build(par):
        if kind == "kf":
            return wf.KeyFarmBuilder(sum_win).with_parallelism(par) \
                .with_tb_windows(50, 25, 500).build()
        if kind == "wf":
            return wf.WinFarmBuilder(sum_win).with_parallelism(par) \
                .with_tb_windows(50, 25, 500).build()
        if kind == "pf":
            return wf.PaneFarmBuilder(sum_win, sum_win) \
                .with_parallelism(par, 1) \
                .with_tb_windows(50, 25, 500).build()
        if kind == "wmr":
            return wf.WinMapReduceBuilder(sum_win, sum_win) \
                .with_parallelism(max(2, par), 1) \
                .with_tb_windows(50, 25, 500).build()
        return wf.KeyFarmTPUBuilder("sum").with_parallelism(par) \
            .with_tb_windows(50, 25, 500).build()

    totals = []
    for par in (1, 3):
        sink = SumSink()
        g = wf.PipeGraph("det", Mode.DEFAULT)
        src = pareto_ooo_stream(N_KEYS, PER_KEY, jitter=4, seed=7)
        g.add_source(wf.SourceBuilder(src).build()) \
            .add(build(par)).add_sink(wf.SinkBuilder(sink).build())
        g.run()
        totals.append(sink.total)
    assert totals[0] == totals[1]
    src = pareto_ooo_stream(N_KEYS, PER_KEY, jitter=4, seed=7)
    assert totals[0] == expected_sum_of_events(src.events, 50, 25)


def test_deterministic_mode_cross_channel_exact():
    """DETERMINISTIC mode restores order ACROSS channels: two in-order
    source replicas with interleaved timestamps produce exact results
    at any parallelism (the ordering collector's contract)."""
    per_src = 40

    def make_src():
        state = {}

        def fn(shipper, ctx):
            ridx = ctx.get_replica_index()
            st = state.setdefault(ridx, {"i": 0})
            i = st["i"]
            if i >= per_src:
                return False
            key = i % N_KEYS
            tid = i // N_KEYS
            # replica 0: even ts, replica 1: odd ts -- interleaved
            shipper.push(BasicRecord(key, tid, 2 * tid + ridx,
                                     float(tid)))
            st["i"] = i + 1
            return True

        return fn

    totals = []
    for par in (1, 3):
        sink = SumSink()
        g = wf.PipeGraph("det2", Mode.DETERMINISTIC)
        src = wf.SourceBuilder(make_src()).with_parallelism(2).build()
        op = wf.KeyFarmBuilder(sum_win).with_parallelism(par) \
            .with_tb_windows(8, 4).build()
        g.add_source(src).add(op).add_sink(wf.SinkBuilder(sink).build())
        g.run()
        totals.append(sink.total)
    events = []
    for ridx in range(2):
        for i in range(per_src):
            events.append((i % N_KEYS, i // N_KEYS, 2 * (i // N_KEYS) + ridx))
    assert totals[0] == totals[1] == expected_sum_of_events(events, 8, 4)


@pytest.mark.parametrize("kind", ["kf", "kff"])
def test_cb_default_renumbering_tumbling(kind):
    """DEFAULT mode + CB tumbling windows behind a parallel prefix:
    per-key renumbering (win_seq.hpp:342-347) assigns arrival-dense ids,
    and tumbling sums are invariant to arrival order."""
    totals = []
    for trial in range(2):
        rnd = random.Random(trial)
        sink = SumSink()
        g = wf.PipeGraph("renum", Mode.DEFAULT)
        fil, fm, mp_ = prefix_ops(rnd)
        if kind == "kf":
            op = wf.KeyFarmBuilder(sum_win).with_parallelism(3) \
                .with_cb_windows(10, 10).build()
        else:
            op = wf.KeyFFATBuilder(
                lambda t, r: setattr(r, "value", t.value),
                lambda a, c, o: setattr(o, "value", a.value + c.value)) \
                .with_parallelism(3).with_cb_windows(10, 10).build()
        g.add_source(wf.SourceBuilder(
            ordered_keyed_stream(N_KEYS, PER_KEY)).build()) \
            .add(fil).add(fm).add(mp_) \
            .add(op).add_sink(wf.SinkBuilder(sink).build())
        g.run()
        totals.append(sink.total)
    assert totals[0] == totals[1] == expected_total(PER_KEY, N_KEYS, 10, 10)


@pytest.mark.parametrize("kind", ["wf", "wf+pf"])
def test_cb_broadcast_plane_filtered_prefix(kind):
    """CB windows entering a WF-multicast stage behind a FILTERING
    prefix: upstream ids are not per-key dense, so id-based multicast
    membership is wrong -- the broadcast + TS-renumbering plane
    (multipipe.hpp:1039-1051) must yield windows over the arrival-dense
    renumbered ids of the surviving tuples."""
    def keep(t):
        return t.value % 3 != 0  # drop every third value

    per_key = 90
    survivors = [float(v) for v in range(per_key) if v % 3 != 0]
    # wf+pf needs win > SLIDE * outer_par (pane_farm.hpp:170-173)
    win = 20 if kind == "wf+pf" else WIN

    def expect_total():
        total, g = 0.0, 0
        while g * SLIDE < len(survivors):
            total += sum(survivors[g * SLIDE: g * SLIDE + win])
            g += 1
        return total * N_KEYS

    totals = []
    for par in (2, 3):
        sink = SumSink()
        g = wf.PipeGraph("cbf", Mode.DETERMINISTIC)
        op = build_window_op(kind, WinType.CB, par, win)
        g.add_source(wf.SourceBuilder(
            ordered_keyed_stream(N_KEYS, per_key)).build()) \
            .add(wf.FilterBuilder(keep).build()) \
            .add(op).add_sink(wf.SinkBuilder(sink).build())
        g.run()
        totals.append(sink.total)
    assert totals[0] == totals[1] == expect_total()


@pytest.mark.parametrize("mode", [Mode.DETERMINISTIC, Mode.PROBABILISTIC])
def test_columnar_plane_ordering_modes(mode):
    """The batch plane under DETERMINISTIC/PROBABILISTIC: TupleBatch
    items ride the collectors' columnar lanes (per-channel sort-merge /
    columnar K-slack) -- two batch sources with interleaved-batch
    timestamps through a TB device window produce the exact oracle
    (DETERMINISTIC) or exact accounting (PROBABILISTIC in-order input
    drops nothing)."""
    import numpy as np
    from windflow_tpu.core.tuples import TupleBatch
    from windflow_tpu.operators.batch_ops import BatchSource
    from windflow_tpu.operators.basic_ops import Sink
    from windflow_tpu.operators.tpu.win_seq_tpu import WinSeqTPU

    N, BS, NK, WINL, SL = 40_000, 2048, 4, 100, 50
    source = interleaved_batch_source(
        N, BS, NK, lambda ids: ids.astype(np.float64), stride=2)

    got = {}
    lock = threading.Lock()

    def sink(item):
        if item is None:
            return
        with lock:
            if isinstance(item, TupleBatch):
                for j in range(len(item)):
                    got[(int(item.key[j]), int(item.id[j]))] = \
                        float(item["value"][j])
            else:
                k, w, _ = item.get_control_fields()
                got[(k, w)] = item.value

    g = wf.PipeGraph("colmode", mode)
    op = WinSeqTPU("sum", WINL, SL, WinType.TB, batch_len=256,
                   emit_batches=True)
    g.add_source(BatchSource(source, 2)).add(op) \
        .add_sink(Sink(sink))
    g.run()
    per_key = N // NK
    if mode == Mode.DETERMINISTIC:
        expect = {}
        for k in range(NK):
            w = 0
            while w * SL < per_key:
                expect[(k, w)] = float(sum(
                    v for v in range(per_key)
                    if w * SL <= v < w * SL + WINL))
                w += 1
        assert got == expect
        assert g.get_num_dropped_tuples() == 0
        return
    # PROBABILISTIC is lossy until K adapts to the cross-replica skew:
    # exact accounting instead (every tuple either contributes or is in
    # a collector's dropped_records; same for window-result batches)
    dropped_src, dropped_res = collect_dropped(g)
    assert g.get_num_dropped_tuples() == len(dropped_src) + len(dropped_res)
    dropped_ids = {(k, t) for k, t, _ in dropped_src}
    events = [(i % NK, i // NK, i // NK) for i in range(N)]
    surviving = [e for e in events if (e[0], e[1]) not in dropped_ids]
    wins = window_sums_of_events(surviving, WINL, SL)
    expect_total = (sum(wins.values())
                    - sum(wins[(k, gw)] for k, gw, _ in dropped_res))
    assert sum(got.values()) == expect_total


def test_mixed_plane_collector_rejected():
    """A collector serving both records and TupleBatches would hold two
    independent orderings; the mix is rejected loudly."""
    import numpy as np
    from windflow_tpu.core.basic import OrderingMode
    from windflow_tpu.core.tuples import TupleBatch
    from windflow_tpu.runtime.ordering import KSlackLogic, OrderingLogic

    for logic in (OrderingLogic(OrderingMode.TS, 1), KSlackLogic()):
        logic.svc(BasicRecord(0, 0, 0, 1.0), 0, lambda x: None)
        with pytest.raises(RuntimeError, match="mixed"):
            logic.svc(TupleBatch({"key": np.zeros(1, np.int64),
                                  "id": np.zeros(1, np.int64),
                                  "ts": np.zeros(1, np.int64),
                                  "value": np.ones(1)}), 0,
                      lambda x: None)


def test_eos_markers_are_plane_neutral():
    """Batch streams carry per-key RECORD EOS markers (WFEmitter); the
    mixed-plane guard must not reject them."""
    import numpy as np
    from windflow_tpu.core.basic import OrderingMode
    from windflow_tpu.core.tuples import TupleBatch
    from windflow_tpu.runtime.node import EOSMarker
    from windflow_tpu.runtime.ordering import KSlackLogic, OrderingLogic

    for logic in (OrderingLogic(OrderingMode.TS, 1), KSlackLogic()):
        logic.svc(TupleBatch({"key": np.zeros(1, np.int64),
                              "id": np.zeros(1, np.int64),
                              "ts": np.zeros(1, np.int64),
                              "value": np.ones(1)}), 0, lambda x: None)
        logic.svc(EOSMarker(BasicRecord(0, 5, 5, 0.0)), 0,
                  lambda x: None)  # must not raise


def test_kslack_adaptive_k_converges():
    """K-slack drop-rate characterization (advisor r3 follow-up):
    SOURCE-plane drops are deterministic (one source thread, fixed
    partition), and with bounded disorder the adaptive K = max observed
    delay covers the jitter after a warm-up prefix -- so source drops
    stay under 2% and none occur in the stream's second half
    (kslack_node.hpp:93-139 adaptation, :193-200 drop rule).

    The RESULT plane (sink collector) is deliberately NOT bounded here:
    its disorder is cross-replica scheduling skew, which varies run to
    run (observed 3-255 dropped results for this same config), so the
    only stable claim is exact accounting -- every drop is recorded and
    the graph counter matches."""
    per_key, n_keys = 600, 4
    sink = SumSink()
    g = wf.PipeGraph("kconv", Mode.PROBABILISTIC)
    src = pareto_ooo_stream(n_keys, per_key, jitter=6, seed=3)
    op = build_window_op("kf", WinType.TB, 3)
    g.add_source(wf.SourceBuilder(src).build()) \
        .add(op).add_sink(wf.SinkBuilder(sink).build())
    g.run()

    dropped_src, dropped_res = collect_dropped(g)
    assert g.get_num_dropped_tuples() == len(dropped_src) + len(dropped_res)
    n_events = len(src.events)
    assert sink.count > 0
    # source drop fraction is small...
    assert len(dropped_src) <= 0.02 * n_events, (
        len(dropped_src), n_events)
    # ...and K has converged: nothing from the stream's second half
    # (by per-key tuple index) is dropped
    half = per_key // 2
    late_drops = [(k, tid) for k, tid, _ts in dropped_src if tid >= half]
    assert not late_drops, late_drops


def test_columnar_plane_soak_deterministic():
    """Scale soak for the columnar DETERMINISTIC plane: 2M events from
    two interleaved batch sources through the device window engine,
    exact per-window oracle. Catches watermark/merge bugs that only
    appear past many drain cycles and archive-purge boundaries (the
    40k-event test above cannot)."""
    import numpy as np
    from windflow_tpu.core.tuples import TupleBatch
    from windflow_tpu.operators.basic_ops import Sink
    from windflow_tpu.operators.batch_ops import BatchSource
    from windflow_tpu.operators.tpu.win_seq_tpu import WinSeqTPU

    N, BS, NK, WINL, SL = 2_000_000, 65_536, 16, 1024, 512
    source = interleaved_batch_source(
        N, BS, NK, lambda ids: np.ones(len(ids), np.float32), stride=2)

    tot = {"windows": 0, "sum": 0.0}
    lock = threading.Lock()

    def sink(item):
        if item is None:
            return
        with lock:
            if isinstance(item, TupleBatch):
                tot["windows"] += len(item)
                tot["sum"] += float(item["value"].sum())
            else:
                tot["windows"] += 1
                tot["sum"] += item.value

    g = wf.PipeGraph("soak", Mode.DETERMINISTIC)
    op = WinSeqTPU("sum", WINL, SL, WinType.TB, batch_len=4096,
                   emit_batches=True)
    g.add_source(BatchSource(source, 2)).add(op).add_sink(Sink(sink))
    g.run()

    per_key = N // NK
    exp_windows, exp_sum, w = 0, 0, 0
    while w * SL < per_key:
        exp_windows += 1
        exp_sum += min(per_key, w * SL + WINL) - w * SL
        w += 1
    assert tot["windows"] == exp_windows * NK, (tot["windows"],
                                                exp_windows * NK)
    assert tot["sum"] == float(exp_sum * NK), (tot["sum"], exp_sum * NK)


def test_chunked_synth_soak_exact_oracle():
    """Scale soak of the headline lane: 2M events as SynthChunk
    descriptors through the fused C++ generate+fold, EVERY window's sum
    checked against the closed form of the synthetic law (value =
    global event index mod 97 -- per-window sums are exactly
    computable, so this catches any drift between the fused lane and
    the law across many eviction/flush cycles)."""
    import numpy as np
    from windflow_tpu.operators.basic_ops import Sink
    from windflow_tpu.operators.synth import SyntheticSource
    from windflow_tpu.operators.tpu.win_seq_tpu import WinSeqTPU

    N, NK, WINL, SL, VMOD = 2_000_000, 16, 1024, 512, 97
    got = {}
    lock = threading.Lock()

    def sink(item):
        if item is None:
            return
        with lock:
            for j in range(len(item)):
                got[(int(item.key[j]), int(item.id[j]))] = \
                    float(item["value"][j])

    g = wf.PipeGraph("chunk-soak", Mode.DEFAULT)
    op = WinSeqTPU("sum", WINL, SL, WinType.TB, batch_len=4096,
                   emit_batches=True)
    g.add_source(SyntheticSource(N, NK, batch=131_072, chunked=True)) \
        .add(op).add_sink(Sink(sink))
    g.run()

    per_key = N // NK
    # oracle: value of (key k, id i) = (i * NK + k) % VMOD; window sums
    # via one vectorized pass per key over the law
    ids = np.arange(per_key, dtype=np.int64)
    n_windows = -(-per_key // SL)
    checked = 0
    for k in range(NK):
        vals = ((ids * NK + k) % VMOD).astype(np.float64)
        cs = np.concatenate([[0.0], np.cumsum(vals)])
        for w in range(n_windows):
            lo, hi = w * SL, min(w * SL + WINL, per_key)
            want = cs[hi] - cs[lo]
            assert got[(k, w)] == want, ((k, w), got[(k, w)], want)
            checked += 1
    assert checked == len(got) == n_windows * NK


@pytest.mark.parametrize("kind", ["wf", "kf", "kff", "wmr"])
@pytest.mark.parametrize("win_type", [WinType.CB, WinType.TB])
def test_hopping_windows_matrix(kind, win_type):
    """Hopping windows (slide > win leave gaps, win_seq.hpp:388-411):
    gap tuples belong to NO window on every engine -- including the
    FFAT engine, whose pending buffer once leaked the previous
    window's trigger tuple into the next window (the r4 hopping fix).
    Pane_Farm kinds are excluded: pane decomposition is
    sliding-windows-only and rejects win <= slide."""
    win, slide, per_key = 4, 10, 200
    totals = []
    for par in (1, 3):
        sink = SumSink()
        g = wf.PipeGraph("hop", Mode.DETERMINISTIC)
        op = build_window_op(kind, win_type, par, win, slide)
        g.add_source(wf.SourceBuilder(
            ordered_keyed_stream(N_KEYS, per_key)).build()) \
            .add(op).add_sink(wf.SinkBuilder(sink).build())
        g.run()
        totals.append(sink.total)
    assert totals[0] == totals[1] == \
        expected_total(per_key, N_KEYS, win, slide)


@pytest.mark.parametrize("geometry", [(1, 1, 40), (1, 2, 40),
                                      (100, 10, 7), (100, 100, 37),
                                      (3, 7, 50)])
@pytest.mark.parametrize("kind", ["wf", "kff", "wmr",
                                  "kf_tpu", "kff_tpu"])
def test_window_geometry_edges(kind, geometry):
    """Degenerate window geometries against the sequential oracle:
    win=1, tumbling win=slide, windows longer than the whole stream
    (EOS flush emits only opened partials), and hopping -- across host
    and device engine families. The full sweep (12 kinds x 8 geometries
    x CB/TB, 0 mismatches) ran offline; this keeps the spiciest
    fraction as regression armor."""
    win, slide, per_key = geometry
    totals = []
    for win_type in (WinType.CB, WinType.TB):
        sink = SumSink()
        g = wf.PipeGraph("geo", Mode.DETERMINISTIC)
        if kind == "kf_tpu":
            op = _with_wins(wf.KeyFarmTPUBuilder("sum")
                            .with_parallelism(3), win_type, win, slide) \
                .build()
        elif kind == "kff_tpu":
            op = _with_wins(wf.KeyFFATTPUBuilder(lambda t: t.value, "sum")
                            .with_parallelism(3), win_type, win, slide) \
                .build()
        else:
            op = build_window_op(kind, win_type, 3, win, slide)
        g.add_source(wf.SourceBuilder(
            ordered_keyed_stream(N_KEYS, per_key)).build()) \
            .add(op).add_sink(wf.SinkBuilder(sink).build())
        g.run()
        totals.append(sink.total)
    expect = expected_total(per_key, N_KEYS, win, slide)
    assert totals[0] == totals[1] == expect, (totals, expect)


def test_string_keys_device_results_carry_original_keys():
    """Interned device-plane keys are restored on emitted results (the
    sink sees 'user_3', not the reserved internal id), and the intern
    tables survive a state_dict round trip."""
    from windflow_tpu.operators.tpu.win_seq_tpu import WinSeqTPULogic

    seen = set()
    lock = threading.Lock()

    def sink(rec):
        if rec is not None:
            with lock:
                seen.add(rec.key)

    state = {"i": 0}

    def src(shipper, ctx):
        i = state["i"]
        if i >= 400:
            return False
        shipper.push(BasicRecord(f"user_{i % 4}", i // 4, i // 4,
                                 float(i)))
        state["i"] = i + 1
        return True

    g = wf.PipeGraph("strdev", Mode.DEFAULT)
    g.add_source(wf.SourceBuilder(src).build()) \
        .add(wf.WinSeqTPUBuilder("sum").withCBWindows(20, 10).build()) \
        .add_sink(wf.SinkBuilder(sink).build())
    g.run()
    assert seen == {f"user_{k}" for k in range(4)}, seen

    logic = WinSeqTPULogic("sum", 20, 10, WinType.CB)
    if logic._native is None:
        pytest.skip("native engine unavailable: intern round-trip "
                    "rides the native snapshot")
    logic._native.intern_key("alpha")
    logic._native.intern_key("beta")
    st = logic.state_dict()
    fresh = WinSeqTPULogic("sum", 20, 10, WinType.CB)
    fresh.load_state(st)
    assert fresh._native.key_intern == logic._native.key_intern
    assert fresh._native.key_extern[logic._native.key_intern["beta"]] \
        == "beta"


def test_mixed_int_and_string_keys_device_batches():
    """Int and string keys in ONE stream through the native device lane
    with columnar output: int-only result batches stay columnar, any
    batch carrying an interned key degrades to records, and every
    original key appears on results."""
    from windflow_tpu.core.tuples import TupleBatch as TB

    seen, lock = set(), threading.Lock()

    def sink(item):
        if item is None:
            return
        with lock:
            if isinstance(item, TB):
                seen.update(int(k) for k in item.key)
            else:
                seen.add(item.key)

    state = {"i": 0}

    def src(shipper, ctx):
        i = state["i"]
        if i >= 400:
            return False
        key = i % 2 if i % 4 < 2 else f"s{i % 2}"
        shipper.push(BasicRecord(key, i // 4, i // 4, 1.0))
        state["i"] = i + 1
        return True

    g = wf.PipeGraph("mixed", Mode.DEFAULT)
    g.add_source(wf.SourceBuilder(src).build()) \
        .add(wf.WinSeqTPUBuilder("sum").withCBWindows(10, 5)
             .withBatchOutput().build()) \
        .add_sink(wf.SinkBuilder(sink).build())
    g.run()
    assert seen == {0, 1, "s0", "s1"}, seen
