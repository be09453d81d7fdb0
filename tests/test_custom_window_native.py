"""A user's window function on the native engine's raw-tuple lane
(``tuples``, docs/RUNTIME.md 5d): the lane, the Python store and a plain
numpy per-window sort agree row for row, bit for bit, on seeded streams
(17 keys, windows of 40 panes sliding by one), in order, disordered
within ``triggering_delay`` and cut by EOS; through a snapshot and a
restore; and the placement is said and never silently changed."""
import copy

import numpy as np
import pytest

import windflow_tpu as wf
from windflow_tpu.core.basic import WinType
from windflow_tpu.core.tuples import TupleBatch
from windflow_tpu.operators.tpu.win_seq_tpu import WinSeqTPU, WinSeqTPULogic
from windflow_tpu.runtime.native import NativeWindowEngine, native_available

pytestmark = pytest.mark.skipif(not native_available(),
                                reason="native library not built")

K, WIN, SLIDE, DELAY, CHUNK = 17, 40, 1, 5, 500


def median(gwid, cols, mask):
    """The median, the mean of the two middle values of an even count."""
    import jax.numpy as jnp
    n = mask.sum()
    v = jnp.sort(jnp.where(mask, cols["value"], jnp.inf))
    return ((v[(n - 1) // 2] + v[n // 2]) / 2).astype(jnp.float32)


def weighted(gwid, cols, mask):
    """Order-sensitive: each value times its place in the window (from
    1); whole numbers under 2**24, so exact in float32 whatever the
    order of the adds."""
    import jax.numpy as jnp
    place = jnp.cumsum(mask.astype(jnp.int32))
    return jnp.where(mask, cols["value"] * place, 0).sum()


def np_median(v):
    s = np.sort(v)
    n = len(s)
    return float((np.float32(s[(n - 1) // 2]) + np.float32(s[n // 2])) / 2)


def np_weighted(v):
    return float(np.sum(v * np.arange(1, len(v) + 1)))


FNS = {"median": (median, np_median), "weighted": (weighted, np_weighted)}


def stream(seed, n=6000, disorder=0):
    """Keys drawn at random, ``ts = i // K`` (a tuple a key and pane on
    average, Poisson), whole values 0..99; with ``disorder`` one tuple in
    five comes up to that many stamps late (within the delay)."""
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, K, n).astype(np.int64)
    ts = np.arange(n, dtype=np.int64) // K
    if disorder:
        late = rng.random(n) < 0.2
        ts = np.maximum(ts - late * rng.integers(1, disorder + 1, n), 0)
    vals = rng.integers(0, 100, n).astype(np.float64)
    return keys, ts, vals


def windows(keys, ts, vals):
    """(key, window) -> the window's values in the staging order: by
    stamp, equal stamps in arrival order (every window that holds a
    tuple, the EOS-cut ones included)."""
    out = {}
    for k in range(K):
        mine = keys == k
        t, v = ts[mine], vals[mine]
        order = np.argsort(t, kind="stable")
        t, v = t[order], v[order]
        for w in range(max(0, int(t.min()) - WIN + 1), int(t.max()) + 1):
            lo, hi = np.searchsorted(t, (w * SLIDE, w * SLIDE + WIN))
            if hi > lo:
                out[(k, w)] = v[lo:hi]
    return out


def logic_for(fn, python_store, delay):
    logic = WinSeqTPULogic(fn, WIN, SLIDE, WinType.TB, batch_len=256,
                           triggering_delay=delay, emit_batches=True,
                           async_dispatch=False, max_batch_delay_ms=1e9)
    if python_store:
        logic._native = None
    return logic


def feed(logic, keys, ts, vals, lo, hi, out):
    for i in range(lo, hi, CHUNK):
        j = min(i + CHUNK, hi)
        logic.svc(TupleBatch({"key": keys[i:j], "id": np.arange(i, j),
                              "ts": ts[i:j], "value": vals[i:j]}),
                  0, out.append)


def rows_of(out):
    rows = {}
    for b in out:
        for k, w, v in zip(b.key.tolist(), b.id.tolist(),
                           b["value"].tolist()):
            assert (k, w) not in rows, "a window emitted twice"
            rows[(k, w)] = v
    return rows


def run(fn, stream_, python_store, delay):
    keys, ts, vals = stream_
    logic, out = logic_for(fn, python_store, delay), []
    assert (logic._native is None) == python_store
    feed(logic, keys, ts, vals, 0, len(keys), out)
    logic.eos_flush(out.append)
    return rows_of(out)


@pytest.mark.parametrize("disorder", [0, DELAY],
                         ids=["in_order", "within_delay"])
@pytest.mark.parametrize("name", list(FNS))
def test_the_lane_the_python_store_and_a_sort_agree(name, disorder):
    fn, oracle = FNS[name]
    s = stream(11 + disorder, disorder=disorder)
    want = {kw: oracle(v) for kw, v in windows(*s).items()}
    native = run(fn, s, False, disorder)
    assert native == run(fn, s, True, disorder) == want
    counts = [len(v) for v in windows(*s).values()]
    assert {c % 2 for c in counts} == {0, 1}     # odd and even counts
    assert min(counts) < 10 < WIN - 5 < max(counts)  # EOS-cut and whole


@pytest.mark.parametrize("role", ["seq", "plq"])
def test_count_based_windows_agree_with_the_python_store(role):
    """CB windows over each key's own ids (a result's stamp is its
    window's last tuple's, read from the ring's stamp lane), and a PLQ
    replica's dense windows: the lane's rows are the Python store's."""
    from windflow_tpu.core.basic import Role
    keys, ts, vals = stream(7, n=4000)
    ids = np.zeros(len(keys), np.int64)
    for k in range(K):
        ids[keys == k] = np.arange(int((keys == k).sum()))
    got = []
    for python_store in (False, True):
        logic = WinSeqTPULogic(
            weighted, 24, 8, WinType.CB, batch_len=64, emit_batches=True,
            async_dispatch=False, max_batch_delay_ms=1e9,
            role=Role.SEQ if role == "seq" else Role.PLQ)
        if python_store:
            logic._native = None
        out = []
        for i in range(0, len(keys), CHUNK):
            j = i + CHUNK
            logic.svc(TupleBatch({"key": keys[i:j], "id": ids[i:j],
                                  "ts": ts[i:j], "value": vals[i:j]}),
                      0, out.append)
        logic.eos_flush(out.append)
        # batches, or a record a row where the keys are no column (the
        # Python store's PLQ rows)
        rows = []
        for b in out:
            if isinstance(b, TupleBatch):
                rows += zip(*(np.asarray(c).tolist()
                              for c in (b.key, b.id, b.ts, b["value"])))
            else:
                rows.append((*b.get_control_fields(), b.value))
        got.append(sorted(rows))
    assert got[0] == got[1] and len(got[0]) > 400


@pytest.mark.parametrize("disorder", [0, DELAY])
def test_the_lane_stages_each_window_in_order(disorder):
    """The engine alone, no device: each staged window's slice of the
    flat buffer is its values by stamp, equal stamps in arrival order."""
    keys, ts, vals = stream(5, n=9000, disorder=disorder)
    vals = np.arange(len(keys), dtype=np.float64)   # arrival order shows
    eng = NativeWindowEngine(WIN, SLIDE, True, disorder, kind="tuples")
    got = {}

    def take():
        out = eng.flush(1 << 20)
        if out is None:
            return
        cols, starts, ends, d_keys, gwids = out[:5]
        assert out[6] is None                 # the user's function's own
        for s, e, k, w in zip(starts, ends, d_keys, gwids):
            got[(int(k), int(w))] = cols["value"][s:e].copy()

    for i in range(0, len(keys), 700):
        j = i + 700
        eng.ingest(keys[i:j], ts[i:j], np.arange(i, min(j, len(keys))),
                   vals[i:j])
        take()
    eng.eos()
    take()
    want = windows(keys, ts, vals)
    assert got.keys() == want.keys()
    assert all((got[kw] == want[kw]).all() for kw in want)
    s = eng.snapshot()
    assert s["windows_staged"] == len(want) and s["inputs_ignored"] == 0
    assert s["tuples_staged"] >= len(keys) and s["panes_staged"] > 0
    assert s["keys_live"] == 0            # every key evicted at the end


@pytest.mark.parametrize("python_store", [False, True],
                         ids=["native", "python"])
def test_a_snapshot_and_a_restore_change_no_row(python_store):
    s = stream(23, disorder=DELAY)
    keys, ts, vals = s
    want = run(weighted, s, python_store, DELAY)
    first, out = logic_for(weighted, python_store, DELAY), []
    cut = len(keys) // 2 + 123
    feed(first, keys, ts, vals, 0, cut, out)
    first.quiesce(out.append)
    state = copy.deepcopy(first.state_dict())
    assert ("native" in state) != python_store
    second = logic_for(weighted, python_store, DELAY)
    second.load_state(state)
    feed(second, keys, ts, vals, cut, len(keys), out)
    second.eos_flush(out.append)
    assert rows_of(out) == want


def test_a_tuples_snapshot_is_written_back_byte_for_byte():
    keys, ts, vals = stream(31, disorder=DELAY)
    eng = NativeWindowEngine(WIN, SLIDE, True, DELAY, kind="tuples")
    eng.ingest(keys[:3000], ts[:3000], np.arange(3000), vals[:3000])
    blob = eng.serialize()
    again = NativeWindowEngine(WIN, SLIDE, True, DELAY, kind="tuples")
    again.deserialize(blob)
    assert again.serialize()["native"] == blob["native"]
    with pytest.raises(ValueError):       # a count engine's is another kind
        NativeWindowEngine(WIN, SLIDE, True, DELAY,
                           kind="count").deserialize(blob)


def test_the_placement_is_said_and_a_host_request_refused(capsys):
    from windflow_tpu.operators.basic_ops import Sink
    from windflow_tpu.operators.batch_ops import BatchSource
    keys, ts, vals = stream(3, n=2000)
    chunks = iter(range(0, len(keys), CHUNK))
    got = []

    def source(ctx=None):
        i = next(chunks, None)
        if i is None:
            return None
        j = i + CHUNK
        return TupleBatch({"key": keys[i:j], "id": np.arange(i, j),
                           "ts": ts[i:j], "value": vals[i:j]})

    def sink(item):
        if item is not None:
            got.append(item)

    g = wf.PipeGraph("custom_native", wf.Mode.DEFAULT)
    g.add_source(BatchSource(source)).add(WinSeqTPU(
        median, WIN, SLIDE, WinType.TB, emit_batches=True,
        name="plug_median")).add_sink(Sink(sink))
    g.run()
    entry = next(p for p in g.placements if "plug_median" in p["operator"])
    assert entry["placement"] == "device" and entry["store"] == "native"
    assert "the user's window function runs on the device" \
        in capsys.readouterr().err
    assert rows_of(got) == {kw: np_median(v)
                            for kw, v in windows(keys, ts, vals).items()}
    h = wf.PipeGraph("custom_host", wf.Mode.DEFAULT)
    op = WinSeqTPU(median, WIN, SLIDE, WinType.TB, emit_batches=True)
    h.add_source(BatchSource(lambda ctx=None: None)).add(op) \
        .add_sink(Sink(sink))
    for node in h._all_nodes():
        if isinstance(node.logic, WinSeqTPULogic):
            node.logic.placement = "host"
    with pytest.raises(ValueError, match="no host program"):
        h.start()
