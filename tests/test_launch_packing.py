"""What a launch hands the device: ONE packed host buffer, inside the
jitted call (docs/RUNTIME.md 5c "What a launch hands the device").

``WindowComputeEngine`` lays a builtin launch out in one pooled int32
array, ``[values | counts ('mean_panes') | starts | ends]``, writes the
float32 sections through a float32 view of the same memory and calls the
jitted program with the numpy array itself: the program slices at static
offsets and casts the value sections back bit for bit.  So the results
are the ones the two- and three-buffer launches gave.  The expectations
below are a plain numpy recomputation over whole numbers (every sum under
2**24: float32 holds them, the comparison is ``==``); the same cases were
run against the engine of the commit before the layout moved (PR 36's
parent: 63 of 63 equal) before any of its code changed.
"""
import json

import numpy as np
import pytest

import windflow_tpu as wf
from windflow_tpu.core.basic import WinType
from windflow_tpu.core.tuples import ColumnPool, TupleBatch
from windflow_tpu.operators.basic_ops import Sink
from windflow_tpu.operators.batch_ops import BatchSource
from windflow_tpu.operators.tpu.farms_tpu import KeyFarmTPU
from windflow_tpu.operators.tpu.win_seq_tpu import DEFAULT_INFLIGHT_DEPTH
from windflow_tpu.ops.backend import jax_modules
from windflow_tpu.ops.window_compute import WindowComputeEngine
from windflow_tpu.telemetry import spans

KINDS = ("sum", "count", "mean", "mean_panes", "max", "min", "ffat")
EXTENTS = (2, 33, 3600)
ROWS = (1, 111, 2049)


def engine(kind):
    if kind != "ffat":
        return WindowComputeEngine(kind)
    _, jnp = jax_modules()
    return WindowComputeEngine(("ffat", jnp.add, 0.0))


def launch(extent, rows, seed=0):
    """One key's series and ``rows`` windows sliding over it by one
    pane, the last of them (up to a third) cut short by the end of the
    buffer, as EOS cuts them, none empty: (cols, starts, ends)."""
    T = extent + rows - 1 - min(rows // 3, extent - 1)
    rng = np.random.default_rng([seed, extent, rows])
    cols = {"value": rng.integers(0, 4001, T).astype(np.float64),
            "count": rng.integers(1, 5, T).astype(np.float64)}
    starts = np.arange(rows, dtype=np.int64)
    return cols, starts, np.minimum(starts + extent, T)


def recomputed(kind, cols, starts, ends):
    v = cols["value"].astype(np.float32)
    one = {"sum": np.sum, "ffat": np.sum, "count": len, "max": np.max,
           "min": np.min, "mean": lambda w: np.sum(w) / np.float32(len(w))}
    if kind == "mean_panes":
        c = cols["count"].astype(np.float32)
        return np.array([np.sum(v[s:e]) / np.sum(c[s:e])
                         for s, e in zip(starts, ends)], np.float32)
    return np.array([one[kind](v[s:e]) for s, e in zip(starts, ends)],
                    np.float32)


@pytest.mark.parametrize("rows", ROWS)
@pytest.mark.parametrize("extent", EXTENTS)
@pytest.mark.parametrize("kind", KINDS)
def test_a_launch_equals_the_recomputation_bit_for_bit(kind, extent, rows):
    cols, starts, ends = launch(extent, rows)
    if kind != "mean_panes":
        cols = {"value": cols["value"]}
    got = engine(kind).compute(cols, starts, ends,
                               np.arange(rows)).block()
    want = recomputed(kind, cols, starts, ends)
    assert got.dtype == np.float32 and got.shape == (rows,)
    assert (got == want).all()


# -- what the dispatcher's thread no longer does ------------------------------

@pytest.mark.parametrize("kind", KINDS)
def test_a_builtin_launch_converts_nothing_on_the_callers_thread(
        kind, monkeypatch):
    """The host array goes into the jitted call as it is: no
    ``jnp.asarray`` and no ``jax.device_put`` inside ``compute``."""
    jax, jnp = jax_modules()
    cols, starts, ends = launch(33, 111)
    if kind != "mean_panes":
        cols = {"value": cols["value"]}
    eng = engine(kind)
    want = eng.compute(cols, starts, ends, np.arange(111)).block()  # traced

    def refuse(*a, **kw):
        raise AssertionError("a launch converted an operand in Python")

    monkeypatch.setattr(jnp, "asarray", refuse)
    monkeypatch.setattr(jax, "device_put", refuse)
    handle = eng.compute(cols, starts, ends, np.arange(111))
    monkeypatch.undo()
    assert handle.buffers_in == 1
    assert (handle.block() == want).all()


def test_a_users_window_function_keeps_its_columns():
    _, jnp = jax_modules()
    cols, starts, ends = launch(33, 111)

    def spread(gwid, win, mask):
        return jnp.where(mask, win["value"], 0).sum() \
            - jnp.where(mask, win["count"], 0).sum()

    handle = WindowComputeEngine(spread).compute(cols, starts, ends,
                                                 np.arange(111))
    # gwids, starts, ends, valid and the user's two columns
    assert handle.buffers_in == 6
    assert (handle.block() == recomputed("sum", cols, starts, ends)
            - recomputed("sum", {"value": cols["count"]}, starts,
                         ends)).all()


# -- the counter, through an ordinary graph ---------------------------------

N_KEYS, N_EVENTS = 7, 40_000


def seven_keys(chunk=4096):
    """A ``BatchSource`` body: ``N_EVENTS`` events in chunks, id = ts =
    index, key = index % 7, value = index % 5."""
    sent = {"i": 0}

    def body(ctx=None):
        a = sent["i"]
        if a >= N_EVENTS:
            return None
        sent["i"] = b = min(a + chunk, N_EVENTS)
        i = np.arange(a, b, dtype=np.int64)
        return TupleBatch({"key": i % N_KEYS, "id": i, "ts": i,
                           "value": (i % 5).astype(np.float64)})
    return body


def test_a_graphs_launches_hand_the_device_one_buffer_each():
    rows = []
    g = wf.PipeGraph("launch_packing", wf.Mode.DEFAULT)
    pipe = g.add_source(BatchSource(seven_keys()))
    pipe.add(KeyFarmTPU("sum", 2048, 1024, WinType.TB, name="packed",
                        emit_batches=True))
    pipe.add_sink(Sink(lambda b: rows.append(b) if b is not None else None))
    g.run()
    assert rows
    done = [r for ring in spans.graph("launch_packing").rings.values()
            for r in ring.finished()]
    assert done and all(r.buffers_in == 1 for r in done)
    rep = json.loads(g.stats.to_json())
    assert rep["Schema_version"] >= 19
    launches = rep["Spans"]["Launches"]
    assert launches and all(
        row["Buffers_in"] == row["Launches"] > 0
        and row["Slowest"]["Buffers_in"] == 1 for row in launches)


# -- the two stamps inside dispatch (PR 37) ----------------------------------

def test_a_handle_brings_back_the_engines_two_stamps():
    """``t_packed`` after the host's preparation and before the jitted
    call, ``t_called`` as that call returns: on the packed path and on
    the path that keeps its columns; the host lane takes none."""
    import time
    from windflow_tpu.ops.host_compute import HostComputeEngine
    _, jnp = jax_modules()
    cols, starts, ends = launch(33, 111)
    one = {"value": cols["value"]}

    def total(gwid, win, mask):
        return jnp.where(mask, win["value"], 0).sum()

    for eng in (engine("sum"), engine("mean"), WindowComputeEngine(total)):
        t0 = time.perf_counter()
        handle = eng.compute(one, starts, ends, np.arange(111))
        t1 = time.perf_counter()
        assert t0 <= handle.t_packed <= handle.t_called <= t1
        assert len(handle.block()) == 111
    host = HostComputeEngine("sum").compute(one, starts, ends,
                                                  np.arange(111))
    assert host.t_packed is None and host.t_called is None


@pytest.mark.parametrize("lane", ("packed", "unpacked", "host"))
def test_pack_call_and_handoff_tile_a_launchs_dispatch(lane):
    """``t_picked <= t_packed <= t_called <= t_dispatched`` and the three
    parts add up to the ``dispatch`` that stays, in the records and in
    the stats JSON (schema 20); a lane without the stamps leaves the
    parts None and every reader skips them."""
    kind = "sum"
    if lane == "unpacked":
        _, jnp = jax_modules()

        def kind(gwid, win, mask):
            return jnp.where(mask, win["value"], 0).sum()
    rows = {}

    def sink(b):
        if b is not None:
            rows.update(zip(zip(b.key.tolist(), b.id.tolist()),
                            np.asarray(b["value"]).tolist()))
    name = f"launch_stamps_{lane}"
    g = wf.PipeGraph(name, wf.Mode.DEFAULT)
    pipe = g.add_source(BatchSource(seven_keys()))
    pipe.add(KeyFarmTPU(kind, 2048, 1024, WinType.TB, name="stamped",
                        emit_batches=True,
                        placement="host" if lane == "host" else "device"))
    pipe.add_sink(Sink(sink))
    g.run()
    # the rows are the plain recomputation's whatever the lane
    i = np.arange(N_EVENTS)
    want = {}
    for back in (0, 1):
        w = i // 1024 - back
        for k, x, v in zip((i % N_KEYS)[w >= 0].tolist(), w[w >= 0].tolist(),
                           (i % 5)[w >= 0].tolist()):
            want[(k, x)] = want.get((k, x), 0.0) + v
    assert rows == want
    ring, = spans.graph(name).rings.values()
    done = ring.finished()
    assert len(done) >= 2
    summary = ring.summary()
    launches, = json.loads(g.stats.to_json())["Spans"]["Launches"]
    if lane == "host":
        assert all(r.t_packed is None and r.t_called is None for r in done)
        assert all(r.stages_ms()[part] is None and summary[part] is None
                   and launches[part] is None
                   and part not in launches["Slowest"]
                   for part in spans.DISPATCH_PARTS for r in done)
        assert summary["dispatch"]["mean_ms"] >= 0
        return
    for r in done:
        assert r.t_picked <= r.t_packed <= r.t_called <= r.t_dispatched
        st = r.stages_ms()
        assert st["pack"] + st["call"] + st["handoff"] \
            == pytest.approx(st["dispatch"], abs=1e-9)
    assert sum(summary[part]["mean_ms"] for part in spans.DISPATCH_PARTS) \
        == pytest.approx(summary["dispatch"]["mean_ms"], abs=1e-3)
    assert all(launches[part]["max_ms"] >= launches[part]["mean_ms"] >= 0
               and launches["Slowest"][part] >= 0
               for part in spans.DISPATCH_PARTS)


# -- the pool's promise -----------------------------------------------------

@pytest.mark.parametrize("kind", ("sum", "mean_panes", "max"))
def test_a_buffer_in_flight_is_not_lent_again(kind):
    """``inflight_depth`` launches of different contents, dispatched
    before the first is collected, from a pool cut to ONE buffer a
    bucket: every result is its own launch's.

    ``ColumnPool`` re-lends a buffer once nothing outside the pool holds
    it, so the promise rests on the runtime holding the host array a
    jitted call was given for as long as it reads it.  The CPU backend is
    the harsh case: it may ALIAS a host array instead of copying it (the
    "transfer" is over at once, the program reads the caller's memory
    while it runs), where the chip's runtime has its copy as soon as the
    transfer is done.  A buffer lent again too early is overwritten by
    the next launch's ``pack_launch`` and shows as another launch's
    values here (in a benchmark run as ``rows_wrong``)."""
    eng = engine(kind)
    eng._padded = ColumnPool(max_per_bucket=1)
    launches = [launch(33, 2049, seed) for seed in
                range(DEFAULT_INFLIGHT_DEPTH)]
    if kind != "mean_panes":
        launches = [({"value": c["value"]}, s, e) for c, s, e in launches]
    for rounds in range(3):        # the pool's one buffer comes round again
        handles = [eng.compute(c, s, e, np.arange(len(s)))
                   for c, s, e in launches]
        for (c, s, e), h in zip(launches, handles):
            assert (h.block() == recomputed(kind, c, s, e)).all()
    # and the one pooled buffer did come back: the runtime lets go of a
    # host array once it is done with it (a pool that only ever missed
    # would pay a fresh buffer's page faults every launch)
    stats = eng._padded.stats()
    assert stats["buffers"] == 1 and stats["hits"] >= 2
