"""DEBS 2014 Grand Challenge Query 2's per-plug stage on one chip: the
wiring, the user's window function and the plain reference.

    select timestamp, plug, household, house, MEDIAN(value)
    from SmartGridStr [range 3600 slide 1] group by plug, household, house

over the smart-plug stream SABER's SG2 runs on: 2,125 plugs in 40 houses,
a reading a plug about every second.  The stream, its seeded deal of the
plugs and the chained ``BatchMap`` that looks the dense plug index up on
the timed path are ``smartgrid_sg2``'s own (its ``pipeline.py``, loaded
here).  The aggregate is the challenge's: a median is holistic, no pane
partial gives it and it is no builtin, so it runs as a user's function
``fn(gwid, cols, mask)`` over each window's raw readings on the device
(:func:`median`, written as a user writes one).  Loads are whole watts
under 4,096, so the median and the mean of two middle loads are exact in
float32 and the check is exact.  ``reference`` imports nothing of the
program: it counts each plug's integer loads over each window.  ``build``
asks the program for what this query needs before anything starts: a
native lane that keeps a window's tuples for a user's function.  Through
the Python store a chunk's 2,125 keys are a Python loop and a flush of
65,875 windows two ``searchsorted`` each, some 12 times under SG2's rate
before the device is reached.
"""
import os

import numpy as np

from benchmarks.harness.runner import load_module

SG2 = load_module(
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 "smartgrid_sg2", "pipeline.py"),
    "benchmarks_pipeline_smartgrid_sg2_for_median")
shape, deal, plug_index, make_pool = (SG2.shape, SG2.deal, SG2.plug_index,
                                      SG2.make_pool)


def require_program():
    """Exit, with the reason, where the program cannot run this query."""
    from windflow_tpu.ops import window_compute
    from windflow_tpu.runtime.native import NativeWindowEngine
    if "tuples" not in NativeWindowEngine.KINDS \
            or not hasattr(window_compute, "fn_rows"):
        raise SystemExit(
            "smartgrid_median: this program has no native lane for a "
            "user's window function (windflow_tpu.runtime.native."
            "NativeWindowEngine.KINDS has no 'tuples'), nor a packed "
            "program for it at width (windflow_tpu.ops.window_compute has "
            "no 'fn_rows'): the median would be staged by the Python store "
            "at some 12 times under SG2's rate, a [131,072 x 4,096] tile a "
            "launch; refusing to start")


# asked as the cell is resolved too, before the device is opened: a
# program that cannot run this query is turned away in a second
require_program()


def median(gwid, cols, mask):
    """The user's window function: the median of the window's loads, the
    mean of the two middle ones where the count is even."""
    import jax.numpy as jnp
    n = mask.sum()
    loads = jnp.sort(jnp.where(mask, cols["value"], jnp.inf))
    lo, hi = loads[(n - 1) // 2], loads[n // 2]
    return ((lo + hi) / 2).astype(jnp.float32)


def build(graph, cfg, source_body, sink, seed):
    require_program()
    import windflow_tpu as wf
    from windflow_tpu.operators.basic_ops import Sink
    from windflow_tpu.operators.batch_ops import BatchMap, BatchSource
    from windflow_tpu.operators.tpu.farms_tpu import KeyFarmTPU
    win, slide = shape(cfg)
    index = plug_index(cfg, seed)

    def group_by_key(batch):
        return batch.with_cols(
            key=index[batch["house"], batch["household"], batch.key])

    op = KeyFarmTPU(median, win, slide, wf.WinType.TB,
                    batch_len=cfg["device_batch"], name="plug_median",
                    emit_batches=True)
    pipe = graph.add_source(BatchSource(source_body))
    pipe.chain(BatchMap(group_by_key)).add(op)
    pipe.add_sink(Sink(sink, name="median_sink"))


def launches(graph):
    from benchmarks.harness.runner import stats_sum
    return stats_sum(graph, "plug_median", "num_launches")


def device_time_ms(graph):
    from benchmarks.harness.runner import stats_sum
    return stats_sum(graph, "plug_median", "device_time_ms")


def logical_bytes_per_row(cfg):
    """HBM bytes any correct implementation must move per result row, and
    no more.  A row is one (plug, slide), and a median reads every
    reading of its window: 4 bytes for each of the window's readings (a
    plug's hour holds ``win_events / n_plugs`` = 3,600 on average), read
    once; the window's extent (two int32, 8) and one float32 result (4).
    Nothing of one window serves the next: no partial gives a median."""
    readings = cfg["win_events"] / cfg["n_plugs"]
    return 4 * readings + 8 + 4


def reference(cfg, seed, n_events, dtype=np.float64):
    """Every (plug, window, median load) the offered stream owes, from the
    seed alone: event ``i`` is pool row ``i % pool_rows`` with ``ts = i``.
    The loads are integers, so a window's median is found by counting,
    a plug at a time: over panes of ``slide_events`` ids, how many of the
    plug's readings took each load (a plug's loads span a few hundred
    watts at most); a running count along the panes gives each window's
    count of every load as the difference of two rows (the stream's end
    cuts the last windows short), and a running count along the loads the
    two middle ranks ``(n - 1) // 2`` and ``n // 2``.  A row is owed for
    window ``w`` of a plug where ``w * slide <=`` the plug's last stamp and
    the window holds a reading.  ``dtype`` is the precision the loads
    reach the median in (the control's door): float64 means exactly."""
    win, slide = shape(cfg)
    per_win, n_keys = win // slide, cfg["n_plugs"]
    if not n_events:
        z = np.zeros(0, np.int64)
        return z, z, z.astype(np.float64), 0
    pool = make_pool(cfg, seed)
    plug = plug_index(cfg, seed)[pool["house"], pool["household"],
                                 pool["key"]]
    load = pool["value"]
    if np.dtype(dtype) != np.float64:
        load = load.astype(dtype).astype(np.float64)
    if (load != np.round(load)).any():
        raise AssertionError("a load is not a whole number of watts: the "
                             "reference counts integer loads")
    load = load.astype(np.int64)
    rows = len(plug)
    n_panes = (n_events - 1) // slide + 1
    # each plug's pool rows, ascending; the stream passes the pool
    # ``passes`` times
    by_plug = np.argsort(plug, kind="stable")
    bounds = np.searchsorted(plug[by_plug], np.arange(n_keys + 1))
    passes = np.arange(-(-n_events // rows), dtype=np.int64)[:, None] * rows

    def one(k):
        """Plug ``k``'s (window ids, medians)."""
        r = by_plug[bounds[k]:bounds[k + 1]]
        ev = (passes + r[None, :]).ravel()
        ev = ev[ev < n_events]
        if not len(ev):
            return None
        loads = load[ev % rows]
        base = int(loads.min())
        width = int(loads.max()) - base + 1
        counts = np.bincount((ev // slide + 1) * width + (loads - base),
                             minlength=(n_panes + 1) * width)  # row p+1: p
        run = np.cumsum(counts.reshape(n_panes + 1, width), axis=0,
                        dtype=np.int32)
        # the windows up to the plug's last pane; those a whole window
        # long first, then those the stream's end cuts short
        n_w = int(ev.max()) // slide + 1
        whole = max(0, min(n_w, n_panes - per_win + 1))
        # a window's counts are held in int16: the law keeps a plug's
        # window near 3,600 readings, and a law that did not is refused
        held_by = np.cumsum(counts.reshape(n_panes + 1, width).sum(axis=1))
        if int((held_by[np.minimum(np.arange(n_w) + per_win, n_panes)]
                - held_by[:n_w]).max()) >= 1 << 15:
            raise AssertionError(f"plug {k} holds 2**15 readings in a "
                                 "window: the reference counts in int16")
        in_win = np.empty((n_w, width), np.int16)   # a window's readings
        np.subtract(run[per_win:per_win + whole], run[:whole],
                    out=in_win[:whole], casting="unsafe")
        np.subtract(run[n_panes], run[whole:n_w], out=in_win[whole:],
                    casting="unsafe")
        del run, counts
        upto = np.cumsum(in_win, axis=1, dtype=np.int16)
        n = upto[:, -1].astype(np.int64)
        held = np.flatnonzero(n > 0)      # owed: the window holds one
        upto, n = upto[held], n[held]
        lo = (upto <= ((n - 1) // 2)[:, None]).sum(axis=1)
        hi = (upto <= (n // 2)[:, None]).sum(axis=1)
        return held, (lo + hi + 2 * base) / 2.0

    # a plug's arrays are numpy's loops, which let go of the GIL: the
    # plugs are counted on a few threads, and put together in plug order
    from concurrent.futures import ThreadPoolExecutor
    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool_:
        got = list(pool_.map(one, range(n_keys)))
    keys = [np.full(len(g[0]), k, np.int64) for k, g in enumerate(got)
            if g is not None]
    wids = [g[0] for g in got if g is not None]
    vals = [g[1] for g in got if g is not None]
    return (np.concatenate(keys), np.concatenate(wids).astype(np.int64),
            np.concatenate(vals).astype(np.float64), int(n_events))
