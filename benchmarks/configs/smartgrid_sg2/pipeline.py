"""SABER's Smart Grid query SG2 on one chip: the wiring, the stream's law
and the plain reference.

    select timestamp, plug, household, house, AVG(value) as localAvgLoad
    from SmartGridStr [range 3600 slide 1] group by plug, household, house

over the smart-plug stream of the DEBS 2014 Grand Challenge: 2,125 plugs
in 40 houses, a reading a plug about every second.  In the harness's
stamps (``ts = id =`` event index) a second of stream is ``n_plugs``
events, so the slide is 2,125 ids and the window 3,600 slides: every plug
fires a window a slide, a result row an event, and a window is 3,600
panes where the NEXmark cells have two.

The pool holds the readings as the trace does (``house``, ``household``,
the plug's id local to its household under ``key``, ``property``,
``value``); the source's chained ``BatchMap`` looks the group-by key up
from the three ids on the timed path, as YSB's join does.  Loads are
whole watts, so a window's sum is exact in float32 and the check is
exact.  ``reference`` imports nothing of the program.  ``build`` asks the
program for what this query needs before anything starts: a wide-window
combine that adds a window's own panes and no one else's.  One that
differences ONE float32 running sum over a launch's whole buffer (7.7 M
partials of a thousand watts each) is off from the fourth digit on, and
says nothing.
"""
import numpy as np

EXACT_BELOW = 1 << 24     # integers float32 holds exactly
BLOCK_PANES = 1024        # panes the reference bins at a time


def require_program():
    """Exit, with the reason, where the program cannot run this query."""
    from windflow_tpu.ops import window_compute
    if not hasattr(window_compute, "_block_sum_program"):
        raise SystemExit(
            "smartgrid_sg2: this program's wide-window combine "
            "(windflow_tpu.ops.window_compute has no '_block_sum_program') "
            "takes a window's sum as the difference of ONE float32 running "
            "sum over the launch's whole buffer: over 2,125 plugs x 3,630 "
            "panes the running sum passes 2**24 after the first few plugs "
            "and every later mean is wrong from the fourth digit on, in "
            "silence; refusing to start")


# asked as the cell is resolved too, before the device is opened: a
# program that cannot run this query is turned away in a second
require_program()


def shape(cfg):
    """(window, slide) in event ids, as the configuration's file states
    them: a second of stream is ``n_plugs`` events."""
    slide = cfg["n_plugs"] * cfg["source_slide_s"]
    win = cfg["n_plugs"] * cfg["source_window_s"]
    if (cfg["slide_events"], cfg["win_events"],
            cfg["events_per_s_of_stream"]) != (slide, win, cfg["n_plugs"]):
        raise ValueError(
            f"slide_events {cfg['slide_events']} and win_events "
            f"{cfg['win_events']} are not {cfg['source_slide_s']} s and "
            f"{cfg['source_window_s']} s of {cfg['n_plugs']} readings a "
            f"second ({slide}, {win})")
    return win, slide


def deal(cfg, seed):
    """The seeded deal of the plugs: ``(house, household, plug id)`` of
    every dense plug index ``0..n_plugs-1``, each an int64 array.  Houses
    get the plugs evenly (53 or 54 each) in a seeded order; a house has
    ``1..max_households_per_house`` households and a plug lives in one
    of its house's, uniformly; the plug id is the plug's rank in its
    household."""
    rng = np.random.default_rng([int(seed), 34])
    n, n_houses = cfg["n_plugs"], cfg["n_houses"]
    house = rng.permutation(n).astype(np.int64) % n_houses
    n_households = rng.integers(1, cfg["max_households_per_house"] + 1,
                                n_houses)
    household = rng.integers(0, n_households[house])
    group = house * cfg["max_households_per_house"] + household
    order = np.argsort(group, kind="stable")
    first = np.flatnonzero(np.r_[True, np.diff(group[order]) != 0])
    rank = np.arange(n) - np.repeat(first, np.diff(np.r_[first, n]))
    plug = np.empty(n, np.int64)
    plug[order] = rank
    return house, household.astype(np.int64), plug


def plug_index(cfg, seed):
    """The group-by key: the table that ``[house, household, plug id]``
    looks the dense plug index up in (-1 where no such plug exists).  It
    is the law, not the program: the graph's map and the reference both
    read it."""
    house, household, plug = deal(cfg, seed)
    table = np.full((cfg["n_houses"], cfg["max_households_per_house"],
                     int(plug.max()) + 1), -1, np.int64)
    table[house, household, plug] = np.arange(cfg["n_plugs"])
    return table


def make_pool(cfg, seed):
    """The reading pool, from the seed alone: each row's plug is drawn
    uniformly from the ``n_plugs`` and carried as the trace carries it
    (house, household, the plug's id under ``key``); its load is the
    plug's base level (drawn once, ``0..load_base_max_w``) plus a uniform
    integer noise, clipped to ``0..load_clip_w``: whole watts."""
    shape(cfg)
    rng = np.random.default_rng(seed)
    n, n_plugs = cfg["pool_rows"], cfg["n_plugs"]
    house, household, plug = deal(cfg, seed)
    base = rng.integers(0, cfg["load_base_max_w"] + 1, n_plugs)
    which = rng.integers(0, n_plugs, n)
    noise = rng.integers(-cfg["load_noise_w"], cfg["load_noise_w"] + 1, n)
    load = np.clip(base[which] + noise, 0, cfg["load_clip_w"])
    return {
        "house": house[which],
        "household": household[which],
        "key": plug[which],
        "property": np.ones(n, np.int64),
        "value": load.astype(np.float64),
    }


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

    mean = KeyFarmTPU("mean", win, slide, wf.WinType.TB,
                      batch_len=cfg["device_batch"], name="sg2_mean",
                      emit_batches=True)
    pipe = graph.add_source(BatchSource(source_body))
    pipe.chain(BatchMap(group_by_key)).add(mean)
    pipe.add_sink(Sink(sink, name="sg2_sink"))


def launches(graph):
    from benchmarks.harness.runner import stats_sum
    return stats_sum(graph, "sg2_mean", "num_launches")


def device_time_ms(graph):
    from benchmarks.harness.runner import stats_sum
    return stats_sum(graph, "sg2_mean", "device_time_ms")


def logical_bytes_per_row(cfg):
    """HBM bytes any correct implementation must move per result row, and
    no more.  A row is one (plug, slide); what is new to the device since
    the plug's last row is ONE pane: its partial pair (sum and count, two
    float32) is shipped in and written (8 + 8) and read once by the
    combine (8); the window's extent is read (two int32, 8) and one
    float32 result written (4): 36 B.  The window's other 3,599 panes
    were the device's already in any implementation that keeps them, so
    they are not counted: ``harness/window.fold_bytes_per_row``'s
    ``4 * (win // slide)`` term would count every pane 3,600 times and let
    a scan, which reads each once a launch, read over 100 % of the
    roofline."""
    pair_in, pair_written, pair_read = 8, 8, 8
    extent, result = 8, 4
    return pair_in + pair_written + pair_read + extent + result


def reference(cfg, seed, n_events, dtype=np.float64):
    """Every (plug, window, mean load) the offered stream owes, from the
    seed alone: event ``i`` is pool row ``i % pool_rows`` with ``ts =
    i``.  Panes of ``slide_events`` ids; per pane and plug the integer sum
    and count of its readings (a bincount with and without weights, block
    by block); window ``w`` is panes ``w .. w + win // slide - 1``, taken
    as the difference of an int64 running sum along the pane axis (exact;
    the stream's end cuts the last ones short).  A row is owed for window
    ``w`` of a plug where ``w * slide <=`` the plug's last stamp and the
    window holds a reading.  The value is the IEEE float32 quotient of
    sum and count.  ``dtype`` is the precision the panes are added in
    (the control's door): float64 means exactly, in int64."""
    win, slide = shape(cfg)
    per_win, n_keys = win // slide, cfg["n_plugs"]
    if not n_events:
        z = np.zeros(0, np.int64)
        return z, z, z.astype(np.float64), 0
    pool = make_pool(cfg, seed)
    plug = plug_index(cfg, seed)[pool["house"], pool["household"],
                                 pool["key"]]
    load, rows = pool["value"], len(plug)
    n_panes = (n_events - 1) // slide + 1
    exact = np.dtype(dtype) == np.float64
    sums = np.zeros((n_panes + 1, n_keys), np.int64)   # row p + 1: pane p
    cnts = np.zeros((n_panes + 1, n_keys), np.int64)
    lane = np.arange(BLOCK_PANES * slide, dtype=np.int64)
    for i0 in range(0, n_events, len(lane)):
        n = min(len(lane), n_events - i0)
        idx = (i0 + lane[:n]) % rows
        code = lane[:n] // slide * n_keys + plug[idx]
        p0, k = i0 // slide, (n - 1) // slide + 1
        cnts[p0 + 1:p0 + k + 1] = np.bincount(
            code, minlength=k * n_keys).reshape(k, n_keys)
        sums[p0 + 1:p0 + k + 1] = np.bincount(
            code, weights=load[idx], minlength=k * n_keys) \
            .astype(np.int64).reshape(k, n_keys)
    lo = np.arange(n_panes, dtype=np.int64)
    hi = np.minimum(lo + per_win, n_panes)

    def windows(panes, acc):
        """Each window's panes added in ``acc``: the difference of the
        running sum along the pane axis."""
        run = np.cumsum(panes.astype(acc, copy=False), axis=0, dtype=acc)
        return (run[hi] - run[lo]).astype(acc)

    # a plug's windows are owed up to the last pane it has a reading in,
    # where they hold one: decided exactly, whatever ``dtype``
    held = cnts[1:] > 0
    last_pane = np.where(held.any(axis=0),
                         n_panes - 1 - np.argmax(held[::-1], axis=0), -1)
    del held
    n_exact = windows(cnts, np.int64)
    owed = (lo[:, None] <= last_pane[None, :]) & (n_exact > 0)
    if exact:
        w_sum, w_cnt = windows(sums, np.int64), n_exact
        if int(w_sum.max()) >= EXACT_BELOW:
            raise AssertionError(
                f"a window's sum reaches {int(w_sum.max())}: not exact in "
                "float32, the law of the loads was to keep it under 2**24")
    else:
        w_sum, w_cnt = windows(sums, dtype), windows(cnts, dtype)
    del sums, cnts
    wids, keys = np.nonzero(owed)     # in the order ``[owed]`` reads
    mean = w_sum[owed].astype(np.float32) \
        / np.maximum(w_cnt[owed].astype(np.float32), 1)
    return (keys.astype(np.int64), wids.astype(np.int64),
            mean.astype(np.float64), int(n_events))
