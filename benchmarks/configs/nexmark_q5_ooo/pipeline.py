"""NEXmark Q5 (hot items) with the generator's own delayed events: the
wiring, the stream's law, and the plain reference.

``nexmark_q5_live`` plus the two defaults of Beam's
``NexmarkConfiguration`` that it assumed away: ``probDelayedEvent = 0.1``
and ``occasionalDelaySec = 3``.  Beam's ``UnboundedEventSource`` holds
one event in ten back and reissues it after a delay drawn uniformly from
1 ms to 3 s, and holds the watermark at the oldest held event: no event
is late against the watermark, results wait for the stragglers.  Here
the pool gains a column ``delay`` (0 with probability 0.9, else a uniform
integer in ``1..D`` event ids, ``D`` = 3 s of the cut window); the event
handed over at arrival index ``i`` is the bid created at ``e = max(i -
delay, 0)``: the source's chained ``BatchMap`` sets ``ts = e`` and draws
the auction the generator drew when the bid was created, at ``e``.
``id`` stays the arrival index.  The window operator holds its firing
back by ``D`` (``triggering_delay``): the reader's hold of the watermark.

The law of the auctions, the sink's fold, the launch counters and the
fold's reference are ``nexmark_q5_live``'s and ``nexmark_q5``'s.
``reference`` imports nothing of the program.  ``build`` asks the
program for what this stream needs before anything starts: a window
engine in which a key's anchor can move back.  One that anchors a key at
its first tuple to arrive loses the windows its stragglers open, and
says nothing.
"""
import os

import numpy as np

from benchmarks.harness.runner import load_module

_live = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 os.pardir, "nexmark_q5_live", "pipeline.py"),
                    "benchmarks_pipeline_nexmark_q5_live_for_ooo")

SinkFold, reference_fold = _live.SinkFold, _live.reference_fold
launches, device_time_ms = _live.launches, _live.device_time_ms
logical_bytes_per_row = _live.logical_bytes_per_row
HOT, cold_ids = _live.HOT, _live.cold_ids
HOT_AUCTION_BATCH = _live.HOT_AUCTION_BATCH


def require_program():
    """Exit, with the reason, where the program cannot run this stream."""
    from windflow_tpu.runtime.native import NativeWindowEngine
    if "anchors_moved" not in getattr(NativeWindowEngine, "STATS", ()):
        raise SystemExit(
            "nexmark_q5_ooo: this program's window engine does not count "
            "moved anchors (NativeWindowEngine.STATS has no "
            "'anchors_moved'): it anchors a key at its first tuple to "
            "arrive and never emits the windows an earlier straggler "
            "opens, so one bid in ten arriving late would lose rows in "
            "silence; refusing to start")


# asked as the cell is resolved too, before the device is opened: a
# program that cannot run this stream is turned away in a second
require_program()


def delay_ids(cfg):
    """``D``: ``occasional_delay_sec`` in event ids of the cut window,
    as the configuration's file states it."""
    d = cfg["occasional_delay_sec"] * cfg["win_events"] \
        // cfg["source_window_s"]
    if cfg["delay_events"] != d:
        raise ValueError(f"delay_events {cfg['delay_events']} is not "
                         f"{cfg['occasional_delay_sec']} s of a window of "
                         f"{cfg['win_events']} ids ({d})")
    return d


def make_pool(cfg, seed):
    """``nexmark_q5_live``'s draws, and the delay of each arrival slot
    from a stream of the seed's own: 0 with probability ``1 -
    prob_delayed_event``, else uniform over ``1..D``."""
    pool = _live.make_pool(cfg, seed)
    rng = np.random.default_rng([int(seed), 32])
    n = cfg["pool_rows"]
    held = rng.random(n) < cfg["prob_delayed_event"]
    pool["delay"] = np.where(held, rng.integers(1, delay_ids(cfg) + 1, n), 0)
    return pool


def auction_of(cfg, event_id, draw):
    """``BidGenerator.nextBid``'s auction for the bids created at
    ``event_id`` with the pool's ``draw``, element by element: creation
    ids are not in order here, so whether a bid meets the head of the
    stream (fewer than ``num_in_flight_auctions`` auctions exist, the
    cold range is the shorter one) is each bid's own matter.  Plain
    numpy, used by the graph's map and by the reference alike."""
    in_flight = cfg["num_in_flight_auctions"]
    last = event_id * cfg["auction_proportion"] // cfg["bid_proportion"]
    lo = np.maximum(last - in_flight, 0)
    if len(lo) and int(lo.min()) > 0:   # all past the head
        cold = lo + draw
    else:
        cold = lo + draw * (last - lo + 1 + cfg["auction_id_lead"]) \
            // cold_ids(cfg)
    hot = last // HOT_AUCTION_BATCH * HOT_AUCTION_BATCH
    return np.where(draw == HOT, hot, cold) + cfg["first_auction_id"]


def created_at(arrival, delay):
    """The creation id of the event handed over at ``arrival``."""
    return np.maximum(arrival - delay, 0)


def build(graph, cfg, source_body, sink, seed):
    require_program()
    import windflow_tpu as wf
    from windflow_tpu.operators.basic_ops import Sink
    from windflow_tpu.operators.batch_ops import BatchMap, BatchSource
    from windflow_tpu.operators.tpu.farms_tpu import KeyFarmTPU

    def next_bid(batch):
        e = created_at(batch.id, batch["delay"])
        return batch.with_cols(ts=e, key=auction_of(cfg, e, batch.key))

    counter = KeyFarmTPU("count", cfg["win_events"], cfg["slide_events"],
                         wf.WinType.TB, batch_len=cfg["device_batch"],
                         triggering_delay=delay_ids(cfg),
                         name="q5_counts", emit_batches=True)
    pipe = graph.add_source(BatchSource(source_body))
    pipe.chain(BatchMap(next_bid)).add(counter)
    pipe.add_sink(Sink(sink, name="q5_sink"))


def reference(cfg, seed, n_events, dtype=np.float64):
    """Every (auction, window, count) the offered stream owes, from the
    seed alone: arrival ``i`` is pool row ``i % pool_rows``, the bid
    created at ``e = max(i - delay, 0)`` on the auction the law draws at
    ``e``.  A row is owed for every window that holds a bid of the
    auction, stragglers included: window ``w`` is event-time panes ``w``
    and ``w + 1`` of ``slide_events`` ids (the stream's end cuts the last
    ones short).  Pane by pane: event-time pane ``p`` draws from arrivals
    ``p * slide .. (p + 1) * slide + D``, a bincount over the narrow
    range of ids a pane can hold; ``dtype`` is the precision the panes
    are added in (the control's door)."""
    win, slide = cfg["win_events"], cfg["slide_events"]
    if win != 2 * slide:
        raise ValueError("the reference adds two panes a window")
    pool = make_pool(cfg, seed)
    draw, delay = pool["key"], pool["delay"]
    rows, d = len(draw), delay_ids(cfg)
    lane = np.arange(slide + d, dtype=np.int64)
    n_panes = (n_events - 1) // slide + 1 if n_events else 0

    def pane(p):
        """(lowest auction id, counts from it on) of event-time pane
        ``p``; (0, nothing) where no bid was created in it."""
        arrival = p * slide + lane[:max(0, min(slide + d,
                                               n_events - p * slide))]
        row = arrival % rows
        e = created_at(arrival, delay[row])
        mine = (e >= p * slide) & (e < (p + 1) * slide)
        if not mine.any():
            return 0, np.zeros(0, np.int64)
        auction = auction_of(cfg, e[mine], draw[row[mine]])
        lo = int(auction.min())
        return lo, np.bincount(auction - lo)

    keys, wids, counts = [], [], []
    lo_a, a = pane(0) if n_panes else (0, np.zeros(0, np.int64))
    for w in range(n_panes):
        lo_b, b = pane(w + 1) if w + 1 < n_panes \
            else (0, np.zeros(0, np.int64))
        # an empty pane's lowest id is no one's
        lo = min(lo_a if len(a) else lo_b, lo_b if len(b) else lo_a)
        both = np.zeros(max(lo_a + len(a), lo_b + len(b), lo) - lo, dtype)
        both[lo_a - lo:lo_a - lo + len(a)] += a.astype(dtype)
        both[lo_b - lo:lo_b - lo + len(b)] = (
            both[lo_b - lo:lo_b - lo + len(b)] + b.astype(dtype)
        ).astype(dtype)
        held = np.flatnonzero(both)
        keys.append(held + lo)
        wids.append(np.full(len(held), w, np.int64))
        counts.append(both[held].astype(np.float64))
        lo_a, a = lo_b, b
    cat = np.concatenate
    if not keys:
        z = np.zeros(0, np.int64)
        return z, z, z.astype(np.float64), 0
    return cat(keys), cat(wids), cat(counts), int(n_events)
