"""NEXmark Q5 (hot items) on the generator's own advancing auctions: the
wiring, the stream's law, and the plain reference.

Beam's ``BidGenerator.nextBid`` draws a bid's auction round the newest
auction ``last``, and ``last`` advances with the stream: of every 50
events 3 are auctions and 46 bids, so bid ``i`` finds ``last = 3i/46``.
The one load generator hands out pool rows with ``id = ts = i`` and
cannot advance a key, so the pool holds the law's *draws* (hot or not,
and the cold offset) and the pipeline's first operator, a ``BatchMap``
chained on the source, computes the auction from the event id: the
source's generator, on the timed path, where Beam runs it.

Sink fold, launch counters and the fold's reference are ``nexmark_q5``'s.
``reference`` imports nothing of the program.  ``build`` asks the program
for what this stream needs before anything starts: a window engine that
fires on stream time and forgets dead keys.  One that does not would
hold every auction's state for ever and emit nearly every row at EOS.
"""
import os

import numpy as np

from benchmarks.harness.runner import load_module

_q5 = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               os.pardir, "nexmark_q5", "pipeline.py"),
                  "benchmarks_pipeline_nexmark_q5_for_live")

SinkFold, reference_fold = _q5.SinkFold, _q5.reference_fold
launches, device_time_ms = _q5.launches, _q5.device_time_ms
logical_bytes_per_row = _q5.logical_bytes_per_row
HOT_AUCTION_BATCH = _q5.HOT_AUCTION_BATCH
HOT = -1                      # the pool's draw for "the hot auction"


def require_program():
    """Exit, with the reason, where the program cannot run this stream."""
    from windflow_tpu.runtime.native import NativeWindowEngine
    if not hasattr(NativeWindowEngine, "snapshot"):
        raise SystemExit(
            "nexmark_q5_live: this program's window engine has no "
            "live-key counter (NativeWindowEngine.snapshot): it fires a "
            "key's windows on the key's own next tuple and never drops a "
            "key, so advancing auctions would fire nothing and grow "
            "without bound; refusing to start")


# asked as the cell is resolved too, before the device is opened: a
# program that cannot run this stream is turned away in a second
require_program()


def cold_ids(cfg):
    """How many ids the cold draw ranges over in the steady state."""
    return cfg["num_in_flight_auctions"] + 1 + cfg["auction_id_lead"]


def make_pool(cfg, seed):
    """The bids' draws, from the seed alone: ``key`` is ``HOT`` with
    probability ``(hot_auction_ratio - 1) / hot_auction_ratio``, else the
    cold offset, uniform over ``cold_ids``; bidders and prices as
    ``nexmark_q5`` (carried)."""
    rng = np.random.default_rng(seed)
    n = cfg["pool_rows"]
    offset = rng.integers(0, cold_ids(cfg), n, dtype=np.int64)
    hot = rng.integers(0, cfg["hot_auction_ratio"], n) > 0
    last_p = cfg["num_active_people"]
    return {
        "key": np.where(hot, HOT, offset),
        "bidder": _q5._draw(rng, n, last_p, cfg["num_active_people"],
                            cfg["person_id_lead"], cfg["hot_bidders_ratio"],
                            last_p // _q5.HOT_BIDDER_BATCH
                            * _q5.HOT_BIDDER_BATCH + 1,
                            cfg["first_person_id"]),
        "value": rng.integers(1, 10_000, n).astype(np.float64),
    }


def auction_of(cfg, event_id, draw):
    """``BidGenerator.nextBid``'s auction for bids ``event_id`` with the
    pool's ``draw``: plain numpy, used by the graph's map and by the
    reference alike (it is the law, not the program)."""
    in_flight = cfg["num_in_flight_auctions"]
    last = event_id * cfg["auction_proportion"] // cfg["bid_proportion"]
    lo = np.maximum(last - in_flight, 0)
    if lo[0] > 0:             # past the head: the whole range, as drawn
        cold = lo + draw
    else:                     # fewer than in_flight auctions exist
        cold = lo + draw * (last - lo + 1 + cfg["auction_id_lead"]) \
            // cold_ids(cfg)
    hot = last // HOT_AUCTION_BATCH * HOT_AUCTION_BATCH
    return np.where(draw == HOT, hot, cold) + cfg["first_auction_id"]


def build(graph, cfg, source_body, sink, seed):
    require_program()
    import windflow_tpu as wf
    from windflow_tpu.operators.basic_ops import Sink
    from windflow_tpu.operators.batch_ops import BatchMap, BatchSource
    from windflow_tpu.operators.tpu.farms_tpu import KeyFarmTPU

    def next_bid(batch):
        return batch.with_cols(key=auction_of(cfg, batch.id, batch.key))

    counter = KeyFarmTPU("count", cfg["win_events"], cfg["slide_events"],
                         wf.WinType.TB, batch_len=cfg["device_batch"],
                         name="q5_counts", emit_batches=True)
    pipe = graph.add_source(BatchSource(source_body))
    pipe.chain(BatchMap(next_bid)).add(counter)
    pipe.add_sink(Sink(sink, name="q5_sink"))


def reference(cfg, seed, n_events, dtype=np.float64):
    """Every (auction, window, count) the offered stream owes, from the
    seed alone: event i is pool row ``i % pool_rows`` with ``ts = i``,
    its auction the law's.  A row is owed for every window that holds a
    bid of the auction: window ``w`` is panes ``w`` and ``w + 1`` of
    ``slide_events`` ids (the stream's end cuts the last ones short).
    Pane by pane, a bincount over the narrow range of ids a pane can
    hold; ``dtype`` is the precision the panes are added in (the
    control's door)."""
    win, slide = cfg["win_events"], cfg["slide_events"]
    if win != 2 * slide:
        raise ValueError("the reference adds two panes a window")
    draw = make_pool(cfg, seed)["key"]
    rows = len(draw)
    lane = np.arange(slide, dtype=np.int64)
    n_panes = (n_events - 1) // slide + 1 if n_events else 0

    def pane(p):
        """(lowest auction id, counts from it on) of pane ``p``."""
        n = min(slide, n_events - p * slide)
        ids = p * slide + lane[:n]
        r = p * slide % rows
        # one contiguous piece of the pool where the pool holds whole panes
        mine = draw[r:r + n] if rows % slide == 0 else draw[ids % rows]
        auction = auction_of(cfg, ids, mine)
        lo = int(auction.min())
        return lo, np.bincount(auction - lo)

    keys, wids, counts = [], [], []
    lo_a, a = pane(0) if n_panes else (0, np.zeros(0, np.int64))
    for w in range(n_panes):
        lo_b, b = pane(w + 1) if w + 1 < n_panes \
            else (lo_a, np.zeros(0, np.int64))
        lo = min(lo_a, lo_b)
        both = np.zeros(max(lo_a + len(a), lo_b + len(b)) - lo, dtype)
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
