"""NEXmark Q5 (hot items) on one chip: the wiring, the stream's law, the
sink's fold and the plain reference.

``build`` is a copy of ``windflow_tpu/models/nexmark.build_q5_hot_items``
with the source body and the sink handed in (the model makes its own
source and cannot be fed).  The query's second half, the hottest auction
of each window, is the sink's fold there and here: :class:`SinkFold` runs
inside the timed sink.  ``reference`` and ``reference_fold`` import
nothing of the program.
"""
import numpy as np

# BidGenerator's constants (not NexmarkConfiguration options): the hot
# auction and the hot bidder are the first and second id of the current
# batch of 100
HOT_AUCTION_BATCH = HOT_BIDDER_BATCH = 100


def _draw(rng, n, last, in_flight, lead, hot_ratio, hot_id, first_id):
    """Beam's ``BidGenerator.nextBid`` for one column with the stream
    standing at base-0 id ``last``: with probability ``(hot_ratio - 1) /
    hot_ratio`` the hot id, else uniform over the ``in_flight`` ids behind
    ``last``, ``last`` itself and the ``lead`` ids ahead of it."""
    lo = max(last - in_flight, 0)
    cold = lo + rng.integers(0, last - lo + 1 + lead, n, dtype=np.int64)
    hot = rng.integers(0, hot_ratio, n) > 0
    return np.where(hot, hot_id, cold) + first_id


def n_key_ids(cfg):
    """One more than the largest auction id the law can draw."""
    return (cfg["first_auction_id"] + cfg["num_in_flight_auctions"]
            + cfg["auction_id_lead"] + 1)


def make_pool(cfg, seed):
    """The bid pool, from the seed alone.  The stream stands where the
    generator has just opened its ``num_in_flight_auctions``-th auction
    and registered its ``num_active_people``-th person, and stays there
    (``assumed`` in config.json says why)."""
    rng = np.random.default_rng(seed)
    n = cfg["pool_rows"]
    last_a, last_p = cfg["num_in_flight_auctions"], cfg["num_active_people"]
    return {
        "key": _draw(rng, n, last_a, cfg["num_in_flight_auctions"],
                     cfg["auction_id_lead"], cfg["hot_auction_ratio"],
                     last_a // HOT_AUCTION_BATCH * HOT_AUCTION_BATCH,
                     cfg["first_auction_id"]),
        "bidder": _draw(rng, n, last_p, cfg["num_active_people"],
                        cfg["person_id_lead"], cfg["hot_bidders_ratio"],
                        last_p // HOT_BIDDER_BATCH * HOT_BIDDER_BATCH + 1,
                        cfg["first_person_id"]),
        "value": rng.integers(1, 10_000, n).astype(np.float64),
    }


def build(graph, cfg, source_body, sink, seed):
    import windflow_tpu as wf
    from windflow_tpu.operators.basic_ops import Sink
    from windflow_tpu.operators.batch_ops import BatchSource
    from windflow_tpu.operators.tpu.farms_tpu import KeyFarmTPU
    counter = KeyFarmTPU("count", cfg["win_events"], cfg["slide_events"],
                         wf.WinType.TB, batch_len=cfg["device_batch"],
                         name="q5_counts", emit_batches=True)
    graph.add_source(BatchSource(source_body)).add(counter) \
        .add_sink(Sink(sink, name="q5_sink"))


def launches(graph):
    from benchmarks.harness.runner import stats_sum
    return stats_sum(graph, "q5_counts", "num_launches")


def device_time_ms(graph):
    from benchmarks.harness.runner import stats_sum
    return stats_sum(graph, "q5_counts", "device_time_ms")


class SinkFold:
    """Q5's second half, in the sink: per window the most bids any
    auction has, and which auctions have that many.  A key's windows fire
    when its next bid has passed them, so a window's rows come in several
    batches and the fold is a running one."""

    def __init__(self, cfg):
        self.best = {}          # window id -> [count, set of auctions]

    def add(self, keys, wids, counts):
        keys, wids, counts = (np.asarray(a) for a in (keys, wids, counts))
        for w in np.unique(wids):
            mine = wids == w
            c, k = counts[mine], keys[mine]
            top = c.max()
            have = self.best.get(int(w))
            if have is None or top > have[0]:
                self.best[int(w)] = [float(top), set(k[c == top].tolist())]
            elif top == have[0]:
                have[1].update(k[c == top].tolist())

    def result(self):
        return {w: (c, frozenset(ks)) for w, (c, ks) in self.best.items()}


def reference(cfg, seed, n_events, dtype=np.float64):
    """Every (auction, window, count) the offered stream owes, from the
    seed alone: event i is pool row i % pool_rows with ts = i.  ``dtype``
    is the precision the pane counts are added in (the control's door)."""
    from benchmarks.harness.check import sliding_counts
    pool = make_pool(cfg, seed)
    return sliding_counts(pool["key"], None, n_events, cfg["win_events"],
                          cfg["slide_events"], n_key_ids(cfg), dtype)


def reference_fold(rows):
    """The hottest auctions per window from (keys, window ids, counts):
    plain, one pass in window order."""
    keys, wids, counts = (np.asarray(a) for a in rows[:3])
    order = np.lexsort((keys, wids))
    keys, wids, counts = keys[order], wids[order], counts[order]
    cuts = np.flatnonzero(np.diff(wids)) + 1
    out = {}
    for lo, hi in zip(np.r_[0, cuts], np.r_[cuts, len(wids)]):
        if hi > lo:
            top = counts[lo:hi].max()
            out[int(wids[lo])] = (float(top), frozenset(
                keys[lo:hi][counts[lo:hi] == top].tolist()))
    return out


def logical_bytes_per_row(cfg):
    from benchmarks.harness.window import fold_bytes_per_row
    return fold_bytes_per_row(cfg["win_events"], cfg["slide_events"])
