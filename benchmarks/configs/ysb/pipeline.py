"""Yahoo Streaming Benchmark on one chip: the wiring, the stream's law
and the plain reference.

``build`` is a copy of ``windflow_tpu/models/yahoo.build_pipeline`` with
the source body and the sink handed in (the model makes its own source
and cannot be fed).  ``reference`` imports nothing of the program.
"""
import numpy as np

VIEW = 0


def n_ads(cfg):
    return cfg["n_campaigns"] * cfg["ads_per_campaign"]


def campaign_map(cfg, seed):
    """ad -> campaign: ``ads_per_campaign`` ads to each campaign, in an
    order drawn from the seed."""
    rng = np.random.default_rng([seed, 1])
    return rng.permutation(n_ads(cfg)).astype(np.int64) \
        // cfg["ads_per_campaign"]


def make_pool(cfg, seed):
    """The ad-event pool, from the seed alone: the columns
    ``models/yahoo.synth_events`` has, and the constant value column."""
    rng = np.random.default_rng(seed)
    n = cfg["pool_rows"]
    return {
        "key": rng.integers(0, n_ads(cfg), n, dtype=np.int64),
        "event_type": rng.integers(0, cfg["n_event_types"], n,
                                   dtype=np.int64),
        "value": np.ones(n, np.float64),
    }


def build(graph, cfg, source_body, sink, seed):
    import windflow_tpu as wf
    from windflow_tpu.operators.basic_ops import Sink
    from windflow_tpu.operators.batch_ops import (BatchFilter, BatchMap,
                                                  BatchSource)
    from windflow_tpu.operators.tpu.farms_tpu import KeyFarmTPU
    campaign_of_ad = campaign_map(cfg, seed)

    def views_only(batch):
        return batch["event_type"] == VIEW

    def join_campaign(batch):
        return batch.with_cols(key=campaign_of_ad[batch.key])

    counter = KeyFarmTPU("count", cfg["win_events"], cfg["slide_events"],
                         wf.WinType.TB, batch_len=cfg["device_batch"],
                         name="campaign_count", emit_batches=True)
    pipe = graph.add_source(BatchSource(source_body))
    pipe.chain(BatchFilter(views_only)).chain(BatchMap(join_campaign)) \
        .add(counter)
    pipe.add_sink(Sink(sink, name="count_sink"))


def launches(graph):
    from benchmarks.harness.runner import stats_sum
    return stats_sum(graph, "campaign_count", "num_launches")


def device_time_ms(graph):
    from benchmarks.harness.runner import stats_sum
    return stats_sum(graph, "campaign_count", "device_time_ms")


def reference(cfg, seed, n_events, dtype=np.float64):
    """Every (campaign, window, count of views) the offered stream owes,
    from the seed alone: event i is pool row i % pool_rows with ts = i."""
    from benchmarks.harness.check import sliding_counts
    pool = make_pool(cfg, seed)
    campaign = campaign_map(cfg, seed)[pool["key"]]
    return sliding_counts(campaign, pool["event_type"] == VIEW, n_events,
                          cfg["win_events"], cfg["slide_events"],
                          cfg["n_campaigns"], dtype)


def logical_bytes_per_row(cfg):
    from benchmarks.harness.window import fold_bytes_per_row
    return fold_bytes_per_row(cfg["win_events"], cfg["slide_events"])
