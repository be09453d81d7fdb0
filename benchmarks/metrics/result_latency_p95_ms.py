"""Whole path: 95th percentile over every result row received in the window of
receipt time minus the scheduled creation time of the last event of the
row's window (paced traffic only)."""
import numpy as np


def read(rec):
    if rec["latency_s"] is None or not len(rec["latency_s"]):
        return None
    return 1e3 * float(np.quantile(rec["latency_s"], 0.95))
