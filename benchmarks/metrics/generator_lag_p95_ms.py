"""Load generator: 95th percentile of hand-off time minus scheduled time
over the window's chunks (paced traffic only)."""
import numpy as np


def read(rec):
    if rec["lag_s"] is None or not len(rec["lag_s"]):
        return None
    return 1e3 * float(np.quantile(rec["lag_s"], 0.95))
