"""The window's arithmetic: latency from scheduled creation stamps, the
sink's busy time, quantiles, and the per-second timeline."""
import numpy as np


def quantile(values, q):
    """Linear-interpolated quantile of all the values (numpy's default)."""
    return float(np.quantile(np.asarray(values, np.float64), q))


def result_latency_s(row_t, row_wid, win, slide, created_at):
    """Per result row: time of receipt minus the scheduled creation time
    of the last event of the row's window, ``created_at(window_end - 1)``
    with ``window_end = wid * slide + win`` (at a fixed rate,
    ``t0 + (window_end - 1) / rate``).  A window the chunking completes
    late, a generator that lags and a queue that waits all count as
    latency."""
    window_end = np.asarray(row_wid, np.int64) * slide + win
    return np.asarray(row_t, np.float64) - created_at(window_end - 1)


def fold_bytes_per_row(win, slide):
    """HBM bytes a pane-partial window fold must move per result row, from
    the shapes alone: one new f32 pane partial per key and slide shipped
    in and written (4 + 4), the window's extent (two int32), its
    ``win // slide`` partials read, one f32 result written."""
    return 4 + 4 + 8 + 4 * (win // slide) + 4


def busy_s(t_in, t_out, lo, hi):
    """Seconds of [lo, hi] covered by the (t_in, t_out) spans."""
    t_in, t_out = np.asarray(t_in, np.float64), np.asarray(t_out, np.float64)
    return float(np.clip(np.minimum(t_out, hi) - np.maximum(t_in, lo),
                         0, None).sum())


def timeline(t_open, window_s, chunk, handoff_t, lag_s, row_t, latency_s):
    """Per second of the window: events handed over, the worst generator
    lag, result rows and their median latency (the last two for paced
    traffic).  A tipped run shows here as lag and latency that climb and
    do not come back."""
    sec_h = np.floor(np.asarray(handoff_t) - t_open).astype(int)
    sec_r = np.floor(np.asarray(row_t) - t_open).astype(int)
    rows = []
    for s in range(int(np.ceil(window_s))):
        h, r = sec_h == s, sec_r == s
        row = {"kind": "timeline", "second": s,
               "events": int(h.sum()) * chunk, "rows": int(r.sum())}
        if lag_s is not None:
            row["lag_max_ms"] = (1e3 * float(np.asarray(lag_s)[h].max())
                                 if h.any() else None)
            row["latency_p50_ms"] = (
                1e3 * quantile(np.asarray(latency_s)[r], 0.5)
                if r.any() else None)
        rows.append(row)
    return rows
