"""The comparison that decides ``correct``: what the sink received
against a plain numpy recomputation of the same windows from the events
the generator offered.  Imports nothing of the program.

Exact comparison: every number compared has the limit 0.
"""
import sys

import numpy as np

LIMITS = {
    "rows_missing": 0,       # owed by the stream, never received
    "rows_unexpected": 0,    # received twice, or owed by no window
    "rows_wrong": 0,         # received with another count
    "max_count_error": 0,    # the widest |count - reference|
    "events_uncounted": 0,   # |sum of tumbling counts - events kept|
    "folds_wrong": 0,        # windows whose sink fold differs or is missing
    "dropped_tuples": 0,
    "dead_letters": 0,
    "svc_failures": 0,
    "shed_tuples": 0,
}


def sliding_counts(key_of_row, keep_of_row, n_events, win, slide, n_keys,
                   dtype=np.float64):
    """(keys, window ids, counts, events kept) of a count-per-key sliding
    window over the stream whose event ``i`` is pool row ``i % rows`` with
    ``ts = i``: window ``w`` of key ``k`` covers ``w*slide <= ts <
    w*slide + win`` and is owed when ``w*slide <= `` the key's last ts
    (the stream's end cuts the last ones short).  Straightforward: a
    bincount of (pane, key) over blocks of the stream, then each window
    adds its ``win // slide`` panes in ``dtype``."""
    if win % slide:
        raise ValueError("win must be a multiple of slide")
    rows = len(key_of_row)
    n_panes = (n_events - 1) // slide + 1 if n_events else 0
    panes = np.zeros((n_panes, n_keys), np.int64)
    # blocks of one pool pass where the pool holds whole panes: the rows
    # then come in pool order and need no gather
    block = rows if rows % slide == 0 else slide
    lane = np.arange(block, dtype=np.int64)

    def codes(i0, n):
        idx = (i0 + lane[:n]) % rows
        code = lane[:n] // slide * n_keys + key_of_row[idx]
        return code if keep_of_row is None else code[keep_of_row[idx]]

    whole = codes(0, block) if block == rows else None
    for i0 in range(0, n_events, block):
        n = min(block, n_events - i0)
        # a whole pool pass reads the same rows in the same order
        code = whole if whole is not None and n == block else codes(i0, n)
        flat = np.bincount(code, minlength=((n - 1) // slide + 1) * n_keys)
        p0 = i0 // slide
        panes[p0:p0 + len(flat) // n_keys] += flat.reshape(-1, n_keys)
    # a key's windows are owed up to the last pane it has an event in
    seen = panes > 0
    last_pane = np.where(seen.any(axis=0),
                         n_panes - 1 - np.argmax(seen[::-1], axis=0), -1)
    per_win = win // slide
    padded = np.concatenate([panes, np.zeros((per_win, n_keys), np.int64)])
    counts = np.zeros((n_panes, n_keys), dtype)
    for j in range(per_win):
        counts = (counts + padded[j:j + n_panes].astype(dtype)).astype(dtype)
    owed = np.arange(n_panes, dtype=np.int64)[:, None] <= last_pane[None, :]
    wids, keys = np.nonzero(owed)
    return (keys.astype(np.int64), wids.astype(np.int64),
            counts[wids, keys].astype(np.float64), int(panes.sum()))


def compare(got, want, win, slide, counters):
    """The numbers compared, each beside its limit.  ``got`` is (keys,
    window ids, values) as the sink received them; ``want`` is what
    :func:`sliding_counts` gave; ``counters`` are the program's own
    failure counts."""
    g_keys, g_wids, g_vals = (np.asarray(a) for a in got)
    w_keys, w_wids, w_vals, kept = want
    n_keys = int(max(w_keys.max(initial=0), g_keys.max(initial=0))) + 1
    w_code = w_wids * n_keys + w_keys
    g_code = g_wids.astype(np.int64) * n_keys + g_keys.astype(np.int64)
    order = np.argsort(w_code)
    w_code, w_sorted = w_code[order], w_vals[order]
    uniq, first, n_seen = np.unique(g_code, return_index=True,
                                    return_counts=True)
    pos = np.searchsorted(w_code, uniq)
    pos_c = np.minimum(pos, max(len(w_code) - 1, 0))
    known = (pos < len(w_code)) & (w_code[pos_c] == uniq) \
        if len(w_code) else np.zeros(len(uniq), bool)
    err = np.abs(g_vals[first][known].astype(np.float64)
                 - w_sorted[pos_c[known]])
    # each event is in win // slide windows; the EOS-cut tail makes the
    # identity exact only for tumbling windows, so sum those alone
    numbers = {
        "rows_missing": int(len(w_code) - known.sum()),
        "rows_unexpected": int((~known).sum() + (n_seen[known] - 1).sum()),
        "rows_wrong": int((err > 0).sum()),
        "max_count_error": float(err.max(initial=0.0)),
        "events_uncounted": (abs(float(g_vals.sum()) - kept)
                             if win == slide else 0.0),
    }
    numbers.update({k: counters.get(k, 0) for k in
                    ("dropped_tuples", "dead_letters", "svc_failures",
                     "shed_tuples")})
    return numbers


def compare_folds(got, want):
    """``folds_wrong``: windows whose fold in the sink (for Q5 the most
    bids and the auctions that have them) is not the reference's, or is
    on one side alone."""
    return {"folds_wrong": sum(got.get(w) != v for w, v in want.items())
            + len(set(got) - set(want))}


def verdict(numbers, out=sys.stderr):
    """``(correct, compared)``; prints each number beside its limit.  A
    configuration with no fold in its sink has no ``folds_wrong``."""
    compared = {k: {"value": numbers[k], "limit": LIMITS[k]}
                for k in LIMITS if k in numbers}
    correct = all(v["value"] <= v["limit"] for v in compared.values())
    for k, v in compared.items():
        print(f"check {k}: {v['value']} (limit {v['limit']})", file=out)
    print(f"check correct: {correct}", file=out)
    return correct, compared
