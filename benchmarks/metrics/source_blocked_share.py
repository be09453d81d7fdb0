"""Source and ingest: share of the window in which a chunk was due and
the ``BatchSource`` body had returned and had not been called again: the
graph held the source.  Near 1 when the graph sets the pace, near 0 when
the generator does."""


def read(rec):
    return float(rec["blocked_s"].sum()) / rec["window_s"]
