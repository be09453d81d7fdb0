"""Result extraction and sink: share of the window the sink thread spent
between receiving a batch and returning."""


def read(rec):
    return rec["sink_busy_s"] / rec["window_s"]
