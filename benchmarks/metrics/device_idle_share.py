"""Device: 100 x (1 - union of device-op time / window) from the traced
window; the fullest device where there are several."""


def read(rec):
    if rec["trace"] is None:
        return None
    return 100.0 * rec["trace"]["idle_share"]
