"""Shared helpers for the examples: the size knob and a counting sink,
so each walkthrough stays focused on the feature it shows."""
import os
import threading


def scale(n: int) -> int:
    """Stream length, shrunk under the smoke test."""
    return max(1000, n // 100) if os.environ.get(
        "WINDFLOW_EXAMPLES_SMALL") == "1" else n


class CountingSink:
    """Thread-safe sink callback: counts results and sums .value."""

    def __init__(self):
        self.lock = threading.Lock()
        self.count = 0
        self.total = 0.0

    def __call__(self, rec):
        if rec is None:
            return
        with self.lock:
            try:
                n = len(rec)            # columnar TupleBatch
                self.count += n
                self.total += float(rec["value"].sum())
            except TypeError:
                self.count += 1
                self.total += rec.value
