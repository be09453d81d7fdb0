"""The latency arithmetic on a hand-made stream whose window ends fall
inside chunks."""
import numpy as np
import pytest

from benchmarks.harness import window


def test_latency_is_from_the_scheduled_creation_of_the_windows_last_event():
    # rate 1000 events/s from t0 = 50: event i is created at 50 + i ms.
    # win 300, slide 100: window 0 ends with event 299, window 2 with 499.
    # Chunks of 250 events are handed over at 50.250, 50.500, ...: window
    # 0's last event is inside the second chunk, which exists at 50.500.
    t0, rate, win, slide = 50.0, 1000.0, 300, 100
    wid = np.array([0, 0, 2, 7])
    recv = np.array([50.520, 50.530, 50.520, 52.0])
    lat = window.result_latency_s(recv, wid, win, slide,
                                  lambda i: t0 + i / rate)
    assert lat == pytest.approx([0.221, 0.231, 0.021, 1.001])
    # the chunking wait (50.500 - 50.299 = 201 ms) is inside the latency
    assert lat[0] > 0.201


def test_quantile_is_over_all_rows():
    assert window.quantile([1, 2, 3, 4, 100], 0.5) == 3
    assert window.quantile(np.arange(101), 0.95) == 95


def test_busy_is_clipped_to_the_window():
    t_in = [0.0, 1.0, 2.9, 5.0]
    t_out = [0.5, 1.25, 3.5, 6.0]
    assert window.busy_s(t_in, t_out, 1.0, 3.0) == pytest.approx(0.35)


def test_a_tipped_run_shows_in_the_timeline():
    t_open = 10.0
    handoff = t_open + np.arange(1, 301) * 0.01
    lag = np.where(handoff > t_open + 1.5, handoff - t_open - 1.5, 0.0)
    row_t = t_open + np.arange(1, 31) * 0.1
    lat = np.where(row_t > t_open + 1.5, 0.4, 0.008)
    rows = window.timeline(t_open, 3.0, 50, handoff, lag, row_t, lat)
    assert [r["second"] for r in rows] == [0, 1, 2]
    assert sum(r["events"] for r in rows) == 299 * 50   # the last hand-off closes it
    assert rows[0]["latency_p50_ms"] == pytest.approx(8.0)
    assert rows[2]["latency_p50_ms"] == pytest.approx(400.0)
    assert rows[2]["lag_max_ms"] > rows[1]["lag_max_ms"] > 0
    sat = window.timeline(t_open, 3.0, 50, handoff, None, row_t, None)
    assert "lag_max_ms" not in sat[0]
