"""The generator's schedule, stamps and lag, with a fake clock."""
import numpy as np
import pytest

from benchmarks.harness.generator import Generator


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        self.t += 1e-6          # reading the clock takes a moment
        return self.t

    def sleep(self, s):
        self.t += s + 1e-5      # a sleep always overshoots a little


def make(traffic, clock, seconds=1.0, rows=4096, results=lambda: 99):
    pool = {"key": np.arange(rows, dtype=np.int64) % 7,
            "value": np.ones(rows)}
    gen = Generator(pool, traffic, seconds, dict, clock=clock,
                    sleep=clock.sleep, results_seen=results)
    gen.go.set()
    return gen


PACED = {"mode": "paced", "rate_events_per_s": 10_000, "chunk_events": 100,
         "warmup_s": 0.05, "warmup_min_result_batches": 1,
         "settle_lag_chunks": 1.0, "settle_s": 0.05, "settle_max_s": 1.0}
SAT = {"mode": "sat", "chunk_events": 256, "warmup_s": 0.05,
       "warmup_min_result_batches": 1}


def test_ids_are_dense_whatever_the_chunk_and_the_pool_wraps():
    clock = FakeClock()
    gen = make(dict(SAT, chunk_events=1000), clock, rows=4096)
    seen = []
    for _ in range(9):
        clock.t += 0.01
        b = gen()
        seen.append(b)
    ids = np.concatenate([b["id"] for b in seen])
    assert (ids == np.arange(9000)).all()
    keys = np.concatenate([b["key"] for b in seen])
    assert (keys == (np.arange(9000) % 4096) % 7).all()
    assert all((b["ts"] == b["id"]).all() for b in seen)


def test_paced_hands_over_when_the_last_event_exists():
    clock = FakeClock()
    gen = make(PACED, clock, seconds=0.5)
    while gen() is not None:
        pass
    t, due = np.asarray(gen.handoff_t), np.asarray(gen.due_t)
    k = np.arange(1, len(t) + 1)
    # chunk k is due when event 100 k - 1 is created
    assert due == pytest.approx(gen.t0 + (100 * k - 1) / 10_000)
    assert ((t - due) >= 0).all() and ((t - due) < 1e-3).all()
    ht, hdue, blocked = gen.window()
    assert len(ht) * 100 == pytest.approx(0.5 * 10_000, abs=100)
    assert blocked.sum() == 0.0
    assert gen.t_close - gen.t_open == 0.5


def test_a_late_body_is_lag_not_a_lower_rate():
    clock = FakeClock()
    gen = make(PACED, clock, seconds=0.5)
    n = 0
    while True:
        if n == 30:
            clock.t += 0.055      # the graph holds the source 55 ms
        if gen() is None:
            break
        n += 1
    lag = np.asarray(gen.handoff_t) - np.asarray(gen.due_t)
    assert lag[30] == pytest.approx(0.045, abs=2e-3)
    # caught up chunk by chunk with no sleep: lag falls to nothing
    assert (np.diff(lag[30:35]) < 0).all() and lag[36] < 1e-3
    # as many events as the schedule owes, stall or not
    assert gen.sent == pytest.approx((clock.t - gen.t0) * 10_000, abs=200)
    # the stall is time the graph held a chunk that was due
    assert sum(gen.blocked_s) == pytest.approx(0.045, abs=2e-3)


def test_the_window_opens_only_after_the_lag_has_settled():
    clock = FakeClock()
    stall = {"until": 0}
    gen = make(dict(PACED, settle_s=0.1), clock, seconds=0.2)
    n = 0
    while gen.phase == "warm":
        if n == 10:
            clock.t += 0.2        # a compile in the warm-up
            stall["until"] = clock.t
        assert gen() is not None
        n += 1
    lag_at_open = gen.t_open - gen.due_t[gen.i_open - 1]
    assert lag_at_open < 0.01
    assert gen.t_open >= stall["until"] + 0.1


def test_no_results_no_window():
    clock = FakeClock()
    gen = make(SAT, clock, results=lambda: 0)
    for _ in range(50):
        clock.t += 0.01
        gen()
    assert gen.phase == "warm"


def test_sat_counts_the_graphs_hold_as_blocked():
    clock = FakeClock()
    gen = make(SAT, clock, seconds=1.0)
    while True:
        clock.t += 0.004          # the graph takes 4 ms per chunk
        if gen() is None:
            break
    ht, _, blocked = gen.window()
    assert blocked.sum() == pytest.approx(1.0, abs=0.01)
    assert len(ht) == pytest.approx(250, abs=2)


def test_unknown_mode():
    with pytest.raises(ValueError):
        make({"mode": "replay", "chunk_events": 1}, FakeClock())


BURST = dict(PACED, phases=[[0.2, 20_000], [0.2, 0]])   # 10,000 mean


def test_bursts_are_a_traffic_file_and_no_new_code():
    clock = FakeClock()
    gen = make(BURST, clock, seconds=1.0)
    while gen() is not None:
        pass
    t, due = np.asarray(gen.handoff_t), np.asarray(gen.due_t)
    assert ((t - due) >= 0).all() and ((t - due) < 1e-3).all()
    # 4,000 events in each 0.2 s burst, none in the pauses between
    since = (t - gen.t0) % 0.4
    assert (since < 0.2 + 1e-3).all()
    assert gen.sent == pytest.approx((clock.t - gen.t0) * 10_000, abs=4100)
    # event 4000 is the first of the second burst: created at 0.4 s
    assert gen.created_at(4000) - gen.t0 == pytest.approx(0.4)
    assert gen.created_at(np.array([0, 3999, 8000])) - gen.t0 == \
        pytest.approx([0.0, 0.19995, 0.8])


def test_over_the_knee_is_a_rate_and_the_lag_grows():
    clock = FakeClock()
    gen = make(dict(PACED, settle_max_s=0.1), clock, seconds=0.5)
    while True:
        clock.t += 0.02           # the graph takes 20 ms per 10 ms chunk
        if gen() is None:
            break
    ht, due, blocked = gen.window()
    lag = ht - due
    assert (np.diff(lag) > 0).all() and lag[-1] > 0.2
    # completed events per second is what the graph took, not the offer
    assert len(ht) * 100 / 0.5 == pytest.approx(5_000, rel=0.05)
