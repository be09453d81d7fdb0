"""The load generator: the body of the graph's ``BatchSource``.

One general generator reads a traffic file's parameters.  Event ``i`` of
the offered stream is pool row ``i % pool_rows`` with ``id = ts = i``, so
timestamps stay dense whatever the chunk size.  The body runs on the
source's own thread: there is no second thread to starve.

* ``sat``: closed loop.  Every call hands over the next chunk at once.
* ``paced``: open loop.  Event ``i`` is created when the file's
  :class:`Schedule` says (a fixed ``rate_events_per_s``, or ``phases`` of
  ``[seconds, rate]`` that repeat: bursts, on and off); a chunk is due
  when its last event exists, the body sleeps until then, and a body that
  is called late hands over at once: lag, not a lower rate.  A rate over
  what the graph sustains needs nothing else: the lag then grows.

Phases: ``warm`` (flat out or at rate, until the warm-up rule holds),
``window`` (``seconds`` long, from the hand-off that opened it), then the
body returns ``None`` and the graph drains.  The clock is handed in, so
the schedule can be tested with a fake one.
"""
import gc
import threading
import time

import numpy as np


class Schedule:
    """When event ``i`` is created, in seconds after event 0's clock
    start: ``phases`` of ``[seconds, events per second]`` repeated for
    ever (a rate of 0 is a pause).  A fixed ``rate_events_per_s`` is one
    phase."""

    def __init__(self, traffic):
        phases = traffic.get("phases") \
            or [[1.0, traffic["rate_events_per_s"]]]
        self.dur = np.asarray([p[0] for p in phases], np.float64)
        self.rates = np.asarray([p[1] for p in phases], np.float64)
        if (self.dur <= 0).any() or (self.rates < 0).any() \
                or not (self.rates > 0).any():
            raise ValueError("phases want seconds > 0 and a rate > 0")
        ev = self.dur * self.rates
        self.ev_end = np.cumsum(ev)
        self.ev_start = self.ev_end - ev
        self.t_start = np.cumsum(self.dur) - self.dur
        self.cycle_events, self.cycle_s = ev.sum(), self.dur.sum()
        self.peak_rate = self.rates.max()

    def created_s(self, i):
        """Seconds after the start at which event(s) ``i`` exist."""
        cycle, r = np.divmod(np.asarray(i, np.float64), self.cycle_events)
        # the first phase that holds more than r events of the cycle
        k = np.minimum(np.searchsorted(self.ev_end, r, side="right"),
                       len(self.dur) - 1)
        return (cycle * self.cycle_s + self.t_start[k]
                + (r - self.ev_start[k]) / np.maximum(self.rates[k], 1e-300))


class Generator:
    def __init__(self, pool, traffic, seconds, make_batch, clock=None,
                 sleep=None, results_seen=lambda: 0, on_open=None,
                 on_close=None):
        self.traffic = traffic
        self.paced = traffic["mode"] == "paced"
        if traffic["mode"] not in ("sat", "paced"):
            raise ValueError(f"unknown traffic mode {traffic['mode']!r}")
        self.chunk = int(traffic["chunk_events"])
        self.schedule = Schedule(traffic) if self.paced else None
        self.seconds = float(seconds)
        self.clock = clock or time.perf_counter
        self.sleep = sleep or time.sleep
        self.make_batch = make_batch
        self.results_seen = results_seen
        self.on_open, self.on_close = on_open, on_close
        n = len(next(iter(pool.values())))
        self.pool_rows = n
        # the pool with its head repeated behind it: every chunk is one
        # contiguous view, whatever its size
        self.cols = {k: np.concatenate([v, v[:self.chunk]])
                     for k, v in pool.items()}
        self.lane = np.arange(self.chunk, dtype=np.int64)
        self.go = threading.Event()      # set when the window may open
        self.closed = threading.Event()  # set when the window has closed
        self.phase = "warm"
        self.sent = 0                    # events handed over so far
        self.t0 = None                   # first call: creation time of event 0
        self.t_open = self.t_close = None
        self.settled_since = None
        self.frozen = False
        self.t_prev_exit = None
        # per hand-off, from the first one on (warm-up included)
        self.handoff_t, self.due_t, self.blocked_s = [], [], []

    # -- the schedule ------------------------------------------------------
    def due(self, n_sent_after):
        """When the chunk that ends at event ``n_sent_after - 1`` is due:
        the creation time of its last event."""
        return self.t0 + float(self.schedule.created_s(n_sent_after - 1))

    def created_at(self, i):
        """Scheduled creation time(s) of event(s) ``i`` (paced only)."""
        return self.t0 + self.schedule.created_s(i)

    def _warm(self, now, lag):
        """Whether the warm-up rule holds at this hand-off."""
        tr = self.traffic
        since = now - self.t0
        if since < tr["warmup_s"] \
                or self.results_seen() < tr["warmup_min_result_batches"]:
            return False
        if not self.frozen:
            # what warm-up built stays out of the collector's way; the
            # collection takes its time, so no window opens on this chunk
            self.frozen = True
            gc.collect()
            gc.freeze()
            return False
        if not self.paced:
            return True
        period = self.chunk / self.schedule.peak_rate
        if lag <= tr["settle_lag_chunks"] * period:
            if self.settled_since is None:
                self.settled_since = now
        else:
            self.settled_since = None
        settled = (self.settled_since is not None
                   and now - self.settled_since >= tr["settle_s"])
        return settled or since >= tr["warmup_s"] + tr["settle_max_s"]

    # -- the BatchSource body ----------------------------------------------
    def __call__(self, ctx=None):
        t_enter = self.clock()
        if self.phase == "done":
            return None
        if self.t0 is None:
            self.t0 = t_enter
        if self.phase == "window" and t_enter >= self.t_open + self.seconds:
            return self._close(t_enter)
        i0 = self.sent
        n = self.chunk
        r = i0 % self.pool_rows
        ts = self.lane + i0
        batch = self.make_batch({**{k: v[r:r + n]
                                    for k, v in self.cols.items()},
                                 "id": ts, "ts": ts})
        due = None
        if self.paced:
            due = self.due(i0 + n)
            while True:
                left = due - self.clock()
                if left <= 0:
                    break
                # sleep most of the wait, spin the last fifth of a ms
                if left > 3e-4:
                    self.sleep(left - 2e-4)
            if self.phase == "window" \
                    and self.clock() >= self.t_open + self.seconds:
                return self._close(self.clock())
        now = self.clock()
        # the graph held the source: the body was free to be called
        # (after its last return, and once this chunk was due) and was not
        free_from = self.t_prev_exit if self.t_prev_exit is not None \
            else t_enter
        if due is not None:
            free_from = max(free_from, due)
        self.blocked_s.append(max(0.0, t_enter - free_from))
        self.handoff_t.append(now)
        self.due_t.append(due if due is not None else now)
        self.sent = i0 + n
        if self.phase == "warm" and self.go.is_set() \
                and self._warm(now, now - (due if due is not None else now)):
            self.phase = "window"
            self.t_open = now
            self.i_open = len(self.handoff_t)
            if self.on_open:
                self.on_open(now)
        self.t_prev_exit = self.clock()
        return batch

    def _close(self, now):
        self.phase = "done"
        self.t_close = self.t_open + self.seconds
        self.i_close = len(self.handoff_t)
        if self.on_close:
            self.on_close(now)
        self.closed.set()
        return None

    # -- what the window saw -----------------------------------------------
    def window(self):
        """Hand-offs inside the window: (times, due times, blocked s)."""
        lo, hi = self.i_open, self.i_close
        t = np.asarray(self.handoff_t[lo:hi])
        keep = t <= self.t_close
        return (t[keep], np.asarray(self.due_t[lo:hi])[keep],
                np.asarray(self.blocked_s[lo:hi])[keep])
