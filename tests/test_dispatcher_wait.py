"""The window engine's dispatcher thread (``_AsyncDispatcher`` in
operators/tpu/win_seq_tpu.py) waits for the oldest in-flight result
itself: no timer, no poll.  A fake engine whose handles become ready on
a ``threading.Event`` scripts the device; no assertion reads a clock,
every wait in here has a timeout and is on a condition, not a duration."""
import json
import threading
import time

import numpy as np
import pytest

import windflow_tpu as wf
from windflow_tpu.operators.basic_ops import Sink
from windflow_tpu.operators.batch_ops import BatchSource
from windflow_tpu.operators.tpu.win_seq_tpu import (WinSeqTPU,
                                                    WinSeqTPULogic)
from windflow_tpu.telemetry import spans

from test_spans import chunks

LONG = 30.0   # seconds: the timeout of every wait, never reached


def until(cond, what):
    deadline = time.monotonic() + LONG
    while not cond():
        assert time.monotonic() < deadline, f"timed out: {what}"
        time.sleep(0.001)


class Handle:
    """A launch's result: ready once ``event`` is set."""

    buffers_in = 1      # what the launch record takes from a handle
    t_packed = t_called = None

    def __init__(self, n, log):
        self.n, self.log = n, log
        self.event = threading.Event()
        self.waiting = threading.Event()   # the dispatcher is in wait()
        self.ready_calls = self.wait_calls = self.block_calls = 0
        self.fail_wait = self.fail_block = False

    def ready(self):
        self.ready_calls += 1
        return self.event.is_set()

    def wait(self):
        self.wait_calls += 1
        self.log.append(("wait", self.n))
        self.waiting.set()
        assert self.event.wait(LONG)
        if self.fail_wait:
            raise OSError("the device went away")

    def block(self):
        self.block_calls += 1
        assert self.event.is_set(), "block() entered before the result"
        if self.fail_block:
            raise OSError("the copy failed")
        return np.asarray([self.n], np.float64)


class Rig:
    """A ``WinSeqTPULogic`` fed launches by hand: ``launch()`` is what
    ``_launch`` does with a staged batch, ``emitted`` what the
    sink would see."""

    def __init__(self, depth=3):
        self.logic = WinSeqTPULogic("sum", 8, 8, wf.WinType.CB,
                                    inflight_depth=depth, emit_batches=True)
        self.log, self.handles, self.emitted = [], [], []
        self.most_in_flight = 0

    # the engine
    def compute(self, cols, starts, ends, gwids):
        h = Handle(len(self.handles) + 1, self.log)
        self.handles.append(h)
        self.log.append(("dispatch", h.n))
        in_flight = len(self.handles) - sum(x.block_calls
                                            for x in self.handles)
        self.most_in_flight = max(self.most_in_flight, in_flight)
        return h

    def _emit(self, batch):
        n = int(batch["value"][0])
        self.log.append(("emit", n))
        self.emitted.append(n)

    def launch(self):
        n = self.logic._launches.seq + 1
        one = np.asarray([n], np.int64)
        self.logic._submit({}, one, one, one, (one, one, one),
                           self._emit, engine=self)

    @property
    def dispatcher(self):
        return self.logic._dispatcher

    def in_wait(self, n):
        """Block until the dispatcher sleeps on launch ``n``'s result."""
        until(lambda: len(self.handles) >= n, f"launch {n} dispatched")
        assert self.handles[n - 1].waiting.wait(LONG)
        return self.handles[n - 1]

    def drain(self):
        self.logic._drain_all(self._emit)

    def records(self):
        return list(self.logic._launches.records)


def test_it_sleeps_on_the_result_and_does_not_poll():
    rig = Rig()
    rig.launch()
    h = rig.in_wait(1)
    looked = h.ready_calls
    assert looked <= 1 and h.wait_calls == 1 and rig.emitted == []
    # a poll would come round many times in this stretch; nothing here
    # depends on how long it is
    time.sleep(0.05)
    assert h.ready_calls == looked and h.wait_calls == 1
    assert rig.emitted == []
    h.event.set()
    until(lambda: rig.emitted == [1], "the result emitted on the event")
    rig.drain()
    assert h.wait_calls == 1 and h.block_calls == 1
    assert rig.records()[0].collected == spans.WAITED


def test_work_staged_during_a_wait_goes_out_after_it_in_order():
    rig = Rig()
    rig.launch()
    first = rig.in_wait(1)
    rig.launch()              # staged: the dispatcher is asleep on 1
    assert len(rig.handles) == 1
    first.event.set()
    second = rig.in_wait(2)
    assert rig.emitted == [1]
    second.event.set()
    rig.drain()
    assert rig.emitted == [1, 2]
    assert rig.log == [("dispatch", 1), ("wait", 1), ("emit", 1),
                       ("dispatch", 2), ("wait", 2), ("emit", 2)]
    assert [h.block_calls for h in rig.handles] == [1, 1]


def all_four_ways(rig):
    """Launch 1 waited for; 2, 3 and 4 a backlog behind it, so 2 is
    forced at depth 3 and 3 and 4 found ready; 5 flushed at EOS."""
    rig.launch()
    first = rig.in_wait(1)
    for _ in range(3):
        rig.launch()
    first.event.set()
    second = rig.in_wait(2)
    assert rig.emitted == [1]
    # 5 and the EOS sentinel are both staged while the thread sleeps on 2
    rig.launch()
    eos = threading.Thread(target=rig.drain)
    eos.start()
    until(lambda: rig.dispatcher.work.qsize() == 2, "5 and EOS staged")
    for h in rig.handles[1:4]:
        h.event.set()
    rig.in_wait(5).event.set()
    eos.join(LONG)
    assert not eos.is_alive()


def test_a_backlog_is_dispatched_before_any_wait_and_depth_holds():
    rig = Rig(depth=3)
    all_four_ways(rig)
    assert rig.emitted == [1, 2, 3, 4, 5]
    i = rig.log.index(("emit", 1))
    assert rig.log[i + 1:i + 5] == [("dispatch", 2), ("dispatch", 3),
                                    ("dispatch", 4), ("wait", 2)]
    assert rig.most_in_flight == 3
    # a result found ready is not waited for
    assert [h.wait_calls for h in rig.handles] == [1, 1, 0, 0, 1]
    assert [h.block_calls for h in rig.handles] == [1] * 5


def test_the_collected_by_counts_and_the_stamps():
    rig = Rig(depth=3)
    all_four_ways(rig)
    recs = rig.records()
    assert [r.collected for r in recs] == [
        spans.WAITED, spans.FORCED, spans.READY, spans.READY, spans.FLUSHED]
    for r in recs:
        assert r.t_submitted <= r.t_picked <= r.t_dispatched \
            <= r.t_ready_seen <= r.t_on_host <= r.t_emitted
    summary = rig.logic._launches.summary()
    assert summary["Collected"] == {"ready": 2, "waited": 1, "forced": 1,
                                    "flushed": 1}
    assert sum(summary["Collected"].values()) == summary["Launches"] == 5
    assert summary["Slowest"]["Collected"] in spans.COLLECTED


@pytest.mark.parametrize("where", ["wait", "block"])
def test_a_failure_surfaces_on_the_next_submit_and_on_drain(where):
    rig = Rig()
    rig.launch()
    h = rig.in_wait(1)
    setattr(h, "fail_" + where, True)
    h.event.set()
    until(lambda: rig.dispatcher.error is not None, "the error recorded")
    with pytest.raises(RuntimeError, match="dispatch thread failed") as e:
        rig.launch()
    assert isinstance(e.value.__cause__, OSError)
    with pytest.raises(RuntimeError, match="dispatch thread failed"):
        rig.drain()
    assert rig.emitted == [] and len(rig.handles) == 1


def test_abort_during_a_wait_returns_once_the_handle_completes():
    rig = Rig()
    rig.launch()
    h = rig.in_wait(1)
    disp = rig.dispatcher
    gone = threading.Thread(target=disp.abort)
    gone.start()
    until(lambda: disp.aborting, "abort under way")
    assert disp.thread.is_alive()     # asleep on the device, as at depth
    h.event.set()
    gone.join(LONG)
    assert not gone.is_alive() and not disp.thread.is_alive()
    assert rig.emitted == [] and h.block_calls == 0


@pytest.mark.parametrize("kwargs", [
    {}, {"async_dispatch": False}, {"placement": "host"}],
    ids=["async", "inline", "host"])
def test_every_lane_counts_how_each_launch_was_collected(kwargs):
    """The real engines on the CPU backend: every launch of the ring is
    collected in exactly one of the four ways, in the stats JSON too."""
    name = "collected_" + "_".join(kwargs) if kwargs else "collected_async"
    got = []
    g = wf.PipeGraph(name, wf.Mode.DEFAULT)
    g.add_source(BatchSource(chunks(64))) \
        .add(WinSeqTPU("sum", 4096, 2048, wf.WinType.TB, batch_len=8,
                       name="win", emit_batches=True, **kwargs)) \
        .add_sink(Sink(lambda b: got.append(b) if b is not None else None,
                       name="outlet"))
    g.run()
    ring = next(iter(spans.graph(name).rings.values()))
    assert all(r.collected in spans.COLLECTED for r in ring.records)
    block = json.loads(g.stats.to_json())["Spans"]["Launches"][0]
    assert sum(block["Collected"].values()) == block["Launches"] \
        == len(ring.records) > 3
    if kwargs.get("placement") == "host":   # ready as compute returns
        assert block["Collected"]["waited"] == 0
    if kwargs.get("async_dispatch") is False:
        assert block["Collected"]["waited"] == 0
