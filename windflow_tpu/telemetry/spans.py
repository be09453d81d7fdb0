"""The span layer inside the program (docs/OBSERVABILITY.md "Spans").

A span has a name ``wf/<operator>/<phase>``, a start and an end on
``time.perf_counter`` (read as integer nanoseconds), the thread it ran
on and its parent: the span open on that thread when it began.  Spans
are taken per *chunk*, per *batch taken from a channel* or per *launch*,
never per event or per tuple.  They are always on; there is no switch.

* Each thread has one :class:`Track` (thread-local), written by that
  thread alone, with one :class:`Cell` per span name: count, total,
  self and longest nanoseconds, and a bounded timeline of self
  nanoseconds per 100 ms bucket, so that any two instants can be cut
  out afterwards (:func:`self_seconds`).  Time is attributed to the
  innermost open span: a cell's self time is its spans' time minus what
  their child spans cover, exactly, accounted as the spans open and
  close.
* Tracks and launch rings hang under a :class:`SpanGraph` in one
  process-wide registry that outlives the graph (threads are the
  process's, and a reader may come after the graph is gone).  A graph's
  entry is dropped when a graph of the same name starts again.
* While a ``jax.profiler`` session runs, every span is also a
  ``TraceAnnotation`` of the same name, so the ``.xplane.pb`` holds the
  program's spans on the trace's clock beside the device's ops.  Whether
  a session runs is asked of the profiler itself (``TraceMe.is_enabled``,
  a flag test) as each span begins; JAX is never imported from here.
* A wait span over 30 ms and any working span over 100 ms goes to the
  graph's :class:`~windflow_tpu.telemetry.recorder.FlightRecorder` as
  ``slow_span``, with what the graph's other threads had open when it
  began.
* :class:`LaunchRing` keeps one :class:`Launch` record per device
  launch: eight host stamps whose differences are the stages of a
  launch's round trip and the parts of its ``dispatch``, and what the
  launch counted (:data:`LAUNCH_COUNTS`).
* Work that native code timed on its own steady clock (the window
  engine's ``open``, ``trigger`` and ``evict``) is entered as a child of
  the span open round the call (:meth:`Track.account`), with no clock
  read here; what the engine counts (:data:`ENGINE_STATS`, the one
  declaration of each count) is kept per operator in :class:`Counters`,
  and with it what the engine's other clocks read of the inside of
  ``fold`` and ``flush`` (:data:`ENGINE_CLOCKS`: kept as counters, never
  entered as spans, so the two spans' self time means what it meant).
"""
from __future__ import annotations

import sys
import threading
import time
from collections import deque
from typing import Dict, List, NamedTuple, Optional

_now = time.perf_counter_ns

BUCKET_NS = 100_000_000          # timeline bucket: 100 ms
TIMELINE_BUCKETS = 4096          # per cell: 6.8 minutes of buckets
LAUNCH_RING = 8192               # launches kept per window operator
MAX_ENDED_GRAPHS = 32            # ended graphs the registry keeps

# phases by what the thread is doing in them (the triad of
# choosing-metrics, stream processing: busy, idle, blocked because the
# next operator cannot accept output).  Anything else is busy, except a
# source's ``body``: the user's generator is not the program's work.
IDLE_PHASES = frozenset(("get_wait", "work_wait", "ready_wait", "block"))
BLOCKED_PHASES = frozenset(("put_wait", "submit_wait"))
BODY_PHASE = "body"
# never a slow_span however long: the user's generator, and a dispatcher
# with nothing in flight waiting for work
QUIET_PHASES = frozenset((BODY_PHASE, "work_wait"))
# waits that are recorded as ``slow_span`` and read as stalls
STALL_PHASES = frozenset(("get_wait", "put_wait", "submit_wait",
                          "ready_wait"))
SLOW_WAIT_NS = 30_000_000
SLOW_ANY_NS = 100_000_000
SLOW_EVERY_NS = 1_000_000_000    # at most one slow_span a second a cell
RECENT_NS = 1_000_000            # closed spans this long are remembered
#                                  as context for another thread's slow_span


# -- the profiler's flag, without importing JAX ----------------------------

_annotation = None


def _never():
    return False


def _probe():
    """Until ``jax.profiler`` is in the process no session can run; once
    it is, the flag test is the profiler's own.  Nothing is imported
    here: another thread may be in the middle of importing JAX."""
    global _annotation, _session_on
    ann = getattr(sys.modules.get("jax.profiler"), "TraceAnnotation", None)
    if ann is None:
        return False
    _annotation = ann
    _session_on = getattr(ann, "is_enabled", _never)
    return _session_on()


_session_on = _probe


def session_active() -> bool:
    """Whether a ``jax.profiler`` session is recording right now."""
    return _session_on()


# -- cells, tracks ---------------------------------------------------------

class Cell:
    """One (thread, span name): written by that thread alone."""

    __slots__ = ("name", "operator", "phase", "wait", "count", "total_ns",
                 "self_ns", "longest_ns", "timeline", "lo", "hi", "ns",
                 "peaks", "last_slow_ns", "suppressed")

    def __init__(self, name: str):
        self.name = name
        head, _, self.phase = name.rpartition("/")
        self.operator = head[3:] if head.startswith("wf/") else head
        self.wait = self.phase in STALL_PHASES
        self.count = 0
        self.total_ns = 0        # inclusive
        self.self_ns = 0         # minus what child spans cover
        self.longest_ns = 0
        self.timeline: Dict[int, int] = {}   # closed buckets -> self ns
        self.lo = self.hi = self.ns = 0      # the current bucket, its self ns
        self.peaks: Dict[int, int] = {}      # waits: end bucket -> longest ns
        self.last_slow_ns = 0
        self.suppressed = 0


def _trim(d: dict) -> None:
    # buckets are inserted in time order, so the first key is the oldest
    while len(d) > TIMELINE_BUCKETS:
        del d[next(iter(d))]


class Track:
    """The spans of one thread, accounted as they open and close: a
    stack of open frames ``[cell, t_begin, t_segment, longest own
    segment, annotation]`` and the thread's cells.  Written by the owner
    thread alone; what another thread reads of it is current."""

    __slots__ = ("thread", "graph", "cells", "stack", "recent", "born_ns",
                 "last_ns")

    def __init__(self, thread_name: str):
        self.thread = thread_name
        self.graph: Optional["SpanGraph"] = None
        self.cells: Dict[str, Cell] = {}
        self.stack: List[list] = []
        self.recent: deque = deque(maxlen=8)   # (name, t0, t1), >= RECENT_NS
        self.born_ns: Optional[int] = None
        self.last_ns = 0

    def begin(self, name: str, meta: Optional[dict] = None) -> None:
        cell = self.cells.get(name)
        if cell is None:
            cell = self.cells[name] = Cell(name)
        ann = None
        if _session_on():
            ann = _annotation(name, **meta) if meta else _annotation(name)
            ann.__enter__()
        now = _now()
        stack = self.stack
        if stack:
            # the parent's own segment ends here (adding [seg, now) to its
            # self time: into its current bucket, else through _spread)
            top = stack[-1]
            parent, seg = top[0], top[2]
            d = now - seg
            if d > 0:
                parent.self_ns += d
                if parent.lo <= seg and now < parent.hi:
                    parent.ns += d
                else:
                    _spread(parent, seg, now)
                if d > top[3]:
                    top[3] = d
        elif self.born_ns is None:
            self.born_ns = now
        # [cell, t_begin, t_segment, its longest segment without a child]
        stack.append([cell, now, now, 0, ann])

    def end(self) -> None:
        """Close the innermost open span."""
        now = _now()
        stack = self.stack
        cell, t0, seg, own, ann = stack.pop()
        if ann is not None:
            ann.__exit__(None, None, None)
        d = now - seg
        if d > 0:
            cell.self_ns += d
            if cell.lo <= seg and now < cell.hi:
                cell.ns += d
            else:
                _spread(cell, seg, now)
            if d > own:
                own = d
        dur = now - t0
        cell.count += 1
        cell.total_ns += dur
        if dur > cell.longest_ns:
            cell.longest_ns = dur
        if cell.wait:
            b = now // BUCKET_NS
            peaks = cell.peaks
            if dur > peaks.get(b, 0):
                peaks[b] = dur
                if len(peaks) > TIMELINE_BUCKETS:
                    _trim(peaks)
        if stack:
            stack[-1][2] = now
        self.last_ns = now
        if dur >= RECENT_NS:
            self._long(cell, t0, now, own)

    def account(self, name: str, ns: int) -> None:
        """A span of ``ns`` nanoseconds that something else timed (native
        code, on its own steady clock) inside the innermost open span,
        since that span's last child: entered as its child, so the
        parent's self time loses what the child gains.  No clock is read
        here.  In a profiler session it shows as an instant of the same
        name that carries ``ns``."""
        cell = self.cells.get(name)
        if cell is None:
            cell = self.cells[name] = Cell(name)
        if self.stack:
            top = self.stack[-1]
            a = top[2]
            top[2] = a + ns      # the parent's segment goes on after it
        else:
            a = self.last_ns
        cell.count += 1
        cell.total_ns += ns
        cell.self_ns += ns
        if ns > cell.longest_ns:
            cell.longest_ns = ns
        if cell.lo <= a and a + ns < cell.hi:
            cell.ns += ns
        else:
            _spread(cell, a, a + ns)
        if _session_on():
            with _annotation(name, ns=ns):
                pass

    def close_all(self) -> None:
        """Thread end or unwinding: close whatever is still open."""
        while self.stack:
            self.end()

    def _long(self, cell: Cell, t0: int, now: int, own: int) -> None:
        """A span of a millisecond or more has closed; ``own`` is the
        longest stretch it ran without a child span, so that one stall
        is recorded once, under the innermost span that holds it, and a
        span that is long by many short stretches (a source's loop) is
        not slow."""
        self.recent.append((cell.name, t0, now))
        if own < SLOW_WAIT_NS or not (
                cell.wait or (own >= SLOW_ANY_NS
                              and cell.phase not in QUIET_PHASES)):
            return
        g = self.graph
        flight = g.flight if g is not None else None
        if flight is None:
            return
        if now - cell.last_slow_ns < SLOW_EVERY_NS:
            cell.suppressed += 1
            return
        cell.last_slow_ns = now
        flight.record("slow_span", name=cell.name, thread=self.thread,
                      start_s=t0 / 1e9, ms=round((now - t0) / 1e6, 3),
                      own_ms=round(own / 1e6, 3),
                      suppressed=cell.suppressed,
                      others=g.open_at(t0, but=self))
        cell.suppressed = 0

    # -- reading (any thread; gauge-grade against a live writer) --------
    def open_spans(self) -> list:
        """``(name, t_begin, t_segment)`` of the spans open now,
        outermost first."""
        return [(f[0].name, f[1], f[2]) for f in list(self.stack)]

    def life_ns(self, now: Optional[int] = None):
        """(first begin, last end or ``now`` while a span is open)."""
        if self.stack:
            return self.born_ns, now or _now()
        return self.born_ns, self.last_ns


def _spread(cell: Cell, a: int, b: int) -> None:
    """The segment [a, b) of the cell's self time does not lie in the
    cell's current bucket: close that bucket into the timeline, give
    every bucket the segment crosses its part, and open the last one."""
    tl = cell.timeline
    if cell.ns:
        k = cell.lo // BUCKET_NS
        tl[k] = tl.get(k, 0) + cell.ns
    ba, bb = a // BUCKET_NS, b // BUCKET_NS
    if ba != bb:
        tl[ba] = tl.get(ba, 0) + (ba + 1) * BUCKET_NS - a
        for k in range(ba + 1, bb):
            tl[k] = tl.get(k, 0) + BUCKET_NS
        a = bb * BUCKET_NS
    cell.lo, cell.hi = bb * BUCKET_NS, (bb + 1) * BUCKET_NS
    cell.ns = tl.pop(bb, 0) + b - a
    if len(tl) > TIMELINE_BUCKETS:
        _trim(tl)


def timeline(cell: Cell) -> Dict[int, int]:
    """{bucket: self ns}: the closed buckets and the current one."""
    tl = cell.timeline.copy()
    if cell.ns:
        k = cell.lo // BUCKET_NS
        tl[k] = tl.get(k, 0) + cell.ns
    return tl


_tls = threading.local()


def track() -> Track:
    """The calling thread's track."""
    try:
        return _tls.track
    except AttributeError:
        t = _tls.track = Track(threading.current_thread().name)
        return t


def bind(graph: Optional["SpanGraph"]) -> Track:
    """The calling thread's track, filed under ``graph``.  A thread
    that served another graph before starts a fresh track."""
    t = track()
    if graph is None or t.graph is graph:
        return t
    if t.graph is not None or t.cells:
        t = _tls.track = Track(threading.current_thread().name)
    t.thread = threading.current_thread().name
    t.graph = graph
    with _lock:
        graph.tracks.append(t)
    return t


# -- launches --------------------------------------------------------------

# What a launch counts, declared here once: :class:`Launch`'s attributes,
# its row in the stats JSON (the name capitalized), the sums of
# :meth:`LaunchRing.summary` and the attributes of the compute engine's
# handle (ops/window_compute.DeviceBatchHandle) all read this.  Each is
# (name, when the operator takes it, help): "submit" from the batch it
# stages, "dispatch" off the handle once the engine has returned it,
# "host" off the handle once the result is on the host.  A handle that
# has no such attribute counts 0.  The summary sums what the handles
# brought back.  A new count is one entry here and the handle's value.
LAUNCH_COUNTS = (
    ("buffers_in", "dispatch", "host arrays the launch handed the device "
     "(1 where the engine packs the launch, docs/RUNTIME.md 5c; 0 on the "
     "host lane)"),
    ("rows", "submit", "the windows the launch computed"),
    ("fn_elems", "dispatch", "the elements a user's window function read "
     "(the sum of the windows' extents' lengths)"),
    ("tile_elems", "dispatch", "the elements of the tile a user's window "
     "function ran over (rows by width, the padding with them)"),
    ("fn_rows_dma", "dispatch", "the rows whose tile the Pallas kernel "
     "gathered (docs/RUNTIME.md 5d: all of them on the TPU, none on the "
     "slice path)"),
    ("fn_rows_sorted", "host", "the rows whose every sort the Pallas "
     "sorting network made, counted on the device (all of a sorting "
     "function's rows on the TPU at its widths but a block that holds "
     "-0.0, a NaN or a subnormal; none elsewhere)"),
)
# what the compute engine's handle carries, and when each is taken
HANDLE_COUNTS = tuple(n for n, taken, _ in LAUNCH_COUNTS if taken != "submit")
_TAKEN = {when: tuple(n for n, taken, _ in LAUNCH_COUNTS if taken == when)
          for when in ("dispatch", "host")}


class Launch:
    """One device launch: host stamps on ``time.perf_counter`` (seconds),
    and the :data:`LAUNCH_COUNTS` (0 until taken).
    ``t_submitted`` is taken on the ingest thread before the hand-off,
    the others by whoever dispatches (the dispatcher thread, or the
    ingest thread on the inline lane).  ``queue_wait``, ``dispatch``,
    ``ready_wait``, ``block`` and ``emit`` are their differences.
    ``collected`` (one of :data:`COLLECTED`) says how the dispatcher
    came to take the result.  ``t_packed`` and
    ``t_called`` are the compute engine's, taken inside ``dispatch`` and
    brought back by the handle: the host has finished preparing what it
    hands the runtime; the jitted call has returned.  They cut
    ``dispatch`` into ``pack``, ``call`` and ``handoff`` (what is left:
    the handle starting the copy back, the way out of the engine); a
    lane that does not take them (mesh, host) leaves them None."""

    __slots__ = ("seq", "chunk_seq", "bytes_in", "bytes_out",
                 *(n for n, _, _ in LAUNCH_COUNTS), "t_submitted",
                 "t_picked", "t_packed", "t_called", "t_dispatched",
                 "t_ready_seen", "t_on_host", "t_emitted", "collected")

    def __init__(self, seq: int, chunk_seq: int, bytes_in: int,
                 t_submitted: float):
        self.seq = seq
        self.chunk_seq = chunk_seq
        self.bytes_in = bytes_in
        self.bytes_out = 0
        for name, _, _ in LAUNCH_COUNTS:
            setattr(self, name, 0)
        self.t_submitted = t_submitted
        self.t_picked = self.t_dispatched = self.t_ready_seen = None
        self.t_packed = self.t_called = None
        self.t_on_host = self.t_emitted = None
        self.collected = None

    def take(self, handle, when: str) -> None:
        """The counts taken at ``when`` ("dispatch" or "host") off the
        compute engine's handle."""
        for name in _TAKEN[when]:
            setattr(self, name, getattr(handle, name, 0))

    def as_row(self) -> dict:
        """An emitted launch for the stats JSON: which launch, the chunk
        whose arrival fired it, what it moved, and its stages."""
        return {"Seq": self.seq, "Chunk_seq": self.chunk_seq,
                "Bytes_in": int(self.bytes_in),
                "Bytes_out": int(self.bytes_out),
                **{n.capitalize(): int(getattr(self, n))
                   for n, _, _ in LAUNCH_COUNTS},
                "Picked_s": round(self.t_picked, 6),
                "Collected": self.collected,
                **{k: round(v, 4) for k, v in self.stages_ms().items()
                   if v is not None}}

    def stages_ms(self) -> Optional[dict]:
        """The five stages in milliseconds and the three parts of
        ``dispatch`` (None where the lane took no stamps for them); None
        until emitted."""
        if self.t_emitted is None:
            return None
        out = {
            "queue_wait": 1e3 * (self.t_picked - self.t_submitted),
            "dispatch": 1e3 * (self.t_dispatched - self.t_picked),
            "ready_wait": 1e3 * (self.t_ready_seen - self.t_dispatched),
            "block": 1e3 * (self.t_on_host - self.t_ready_seen),
            "emit": 1e3 * (self.t_emitted - self.t_on_host),
            "pack": None, "call": None, "handoff": None,
        }
        if self.t_packed is not None:
            out["pack"] = 1e3 * (self.t_packed - self.t_picked)
            out["call"] = 1e3 * (self.t_called - self.t_packed)
            out["handoff"] = 1e3 * (self.t_dispatched - self.t_called)
        return out


# the stages tile a launch's round trip; the parts tile its ``dispatch``
STAGES = ("queue_wait", "dispatch", "ready_wait", "block", "emit")
DISPATCH_PARTS = ("pack", "call", "handoff")

# how a launch's result was collected: found ready straight after a
# dispatch; waited for, with nothing staged to dispatch meanwhile;
# forced, because ``inflight_depth`` launches were in flight (the inline
# lane and a synchronous launch are always at depth); flushed at EOS
COLLECTED = ("ready", "waited", "forced", "flushed")
READY, WAITED, FORCED, FLUSHED = COLLECTED


class LaunchRing:
    """The last :data:`LAUNCH_RING` launches of one window operator,
    keyed by its launch sequence number."""

    __slots__ = ("operator", "records", "seq")

    def __init__(self, operator: str):
        self.operator = operator
        self.records: deque = deque(maxlen=LAUNCH_RING)
        self.seq = 0

    def open(self, chunk_seq: int, bytes_in: int, t_submitted: float
             ) -> Launch:
        self.seq += 1
        rec = Launch(self.seq, chunk_seq, bytes_in, t_submitted)
        self.records.append(rec)
        return rec

    def finished(self, t0: Optional[float] = None,
                 t1: Optional[float] = None) -> List[Launch]:
        """Emitted launches whose result reached the host in (t0, t1]."""
        return [r for r in list(self.records)
                if r.t_emitted is not None
                and (t0 is None or r.t_on_host > t0)
                and (t1 is None or r.t_on_host <= t1)]

    def summary(self, t0: Optional[float] = None,
                t1: Optional[float] = None) -> dict:
        """Mean and longest of each stage and each part of ``dispatch``
        over :meth:`finished` (a part over the launches that took its
        stamps), the :data:`HANDLE_COUNTS` of those launches summed, how
        many of them were collected in each way, and the launch whose
        round trip (picked up to emitted) was the longest, whole: the one
        to look for in a trace or a log."""
        done = self.finished(t0, t1)
        rows = [r.stages_ms() for r in done]
        out = {"Operator": self.operator, "Launches": len(rows),
               **{n.capitalize(): sum(getattr(r, n) for r in done)
                  for n in HANDLE_COUNTS}}
        for s in STAGES + DISPATCH_PARTS:
            vals = [r[s] for r in rows if r[s] is not None]
            out[s] = {"mean_ms": round(sum(vals) / len(vals), 4),
                      "max_ms": round(max(vals), 4)} if vals else None
        out["Collected"] = {how: sum(r.collected == how for r in done)
                            for how in COLLECTED}
        out["Slowest"] = max(
            done, key=lambda r: r.t_emitted - r.t_picked).as_row() \
            if done else None
        return out


# -- counters --------------------------------------------------------------

class Stat(NamedTuple):
    """One slot of the native window engine's stats vector behind its
    three churn clocks (``NativeWindowEngine.STATS``): its name, its help
    text, its kind on ``/metrics`` ("counter" or "gauge"; "" where it is
    no count and stays out of :class:`Counters`), whether the series
    keeps it (:data:`SERIES_COUNTERS`), and its group ("clock":
    :data:`ENGINE_CLOCKS`; "argmax": :data:`ENGINE_ARGMAX`)."""

    name: str
    help: str
    kind: str = "counter"
    series: bool = True
    group: str = ""


# What the native window engine counts, declared here once: the stats
# JSON's ``Counters`` row, the series, ``NativeWindowEngine.STATS`` and
# ``/metrics`` (by name, kind and help text) all read this.  THE ORDER
# IS THE ENGINE'S: ``wfn_engine_stats`` fills ``out[3..)`` in this order
# (native/window_engine.cpp), but for ``copy_out_ns``, which the wrapper
# keeps in its slot; tests/test_native_runtime.py checks each slot
# against what the engine counted on a known stream.  Each ``_ns`` clock
# is nanoseconds on the engine's steady clock, a call at a time (but
# ``copy_out_ns``, the wrapper's ``perf_counter_ns``); the
# clocks of ``fold`` and ``flush`` are counters and never child spans,
# so those two spans keep the self time they had.  A new counter is one
# entry here and its fill line in ``wfn_engine_stats``.
ENGINE_STATS = (
    Stat("keys_opened", "key states the window engine created",
         series=False),
    Stat("keys_evicted", "key states the window engine evicted",
         series=False),
    Stat("keys_live", "key states the window engine holds", "gauge",
         series=False),
    Stat("keys_live_peak", "most key states the window engine held at "
         "once", "gauge", series=False),
    Stat("windows_fired", "windows the engine fired", series=False),
    Stat("folded_by_key", "tuples the engine folded with their key's "
         "others of the call in one combine"),
    Stat("folded_singly", "tuples the engine folded one by one"),
    Stat("late_accepted", "tuples the engine accepted whose stamp lay "
         "behind its stream time when they came"),
    Stat("anchors_moved", "times a live key's anchor moved back for a "
         "tuple earlier than the key's first to arrive"),
    Stat("inputs_ignored", "tuples the engine dropped behind a window "
         "that had fired"),
    Stat("stream_time", "the largest stamp the engine has seen (-1 "
         "before the first)", "", series=False),
    Stat("key_touches", "keys the engine's calls visited (a key once a "
         "call)"),
    Stat("walked_ahead", "of those, the visits of a call that ran ahead "
         "of itself (a table beyond the caches)"),
    Stat("rings_spilled", "pane rings that left their key state for a "
         "block of their own", series=False),
    Stat("panes_staged", "pane partials the engine's flush copied into "
         "launch buffers"),
    Stat("windows_staged", "windows the engine's flush staged for a "
         "launch"),
    Stat("walked_in_lanes", "tuples of the engine's calls whose walk was "
         "split over the host lanes"),
    Stat("ingest_ns", "nanoseconds inside the engine's batch calls, less "
         "their open and trigger", group="clock"),
    Stat("tuple_walk_ns", "of ingest_ns, the walks that cost by the tuple "
         "(gather, note_late, fold_singly)", group="clock"),
    Stat("key_walk_ns", "of ingest_ns, the walks that cost by the key "
         "(prepare of the keys a call did not open, settle)",
         group="clock"),
    Stat("stage_ns", "nanoseconds inside the engine's flush, less its "
         "evict (staging the spans, retiring the rings, the rows)",
         group="clock"),
    Stat("panes_shifted", "ring elements the engine's retire() moved down "
         "after a staging", group="clock"),
    Stat("copy_out_ns", "nanoseconds copying a flush's columns out of the "
         "engine (perf_counter_ns round the copies, "
         "NativeWindowEngine.flush)", group="clock"),
    Stat("argmax_ns", "of ingest_ns, an argmax engine's own walks (each "
         "key's largest value of a call and the ids at it; the one-by-one "
         "fold), out of what would be tuple_walk_ns; where the one-key "
         "walk took the call, the one-by-one fold alone", group="argmax"),
    Stat("max_raised", "times an argmax engine set or raised a pane's "
         "held maximum (and with it its ids)", group="argmax"),
    Stat("tie_ids", "ids beyond the first at the maxima of the windows an "
         "argmax engine staged", group="argmax"),
    Stat("walked_one_key", "tuples of the engine's calls that carried one "
         "key, walked once with no probe"),
    Stat("tuples_staged", "tuples a tuples engine's flush copied into "
         "launch buffers for a user's window function"),
)
# the counts, in the engine's order: a ``Counters`` row's keys
ENGINE_COUNTERS = tuple(s.name for s in ENGINE_STATS if s.kind)
# those of them kept as a series (the last values noted in each 100 ms
# bucket), so that what moved between two instants can be read
SERIES_COUNTERS = tuple(s.name for s in ENGINE_STATS if s.series)
# the inside of ``fold`` and ``flush``, and what an ``argmax`` engine
# clocks and counts of its own (0 on every other kind), by help text
ENGINE_CLOCKS = {s.name: s.help for s in ENGINE_STATS if s.group == "clock"}
ENGINE_ARGMAX = {s.name: s.help for s in ENGINE_STATS
                 if s.group == "argmax"}

# what a window operator counts of the selected batches it ingests, by
# help text
SELECTION_COUNTERS = {
    "cols_selected": "columns the selected batches the operator ingested "
                     "carried",
    "cols_gathered": "columns of selected batches that were gathered on "
                     "read",
    "rows_by_selection": "rows the window store read through a batch's "
                         "selection",
}


class Counters:
    """The latest value of each counter of one operator; of ``keys_live``
    the largest value noted in each 100 ms bucket and of the
    :data:`SERIES_COUNTERS` the last, so that the peak, the tuples folded
    between two instants and the disorder met between them can be read
    afterwards.  Written by the operator's ingest thread alone."""

    __slots__ = ("operator", "values", "live", "series",
                 *SELECTION_COUNTERS)

    def __init__(self, operator: str):
        self.operator = operator
        self.values: Dict[str, int] = dict.fromkeys(ENGINE_COUNTERS, 0)
        self.live: Dict[int, int] = {}      # bucket -> largest keys_live
        self.series: Dict[int, tuple] = {}  # bucket -> SERIES_COUNTERS
        # what the selected batches (core/tuples.py) the operator
        # ingested carried (:data:`SELECTION_COUNTERS`)
        for name in SELECTION_COUNTERS:
            setattr(self, name, 0)

    def selected(self, carried: int, gathered: int, rows: int) -> None:
        """One selected batch ingested (the window operator, after its
        store's ingest): integer adds, no clock."""
        self.cols_selected += carried
        self.cols_gathered += gathered
        self.rows_by_selection += rows

    def selected_totals(self) -> Dict[str, int]:
        return {n: getattr(self, n) for n in SELECTION_COUNTERS}

    def note(self, at_ns: int, values) -> None:
        """The counters' values, in :data:`ENGINE_COUNTERS`' order, as
        read at ``at_ns``."""
        self.values = v = dict(zip(ENGINE_COUNTERS, values))
        b, live = at_ns // BUCKET_NS, v["keys_live"]
        if live > self.live.get(b, -1):
            self.live[b] = live
            if len(self.live) > TIMELINE_BUCKETS:
                _trim(self.live)
        # behind the values the instant of the note, so that a reader can
        # tell how wide a cut of the series really was (:meth:`cut`)
        self.series[b] = (*(v.get(n, 0) for n in SERIES_COUNTERS), at_ns)
        if len(self.series) > TIMELINE_BUCKETS:
            _trim(self.series)

    def live_peak(self, t0_s: float, t1_s: float) -> Optional[int]:
        """The largest ``keys_live`` noted in the buckets of [t0_s,
        t1_s]; where nothing was noted there (no key came or went), the
        last value noted before."""
        b0, b1 = int(t0_s * 1e9) // BUCKET_NS, int(t1_s * 1e9) // BUCKET_NS
        live = self.live.copy()
        inside = [n for b, n in live.items() if b0 <= b <= b1]
        if inside:
            return max(inside)
        before = [b for b in live if b < b0]
        return live[max(before)] if before else None

    def _notes(self, t0_s: float, t1_s: float) -> tuple:
        """The two notes a cut of the series at [t0_s, t1_s] reads
        between: the last in the buckets before ``t0_s``'s and the last
        in those up to ``t1_s``'s (None where there is none)."""
        b0, b1 = int(t0_s * 1e9) // BUCKET_NS, int(t1_s * 1e9) // BUCKET_NS
        series = self.series.copy()
        at = (max((b for b in series if b <= edge), default=None)
              for edge in (b0 - 1, b1))
        return tuple(None if b is None else series[b] for b in at)

    def between(self, names, t0_s: float, t1_s: float) -> tuple:
        """By how much each of ``names`` (of the :data:`SERIES_COUNTERS`)
        moved between the two notes of :meth:`_notes` (good to a bucket
        and a note at each end, and wider than [t0_s, t1_s] by as much:
        :meth:`cut` says by how much), in ``names``' order."""
        none = (0,) * len(SERIES_COUNTERS)
        lo, hi = (n or none for n in self._notes(t0_s, t1_s))
        return tuple(hi[i] - lo[i] for i in map(SERIES_COUNTERS.index, names))

    def cut(self, t0_s: float, t1_s: float) -> Optional[tuple]:
        """The instants (seconds on the spans' clock) of the two notes
        :meth:`between` reads between for [t0_s, t1_s]: what moved
        between them is to be set against a whole, or a count, of the
        same two instants and not of the window's.  None where there is
        no note at one end, or nothing was noted between them."""
        lo, hi = self._notes(t0_s, t1_s)
        if lo is None or hi is None or hi[-1] <= lo[-1]:
            return None
        return lo[-1] / 1e9, hi[-1] / 1e9

    def moved_between(self, t0_s: float, t1_s: float) -> Dict[str, int]:
        """Every one of the :data:`SERIES_COUNTERS` by name
        (:meth:`between`)."""
        return dict(zip(SERIES_COUNTERS,
                        self.between(SERIES_COUNTERS, t0_s, t1_s)))

    def folded_between(self, t0_s: float, t1_s: float) -> tuple:
        """(by key, singly): the tuples folded between two instants."""
        return self.between(("folded_by_key", "folded_singly"), t0_s, t1_s)

    def staged_between(self, t0_s: float, t1_s: float) -> tuple:
        """(pane partials, windows) the store's flush staged between two
        instants."""
        return self.between(("panes_staged", "windows_staged"), t0_s, t1_s)

    def touched_between(self, t0_s: float, t1_s: float) -> tuple:
        """(key touches, those in a call that ran ahead) between two
        instants."""
        return self.between(("key_touches", "walked_ahead"), t0_s, t1_s)


# -- graphs and the registry -----------------------------------------------

class SpanGraph:
    """The tracks and launch rings of one graph."""

    def __init__(self, name: str, flight=None):
        self.name = name
        self.flight = flight
        self.tracks: List[Track] = []
        self.rings: Dict[str, LaunchRing] = {}
        self.counters: Dict[str, Counters] = {}
        self.ended = False

    def ring(self, operator: str) -> LaunchRing:
        with _lock:
            r = self.rings.get(operator)
            if r is None:
                r = self.rings[operator] = LaunchRing(operator)
            return r

    def counters_of(self, operator: str) -> Counters:
        with _lock:
            c = self.counters.get(operator)
            if c is None:
                c = self.counters[operator] = Counters(operator)
            return c

    def open_at(self, t_ns: int, but: Optional[Track] = None) -> list:
        """What the graph's other threads had open at ``t_ns``: frames
        still open that began before it, and remembered spans (1 ms or
        longer) that covered it."""
        out = []
        for tr in list(self.tracks):
            if tr is but:
                continue
            for name, t0, _seg in tr.open_spans():
                if t0 <= t_ns:
                    out.append({"thread": tr.thread, "name": name,
                                "open_ms": round((t_ns - t0) / 1e6, 3)})
            for name, t0, t1 in list(tr.recent):
                if t0 <= t_ns <= t1:
                    out.append({"thread": tr.thread, "name": name,
                                "open_ms": round((t_ns - t0) / 1e6, 3),
                                "ms": round((t1 - t0) / 1e6, 3)})
        return out

    def cells(self):
        """(track, cell) of every cell of the graph."""
        return [(tr, c) for tr in list(self.tracks)
                for c in list(tr.cells.values())]


_lock = threading.Lock()
_graphs: Dict[str, SpanGraph] = {}


def start_graph(name: str, flight=None) -> SpanGraph:
    """A fresh entry for ``name``; one of the same name is dropped."""
    g = SpanGraph(name, flight)
    with _lock:
        _graphs.pop(name, None)
        _graphs[name] = g
        ended = [n for n, x in _graphs.items() if x.ended]
        for n in ended[:max(0, len(ended) - MAX_ENDED_GRAPHS)]:
            del _graphs[n]
    return g


def end_graph(graph: Optional[SpanGraph]) -> None:
    if graph is not None:
        graph.ended = True


def graph(name: str) -> Optional[SpanGraph]:
    return _graphs.get(name)


# -- cutting the timelines -------------------------------------------------

def _bucket_sum(tl: dict, t0: int, t1: int) -> float:
    """Nanoseconds of a bucket timeline between two instants: whole
    buckets inside, and of the two edge buckets the covered part."""
    total = 0.0
    for b, ns in tl.items():
        lo, hi = b * BUCKET_NS, (b + 1) * BUCKET_NS
        if hi <= t0 or lo >= t1:
            continue
        part = (min(hi, t1) - max(lo, t0)) / BUCKET_NS
        total += ns * part
    return total


def self_seconds(tr: Track, cell: Cell, t0_s: Optional[float] = None,
                 t1_s: Optional[float] = None) -> float:
    """The cell's self time in seconds, since start or between two
    ``perf_counter`` instants (good to one bucket at each end).  A span
    of the cell that is open now counts up to now."""
    now = _now()
    open_ns = 0
    frames = tr.open_spans()
    if frames and frames[-1][0] == cell.name:
        seg = frames[-1][2]
        lo = seg if t0_s is None else max(seg, int(t0_s * 1e9))
        hi = now if t1_s is None else min(now, int(t1_s * 1e9))
        open_ns = max(0, hi - lo)
    if t0_s is None and t1_s is None:
        return (cell.self_ns + open_ns) / 1e9
    t0 = 0 if t0_s is None else int(t0_s * 1e9)
    t1 = now if t1_s is None else int(t1_s * 1e9)
    return (_bucket_sum(timeline(cell), t0, t1) + open_ns) / 1e9


def longest_wait_ms(graph: SpanGraph, t0_s: float, t1_s: float) -> float:
    """The longest single wait span (:data:`STALL_PHASES`) of any thread
    of the graph that ended in the buckets of [t0_s, t1_s]."""
    b0, b1 = int(t0_s * 1e9) // BUCKET_NS, int(t1_s * 1e9) // BUCKET_NS
    longest = 0
    for _tr, c in graph.cells():
        if c.wait:
            for b, ns in c.peaks.copy().items():
                if b0 <= b <= b1 and ns > longest:
                    longest = ns
    return longest / 1e6


def triad(graph: SpanGraph, t0_s: Optional[float] = None,
          t1_s: Optional[float] = None) -> List[dict]:
    """Per (operator, thread): seconds busy, idle, blocked and in the
    source's body, and the thread's life in the same cut.  Busy is self
    time of every phase that is neither a wait nor the body."""
    now = _now()
    rows: Dict[tuple, dict] = {}
    for tr in list(graph.tracks):
        born, last = tr.life_ns(now)
        if born is None:
            continue
        lo = born if t0_s is None else max(born, int(t0_s * 1e9))
        hi = last if t1_s is None else min(last, int(t1_s * 1e9))
        life = max(0, hi - lo) / 1e9
        for c in list(tr.cells.values()):
            row = rows.get((c.operator, tr.thread, id(tr)))
            if row is None:
                row = rows[(c.operator, tr.thread, id(tr))] = {
                    "operator": c.operator, "thread": tr.thread,
                    "track": id(tr), "busy_s": 0.0, "idle_s": 0.0,
                    "blocked_s": 0.0, "body_s": 0.0, "life_s": life,
                    "phases": {}}
            s = self_seconds(tr, c, t0_s, t1_s)
            row["phases"][c.phase] = s
            if c.phase in IDLE_PHASES:
                row["idle_s"] += s
            elif c.phase in BLOCKED_PHASES:
                row["blocked_s"] += s
            elif c.phase == BODY_PHASE:
                row["body_s"] += s
            else:
                row["busy_s"] += s
    return list(rows.values())


def report(g: Optional[SpanGraph]) -> Optional[dict]:
    """The ``Spans`` block of the stats JSON: per operator replica and
    thread seconds and shares busy / idle / blocked since start and over
    the last ten seconds, with every phase's closed spans counted (how
    many, their self and whole seconds, the longest single one) and, for
    a window operator on the native lane, its engine's counters; per
    window operator the mean and longest of each launch stage and its
    slowest launch."""
    if g is None:
        return None
    now_s = _now() / 1e9

    def shares(row):
        life = row["life_s"]
        return {k[:-2] + "_share": round(row[k] / life, 4) if life else 0.0
                for k in ("busy_s", "idle_s", "blocked_s")}

    recent = {(r["operator"], r["track"]): r
              for r in triad(g, now_s - 10.0, now_s)}
    counted: Dict[tuple, dict] = {}
    for tr, c in g.cells():
        counted.setdefault((c.operator, id(tr)), {})[c.phase] = {
            "Count": c.count, "Self_s": round(c.self_ns / 1e9, 6),
            "Total_s": round(c.total_ns / 1e9, 6),
            "Longest_ms": round(c.longest_ns / 1e6, 3)}
    ops = []
    for row in triad(g):
        out = {"Operator": row["operator"], "Thread": row["thread"],
               "Busy_s": round(row["busy_s"], 6),
               "Idle_s": round(row["idle_s"], 6),
               "Blocked_s": round(row["blocked_s"], 6),
               "Life_s": round(row["life_s"], 6)}
        if row["body_s"]:
            out["Body_s"] = round(row["body_s"], 6)
        out["Phases"] = counted.get((row["operator"], row["track"]), {})
        kept = g.counters.get(row["operator"])
        if kept is not None:
            out["Counters"] = dict(kept.values)
            if kept.cols_selected:
                out["Counters"].update(kept.selected_totals())
        out.update({k.capitalize(): v for k, v in shares(row).items()})
        last = recent.get((row["operator"], row["track"]))
        if last is not None:
            out["Last_10s"] = {k.capitalize(): v
                               for k, v in shares(last).items()}
        ops.append(out)
    return {"Operators": ops,
            "Launches": [r.summary() for r in list(g.rings.values())]}
