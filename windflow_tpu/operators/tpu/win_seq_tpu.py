"""Win_Seq_TPU: the device-batched keyed window engine.

Re-design of reference ``wf/win_seq_gpu.hpp`` (769 LoC): where the
reference archives tuples per key, batches ``batch_len`` fired windows,
copies them to pinned buffers and launches a CUDA kernel per batch on a
private stream (svc :391-645), this engine:

* keeps the keyed-window state in one store: the C++ engine
  (`runtime/native.NativeWindowEngine`) where it can serve, else its
  Python twin (`window_store.PyWindowStore`), both behind the same
  calls (``ingest``, ``flush``, ``eos``, ``ready``, ...);
* lets fired windows gather in the store until ``batch_len`` (or the
  buffer or age bound);
* flushes one flat buffer + [start, end) extents from the store and
  launches a jitted XLA program via `WindowComputeEngine`
  (ops/window_compute);
* overlaps host batching with device execution through async dispatch,
  flushing the *previous* batch's results lazily -- the double-buffered
  ``waitAndFlush`` protocol (win_seq_gpu.hpp:267-297).

Window-id assignment (config/role arithmetic) is identical to the host
engine, so this operator drops into every composite farm exactly like
Win_Seq_GPU does in the reference (win_farm_gpu.hpp:82-86).
"""
from __future__ import annotations

import threading as _threading
import time as _time
from typing import Any, Callable, List, Optional

import numpy as np

from ...core.basic import (OrderingMode, Pattern, Role, RoutingMode,
                           WinOperatorConfig, WinType)
from ...core.tuples import BasicRecord, SynthChunk, TupleBatch
from ...ops.window_compute import WindowComputeEngine
from ...runtime.emitters import StandardEmitter
from ...runtime.node import EOSMarker, NodeLogic
from ...telemetry import spans
from ..base import Operator, StageSpec
# `_TPUKeyState`: a Python-lane snapshot pickled before PR 30 names it
# by this module
from .window_store import PyWindowStore, _TPUKeyState  # noqa: F401

DEFAULT_BATCH_LEN = 256
# host staging-buffer capacity (elements) before a forced flush
DEFAULT_MAX_BUFFER_ELEMS = 1 << 19
# device launches kept in flight before the oldest is flushed.  8 deep
# (was 4): the pipeline must hold enough programs that one launch round
# trip amortizes over several launches; the adaptive batch resize below
# keeps per-launch latency bounded regardless
DEFAULT_INFLIGHT_DEPTH = 8
# partial-batch launch trigger (latency bound), milliseconds
DEFAULT_MAX_BATCH_DELAY_MS = 10.0

PLACEMENTS = ("device", "host", "auto")


class AdaptiveBatcher:
    """x2 / /2 device-batch resize driven by observed launch latency
    against the measured transport RTT floor -- the adaptation loop of
    the reference's pinned-buffer management (win_seq_gpu.hpp:574-592),
    re-aimed at a transport where the launch floor, not buffer size,
    is the cost.

    * launch latency ~ the floor (<= ``grow_below`` x): the launch is
      transport-bound -- the batch is too small to amortize the round
      trip; after ``patience`` consecutive such launches the batch
      DOUBLES.
    * launch latency >> the floor (>= ``shrink_above`` x): compute or
      queueing dominates and per-window latency grows with the batch;
      after ``patience`` such launches the batch HALVES.
    * in between: the operating point is good; streaks reset.

    Deterministic on a given latency trace (unit-tested against
    scripted traces).  The engine reads ``batch_len`` between launches,
    so resizes take effect on the next batch assembly."""

    __slots__ = ("batch_len", "floor_ms", "lo", "hi", "grow_below",
                 "shrink_above", "patience", "_grow", "_shrink",
                 "resizes")

    def __init__(self, batch_len: int, floor_ms: float, lo: int = 64,
                 hi: int = 1 << 16, grow_below: float = 2.0,
                 shrink_above: float = 8.0, patience: int = 3):
        if floor_ms <= 0:
            raise ValueError("floor_ms must be > 0")
        # an explicitly configured batch_len outside the default band
        # widens the band rather than being silently clamped away
        self.batch_len = max(1, int(batch_len))
        self.floor_ms = floor_ms
        self.lo = min(lo, self.batch_len)
        self.hi = max(hi, self.batch_len)
        self.grow_below = grow_below
        self.shrink_above = shrink_above
        self.patience = patience
        self._grow = 0
        self._shrink = 0
        self.resizes: List = []  # (direction, new_len) decision log

    def observe(self, launch_ms: float) -> int:
        if launch_ms <= self.grow_below * self.floor_ms:
            self._grow += 1
            self._shrink = 0
            if self._grow >= self.patience and self.batch_len < self.hi:
                self.batch_len = min(self.hi, self.batch_len * 2)
                self.resizes.append(("x2", self.batch_len))
                self._grow = 0
        elif launch_ms >= self.shrink_above * self.floor_ms:
            self._shrink += 1
            self._grow = 0
            if self._shrink >= self.patience and self.batch_len > self.lo:
                self.batch_len = max(self.lo, self.batch_len // 2)
                self.resizes.append(("/2", self.batch_len))
                self._shrink = 0
        else:
            self._grow = 0
            self._shrink = 0
        return self.batch_len


class _AsyncDispatcher:
    """Dedicated launch thread: the ingest thread stages numpy buffers
    and hands them off; this thread pays the host->device transfer
    latency, keeps up to ``inflight_depth`` programs in flight, sleeps
    on the oldest one's result whenever nothing is staged, and emits
    each as the device finishes it.  The reference overlaps CUDA
    streams with host batching on ONE thread
    (win_seq_gpu.hpp:267-297); here staging a launch's buffers onto the
    device blocks the caller, so dispatch comes off the ingest thread
    entirely."""

    __slots__ = ("logic", "work", "thread", "error", "aborting")

    def __init__(self, logic: "WinSeqTPULogic"):
        import queue as _q
        import threading as _t
        self.logic = logic
        self.work = _q.Queue(maxsize=max(1, logic.inflight_depth))
        self.error: Optional[BaseException] = None
        self.aborting = False
        self.thread = _t.Thread(target=self._run, daemon=True,
                                name=f"winseq-tpu-dispatch:{logic._span_op}")
        self.thread.start()

    def submit(self, item) -> None:
        import queue as _q
        # bounded put re-checking for a dead/failed dispatcher: a plain
        # blocking put could hang forever if the thread errors out while
        # the queue is full (nothing would ever drain it)
        tr = spans.track()
        tr.begin(self.logic._n_submit_wait)
        try:
            while True:
                if self.error is not None:
                    raise RuntimeError("window dispatch thread failed") \
                        from self.error
                try:
                    self.work.put(item, timeout=0.25)
                    return
                except _q.Full:
                    continue
        finally:
            tr.end()

    def drain(self) -> None:
        """EOS barrier: launch everything staged, flush every handle."""
        import queue as _q
        while True:  # the consumer drains even after an error, so the
            try:     # sentinel always fits eventually
                self.work.put(None, timeout=0.25)
                break
            except _q.Full:
                continue
        self.thread.join()
        if self.error is not None:
            raise RuntimeError("window dispatch thread failed") \
                from self.error

    def abort(self) -> None:
        """Node-error teardown: drop the backlog without launching it
        (no EOS barrier -- the downstream channel is closing)."""
        import queue as _q
        self.aborting = True
        try:
            self.work.put_nowait(None)
        except _q.Full:
            pass  # the run loop checks `aborting` when the queue reads empty
        self.thread.join(timeout=30)

    def _run(self) -> None:
        tr = spans.bind(getattr(self.logic.flight, "spans", None))
        try:
            self._serve(tr)
        finally:
            tr.close_all()

    def _serve(self, tr) -> None:
        from collections import deque
        import queue as _q
        logic = self.logic
        pending = deque()
        last_emit = None
        while True:
            if pending:
                # a launch is in flight.  Staged work is taken without
                # waiting, so a backlog reaches the device (up to
                # inflight_depth) before this thread sleeps; with none
                # staged it sleeps on the oldest result itself and
                # emits the moment the device is done, not at the next
                # launch or the next tick of a timer.  A launch staged
                # meanwhile is picked up when the wait returns: late by
                # what the device still needed for the oldest one.
                try:
                    item = self.work.get_nowait()
                except _q.Empty:
                    if self.aborting:
                        return
                    try:
                        self._collect(tr, pending, last_emit, spans.WAITED)
                    except BaseException as e:
                        # surfaced on next submit / drain; nothing is
                        # launched or collected after a failure
                        self.error = e
                        pending.clear()
                    continue
            else:
                # work_wait: nothing to wait for but the next launch
                tr.begin(logic._n_work_wait)
                try:
                    item = self.work.get(timeout=0.25)
                except _q.Empty:
                    if self.aborting:
                        return
                    continue
                finally:
                    tr.end()
            if item is None:
                break
            if self.aborting or self.error is not None:
                continue  # failed/aborted: drain the queue, launch nothing
            (engine, cols, starts, ends, gwids, descs, emit,
             nbytes_in, rec) = item
            last_emit = emit
            try:
                handle, t_sub = logic._dispatch(
                    tr, engine, cols, starts, ends, gwids, rec)
                pending.append((handle, descs, t_sub,
                                len(pending) + 1, nbytes_in, rec))
                # collect at depth (backpressure) AND any batch whose
                # result is ready already -- otherwise results wait
                # for the pipeline to fill and latency grows with
                # inflight_depth instead of shrinking
                while pending and not self.aborting:
                    how = _collectable(pending, logic.inflight_depth)
                    if how is None:
                        break
                    self._collect(tr, pending, emit, how)
            except BaseException as e:  # surfaced on next submit / drain
                self.error = e
                pending.clear()
        while pending and self.error is None and not self.aborting:
            try:
                self._collect(tr, pending, last_emit, spans.FLUSHED)
            except BaseException as e:
                self.error = e

    def _collect(self, tr, pending, emit, how) -> None:
        """Finish the oldest in-flight launch.  Unless it was found
        ready, this thread first sleeps on its result under the
        ``ready_wait`` span (``handle.wait()`` releases the GIL) and
        stamps ``t_ready_seen`` as the wait returns.  An abort that came
        during the wait leaves the launch where it is."""
        entry = pending[0]
        rec = entry[-1]
        if rec.t_ready_seen is None:
            tr.begin(self.logic._n_ready_wait)
            try:
                entry[0].wait()
            finally:
                tr.end()
            rec.t_ready_seen = _time.perf_counter()
            if self.aborting:
                return
        pending.popleft()
        self.logic._finish(entry, emit, how)


def _ready(entry) -> bool:
    """Whether an in-flight launch's result is ready; the first time it
    reads true is the launch record's ``t_ready_seen``."""
    if not entry[0].ready():
        return False
    rec = entry[-1]
    if rec.t_ready_seen is None:
        rec.t_ready_seen = _time.perf_counter()
    return True


def _collectable(pending, depth: int) -> Optional[str]:
    """How the oldest in-flight launch is to be collected now, if at
    all: forced because the pipeline is at depth, or found ready."""
    if len(pending) >= depth:
        return spans.FORCED
    if _ready(pending[0]):
        return spans.READY
    return None


class WinSeqTPULogic(NodeLogic):
    # the runtime hands SynthChunk descriptors through un-materialized
    accepts_synth_chunks = True
    # async dispatch calls emit from the dispatcher thread AFTER svc
    # returns: the runtime must not hand this logic a buffered emit
    # (set per instance in __init__; inline dispatch is synchronous)
    sync_emit = False

    @property
    def spans_itself(self) -> bool:
        """On the native lane every chunk is under this logic's own
        ``fold`` span, so a FusedLogic adds no ``svc`` span round it
        (runtime/node.py); the Python staging lanes take one."""
        return self._native is not None

    @property
    def _store(self):
        """The keyed-window store this logic stages from: the C++
        engine where there is one, else its Python twin.  ``_native`` is
        read at each call, so a test that sets it to None (or wraps it)
        after construction gets what it asked for."""
        return self._native if self._native is not None else self._py

    def __init__(self, win_kind: Any, win_len: int, slide_len: int,
                 win_type: WinType, *, batch_len: int = DEFAULT_BATCH_LEN,
                 triggering_delay: int = 0, result_factory=BasicRecord,
                 config: WinOperatorConfig = None, role: Role = Role.SEQ,
                 map_indexes=(0, 1), parallelism: int = 1,
                 replica_index: int = 0, renumbering: bool = False,
                 value_of: Callable[[Any], float] = None,
                 closing_func: Callable = None, emit_batches: bool = False,
                 max_buffer_elems: int = DEFAULT_MAX_BUFFER_ELEMS,
                 inflight_depth: int = DEFAULT_INFLIGHT_DEPTH,
                 async_dispatch: bool = True,
                 max_batch_delay_ms: float = DEFAULT_MAX_BATCH_DELAY_MS,
                 placement: str = "device",
                 adaptive_batch: bool = False,
                 rtt_floor_ms: Optional[float] = None):
        if win_len == 0 or slide_len == 0:
            raise ValueError("win_len and slide_len must be > 0")
        if placement not in PLACEMENTS:
            raise ValueError(
                f"placement must be one of {PLACEMENTS}, not {placement!r}")
        # placement plane (graph/planner.py; docs/PLANNER.md): 'device'
        # keeps the XLA lane (status quo), 'host' swaps in the numpy
        # host engine at construction, 'auto' defers to the cost-based
        # planner at PipeGraph.start
        self.placement = placement
        self.resolved_placement = placement if placement != "auto" else None
        self.adaptive_batch = adaptive_batch
        self.rtt_floor_ms = rtt_floor_ms
        self._adaptive: Optional[AdaptiveBatcher] = None
        if placement == "host":
            from ...ops.host_compute import HostComputeEngine
            self.engine = HostComputeEngine(win_kind)  # builtin kinds only
        else:
            self.engine = WindowComputeEngine(win_kind)
        # the count->sum and mean->pair engines a store's flush may ask
        # for, by kind, built on first use on the resolved lane
        self._helpers: dict = {}
        # direct-feed plane (ingest/feed.py): parallel feeder threads
        # call feed_columns concurrently; staging is single-writer
        self._feed_lock = _threading.Lock()
        self.win_len = win_len
        self.slide_len = slide_len
        self.win_type = win_type
        self.batch_len = max(1, batch_len)
        self.triggering_delay = triggering_delay
        self.result_factory = result_factory
        self.config = config or WinOperatorConfig()
        self.role = role
        self.map_indexes = map_indexes
        self.renumbering = renumbering
        self.value_of = value_of or (lambda t: t.value)
        self.closing_func = closing_func
        self.emit_batches = emit_batches
        # in-flight batches, oldest first: (handle, descriptors, ...).
        # Depth > 1 keeps several device programs + async D2H copies in
        # flight so one high-latency transport roundtrip amortizes over
        # many launches (deepens the reference's 2-deep waitAndFlush
        # pipeline, win_seq_gpu.hpp:267-297).
        from collections import deque
        self.pending = deque()
        self.inflight_depth = max(1, inflight_depth)
        self.async_dispatch = async_dispatch
        self.sync_emit = not async_dispatch
        self._dispatcher: Optional[_AsyncDispatcher] = None
        self.launched_batches = 0
        # launch also when this much unshipped data is buffered, even if
        # the window batch is not full -- bounds host memory and keeps
        # device transfers pipelined (the adaptive resize analogue,
        # win_seq_gpu.hpp:574-592)
        self.max_buffer_elems = max_buffer_elems
        self._buffered_since_launch = 0
        # time-based launch trigger: a partial batch launches whenever
        # windows are ready and at least this long has passed since the
        # previous launch -- the latency half of the reference's
        # adaptive batch resize (win_seq_gpu.hpp:574-592), bounding
        # result latency at (delay + transport RTT) instead of
        # (full-batch fill time + RTT)
        self.max_batch_delay_ms = max_batch_delay_ms
        self._last_launch_t = 0.0
        # the emit of the call in hand, for a launch from inside the
        # Python store's ingest (``_on_full``)
        self._emit = None
        # telemetry plane (telemetry/; docs/OBSERVABILITY.md): the
        # trace context of the most recent traced input crosses the
        # async dispatcher -- captured at svc, stamped with a device
        # hop and re-attached to the next finished result batch.  Set
        # on the ingest thread, consumed on the dispatcher thread:
        # gauge-grade for sampled traces, like the depth gauges
        self._trace_ctx = None
        self._trace_name = "win_seq_tpu"
        # whole-partition device step (graph/device_step.py): while a
        # chunk is traversing the fused chain the step logic holds all
        # intra-chunk launch triggers and calls flush_chunk() once at
        # the chunk boundary, so a device segment pays ONE launch per
        # ingest chunk instead of one per trigger site.  eos_flush /
        # quiesce / idle_tick stay unguarded -- they run between
        # chunks, where the hold is always clear.
        self.chunk_hold = False
        # THE STORE.  The Python twin serves every shape and is always
        # there (a test may set ``_native`` to None after construction;
        # on the native lane it stays empty and answers the two gauges
        # that have always read the Python side, ``Inputs_ignored`` and
        # the audit's ``staging``); the C++ columnar engine covers the
        # hot standalone cases
        # (native/window_engine.cpp): builtin kinds, identity window
        # assignment, default value column, role SEQ -- or role PLQ,
        # whose only difference under an identity config is that output
        # ids are per-key dense counters (plq_renumbered_id degenerates
        # to the emit counter), applied on the flushed batch
        self._py = PyWindowStore(
            win_len, slide_len, win_type, triggering_delay,
            renumber=renumbering, kind=win_kind, role=role,
            config=self.config, map_indexes=map_indexes,
            on_kept=self._on_kept, on_full=self._on_full)
        self._native = None
        # a store's churn clock and counters as last read
        self._churn = list(PyWindowStore._NO_STATS)
        cfg = self.config
        if (isinstance(win_kind, str)
                and win_kind in ("sum", "count", "max", "min", "mean")
                and role in (Role.SEQ, Role.PLQ)
                and cfg.n_outer == 1 and cfg.n_inner == 1
                and cfg.id_outer == 0 and cfg.id_inner == 0
                and value_of is None):
            # no library (no toolchain, WINDFLOW_NATIVE=0): the Python
            # store is the supported fallback, and runtime/native.py
            # has said why on stderr
            from ...runtime.native import (NativeWindowEngine,
                                           native_available)
            if native_available():
                # renumbering = per-key arrival-order ids, which the
                # engine implements natively (ids implicit, always on
                # the dense lane)
                self._native = NativeWindowEngine(
                    win_len, slide_len, win_type == WinType.TB,
                    triggering_delay, renumber=renumbering,
                    kind=win_kind, dense=role != Role.SEQ)
        # span layer (telemetry/spans.py): the names of this operator's
        # spans and its launch ring, re-resolved in svc_init once the
        # runtime has named the replica; ``_chunk_seq`` counts the
        # chunks ingested, so a launch record can say which one fired it
        self._chunk_seq = 0
        self._track = self._track_of = None
        self._name_spans("win_seq_tpu", None)

    # -- placement plane (graph/planner.py; docs/PLANNER.md) ---------------
    def apply_placement(self, placement: str,
                        rtt_floor_ms: Optional[float] = None) -> None:
        """Resolve this engine onto a lane.  Called by the planner at
        graph start (before any thread runs) for 'auto' engines, and
        for pinned ones to record the resolution + RTT floor.  Host
        resolution swaps the XLA engine for the numpy host engine and
        drops any cached helper engines so they rebuild on-lane;
        online re-planning (graph/replanner.py) can flip a
        host-resolved engine back."""
        from ...ops.host_compute import HostComputeEngine
        if placement not in ("device", "host"):
            raise ValueError(f"cannot resolve onto {placement!r}")
        self.resolved_placement = placement
        if rtt_floor_ms:
            self.rtt_floor_ms = rtt_floor_ms
        on_host = isinstance(self.engine, HostComputeEngine)
        if (placement == "host") != on_host:
            self.engine = self._make_engine(self.engine.kind)
            self._helpers = {}

    def _make_engine(self, kind):
        """Engine factory honouring the resolved lane (the count->sum
        and mean->pair engines must run where the main engine runs)."""
        if self.resolved_placement == "host":
            from ...ops.host_compute import HostComputeEngine
            return HostComputeEngine(kind)
        return WindowComputeEngine(kind)

    def _helper_engine(self, kind: Optional[str]):
        """The engine a flushed batch needs: this logic's own (None) or
        the helper of the kind the store named."""
        if kind is None:
            return self.engine
        eng = self._helpers.get(kind)
        if eng is None:
            eng = self._helpers[kind] = self._make_engine(kind)
        return eng

    def _name_spans(self, op: str, graph) -> None:
        """Names this logic's spans.  The store decides two of them: the
        native engine folds a chunk in one foreign call under this
        logic's own ``fold`` span and stages under ``flush``, and its
        churn counters go to the graph's registry; the Python store's
        chunk is under the runtime's ``svc`` span and it stages under
        ``stage``."""
        self._span_op = op
        for phase in ("fold", "flush", "stage", "submit_wait", "dispatch",
                      "ready_wait", "work_wait", "block", "emit",
                      "open", "trigger", "evict"):
            setattr(self, "_n_" + phase, f"wf/{op}/{phase}")
        native = self._native is not None
        self._n_chunk = self._n_fold if native else None
        self._n_launch = self._n_flush if native else self._n_stage
        self._launches = (graph.ring(op) if graph is not None
                          else spans.LaunchRing(op))
        self._counters = (graph.counters_of(op)
                          if graph is not None and native
                          else spans.Counters(op))

    def _ingest_track(self):
        """The calling thread's span track, kept while the same thread
        calls (a node's thread does for its life; direct feeders take
        turns under the feed lock)."""
        tid = _threading.get_ident()
        if tid != self._track_of:
            self._track, self._track_of = spans.track(), tid
        return self._track

    def svc_init(self) -> None:
        if self.stats is not None and self.stats.operator_name:
            self._trace_name = self.stats.operator_name
        self._name_spans(self.span_op or self._trace_name,
                         getattr(self.flight, "spans", None))
        # adaptive x2 / /2 batch resize (win_seq_gpu.hpp:574-592): only
        # meaningful against a launch floor, so the device lane measures
        # one (planner-provided, else probed once per process)
        if self.adaptive_batch and self._adaptive is None \
                and self.resolved_placement != "host":
            if not self.rtt_floor_ms:
                from ...graph.planner import rtt_floor_ms
                self.rtt_floor_ms = rtt_floor_ms()
            self._adaptive = AdaptiveBatcher(self.batch_len,
                                             self.rtt_floor_ms)

    # -- direct columnar feed (ingest/feed.py) -----------------------------
    def feed_columns(self, keys, ids, ts, vals, emit) -> None:
        """Thread-safe columnar ingest for parallel feeder threads:
        columns go straight into the store (the C++ engine when built)
        under the feed lock -- no channel hop, no per-tuple Python.
        ``emit`` receives any results whose launch the ingest triggers
        (the async dispatcher keeps emitting after return)."""
        batch = TupleBatch({"key": np.asarray(keys, np.int64),
                            "id": np.asarray(ids, np.int64),
                            "ts": np.asarray(ts, np.int64),
                            "value": np.asarray(vals)})
        with self._feed_lock:
            self._chunk_seq += 1
            self._svc_batch(batch, emit)

    def feed_eos(self, emit) -> None:
        """Drain hook for the direct-feed plane (pairs with
        ``feed_columns`` exactly like the record plane's feed_eos)."""
        with self._feed_lock:
            self.eos_flush(emit)

    # -- the launch pipeline -------------------------------------------------
    def _finish(self, entry, emit, how: str) -> None:
        """Flush one in-flight batch: copy its result to the host
        (``block``), add the launch's host wall (picked up -> result on
        host: dispatch, ready wait and block; no device clock is read)
        to ``Device_time_ms``, feed the adaptive batch resize, emit.
        Stamps the launch record; ``how`` (``spans.COLLECTED``) says what
        brought the caller here."""
        handle, descs, t_sub, depth, nbytes_in, rec = entry
        rec.collected = how
        if rec.t_ready_seen is None:   # inline lane: block() waits too
            rec.t_ready_seen = _time.perf_counter()
        tr = spans.track()
        tr.begin(self._n_block)
        try:
            results = handle.block()
        finally:
            tr.end()
        now = rec.t_on_host = _time.perf_counter()
        rec.bytes_out = results.nbytes
        launch_ms = (now - t_sub) * 1e3
        if self.stats is not None:  # single-writer: dispatcher thread
            self.stats.bytes_from_device += results.nbytes
            self.stats.device_time_ms += launch_ms
        if self._adaptive is not None:
            # x2 / /2 against the RTT floor; the new length applies to
            # the next batch assembly (ingest thread reads batch_len).
            # The wall includes queueing behind the other in-flight
            # launches on a serialized transport, so it is normalized
            # by the depth at submit: otherwise a saturated pipeline at
            # depth 8 always reads >= shrink_above x the floor and the
            # controller can only shrink under exactly the load it is
            # meant to optimize
            before = self.batch_len
            self.batch_len = self._adaptive.observe(launch_ms / depth)
            if self.batch_len != before and self.flight is not None:
                self.flight.record("batch_resize",
                                   operator=self._trace_name,
                                   old_len=before,
                                   new_len=self.batch_len,
                                   launch_ms=round(launch_ms, 3))
        # trace crossing (telemetry/): the sampled context captured at
        # svc gets an engine hop (submit -> result-on-host) and rides
        # the result batch to the sink.  On the device lane the
        # "@device" suffix keys the diagnosis plane's hop-class split
        # (device transport/compute vs host service --
        # diagnosis/attribution.py); the host lane's launches are host
        # service time and stamp plain
        tr = self._trace_ctx
        if tr is not None:
            self._trace_ctx = None
            name = self._trace_name
            if self.resolved_placement != "host":
                # device-lane hops carry launch count + transfer bytes
                # as gauge-grade hop meta so a whole-partition step
                # (graph/device_step.py) stays attributable as ONE
                # launch per chunk in the diagnosis plane
                tr.hop(name + "@device", t_sub, now,
                       meta={"launches": 1,
                             "bytes_in": int(nbytes_in),
                             "bytes_out": int(results.nbytes)})
            else:
                tr.hop(name, t_sub, now)
        sp = spans.track()
        sp.begin(self._n_emit)
        try:
            self._emit_results(results, descs, emit, trace=tr)
        finally:
            sp.end()
        rec.t_emitted = _time.perf_counter()

    def _dispatch(self, tr, eng, cols, starts, ends, gwids, rec):
        """``engine.compute`` under the ``dispatch`` span, whose
        annotation carries the launch sequence number so that a program
        on the device line of a profiler trace can be joined to the
        launch that caused it.  The handle brings back what the engine
        alone knows of the launch: the host arrays it handed the device
        and its two stamps inside ``dispatch`` (None from a lane that
        takes none).  Returns (handle, t_picked)."""
        t_sub = rec.t_picked = _time.perf_counter()
        tr.begin(self._n_dispatch, {"launch": rec.seq})
        try:
            handle = eng.compute(cols, starts, ends, gwids)
        finally:
            tr.end()
        rec.t_dispatched = _time.perf_counter()
        rec.buffers_in = handle.buffers_in
        rec.t_packed, rec.t_called = handle.t_packed, handle.t_called
        self.launched_batches += 1
        return handle, t_sub

    def _submit(self, cols, starts, ends, gwids, descs, emit,
                engine=None) -> None:
        """Hand one staged batch to the device: via the dispatcher
        thread (default) or inline with the waitAndFlush protocol."""
        eng = engine or self.engine
        nbytes_in = (sum(int(np.asarray(c).nbytes) for c in cols.values())
                     + starts.nbytes + ends.nbytes + gwids.nbytes)
        if self.stats is not None:  # single-writer: ingest thread
            self.stats.num_launches += 1
            self.stats.bytes_to_device += nbytes_in
        rec = self._launches.open(self._chunk_seq, nbytes_in,
                                  _time.perf_counter())
        if self.async_dispatch:
            if self._dispatcher is None:
                self._dispatcher = _AsyncDispatcher(self)
            self._dispatcher.submit(
                (eng, cols, starts, ends, gwids, descs, emit,
                 nbytes_in, rec))
        else:
            self._flush_pending(emit)  # waitAndFlush of the previous
            handle, t_sub = self._dispatch(spans.track(), eng, cols,
                                           starts, ends, gwids, rec)
            self.pending.append((handle, descs, t_sub,
                                 len(self.pending) + 1, nbytes_in, rec))
        self._buffered_since_launch = 0
        self._last_launch_t = _time.perf_counter()

    def _flush_pending(self, emit, drain: bool = False) -> None:
        """Emit completed in-flight batches: the oldest when the
        pipeline is at depth (waitAndFlush), any whose async D2H has
        landed, or all when draining (inline-dispatch mode only)."""
        while self.pending:
            how = spans.FLUSHED if drain else _collectable(
                self.pending, self.inflight_depth)
            if how is None:
                break
            self._finish(self.pending.popleft(), emit, how)

    def _drain_all(self, emit) -> None:
        if self._dispatcher is not None:
            self._dispatcher.drain()
            self._dispatcher = None
        self._flush_pending(emit, drain=True)

    def _emit_results(self, results, descs, emit, trace=None) -> None:
        """One finished batch to ``emit``: ``descs`` is the (keys,
        gwids, rts) of the store's flush, row for row with
        ``results``.  One result TupleBatch where the operator emits
        batches and the store's rows have a key column; else a record a
        row (int and string keys can mix, and a TupleBatch key column
        cannot carry the strings)."""
        if trace is not None:
            # the captured trace context rides the first emission of
            # this finished batch to the sink (batch lanes attach to
            # the whole result batch, record lanes to the first record)
            def emit(item, _e=emit, _t=trace):
                nonlocal trace
                if trace is not None:
                    trace = None
                    try:
                        item.trace = _t
                    except AttributeError:
                        pass
                _e(item)
        d_keys, d_gwids, d_rts = descs
        d_keys, ids = self._store.output_ids(d_keys, d_gwids)
        if self.emit_batches and isinstance(d_keys, np.ndarray):
            emit(TupleBatch({"key": d_keys, "id": ids, "ts": d_rts,
                             "value": np.asarray(results, np.float64)}))
            return
        if isinstance(d_keys, np.ndarray):
            d_keys = d_keys.tolist()
        if isinstance(ids, np.ndarray):
            ids = ids.tolist()
        for key, id_, rts, val in zip(d_keys, ids, d_rts.tolist(), results):
            out = self.result_factory()
            out.value = float(val)
            out.set_control_fields(key, id_, rts)
            emit(out)

    def _launch(self, emit, max_windows=None) -> None:
        """Stage ready windows from the store and launch one program
        over the flushed buffer: one ``flush`` / ``stage`` span, with
        ``submit_wait`` its child."""
        store = self._store
        tr = spans.track()
        tr.begin(self._n_launch)
        try:
            out = store.flush(max_windows or max(self.batch_len, 4096))
            self._account_churn(store)
            if out is None:
                return
            cols, starts, ends, d_keys, d_gwids, d_rts, kind = out
            if self.stats is not None:  # single-writer: ingest thread
                # the Python store's late tuples; the native engine's
                # are among its counters (``_account_churn``)
                self.stats.inputs_ignored = self._py.ignored()
            self._submit(cols, starts, ends, d_gwids,
                         (d_keys, d_gwids, d_rts), emit,
                         engine=self._helper_engine(kind))
        finally:
            tr.end()

    def _on_kept(self, n: int) -> None:
        """The Python store's hook: a chunk left ``n`` tuples with a
        key.  That store's buffer bound counts the tuples it kept."""
        self._buffered_since_launch += n

    def _on_full(self, ready: int) -> None:
        """The Python store's hook (window_store.PyWindowStore): a
        window fired inside an ingest and ``ready`` are waiting.  That
        store's batch leaves the moment it is full, and whole, where
        the native engine's leaves after the ingest (``_folded``):
        launch counts and sizes differ, and tests and the
        AdaptiveBatcher read them (ROADMAP D2)."""
        if ready >= self.batch_len and not self.chunk_hold:
            self._launch(self._emit, max_windows=ready)

    def _account_churn(self, store) -> None:
        """What the store timed and counted since the last look (the
        native engine does, ``NativeWindowEngine.STATS``): ``open``,
        ``trigger`` and ``evict`` become children of the span open round
        the call, the counters go to the registry, and with them the
        engine's clocks of the inside of ``fold`` and ``flush``
        (``spans.ENGINE_CLOCKS``: counters, not children).  A look that
        finds no key opened and no churn clock moved costs one call: the
        fold's counts and clocks, which move with every chunk, go with
        the next look that does (every firing, and EOS)."""
        s = store.stats()
        last = self._churn
        if s[0] == last[0] and s[1] == last[1] and s[2] == last[2] \
                and s[3] == last[3]:
            return                # evict and fired move with these
        tr = self._ingest_track()
        for i, name in ((0, self._n_open), (1, self._n_trigger),
                        (2, self._n_evict)):
            if s[i] != last[i]:
                tr.account(name, s[i] - last[i])
        last[:] = s
        # spans.ENGINE_COUNTERS: STATS without the churn clocks and the
        # stream time
        self._counters.note(tr.stack[-1][2] if tr.stack else tr.last_ns,
                            last[3:13] + last[14:])

    def _launch_due(self) -> bool:
        return ((_time.perf_counter() - self._last_launch_t) * 1e3
                >= self.max_batch_delay_ms)

    def _folded(self, ready: int, n: int, emit, chunk: bool = True) -> None:
        """After an ingest of ``n`` events (a chunk, or one record) left
        ``ready`` windows: a launch where one is due.
        The Python store has counted what it kept of a chunk and sent
        its full batches from inside the ingest (``_on_kept``,
        ``_on_full``): what it leaves goes whole, on the age bound or,
        after a chunk, the buffer bound (ROADMAP D2: the two stores'
        rules are not yet one)."""
        if self._native is None:
            if (ready and not self.chunk_hold and (self._launch_due() or (
                    chunk and self._buffered_since_launch
                    >= self.max_buffer_elems))):
                self._launch(emit, max_windows=ready)
            return
        self._buffered_since_launch += n
        if (ready and not self.chunk_hold
                and (ready >= self.batch_len
                     or self._buffered_since_launch >= self.max_buffer_elems
                     or self._launch_due())):
            self._launch(emit)

    # -- ingest ----------------------------------------------------------------
    def _svc_batch(self, item, emit) -> None:
        """One chunk (columns, or a slice of the declared synthetic law
        that the native engine generates and folds in one pass) into the
        store, and a launch where one is due.  On the native lane under
        one ``fold`` span round the foreign call and this operator's
        Python about it (a launch is its child ``flush``)."""
        store = self._store
        self._emit = emit
        name = self._n_chunk
        if name is not None:
            tr = self._ingest_track()
            tr.begin(name)
        try:
            if type(item) is SynthChunk:
                ready = store.synth_ingest(item.start, item.n, item.n_keys,
                                           item.vmod, item.vscale, item.voff)
            elif item.selection is None:
                ready = store.ingest(
                    item.key,
                    item.id if self.win_type == WinType.CB else item.ts,
                    item.ts, item["value"])
            else:
                # a filtered batch that carries its rows: the store reads
                # the columns as the batch holds them, through the rows
                ts = item.held("ts")
                ready = store.ingest(
                    item.held("key"),
                    item.held("id") if self.win_type == WinType.CB else ts,
                    ts, item.held("value"), item.selection)
                self._counters.selected(*item.selection_counts(), len(item))
            self._account_churn(store)
            self._folded(ready, len(item), emit)
        finally:
            if name is not None:
                tr.end()

    def svc(self, item, channel_id, emit):
        if self.telemetry is not None:
            tr = getattr(item, "trace", None)
            if tr is not None:   # crosses the dispatcher (see _finish)
                self._trace_ctx = tr
        if isinstance(item, (TupleBatch, SynthChunk)):
            self._chunk_seq += 1
            self._svc_batch(item, emit)
            return
        # the record plane: one record into the same store, so mixed
        # record/batch streams share it.  An EOS marker carries its
        # key's last stamp and no tuple
        is_marker = isinstance(item, EOSMarker)
        t = item.record if is_marker else item
        store = self._store
        self._emit = emit
        ready = store.ingest_record(
            t, None if is_marker else self.value_of(t))
        self._account_churn(store)
        self._folded(ready, 1, emit, chunk=False)

    def eos_flush(self, emit):
        """Fire every opened window, then drain both batches (the
        reference computes leftovers on CPU at EOS,
        win_seq_gpu.hpp:648-710; we just launch the final batches)."""
        store = self._store
        self._emit = emit
        store.eos()
        while store.ready():
            self._launch(emit)
        self._drain_all(emit)

    def idle_tick(self, emit) -> None:
        """Stalled-stream launch trigger (RtNode timed gets): windows
        that fired but sit ready in the store while no input arrives
        must still launch once the rate-limit allows -- otherwise a
        paused source withholds results until the next batch or EOS."""
        if self.pending:
            # inline-dispatch mode parks computed batches in `pending`
            # until the next launch; a stall must drain the ready ones
            self._flush_pending(emit)
        if self._launch_due() and self._store.ready():
            self._launch(emit)

    def flush_chunk(self, emit) -> int:
        """Chunk-boundary launch for the whole-partition device step
        (graph/device_step.py): everything that fired while
        ``chunk_hold`` suppressed the intra-chunk triggers goes out as
        ONE launch.  Returns the number of launches issued (0 or 1) so
        the step logic can account launches-per-chunk."""
        ready = self._store.ready()
        if ready:
            self._launch(emit, max_windows=ready)
            return 1
        return 0

    def quiesce(self, emit) -> bool:
        """Live-checkpoint barrier hook (pipegraph.quiesce): drain every
        in-flight device batch, emitting its results, so ``state_dict``
        sees no pending work.  Returns True when anything was drained
        (the barrier loops until a drain pass emits nothing).  Called
        only while this node's thread is idle (sources paused, channels
        empty), so touching engine state is safe."""
        had = self._dispatcher is not None or bool(self.pending)
        self._drain_all(emit)
        return had

    # -- audit-plane hooks (audit/; docs/OBSERVABILITY.md): lock-free
    # gauge reads from the auditor thread against the live engine -----
    def audit_in_flight(self) -> dict:
        """Windows absorbed but not yet emitted: submitted device
        batches (the inline pipeline's; the dispatcher's backlog is its
        own) plus the Python store's batch under assembly -- the
        ``in_flight`` term of the conservation ledger's device leg."""
        return {"device_batches": len(self.pending),
                "staging": self._py.ready()}

    def keyed_state_census(self):
        """(key count, byte estimate) of the per-key window state: the
        keys that are live, not every key ever seen.  The Python store
        samples one key state's arrays; the native engine owns its
        buffers and estimates no bytes."""
        snap = self._store.snapshot()
        n, est = snap["keys_live"], snap.get("bytes_est")
        if est is None:
            return (n, 0) if n else None
        return (n, est)

    # -- checkpoint / resume (utils/checkpoint.py policy layer) --------
    def state_dict(self):
        """Pickle-friendly snapshot (quiescent contract: no device
        batches in flight).  The native engine's state is its versioned
        binary blob under ``native``; the Python store's is its per-key
        store under ``keys``, with what it had fired and its times."""
        st = {"launched_batches": self.launched_batches,
              "buffered": self._buffered_since_launch}
        st.update(self._store.serialize())
        return st

    def load_state(self, state):
        self.launched_batches = state.get("launched_batches", 0)
        self._buffered_since_launch = state.get("buffered", 0)
        lanes = ("the native engine", "the Python path")
        if ("native" in state) != (self._native is not None):
            came, runs = lanes if "native" in state else lanes[::-1]
            raise RuntimeError(
                f"snapshot came from {came} but this replica runs {runs}")
        self._store.deserialize(state)

    def svc_end(self):
        # error-path teardown: eos_flush already drained (and cleared)
        # the dispatcher on the normal path, so one still present here
        # means the node thread aborted -- stop launching its backlog
        if self._dispatcher is not None:
            self._dispatcher.abort()
            self._dispatcher = None
        if self.closing_func is not None:
            from ...core.context import RuntimeContext
            self.closing_func(RuntimeContext())


class WinSeqTPU(Operator):
    """Standalone device-batched window operator (builders_gpu.hpp:50
    analogue)."""

    def __init__(self, win_kind, win_len, slide_len, win_type,
                 batch_len=DEFAULT_BATCH_LEN, triggering_delay=0,
                 name="win_seq_tpu", result_factory=BasicRecord,
                 value_of=None, closing_func=None, emit_batches=False,
                 max_buffer_elems=DEFAULT_MAX_BUFFER_ELEMS,
                 inflight_depth=DEFAULT_INFLIGHT_DEPTH,
                 async_dispatch=True,
                 max_batch_delay_ms=DEFAULT_MAX_BATCH_DELAY_MS,
                 placement="device", adaptive_batch=False,
                 rtt_floor_ms=None):
        super().__init__(name, 1, RoutingMode.FORWARD, Pattern.WIN_SEQ_TPU)
        self.win_type = win_type
        self.kwargs = dict(
            win_kind=win_kind, win_len=win_len, slide_len=slide_len,
            win_type=win_type, batch_len=batch_len,
            triggering_delay=triggering_delay, result_factory=result_factory,
            value_of=value_of, closing_func=closing_func,
            emit_batches=emit_batches, max_buffer_elems=max_buffer_elems,
            inflight_depth=inflight_depth, async_dispatch=async_dispatch,
            max_batch_delay_ms=max_batch_delay_ms, placement=placement,
            adaptive_batch=adaptive_batch, rtt_floor_ms=rtt_floor_ms)
        self._renumbering = False

    def enable_renumbering(self):
        self._renumbering = True

    def stages(self):
        logic = WinSeqTPULogic(renumbering=self._renumbering, **self.kwargs)
        return [StageSpec(
            self.name, [logic], StandardEmitter(), self.routing,
            ordering_mode=(OrderingMode.ID if self.win_type == WinType.CB
                           else OrderingMode.TS))]
