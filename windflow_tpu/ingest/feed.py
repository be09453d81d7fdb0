"""Parallel zero-copy columnar feed: N feeder threads, one arena.

The externally-fed gap (VERDICT r5, ROADMAP item 1): the synthetic
fusion lane hits hundreds of M tuples/s because the C++ engine
generates and folds chunks in place, while an external feed used to
pay a single Python source thread materializing fresh numpy columns
per batch.  This module closes the gap from the feed side:

* :class:`FeedSource` -- a graph source whose ``feeders`` replicas
  pull chunk indices from a shared cursor and materialize columns
  **through a shared ColumnPool arena** (`core/tuples.ColumnPool`):
  buffers recycle by refcount, so steady state allocates nothing, and
  the emitted TupleBatches enter the consuming window engine's
  columnar ingest (`WinSeqTPULogic._svc_batch` -> one C++ call
  per chunk) with no per-tuple Python anywhere on the path.
* :class:`ParallelColumnFeeder` -- the channel-free variant: feeder
  threads hand pooled columns **straight into a columnar sink** under
  one lock -- `WinSeqTPULogic.feed_columns` (device staging) or
  `NativeRecordPipeline.feed` (the native record plane; its feed ring
  is SPSC, hence the serialization).  The lock is held for one
  GIL-released C call per chunk, so N feeders overlap their column
  materialization with each other's ingest.

Chunk protocol (both classes): ``chunk_fn(i, take) -> TupleBatch |
(keys, ids, ts, vals) | None`` where ``i`` is the dense chunk index
claimed by a feeder and ``take(n, dtype)`` is the arena allocator.
``None`` ends the stream; every index below the first None must
produce a chunk (feeders claim indices atomically, so the stream is a
partition of the chunk sequence, not an interleaving race).
"""
from __future__ import annotations

import threading
from typing import Callable, Optional

from ..core.basic import Pattern, RoutingMode
from ..core.tuples import ColumnPool, TupleBatch
from ..runtime.emitters import StandardEmitter
from ..runtime.node import SourceLoopLogic
from ..operators.base import Operator, StageSpec


class _ChunkCursor:
    """Atomic claim of dense chunk indices plus an emission
    **turnstile**: feeders materialize their chunks concurrently but
    deliver them in index order.  A window engine drops tuples behind
    its fired frontier (the acceptance rule, win_seq.hpp:417-428), so
    out-of-order chunk delivery from racing feeders would silently
    lose windows -- materialization is the expensive part, delivery is
    one GIL-released C call, so ordering delivery costs ~nothing."""

    __slots__ = ("_cond", "_next_claim", "_next_emit", "ended")

    def __init__(self):
        self._cond = threading.Condition()
        self._next_claim = 0
        self._next_emit = 0
        self.ended = False

    def claim(self) -> int:
        with self._cond:
            i = self._next_claim
            self._next_claim += 1
            return i

    def wait_turn(self, i: int) -> bool:
        """Block until chunk ``i`` may be delivered; False when the
        stream ended first (an earlier chunk was None / a feeder
        failed)."""
        with self._cond:
            while self._next_emit != i and not self.ended:
                self._cond.wait(0.25)
            return not self.ended

    def release_turn(self, i: int) -> None:
        with self._cond:
            if self._next_emit == i:
                self._next_emit = i + 1
            self._cond.notify_all()

    def end(self) -> None:
        with self._cond:
            self.ended = True
            self._cond.notify_all()


def _as_batch(chunk) -> TupleBatch:
    if isinstance(chunk, TupleBatch):
        return chunk
    keys, ids, ts, vals = chunk
    return TupleBatch({"key": keys, "id": ids, "ts": ts, "value": vals})


class _FeedSourceLogic(SourceLoopLogic):
    """One feeder replica: claim index, materialize through the shared
    arena, emit.  Ends when chunk_fn returns None (the cursor's ended
    flag stops the other feeders at their next claim)."""

    def __init__(self, chunk_fn: Callable, cursor: _ChunkCursor,
                 pool: ColumnPool):
        self.chunk_fn = chunk_fn
        self.cursor = cursor
        self.pool = pool

        def step(emit):
            if cursor.ended:
                return False
            i = cursor.claim()
            try:
                chunk = self.chunk_fn(i, pool.take)  # parallel with peers
            except BaseException:
                # a chunk_fn failure must end the turnstile, or peer
                # feeders blocked in wait_turn would never unwind (the
                # cursor is not a channel: poisoning can't reach it)
                cursor.end()
                raise
            if not cursor.wait_turn(i):
                return False
            try:
                if chunk is None:
                    cursor.end()
                    return False
                emit(_as_batch(chunk))  # in chunk order, by the turnstile
            finally:
                cursor.release_turn(i)
            return True

        super().__init__(step)


class FeedSource(Operator):
    """Graph source with N parallel zero-copy feeder replicas.

    The pooled arena is shared across replicas (and sized by the
    deepest in-flight window the downstream engine keeps, via the
    refcount recycling -- no tuning knob needed).  Compared to
    ``BatchSource(fn, parallelism=N)``, the differences are exactly
    the hot-path ones: chunk indices are claimed atomically (a
    partition, not per-replica modular striping), and columns come
    from the arena instead of fresh numpy allocations."""

    def __init__(self, chunk_fn: Callable, feeders: int = 1,
                 name: str = "feed_source",
                 pool: Optional[ColumnPool] = None):
        super().__init__(name, feeders, RoutingMode.NONE, Pattern.SOURCE)
        self.chunk_fn = chunk_fn
        self.pool = pool or ColumnPool(max_per_bucket=max(64, 8 * feeders))
        self._cursor = _ChunkCursor()

    def stages(self):
        reps = [_FeedSourceLogic(self.chunk_fn, self._cursor, self.pool)
                for _ in range(self.parallelism)]
        return [StageSpec(self.name, reps, StandardEmitter(),
                          self.routing)]


class ParallelColumnFeeder:
    """Channel-free parallel feed into a columnar sink.

    ``sink`` is anything accepting ``(keys, ids, ts, vals)`` columns --
    `NativeRecordPipeline.feed` bound, or a wrapper over
    `WinSeqTPULogic.feed_columns`.  Feeders claim chunk indices from
    the shared cursor, materialize through the pooled arena in
    parallel, and serialize only the sink call itself (one
    GIL-released C crossing per chunk)."""

    def __init__(self, chunk_fn: Callable, sink: Callable,
                 feeders: int = 2, pool: Optional[ColumnPool] = None):
        self.chunk_fn = chunk_fn
        self.sink = sink
        self.feeders = max(1, feeders)
        self.pool = pool or ColumnPool(max_per_bucket=max(64, 8 * feeders))
        self._sink_lock = threading.Lock()
        self.chunks_fed = 0
        self.tuples_fed = 0
        self._error: Optional[BaseException] = None

    def _run_one(self, cursor: _ChunkCursor) -> None:
        try:
            while not cursor.ended and self._error is None:
                i = cursor.claim()
                chunk = self.chunk_fn(i, self.pool.take)
                if not cursor.wait_turn(i):
                    return
                try:
                    if chunk is None:
                        cursor.end()
                        return
                    batch = _as_batch(chunk)
                    with self._sink_lock:
                        self.sink(batch.key, batch.id, batch.ts,
                                  batch["value"])
                        self.chunks_fed += 1
                        self.tuples_fed += len(batch)
                finally:
                    cursor.release_turn(i)
        except BaseException as e:  # re-raised by run()
            self._error = e
            cursor.end()

    def run(self) -> int:
        """Feed until a feeder sees None; returns tuples fed."""
        cursor = _ChunkCursor()
        threads = [threading.Thread(target=self._run_one, args=(cursor,),
                                    daemon=True, name=f"col-feeder-{i}")
                   for i in range(self.feeders)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if self._error is not None:
            raise self._error
        return self.tuples_fed
