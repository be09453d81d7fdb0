"""Tuple contract and the columnar batch type.

The reference imposes a structural contract on user types:
``getControlFields() -> (key, id, ts)`` / ``setControlFields(key,id,ts)``
(used e.g. at win_seq.hpp:331-333; test type mp_tests_gpu/mp_common.hpp:44-81).
We keep that contract for the record-oriented plane and add the thing the
reference cannot have: a **columnar TupleBatch** -- the native currency of
the TPU plane.  A stream here is a sequence of batches (struct-of-arrays),
which is what XLA wants; single records exist only at the API edge.
"""
from __future__ import annotations

import sys
import threading
from typing import Any, Dict, Iterator, Optional, Protocol, Tuple, \
    runtime_checkable

import numpy as np


class ColumnPool:
    """Arena of reusable numpy column buffers (per PipeGraph).

    ``take(n, dtype)`` returns a length-``n`` view over a pooled
    power-of-two buffer.  Reuse is **refcount-driven**: the pool keeps a
    strong reference to every base buffer it handed out; a buffer whose
    only remaining referent is the pool itself (every downstream view
    of it has died) is free and gets re-lent.  No explicit release call
    exists, so a consumer holding a batch alive can never have its
    columns scribbled over -- the safety property an explicit-free
    arena cannot give a Python dataflow.

    The per-(dtype, bucket) freelists are bounded (``max_per_bucket``)
    so a burst of in-flight batches degrades to plain allocation
    instead of growing the arena without bound.
    """

    __slots__ = ("_lock", "_buckets", "max_per_bucket", "hits", "misses")

    # refcount of a free base buffer: the bucket list + the loop local
    # + the getrefcount argument
    _FREE_RC = 3

    def __init__(self, max_per_bucket: int = 32):
        self._lock = threading.Lock()
        self._buckets: Dict[Tuple[str, int], list] = {}
        self.max_per_bucket = max_per_bucket
        self.hits = 0
        self.misses = 0

    def take(self, n: int, dtype) -> np.ndarray:
        """A length-``n`` uninitialized view over a pooled buffer."""
        dt = np.dtype(dtype)
        if n <= 0:
            return np.empty(0, dt)
        cap = 1 << (int(n) - 1).bit_length()
        key = (dt.str, cap)
        with self._lock:
            bucket = self._buckets.get(key)
            if bucket is not None:
                for buf in bucket:
                    # free iff nothing outside this pool references it
                    if sys.getrefcount(buf) <= self._FREE_RC:
                        self.hits += 1
                        return buf[:n]
            self.misses += 1
            buf = np.empty(cap, dt)
            if bucket is None:
                bucket = self._buckets[key] = []
            if len(bucket) < self.max_per_bucket:
                bucket.append(buf)
            return buf[:n]

    def stats(self) -> dict:
        with self._lock:
            held = sum(len(b) for b in self._buckets.values())
            held_bytes = sum(buf.nbytes for b in self._buckets.values()
                             for buf in b)
        return {"buffers": held, "bytes": held_bytes,
                "hits": self.hits, "misses": self.misses}

    def drain(self) -> int:
        """Release the arena: drop the pool's strong references to
        every pooled base buffer, returning the byte count let go.
        Buffers with live outside views survive exactly as long as
        those views do (refcounting, not the pool, owns them now); the
        pool stays usable and simply re-allocates on the next take.
        The serving plane calls this at tenant teardown so repeated
        submit/evict cycles reclaim arena memory (docs/SERVING.md)."""
        with self._lock:
            released = sum(buf.nbytes for b in self._buckets.values()
                           for buf in b)
            self._buckets.clear()
        return released


@runtime_checkable
class WFRecord(Protocol):
    """Structural contract every user record type must satisfy."""

    def get_control_fields(self) -> Tuple[Any, int, int]:
        """Return (key, id, ts)."""
        ...

    def set_control_fields(self, key: Any, tid: int, ts: int) -> None:
        ...


class SynthChunk:
    """A descriptor slice of the declared synthetic law
    (operators/synth.SyntheticSource): events [start, start + n) with
    key = e % n_keys, id = ts = e // n_keys,
    value = (e % vmod) * vscale + voff.

    A stream item like TupleBatch: consumers that own a native engine
    fold it without materializing the columns; the runtime materializes
    it transparently at every other plane boundary (RtNode dispatch,
    multi-destination outlets)."""

    # ``trace`` stays UNSET (not None-initialized) so untraced chunks
    # pay zero construction cost; telemetry reads it via getattr-with-
    # default (telemetry/trace.py)
    __slots__ = ("start", "n", "n_keys", "vmod", "vscale", "voff", "trace")

    def __init__(self, start, n, n_keys, vmod, vscale, voff):
        self.start = start
        self.n = n
        self.n_keys = n_keys
        self.vmod = vmod
        self.vscale = vscale
        self.voff = voff

    def __len__(self):
        return self.n

    def materialize(self, pool: Optional[ColumnPool] = None) -> "TupleBatch":
        tr = getattr(self, "trace", None)
        if pool is None:
            idx = self.start + np.arange(self.n)
            ids = idx // self.n_keys
            out = TupleBatch({
                "key": idx % self.n_keys, "id": ids, "ts": ids,
                "value": (idx % self.vmod).astype(np.float64) * self.vscale
                         + self.voff})
            if tr is not None:
                out.trace = tr
            return out
        # pooled lane: all columns come from the graph arena;
        # np.ufunc(..., out=) writes them in place (no fresh allocation
        # per chunk)
        n = self.n
        idx = pool.take(n, np.int64)
        idx[:] = np.arange(self.start, self.start + n)
        keys = np.mod(idx, self.n_keys, out=pool.take(n, np.int64))
        res = np.mod(idx, self.vmod, out=pool.take(n, np.int64))
        ids = np.floor_divide(idx, self.n_keys, out=idx)  # idx is scratch
        vals = np.multiply(res, self.vscale, out=pool.take(n, np.float64),
                           casting="unsafe")
        if self.voff:
            np.add(vals, self.voff, out=vals)
        out = TupleBatch({"key": keys, "id": ids, "ts": ids, "value": vals})
        if tr is not None:
            out.trace = tr
        return out


class BasicRecord:
    """Convenience record: key/id/ts control fields + a float value.

    Mirrors the reference test fixture tuple (mp_common.hpp:44-81) but is
    a library type so users do not have to define one for simple streams.
    """

    # ``trace`` stays unset unless the telemetry plane attaches a
    # context (telemetry/trace.py); no per-record construction cost
    __slots__ = ("key", "id", "ts", "value", "trace")

    def __init__(self, key: Any = 0, tid: int = 0, ts: int = 0, value: float = 0.0):
        self.key = key
        self.id = tid
        self.ts = ts
        self.value = value

    def get_control_fields(self):
        return (self.key, self.id, self.ts)

    def set_control_fields(self, key, tid, ts):
        self.key = key
        self.id = tid
        self.ts = ts

    def __repr__(self):
        return f"BasicRecord(key={self.key}, id={self.id}, ts={self.ts}, value={self.value})"


class _Selection:
    """The rows a selected :class:`TupleBatch` stands for: ``rows[j]`` of
    every column of ``base``.  Shared by a selected batch and what
    ``with_cols`` derives from it, so a column gathered for one is there
    for the other.  ``rows`` is shorter than the base columns (a take
    that would not shorten gathers at once), which is how a reader that
    is handed columns of both kinds tells them apart."""

    __slots__ = ("base", "rows", "read")

    def __init__(self, base: Dict[str, np.ndarray], rows: np.ndarray):
        self.base = base
        self.rows = rows
        self.read: Dict[str, np.ndarray] = {}  # base columns gathered so far

    def column(self, name: str, pool: Optional["ColumnPool"] = None):
        col = self.read.get(name)
        if col is None:
            col = self.read[name] = _gather(self.base[name], self.rows,
                                            pool)
        return col


# the index buffers of selections: a mask's rows are compacted into a
# buffer as long as the mask, and a buffer is lent again once the
# selection that reads it is gone (ColumnPool's refcount rule, under its
# lock).  One for the process: ``take`` is called by user code and by
# logics that hold no graph's pool, and a fresh 512 KB a chunk would be
# mapped and faulted in anew each time
_ROWS_POOL = ColumnPool(max_per_bucket=4)


def _take_rows(n: int) -> np.ndarray:
    return _ROWS_POOL.take(n, np.int64)


def _gather(col: np.ndarray, rows: np.ndarray,
            pool: Optional[ColumnPool]) -> np.ndarray:
    if pool is None or col.ndim != 1 or (
            col.base is not None and not col.flags.owndata
            and not col.flags.c_contiguous):
        return np.take(col, rows, axis=0)   # odd layout: let numpy
    return np.take(col, rows, axis=0, out=pool.take(len(rows), col.dtype))


def _same_length(cols: Dict[str, np.ndarray], n: int) -> None:
    for name, col in cols.items():
        if len(col) != n:
            raise ValueError(f"column '{name}' length {len(col)} != {n}")


_native = None      # runtime/native: that package imports this module


def _rows_of_mask(mask: np.ndarray) -> np.ndarray:
    """``np.nonzero(mask)[0]``: from the native library's branch-free
    pass into a pooled buffer where this process has the library loaded
    (runtime/native.mask_to_rows), else from numpy."""
    global _native
    if _native is None:
        from ..runtime import native as _native
    rows = _native.mask_to_rows(mask, _take_rows)
    return np.nonzero(mask)[0] if rows is None else rows


class TupleBatch:
    """Columnar micro-batch of tuples: dict of equal-length numpy columns.

    Required columns: ``key`` (int64), ``id`` (int64), ``ts`` (int64).
    Any number of payload columns (e.g. ``value``).  This is the unit that
    flows over host queues on the batch plane and the host-side staging
    format for device transfers (the TPU analogue of the reference's
    pinned-buffer batch assembly, win_seq_gpu.hpp:552-596).

    **A row subset carries its selection.**  ``take`` of a mask or of
    scattered indices copies no column: it answers a batch that holds
    the base columns and the rows, whose ``len`` is the rows'.  ``key``,
    ``id``, ``ts`` and ``batch[name]`` gather their column on first read
    and keep it; ``with_cols`` lays compact columns over the selection
    and carries it on; a second ``take`` composes the two.  ``cols``
    gathers whatever is left and answers the dict it always did, after
    which the batch is an ordinary one.  A reader that can follow rows
    itself (the window engine) asks for :attr:`selection` and
    :meth:`held` and gathers nothing.  Like a :class:`SynthChunk`, a
    selected batch is materialized (:meth:`compact`) at every plane
    boundary -- ``Outlet`` does before a put -- so no queue pins a whole
    base chunk for the third of its rows that survived a filter.
    """

    # ``_cols``: the columns that are ``len(self)`` long (all of them,
    # or what was laid over a selection); ``_sel``: None, or the
    # selection the other columns are read through.  ``trace`` carries a
    # sampled telemetry TraceContext end to end (telemetry/trace.py); it
    # stays unset on untraced batches (getattr default read) so batch
    # construction pays nothing for it
    __slots__ = ("_cols", "_sel", "trace")

    CONTROL = ("key", "id", "ts")

    def __init__(self, cols: Dict[str, np.ndarray]):
        for c in self.CONTROL:
            if c not in cols:
                raise ValueError(f"TupleBatch missing control column '{c}'")
        _same_length(cols, len(cols["key"]))
        self._cols = cols
        self._sel = None

    @classmethod
    def _selected(cls, laid: Dict[str, np.ndarray],
                  sel: _Selection) -> "TupleBatch":
        out = cls.__new__(cls)
        out._cols = laid
        out._sel = sel
        return out

    # -- construction ------------------------------------------------------
    @classmethod
    def from_records(cls, records, payload=("value",)) -> "TupleBatch":
        keys, ids, tss = [], [], []
        pay = {p: [] for p in payload}
        for r in records:
            k, i, t = r.get_control_fields()
            keys.append(k)
            ids.append(i)
            tss.append(t)
            for p in payload:
                pay[p].append(getattr(r, p))
        cols = {
            "key": np.asarray(keys, dtype=np.int64),
            "id": np.asarray(ids, dtype=np.int64),
            "ts": np.asarray(tss, dtype=np.int64),
        }
        for p in payload:
            cols[p] = np.asarray(pay[p])
        return cls(cols)

    @classmethod
    def empty_like(cls, other: "TupleBatch") -> "TupleBatch":
        return cls({k: v[:0] for k, v in other.cols.items()})

    # -- accessors ---------------------------------------------------------
    def __len__(self) -> int:
        sel = self._sel
        return len(self._cols["key"]) if sel is None else len(sel.rows)

    def _read(self, name: str) -> np.ndarray:
        """A column that is not among the compact ones: gathered through
        the selection (once), or no column of this batch."""
        if self._sel is None:
            raise KeyError(name)
        return self._sel.column(name)

    @property
    def key(self) -> np.ndarray:
        col = self._cols.get("key")
        return col if col is not None else self._read("key")

    @property
    def id(self) -> np.ndarray:
        col = self._cols.get("id")
        return col if col is not None else self._read("id")

    @property
    def ts(self) -> np.ndarray:
        col = self._cols.get("ts")
        return col if col is not None else self._read("ts")

    def __getitem__(self, name: str) -> np.ndarray:
        col = self._cols.get(name)
        return col if col is not None else self._read(name)

    @property
    def cols(self) -> Dict[str, np.ndarray]:
        """Every column, ``len(self)`` long, by name.  On a selected
        batch this is where the columns nobody read are gathered."""
        if self._sel is not None:
            self.compact()
        return self._cols

    def names(self) -> list:
        """The column names in ``cols``' order, gathering nothing."""
        if self._sel is None:
            return list(self._cols)
        base = self._sel.base
        return list(base) + [k for k in self._cols if k not in base]

    def payload_names(self):
        return [c for c in self.names() if c not in self.CONTROL]

    # -- the selection, for a reader that follows rows itself --------------
    @property
    def selection(self) -> Optional[np.ndarray]:
        """The rows (int64 indices into the base columns) of a selected
        batch, None on an ordinary one."""
        sel = self._sel
        return None if sel is None else sel.rows

    def held(self, name: str) -> np.ndarray:
        """The column as this batch holds it, gathering nothing: compact
        (``len(self)`` long) where it was laid over the selection or has
        been read, else the base column, to be read at ``selection``'s
        rows.  The two are told apart by their length: a selection is
        shorter than its base."""
        col = self._cols.get(name)
        if col is None:
            sel = self._sel
            if sel is None:
                raise KeyError(name)
            col = sel.read.get(name)
            if col is None:
                col = sel.base[name]
        return col

    def selection_counts(self) -> Tuple[int, int]:
        """(columns the selection carries, of those gathered so far);
        (0, 0) on an ordinary batch."""
        sel = self._sel
        return (0, 0) if sel is None else (len(sel.base), len(sel.read))

    def compact(self, pool: Optional[ColumnPool] = None) -> "TupleBatch":
        """Make a selected batch an ordinary one, in place: gather every
        base column that no compact one replaced and that was not read
        yet, and let the base go.  The columns come out in the order an
        eager gather followed by the same ``with_cols`` gave."""
        sel = self._sel
        if sel is not None:
            laid = self._cols
            cols = {k: laid[k] if k in laid else sel.column(k, pool)
                    for k in sel.base}
            for k, v in laid.items():
                cols.setdefault(k, v)
            self._cols, self._sel = cols, None
        return self

    # -- transforms --------------------------------------------------------
    def take(self, idx, pool: Optional[ColumnPool] = None) -> "TupleBatch":
        """Row subset.  Slices and contiguous index runs stay zero-copy
        views.  A boolean mask becomes its rows in one branch-free
        native pass (``np.nonzero`` where the library is not loaded: a
        mispredicted branch a row); the rows, or an index array, then
        ride with the base columns as a selection (see the class) and
        no column is copied until it is read.  With ``pool`` the caller
        is a partitioner whose sub-batches cross a queue next: the
        columns are gathered at once, into arena buffers.  A riding
        trace context propagates to every sub-batch (KEYBY partitions
        keep their sampled path traced)."""
        sel = self._sel
        if isinstance(idx, slice):
            laid = {k: v[idx] for k, v in self._cols.items()}
            if sel is None:
                return self._carry(TupleBatch(laid))
            return self._carry(TupleBatch._selected(
                laid, _Selection(sel.base, sel.rows[idx])))
        idx = np.asarray(idx)
        n_self = len(self)
        if idx.dtype == np.bool_:
            if len(idx) != n_self:
                raise IndexError(
                    f"boolean mask length {len(idx)} != batch "
                    f"length {n_self}")
            idx = _rows_of_mask(idx)
            n = len(idx)
            # a mask's rows ascend: one run iff first and last say so
            run = n > 1 and int(idx[-1]) - int(idx[0]) == n - 1
        else:
            if idx.size == 0:
                idx = idx.astype(np.intp)   # e.g. a bare [] (float64)
            elif idx.dtype.kind not in "iu":
                raise TypeError(f"rows cannot be taken by an index of "
                                f"dtype {idx.dtype}")
            n = len(idx)
            if n:
                # a selection's rows are read by native code: in range
                # and counted from the front, as np.take would have it
                lo, hi = int(idx.min()), int(idx.max())
                if lo < -n_self or hi >= n_self:
                    raise IndexError(
                        f"index {lo if lo < -n_self else hi} is out of "
                        f"bounds for a batch of {n_self} rows")
                if lo < 0:
                    idx = np.where(idx < 0, idx + n_self, idx)
            # the cheap first/last guard gates the O(n) check
            run = n > 1 and int(idx[-1]) - int(idx[0]) == n - 1 \
                and bool((np.diff(idx) == 1).all())
        if run:
            # contiguous ascending run: zero-copy view, not a gather
            lo = int(idx[0])
            return self.take(slice(lo, lo + n))
        if sel is None:
            out = TupleBatch._selected({}, _Selection(self._cols, idx))
            n_base = n_self
        else:
            # a take of a take: the two selections composed; what was
            # laid over the first is compact and is subset now
            out = TupleBatch._selected(
                {k: np.take(v, idx, axis=0) for k, v in self._cols.items()},
                _Selection(sel.base, np.take(sel.rows, idx)))
            n_base = len(next(iter(sel.base.values())))
        if pool is not None or n >= n_base:
            out.compact(pool)   # a plane boundary next, or no subset
        return self._carry(out)

    def _carry(self, out: "TupleBatch") -> "TupleBatch":
        """Propagate a riding trace context onto a derived batch."""
        tr = getattr(self, "trace", None)
        if tr is not None:
            out.trace = tr
        return out

    def concat(self, other: "TupleBatch") -> "TupleBatch":
        theirs = other.cols
        out = TupleBatch(
            {k: np.concatenate([v, theirs[k]]) for k, v in self.cols.items()}
        )
        # either side's context rides on (self's stamp wins: it entered
        # the stream earlier, so the merged batch's latency is honest)
        tr = getattr(self, "trace", None) or getattr(other, "trace", None)
        if tr is not None:
            out.trace = tr
        return out

    def with_cols(self, **cols) -> "TupleBatch":
        out = dict(self._cols)
        out.update(cols)
        if self._sel is None:
            return self._carry(TupleBatch(out))
        _same_length(cols, len(self))
        return self._carry(TupleBatch._selected(out, self._sel))

    def records(self, cls=BasicRecord) -> Iterator[Any]:
        """Materialize records at the API edge (slow path, tests only)."""
        cols = self.cols
        names = self.payload_names()
        for i in range(len(self)):
            r = cls(cols["key"][i].item(), cols["id"][i].item(),
                    cols["ts"][i].item())
            for p in names:
                if hasattr(r, p):
                    setattr(r, p, cols[p][i].item())
            yield r

    def __reduce__(self):
        # a batch that leaves the process leaves compact
        return (_rebuild, (self.cols, getattr(self, "trace", None)))

    def __repr__(self):
        return f"TupleBatch(n={len(self)}, cols={self.names()})"


def _rebuild(cols, trace) -> TupleBatch:
    out = TupleBatch(cols)
    if trace is not None:
        out.trace = trace
    return out


class EOS:
    """End-of-stream marker carried over host queues.

    The reference encodes EOS as a flagged refcounted wrapper
    (meta.hpp:770-783, ``isEOSMarker``); here it is a first-class queue
    item optionally carrying the per-key last tuples a WF emitter needs
    to broadcast (wf_nodes.hpp:207-227).
    """

    __slots__ = ("payload",)

    def __init__(self, payload=None):
        self.payload = payload

    def __repr__(self):
        return "EOS()"


def key_groups(keys: np.ndarray):
    """Stable-group a key column: (order, keys_sorted, bounds) with
    ``order`` None when the column is already sorted (saves the
    re-index on the columnar hot path)."""
    if len(keys) > 1 and not np.all(keys[:-1] <= keys[1:]):
        order = np.argsort(keys, kind="stable")
        keys_s = keys[order]
    else:
        order, keys_s = None, keys
    edges = np.nonzero(np.diff(keys_s))[0] + 1
    bounds = np.concatenate([[0], edges, [len(keys_s)]])
    return order, keys_s, bounds
