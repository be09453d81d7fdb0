"""The keyed-window store in Python: what ``WinSeqTPULogic`` stages from
where the C++ engine (``runtime/native.NativeWindowEngine``) cannot
serve -- no toolchain, a ``value_of``, a custom kind, roles WLQ / MAP /
REDUCE, a nested config -- and the reference the lane-parametrised
tests hold the C++ engine to.

It answers the calls the operator makes on the native engine, with the
same meanings: ``ingest(keys, ids, ts, vals) -> ready``,
``ingest_record``, ``synth_ingest``, ``flush(max_windows)``, ``eos()``,
``ready()``, ``ignored()``, ``stats()``, ``snapshot()``,
``serialize()`` / ``deserialize()`` and ``output_ids``.  ``flush``
returns on both sides ``(cols, starts, ends, keys, gwids, rts,
engine_kind)``: the flat value buffer, every staged window's extent in
it, its key, window id and result stamp, and the helper engine the
buffer needs (``"sum"`` where it holds per-pane counts) or None.

Each key's series lives in growing host buffers, consolidated into
sorted numpy arrays when its windows are staged (the pinned-staging
analogue of win_seq_gpu.hpp); the firing rule is the one of
docs/RUNTIME.md "When a window fires".
"""
from __future__ import annotations

import copy
import heapq as _heapq
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from ...core import win_assign as wa
from ...core.basic import Role, WinOperatorConfig, WinType
from ...core.meta import default_hash
from ...core.tuples import SynthChunk, key_groups


class _TPUKeyState:
    __slots__ = ("sort_keys", "ts", "values", "pending_sort", "pending_ts",
                 "pending_val", "pending_chunks", "next_fire", "opened_max",
                 "max_id", "renumber_next", "emit_counter", "anchor",
                 "queued", "indexed", "due_at")

    def __init__(self, emit_counter_start=0):
        # consolidated sorted arrays
        self.sort_keys = np.empty(0, np.int64)
        self.ts = np.empty(0, np.int64)
        self.values = np.empty(0, np.float64)
        # unsorted pending appends (sorted at consolidation): scalar
        # lists for the record plane, array chunks for the batch plane
        self.pending_sort: List[int] = []
        self.pending_ts: List[int] = []
        self.pending_val: List[float] = []
        self.pending_chunks: List = []
        self.next_fire = 0        # next lwid to fire
        self.anchor = 0           # first window that can ever fire (set
                                  # from the first tuple, like the
                                  # native engine's anchor)
        self.opened_max = -1      # highest lwid opened by any tuple
        self.max_id = -1
        self.queued = 0           # fired windows not yet staged
        self.indexed = False      # listed in the store's ``_due`` heap
        self.due_at = -1          # when the listing that counts fires
        self.renumber_next = 0
        self.emit_counter = emit_counter_start

    def __setstate__(self, state):
        # a snapshot written before PR 30 carries two more slots, of a
        # lane that is gone: take what this class still has
        for name, value in state[1].items():
            if name in self.__slots__:
                setattr(self, name, value)


class PyWindowStore:
    # builtin associative kinds whose pane partials the host can
    # pre-reduce before shipping (the Pane_Farm decomposition, applied
    # as a transport optimization: ship partials, not tuples)
    _PANE_KINDS = {"sum": "sum", "count": "sum", "max": "max", "min": "min"}
    # nothing is timed or counted here: what ``stats()`` answers (no key
    # opened, no clock moved)
    _NO_STATS = (0, 0, 0, 0)

    def __init__(self, win_len: int, slide_len: int, win_type: WinType,
                 triggering_delay: int = 0, *, renumber: bool = False,
                 kind: Any = "sum", role: Role = Role.SEQ,
                 config: WinOperatorConfig = None, map_indexes=(0, 1),
                 on_kept: Optional[Callable[[int], None]] = None,
                 on_full: Optional[Callable[[int], None]] = None):
        self.win_len = win_len
        self.slide_len = slide_len
        self.win_type = win_type
        self.triggering_delay = triggering_delay
        self.renumber = renumber
        self.role = role
        self.config = config or WinOperatorConfig()
        self.map_indexes = map_indexes
        # the operator's launch rule for this store, from inside an
        # ingest (win_seq_tpu ``_on_kept`` / ``_on_full``): told the
        # tuples a chunk left with a key before the key's windows fire,
        # and the number of windows ready each time one is queued, so
        # that a batch can leave the moment it is full
        self.on_kept = on_kept or (lambda n: None)
        self.on_full = on_full or (lambda ready: None)
        pane = int(np.gcd(win_len, slide_len))
        self._pane = pane
        self._pane_kind = (kind if isinstance(kind, str)
                           and kind in self._PANE_KINDS and pane >= 16
                           else None)
        self.keys: Dict[Any, _TPUKeyState] = {}
        # THE FIRING RULE (docs/RUNTIME.md "When a window fires"; the
        # native engine has the same, native/window_engine.cpp): TB
        # windows on real stamps fire on this replica's stream time, the
        # largest stamp it has ingested over all keys, so a key that goes
        # quiet gets its rows when the stream passes them; CB windows and
        # renumbered ids count a key's own arrivals and fire on the key's
        # own largest id.  Under the stream rule a SEQ replica (output
        # ids are window ids) emits a row only for a window that holds a
        # tuple of the key and drops a key whose last window is staged;
        # the other roles number a key's windows densely for the next
        # stage, so they emit every one and keep every key.
        self._stream_rule = win_type == WinType.TB and not renumber
        self._sparse = self._stream_rule and role == Role.SEQ
        self._stream_time = -1
        self._fired_time = -1     # the stream time the last trigger saw
        self._due: List = []      # heap of (fire at, n, key): keys with an
        self._due_n = 0           # opened window, by when it fires next
        # fired windows not yet staged: (key, gwid, start_key, end_key,
        # rts, key)
        self.descriptors: List = []
        # tuples that belonged to a window and were not kept (behind a
        # window that had fired, or below the anchor of a key that cannot
        # move it); and what disorder the stream rule met: tuples kept
        # whose stamp lay behind the stream time when they came, times a
        # live key's anchor moved back (``_reach``)
        self.ignored_tuples = 0
        self.late_accepted = 0
        self.anchors_moved = 0
        # what ``flush`` staged: the elements of the flat buffers it made
        # (pane partials where it pre-reduces, else the keys' own values)
        # and the windows they serve; the native engine's counters of the
        # same names (``NativeWindowEngine.STATS``)
        self.panes_staged = 0
        self.windows_staged = 0
        self._saw_nonint_key = False

    # -- the engine's small calls ------------------------------------------
    def ready(self) -> int:
        return len(self.descriptors)

    def ignored(self) -> int:
        return self.ignored_tuples

    def stats(self):
        return self._NO_STATS

    def snapshot(self) -> dict:
        """The keys that are live (not every key ever seen) and a byte
        estimate from one sampled key state; safe from another thread."""
        keys = self.keys
        n = len(keys)
        try:
            st = next(iter(keys.values()))
            per = (st.sort_keys.nbytes + st.ts.nbytes
                   + st.values.nbytes + 96)
        except (RuntimeError, StopIteration, AttributeError):
            per = 96  # empty, or resized under us: count-only estimate
        return {"keys_live": n, "bytes_est": n * per,
                "late_accepted": self.late_accepted,
                "anchors_moved": self.anchors_moved,
                "inputs_ignored": self.ignored_tuples,
                "panes_staged": self.panes_staged,
                "windows_staged": self.windows_staged}

    def serialize(self) -> dict:
        """The per-key store, the windows fired and not yet staged, and
        the two times, as the checkpoint envelope has always named them
        (``WinSeqTPULogic.state_dict``)."""
        return {
            # deep copy: a live checkpoint resumes the stream after the
            # snapshot, and an aliased store would keep advancing
            "keys": copy.deepcopy(self.keys),
            "descriptors": list(self.descriptors),
            "ignored_tuples": self.ignored_tuples,
            "late_accepted": self.late_accepted,
            "anchors_moved": self.anchors_moved,
            "stream_time": self._stream_time,
            "fired_time": self._fired_time,
        }

    def deserialize(self, state: dict) -> None:
        self.keys = copy.deepcopy(state["keys"])
        self.descriptors = list(state.get("descriptors", []))
        self.ignored_tuples = state.get("ignored_tuples", 0)
        self.late_accepted = state.get("late_accepted", 0)
        self.anchors_moved = state.get("anchors_moved", 0)
        self._stream_time = state.get("stream_time", -1)
        self._fired_time = state.get("fired_time", -1)
        self._due = []
        for key, st in self.keys.items():
            st.indexed = False
            self._index_key(key, st, self._initial_id(key))
        # re-derive the non-integral-key flag from the restored store
        # (every descriptor's key is in it): the columnar key column of
        # ``flush`` keys off the flag, and a fresh replica restoring
        # string-keyed state would otherwise crash in np.fromiter on
        # the first launch
        self._saw_nonint_key = any(
            not isinstance(k, (int, np.integer)) for k in self.keys)

    def output_ids(self, keys, gwids):
        """The keys and output ids of one staged batch's rows, asked as
        the batch is emitted: window ids on a SEQ replica, the key's
        running counter for the next stage on a MAP or PLQ one."""
        if self.role == Role.MAP:
            ids = []
            for key in keys:
                st = self.keys[key]
                ids.append(st.emit_counter)
                st.emit_counter += self.map_indexes[1]
            return keys, ids
        if self.role == Role.PLQ:
            ids = []
            for key in keys:
                st = self.keys[key]
                ids.append(wa.plq_renumbered_id(default_hash(key),
                                                st.emit_counter, self.config))
                st.emit_counter += 1
            return keys, ids
        return keys, gwids

    # -- per-key helpers ---------------------------------------------------
    def _initial_id(self, key) -> int:
        return wa.initial_id_of_key(default_hash(key), self.config,
                                    self.role)

    def _key_state(self, key) -> _TPUKeyState:
        st = self.keys.get(key)
        if st is None:
            start = self.map_indexes[0] if self.role == Role.MAP else 0
            st = self.keys[key] = _TPUKeyState(start)
        return st

    def _consolidate(self, st: _TPUKeyState) -> None:
        if not st.pending_sort and not st.pending_chunks:
            return
        chunks_sk = [c[0] for c in st.pending_chunks]
        chunks_ts = [c[1] for c in st.pending_chunks]
        chunks_v = [c[2] for c in st.pending_chunks]
        if st.pending_sort:
            chunks_sk.append(np.asarray(st.pending_sort, np.int64))
            chunks_ts.append(np.asarray(st.pending_ts, np.int64))
            chunks_v.append(np.asarray(st.pending_val, np.float64))
        st.pending_chunks.clear()
        sk = np.concatenate(chunks_sk)
        ts = np.concatenate(chunks_ts)
        vals = np.concatenate(chunks_v)
        order = np.argsort(sk, kind="stable")
        sk, ts, vals = sk[order], ts[order], vals[order]
        if len(st.sort_keys) and len(sk) and sk[0] < st.sort_keys[-1]:
            # out-of-order across consolidations (TB within delay): merge
            merged = np.concatenate([st.sort_keys, sk])
            order = np.argsort(merged, kind="stable")
            st.sort_keys = merged[order]
            st.ts = np.concatenate([st.ts, ts])[order]
            st.values = np.concatenate([st.values, vals])[order]
        else:
            st.sort_keys = np.concatenate([st.sort_keys, sk])
            st.ts = np.concatenate([st.ts, ts])
            st.values = np.concatenate([st.values, vals])
        st.pending_sort.clear()
        st.pending_ts.clear()
        st.pending_val.clear()

    def _evict(self, st: _TPUKeyState, keep_from: int) -> None:
        """Drop the prefix below ``keep_from``, which no window still to
        be staged can reach (the archive purge,
        win_seq_gpu.hpp:612-614)."""
        cut = np.searchsorted(st.sort_keys, keep_from, side="left")
        if cut:
            st.sort_keys = st.sort_keys[cut:]
            st.ts = st.ts[cut:]
            st.values = st.values[cut:]

    # -- staging -------------------------------------------------------------
    def _pane_partials(self, st: _TPUKeyState, base_key: int, n_panes: int,
                       pane: int, kind: str):
        """Per-pane host pre-reduction over one key's retained series."""
        edges = base_key + np.arange(n_panes + 1, dtype=np.int64) * pane
        pos = np.searchsorted(st.sort_keys, edges)
        if kind == "count":
            return np.diff(pos).astype(np.float64)
        from ...runtime.native import pane_reduce
        red = pane_reduce(st.values, pos, kind)  # exact [pos[i], pos[i+1])
        if red is not None:
            return red
        if kind == "sum":
            cs = np.concatenate([[0.0], np.cumsum(st.values)])
            return cs[pos[1:]] - cs[pos[:-1]]
        neutral = -np.inf if kind == "max" else np.inf
        ufunc = np.maximum if kind == "max" else np.minimum
        # reduceat over the non-empty panes' start edges only: empty
        # panes collapse to equal edges so each segment ends exactly at
        # the next non-empty pane's start, and clipping the buffer at
        # pos[-1] keeps retained tuples beyond the batch's last window
        # edge out of the final segment (reduceat runs it to the end)
        vals = st.values[:int(pos[-1])]
        out = np.full(n_panes, neutral)
        nonempty = np.nonzero(np.diff(pos) > 0)[0]
        if len(nonempty):
            out[nonempty] = ufunc.reduceat(vals, pos[nonempty])
        return out

    def flush(self, max_windows: int):
        """Stage up to ``max_windows`` fired windows, oldest first: one
        flat buffer (pane partials where the kind and the pane length
        allow, else the keys' values) and the windows' extents in it.
        None when nothing has fired."""
        descs = self.descriptors
        if not descs:
            return None
        if len(descs) > max_windows:
            descs, self.descriptors = descs[:max_windows], descs[max_windows:]
        else:
            self.descriptors = []
        # group descriptors per key (preserving order)
        keys_involved: List = []
        per_key: Dict = {}
        for i, d in enumerate(descs):
            if d[5] not in per_key:
                per_key[d[5]] = []
                keys_involved.append(d[5])
            per_key[d[5]].append(i)
        pane, kind = self._pane, self._pane_kind
        starts = np.empty(len(descs), np.int64)
        ends = np.empty(len(descs), np.int64)
        gwids = np.fromiter((d[1] for d in descs), np.int64, len(descs))
        rts = np.fromiter((d[4] for d in descs), np.int64, len(descs))
        bufs_v = []
        off = 0
        for k in keys_involved:
            st = self.keys[k]
            self._consolidate(st)
            idxs = per_key[k]
            if kind is not None:
                # window extents are pane-aligned (pane = gcd(win, slide)
                # divides both the slide stride and the window length)
                base_key = min(descs[i][2] for i in idxs)
                max_end = max(descs[i][3] for i in idxs)
                n_panes = (max_end - base_key) // pane
                bufs_v.append(self._pane_partials(st, base_key, n_panes,
                                                  pane, kind))
                for i in idxs:
                    starts[i] = off + (descs[i][2] - base_key) // pane
                    ends[i] = off + (descs[i][3] - base_key) // pane
                off += n_panes
            else:
                bufs_v.append(st.values)
                for i in idxs:
                    starts[i] = off + np.searchsorted(st.sort_keys,
                                                      descs[i][2], "left")
                    ends[i] = off + np.searchsorted(st.sort_keys,
                                                    descs[i][3], "left")
                off += len(st.values)
            for i in idxs:  # CB: result ts = last tuple in extent
                if rts[i] < 0:
                    hi = int(np.searchsorted(st.sort_keys, descs[i][3],
                                             "left"))
                    lo = int(np.searchsorted(st.sort_keys, descs[i][2],
                                             "left"))
                    rts[i] = int(st.ts[hi - 1]) if hi > lo else 0
        flat_vals = (np.concatenate(bufs_v) if bufs_v
                     else np.empty(0, np.float64))
        self.panes_staged += off
        self.windows_staged += len(descs)
        # the flat buffer is a copy: evict the consumed prefixes, and
        # the keys whose last window this was.  A key with windows still
        # queued (a partial take) keeps what the first of them starts at
        queued_from: Dict = {}
        for d in self.descriptors:
            queued_from.setdefault(d[5], d[2])
        for k in keys_involved:
            st = self.keys[k]
            st.queued -= len(per_key[k])
            if not self._drop_if_done(k, st):
                self._evict(st, queued_from.get(
                    k, self._initial_id(k) + st.next_fire * self.slide_len))
        # a key column where a result batch can carry one (output ids
        # are window ids and every key is integral), else the keys
        if (self.role == Role.SEQ
                and (not self._saw_nonint_key    # O(1) common case
                     or all(isinstance(d[0], (int, np.integer))
                            for d in descs))):
            d_keys = np.fromiter((d[0] for d in descs), np.int64, len(descs))
        else:
            d_keys = [d[0] for d in descs]
        # count windows sum their per-pane counts
        return ({"value": flat_vals}, starts, ends, d_keys, gwids, rts,
                "sum" if kind == "count" else None)

    # -- descriptor generation (window assignment) -------------------------
    def _fire_key(self, key, st: _TPUKeyState, front) -> None:
        """Queue every window of the key that ``front`` has passed: the
        one place the rule is applied (the stream time, a CB key's own
        largest id, or infinity at EOS)."""
        cfg = self.config
        hashcode = default_hash(key)
        first_gwid = wa.first_gwid_of_key(hashcode, cfg)
        initial_id = wa.initial_id_of_key(hashcode, cfg, self.role)
        tb = self.win_type == WinType.TB
        slack = self.triggering_delay if tb else 0
        if self._sparse:
            self._consolidate(st)
        while st.next_fire <= st.opened_max:
            lwid = st.next_fire
            start = initial_id + lwid * self.slide_len
            end = start + self.win_len
            if front < end + slack:
                break
            st.next_fire += 1
            if self._sparse:
                lo, hi = np.searchsorted(st.sort_keys, (start, end))
                if lo == hi:
                    continue      # holds no tuple of the key: no row
            gwid = wa.gwid_of_lwid(first_gwid, lwid, cfg)
            rts = (gwid * self.slide_len + self.win_len - 1
                   if tb else -1)  # CB: resolved at staging
            self.descriptors.append((key, gwid, start, end, rts, key))
            st.queued += 1
            self.on_full(len(self.descriptors))

    def _passed_lwid(self, initial_id: int) -> int:
        """Local id of the last window the stream has passed for a key
        whose windows start at ``initial_id``: fired for every key, so a
        tuple below its end is late for every key, also for one whose
        state is gone.  -1 where there is none (or no stream rule)."""
        if not self._stream_rule:
            return -1
        t = (self._fired_time - self.triggering_delay - self.win_len
             - initial_id)
        return -1 if t < 0 else t // self.slide_len

    def _first_window(self, rel: int) -> int:
        """The first window that holds (or follows) an id ``rel`` past
        the key's initial id."""
        return ((rel - self.win_len) // self.slide_len + 1
                if rel >= self.win_len else 0)

    def _admit(self, st: _TPUKeyState, first_rel: int, passed: int):
        """Anchor a key on its first data and return the acceptance
        boundary, relative to the key's initial id: a tuple below it is
        counted as ignored.  Under the stream rule the stream alone
        decides: the end of the last window it passed (None where it has
        passed none), since a key fires no window the stream has not
        passed; a tuple at or above it that lies before the window the
        key fires next moves the key back (``_reach``).  A CB or
        renumbered key fires on its own ids: its boundary is the end of
        its own last fired window, or its anchor."""
        if st.max_id < 0:
            # first data: anchor the fire frontier at the first
            # containing window (an epoch-scale first id must not fire
            # ~id/slide empty windows), never at one the stream passed
            st.anchor = st.next_fire = max(self._first_window(first_rel),
                                           passed + 1)
        if self._stream_rule:
            return (passed * self.slide_len + self.win_len
                    if passed >= 0 else None)
        if st.next_fire > st.anchor:
            return self.win_len + (st.next_fire - 1) * self.slide_len
        return st.anchor * self.slide_len

    def _reach(self, st: _TPUKeyState, first_rel: int) -> None:
        """The earliest tuple kept of a key (under the stream rule; at
        or above the acceptance boundary) lies in window ``w``.  Before
        the one the key fires next: its first tuple to arrive was not
        its earliest, or the empty windows before a returning key's
        tuple were skipped too soon, and the key fires from ``w`` (its
        listing in ``_due`` is left behind and passed over).  After it,
        with every window the key opened fired: the empty ones between
        are skipped here."""
        w = self._first_window(first_rel)
        if w < st.next_fire:
            st.next_fire = w
            st.anchor = min(st.anchor, w)
            st.indexed = False
            self.anchors_moved += 1
        elif self._sparse and st.next_fire > st.opened_max:
            st.next_fire = w

    def _settle(self, key, st: _TPUKeyState, initial_id: int) -> None:
        """A key has new data: its part in the firing."""
        if not self._stream_rule:
            self._fire_key(key, st, st.max_id)
            return
        if st.max_id > self._stream_time:
            self._stream_time = st.max_id
        self._index_key(key, st, initial_id)

    def _index_key(self, key, st: _TPUKeyState, initial_id: int) -> None:
        if st.indexed or st.next_fire > st.opened_max:
            return
        st.indexed = True
        self._due_n += 1
        st.due_at = (initial_id + st.next_fire * self.slide_len
                     + self.win_len + self.triggering_delay)
        _heapq.heappush(self._due, (st.due_at, self._due_n, key))

    def _trigger(self) -> None:
        """The stream has moved: fire the windows it has passed, for the
        keys that have them."""
        now = self._fired_time = self._stream_time
        due = self._due
        while due and due[0][0] <= now:
            at, _, key = _heapq.heappop(due)
            st = self.keys.get(key)
            if st is None or not st.indexed or st.due_at != at:
                continue      # a listing its key left behind (_reach)
            st.indexed = False
            self._fire_key(key, st, now)
            self._index_key(key, st, self._initial_id(key))
            self._drop_if_done(key, st)

    def _drop_if_done(self, key, st: _TPUKeyState) -> bool:
        """Evict a key whose every opened window has fired and been
        staged: a later tuple of it opens a new key."""
        if (not self._sparse or st.queued or st.indexed
                or st.next_fire <= st.opened_max
                or self.keys.get(key) is not st):
            return False
        del self.keys[key]
        return True

    def eos(self) -> None:
        """Fire every opened window."""
        for key, st in list(self.keys.items()):
            st.indexed = False
            self._fire_key(key, st, float("inf"))
        self._due.clear()

    # -- columnar ingest: a whole chunk is partitioned by key and
    # appended per key vectorized -------------------------------------------
    def ingest(self, keys, ids, tss, vals, sel=None) -> int:
        """One chunk's columns.  With ``sel`` (a selected batch's rows)
        a column longer than ``sel`` is a base column: this store
        gathers its rows here, where the native engine reads through
        them (runtime/native.NativeWindowEngine.ingest)."""
        if sel is not None:
            n = len(sel)
            if n and int(np.min(sel)) < 0:     # np.take would wrap it
                raise IndexError("a selection's row lies outside its "
                                 "base columns")
            keys, ids, tss, vals = (
                c if len(c) == n else np.take(c, sel, axis=0)
                for c in (keys, ids, tss, vals))
        order, keys_s, bounds = key_groups(keys)
        late_s = None
        if self._stream_rule and len(ids):
            # behind the stream time as each came: the largest stamp of
            # every tuple before it, in arrival order
            front = np.maximum.accumulate(
                np.concatenate(([self._stream_time], ids[:-1])))
            late_s = ids < front
        if order is None:
            ids_s, vals_s, tss_s = ids, vals, tss
        else:
            ids_s, vals_s, tss_s = ids[order], vals[order], tss[order]
            if late_s is not None:
                late_s = late_s[order]
        uniq = keys_s[bounds[:-1]]
        for j, key in enumerate(uniq):
            key = key.item()
            lo, hi = bounds[j], bounds[j + 1]
            st = self._key_state(key)
            initial_id = self._initial_id(key)
            k_ids = ids_s[lo:hi]
            if self.renumber:
                k_ids = np.arange(st.renumber_next,
                                  st.renumber_next + (hi - lo))
                st.renumber_next += hi - lo
            if not len(k_ids):
                continue
            # acceptance: tuples behind the already-fired frontier are
            # dropped and counted; a hopping-gap tuple belongs to no
            # window, and nothing is owed it
            passed = self._passed_lwid(initial_id)
            boundary = self._admit(st, int(k_ids.min()) - initial_id, passed)
            keep = (k_ids >= initial_id + boundary if boundary is not None
                    else np.ones(len(k_ids), bool))
            self.ignored_tuples += len(k_ids) - int(keep.sum())
            if self.win_len < self.slide_len:  # hopping: drop gap tuples
                n = (k_ids - initial_id) // self.slide_len
                off = k_ids - initial_id
                keep &= (off >= n * self.slide_len) & \
                    (off < n * self.slide_len + self.win_len)
            n_drop = len(k_ids) - int(keep.sum())
            if n_drop == len(k_ids):
                if self._stream_rule:
                    # late, yet the stream has come this far
                    self._stream_time = max(self._stream_time,
                                            int(k_ids.max()))
                self._drop_if_done(key, st)
                continue
            k_ids = k_ids[keep]
            if self._stream_rule:
                self.late_accepted += int(late_s[lo:hi][keep].sum())
                if st.max_id >= 0:
                    self._reach(st, int(k_ids.min()) - initial_id)
            st.pending_chunks.append(
                (k_ids.astype(np.int64), tss_s[lo:hi][keep],
                 vals_s[lo:hi][keep].astype(np.float64)))
            self.on_kept(len(k_ids))
            st.max_id = max(st.max_id, int(k_ids.max()))
            last_w = wa.last_window_of(st.max_id, initial_id, self.win_len,
                                       self.slide_len)
            if last_w >= 0:
                st.opened_max = max(st.opened_max, last_w)
            self._settle(key, st, initial_id)
        if self._stream_rule:
            self._trigger()
        return len(self.descriptors)

    def synth_ingest(self, start: int, n: int, n_keys: int, vmod: int = 97,
                     vscale: float = 1.0, voff: float = 0.0) -> int:
        """A slice of the declared synthetic law (operators/synth.py),
        materialized: id and ts are one column there."""
        b = SynthChunk(start, n, n_keys, vmod, vscale, voff).materialize()
        return self.ingest(b.key, b.id, b.ts, b["value"])

    def ingest_record(self, t, val) -> int:
        """One record ``t`` of value ``val`` (the record plane: no 1-row
        columns).  ``val`` None is an EOS marker: the key's last stamp
        and no tuple."""
        key, tid, ts = t.get_control_fields()
        if not isinstance(key, (int, np.integer)):
            self._saw_nonint_key = True
        st = self._key_state(key)
        if self.renumber and val is not None:
            tid = st.renumber_next
            st.renumber_next += 1
            t.set_control_fields(key, tid, ts)
        id_ = tid if self.win_type == WinType.CB else ts
        initial_id = self._initial_id(key)
        if val is not None:
            passed = self._passed_lwid(initial_id)
            fresh = st.max_id < 0
            boundary = self._admit(st, id_ - initial_id, passed)
            if boundary is not None and id_ < initial_id + boundary:
                self.ignored_tuples += 1
                self._drop_if_done(key, st)
                return len(self.descriptors)
            last_w = wa.last_window_of(id_, initial_id, self.win_len,
                                       self.slide_len)
            if last_w < 0:
                self._drop_if_done(key, st)
                return len(self.descriptors)  # hopping gap
            if self._stream_rule:
                self.late_accepted += id_ < self._stream_time
                if not fresh:
                    self._reach(st, id_ - initial_id)
            st.opened_max = max(st.opened_max, last_w)
            st.pending_sort.append(id_)
            st.pending_ts.append(ts)
            st.pending_val.append(val)
        st.max_id = max(st.max_id, id_)
        self._settle(key, st, initial_id)
        if self._stream_rule:
            self._trigger()
        return len(self.descriptors)
