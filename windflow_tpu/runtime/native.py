"""ctypes bindings to the native C++ host runtime (native/windflow_native.cpp).

Builds the shared library on first use with g++ (no pip/pybind11
dependency), caches it next to the sources under a content stamp, and
degrades to the pure-Python plane -- saying so on stderr -- when the
toolchain is unavailable (RuntimeConfig.use_native_runtime gates usage).

Object hand-off across the native channel: the producer increfs the
Python object and passes its address; the consumer rebuilds the object
reference and decrefs.  Blocking waits happen in C++ with the GIL
released (ctypes drops it around foreign calls).
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sys
import threading
import time
from typing import Any, Optional

from ..ops.argmax import ArgmaxStaged
from ..telemetry.spans import ENGINE_COUNTERS, ENGINE_STATS
from .queues import CHANNEL_TIMEOUT

_lib = None
_lib_lock = threading.Lock()
_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "native")
_SRCS = [os.path.join(_NATIVE_DIR, f)
         for f in ("windflow_native.cpp", "window_engine.cpp",
                   "record_pipeline.cpp")]
_SO = os.path.join(_NATIVE_DIR, "libwindflow_native.so")


# -ffp-contract=off: the declared Python/numpy plane rounds mul and
# add separately; FMA contraction in the lowered planes would differ
# by 1 ULP at exact filter thresholds (lowering must never change
# results)
_FLAGS = ["-O3", "-march=native", "-ffp-contract=off", "-std=c++17",
          "-shared", "-fPIC", "-pthread"]
_STAMP = _SO + ".stamp"

# "built" | "reused" once this process has a library, else None
build_state: Optional[str] = None


def _cpu_identity() -> str:
    """What ``-march=native`` resolved against: the first processor
    block of /proc/cpuinfo without its per-core and clock lines.  A
    library copied from a host with another CPU must not be loaded."""
    volatile = ("processor", "cpu MHz", "bogomips", "BogoMIPS", "core id",
                "apicid", "initial apicid", "physical id")
    try:
        with open("/proc/cpuinfo") as f:
            block = f.read().split("\n\n", 1)[0]
    except OSError:
        import platform
        return f"{platform.machine()} {platform.processor()}"
    return "\n".join(ln for ln in block.splitlines()
                     if ln.split(":")[0].strip() not in volatile)


def _build_stamp() -> str:
    """Hash of everything the library is a function of: the three
    sources' contents, the flags, the host CPU.  No mtimes and no
    paths, so a checkout copied elsewhere keeps its library and a
    library copied from elsewhere does not pass for this host's."""
    h = hashlib.sha256()
    for src in _SRCS:
        with open(src, "rb") as f:
            h.update(f.read())
    h.update(" ".join(_FLAGS).encode())
    h.update(_cpu_identity().encode())
    return h.hexdigest()


def _build() -> Optional[str]:
    global build_state
    # fault-injection hook (resilience/faults.py): tests force the
    # toolchain probe to fail to exercise the pure-Python fallback
    from ..resilience.faults import native_build_forced_to_fail
    if native_build_forced_to_fail():
        return None
    if os.environ.get("WINDFLOW_NATIVE", "1") == "0":
        return None  # CI pure-Python job: skip the toolchain entirely
    stamp = _build_stamp()
    try:
        with open(_STAMP) as f:
            fresh = os.path.exists(_SO) and f.read() == stamp
    except OSError:
        fresh = False
    if fresh:
        build_state = "reused"
        return _SO
    # build beside the target and rename: processes that start together
    # (fleet workers) must never load a half-written library
    tmp = f"{_SO}.{os.getpid()}.tmp"
    try:
        subprocess.run(["g++", *_FLAGS, *_SRCS, "-o", tmp], check=True,
                       capture_output=True, timeout=180)
        os.replace(tmp, _SO)
        with open(_STAMP, "w") as f:
            f.write(stamp)
    except (OSError, subprocess.SubprocessError) as e:
        # the pure-Python plane is a supported mode, but never a silent
        # one (get_lib caches the outcome: once per process)
        detail = getattr(e, "stderr", b"") or b""
        print(f"[windflow] native library not built ({e}); running the "
              f"pure-Python plane\n{detail.decode(errors='replace')[-2000:]}",
              file=sys.stderr)
        return None
    build_state = "built"
    return _SO


def get_lib():
    """Load (building if needed) the native library; None if unavailable."""
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib if _lib is not False else None
        so = _build()
        if so is None:
            _lib = False
            return None
        lib = ctypes.CDLL(so)
        lib.wfn_channel_new.restype = ctypes.c_void_p
        lib.wfn_channel_new.argtypes = [ctypes.c_size_t]
        lib.wfn_channel_free.argtypes = [ctypes.c_void_p]
        lib.wfn_channel_register_producer.restype = ctypes.c_int
        lib.wfn_channel_register_producer.argtypes = [ctypes.c_void_p]
        lib.wfn_channel_put.restype = ctypes.c_int
        lib.wfn_channel_put.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                        ctypes.c_size_t]
        lib.wfn_channel_close.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.wfn_channel_poison.argtypes = [ctypes.c_void_p]
        lib.wfn_channel_drain.restype = ctypes.c_int
        lib.wfn_channel_drain.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_size_t)]
        lib.wfn_channel_get_timed.restype = ctypes.c_int
        lib.wfn_channel_get_timed.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_size_t),
            ctypes.POINTER(ctypes.c_int), ctypes.c_longlong]
        lib.wfn_channel_get.restype = ctypes.c_int
        lib.wfn_channel_get.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_size_t),
            ctypes.POINTER(ctypes.c_int)]
        lib.wfn_channel_size.restype = ctypes.c_size_t
        lib.wfn_channel_size.argtypes = [ctypes.c_void_p]
        lib.wfn_pane_sum.argtypes = [
            ctypes.POINTER(ctypes.c_double), ctypes.POINTER(ctypes.c_longlong),
            ctypes.c_longlong, ctypes.POINTER(ctypes.c_double)]
        for name in ("wfn_pane_max", "wfn_pane_min"):
            fn = getattr(lib, name)
            fn.argtypes = [
                ctypes.POINTER(ctypes.c_double),
                ctypes.POINTER(ctypes.c_longlong), ctypes.c_longlong,
                ctypes.c_double, ctypes.POINTER(ctypes.c_double)]
        lib.wfn_partition_mod.argtypes = [
            ctypes.POINTER(ctypes.c_longlong), ctypes.c_longlong,
            ctypes.c_longlong, ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_longlong)]
        _PLL = ctypes.POINTER(ctypes.c_longlong)
        lib.wfn_pane_prereduce.restype = ctypes.c_longlong
        lib.wfn_pane_prereduce.argtypes = [
            _PLL, _PLL, ctypes.POINTER(ctypes.c_double),
            ctypes.c_longlong, ctypes.c_longlong, ctypes.c_longlong,
            _PLL, _PLL, ctypes.POINTER(ctypes.c_double)]
        lib.wfn_pane_prereduce_f32.restype = ctypes.c_longlong
        lib.wfn_pane_prereduce_f32.argtypes = [
            _PLL, _PLL, ctypes.POINTER(ctypes.c_float),
            ctypes.c_longlong, ctypes.c_longlong, ctypes.c_longlong,
            _PLL, _PLL, ctypes.POINTER(ctypes.c_double)]
        LL = ctypes.c_longlong
        PLL = ctypes.POINTER(LL)
        PD = ctypes.POINTER(ctypes.c_double)
        lib.wfn_engine_new.restype = ctypes.c_void_p
        lib.wfn_engine_new.argtypes = [LL, LL, ctypes.c_int, LL, ctypes.c_int,
                                       ctypes.c_int, ctypes.c_int]
        lib.wfn_engine_free.argtypes = [ctypes.c_void_p]
        # the calls made once a chunk take their arrays by address
        # (``arr.ctypes.data``): a typed pointer costs a cast an array
        VP = ctypes.c_void_p
        lib.wfn_engine_ingest.restype = LL
        lib.wfn_engine_ingest.argtypes = [VP, VP, VP, VP, VP, LL]
        lib.wfn_engine_ingest_f32.restype = LL
        lib.wfn_engine_ingest_f32.argtypes = [VP, VP, VP, VP, VP, LL]
        lib.wfn_engine_ingest_sel.restype = LL
        lib.wfn_engine_ingest_sel.argtypes = [VP, VP, VP, VP, VP, VP, LL,
                                              ctypes.c_int, LL]
        lib.wfn_engine_ingest_sel_f32.restype = LL
        lib.wfn_engine_ingest_sel_f32.argtypes = [VP, VP, VP, VP, VP, VP,
                                                  LL, ctypes.c_int, LL]
        lib.wfn_mask_to_rows.restype = LL
        lib.wfn_mask_to_rows.argtypes = [VP, LL, VP]
        lib.wfn_engine_synth_ingest.restype = LL
        lib.wfn_engine_synth_ingest.argtypes = [
            ctypes.c_void_p, LL, LL, LL, LL,
            ctypes.c_double, ctypes.c_double]
        lib.wfn_engine_synth_ingest_masked.restype = LL
        lib.wfn_engine_synth_ingest_masked.argtypes = [
            ctypes.c_void_p, LL, LL, LL, LL,
            ctypes.c_double, ctypes.c_double,
            ctypes.POINTER(ctypes.c_ubyte), PD]
        lib.wfn_engine_ready.restype = LL
        lib.wfn_engine_ready.argtypes = [ctypes.c_void_p]
        lib.wfn_engine_ignored.restype = LL
        lib.wfn_engine_ignored.argtypes = [ctypes.c_void_p]
        lib.wfn_engine_stats.argtypes = [ctypes.c_void_p, PLL]
        # test-only (tests/test_native_runtime.py): the lanes' rule held
        # to never / always, what the last call left, the process's lanes
        lib.wfn_engine_lanes_forced.argtypes = [ctypes.c_void_p,
                                                ctypes.c_int]
        lib.wfn_engine_last_call.restype = LL
        lib.wfn_engine_last_call.argtypes = [ctypes.c_void_p, LL, PLL, LL]
        lib.wfn_lanes_info.argtypes = [PLL]
        lib.wfn_engine_eos.argtypes = [ctypes.c_void_p]
        lib.wfn_engine_flush.restype = LL
        lib.wfn_engine_flush.argtypes = [
            ctypes.c_void_p, LL, ctypes.POINTER(PD), PLL,
            ctypes.POINTER(PD), PLL,
            ctypes.POINTER(PLL), ctypes.POINTER(PLL), ctypes.POINTER(PLL),
            ctypes.POINTER(PLL), ctypes.POINTER(PLL)]
        lib.wfn_engine_argmax_staged.restype = LL
        lib.wfn_engine_argmax_staged.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(PLL), ctypes.POINTER(PLL),
            ctypes.POINTER(PLL)]
        lib.wfn_engine_serialize.restype = LL
        lib.wfn_engine_serialize.argtypes = [ctypes.c_void_p,
                                             ctypes.c_char_p, LL]
        lib.wfn_engine_deserialize.restype = ctypes.c_int
        lib.wfn_engine_deserialize.argtypes = [ctypes.c_void_p,
                                               ctypes.c_char_p, LL]
        lib.wfn_rp_new.restype = ctypes.c_void_p
        lib.wfn_rp_new.argtypes = [ctypes.c_int, ctypes.c_int, ctypes.c_int]
        lib.wfn_rp_free.argtypes = [ctypes.c_void_p]
        lib.wfn_rp_add_stage.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            LL, LL, LL, LL, ctypes.c_double, ctypes.c_double]
        lib.wfn_rp_set_synth.argtypes = [ctypes.c_void_p, LL, LL, LL,
                                         ctypes.c_double, ctypes.c_double]
        lib.wfn_rp_set_feed.argtypes = [ctypes.c_void_p]
        lib.wfn_rp_start.argtypes = [ctypes.c_void_p]
        lib.wfn_rp_feed.argtypes = [ctypes.c_void_p, PLL, PLL, PLL, PD, LL]
        lib.wfn_rp_feed_eos.argtypes = [ctypes.c_void_p]
        lib.wfn_rp_poll.restype = LL
        lib.wfn_rp_poll.argtypes = [ctypes.c_void_p, LL, PLL, PLL, PLL, PD,
                                    ctypes.POINTER(ctypes.c_int)]
        lib.wfn_rp_wait.argtypes = [ctypes.c_void_p, PLL, PD, PLL]
        _lib = lib
        return lib


def native_available() -> bool:
    return get_lib() is not None


class NativeChannel:
    """Drop-in for runtime.queues.Channel backed by the C++ channel."""

    __slots__ = ("lib", "ptr", "n_producers", "capacity", "poisoned",
                 "puts", "gets", "high_watermark", "_all_closed")

    def __init__(self, capacity: int = 2048):
        self.lib = get_lib()
        if self.lib is None:
            raise RuntimeError("native runtime unavailable")
        self.ptr = self.lib.wfn_channel_new(capacity)
        self.n_producers = 0
        self.capacity = capacity
        self.poisoned = False
        # raw queue counters (TRACE_FASTFLOW analogue), consumed by
        # the audit plane's conservation ledger (audit/ledger.py) and
        # the Queue_high_watermark gauge.  Unlike the pure-Python
        # channel they are incremented OUTSIDE the C++ ring's lock
        # (one GIL-held += per successful call): exact under the
        # single-consumer contract and at quiescent points (the
        # wait_end closure check), gauge-grade between concurrent
        # producers mid-stream -- which is why the online dup rule in
        # the ledger only fires on an inflight-clean snapshot.
        self.puts = 0
        self.gets = 0
        self.high_watermark = 0
        self._all_closed = False  # sticky once every producer closed

    def register_producer(self) -> int:
        self.n_producers += 1
        return self.lib.wfn_channel_register_producer(self.ptr)

    def put(self, producer_id: int, item: Any) -> None:
        ctypes.pythonapi.Py_IncRef(ctypes.py_object(item))
        rc = self.lib.wfn_channel_put(self.ptr, producer_id, id(item))
        if rc < 0:  # poisoned: the channel did not take ownership
            ctypes.pythonapi.Py_DecRef(ctypes.py_object(item))
            from ..resilience.cancel import GraphCancelled
            raise GraphCancelled(f"native channel poisoned (producer "
                                 f"{producer_id})")
        self.puts += 1
        d = self.lib.wfn_channel_size(self.ptr)
        if d > self.high_watermark:
            self.high_watermark = d

    def put_many(self, producer_id: int, items) -> None:
        """Bulk put.  The C++ ring blocks with the GIL released per
        item already; the win here is one Python-level call per batch
        from the outlet plane (and API parity with the pure-Python
        channel)."""
        for item in items:
            self.put(producer_id, item)

    def close(self, producer_id: int) -> None:
        self.lib.wfn_channel_close(self.ptr, producer_id)

    def get_many(self, max_n: int = 128, timeout: Optional[float] = None):
        """Bulk get: one blocking get, then opportunistic non-blocking
        pops while the ring is non-empty.  Same return contract as
        ``Channel.get_many`` (list / sticky None / CHANNEL_TIMEOUT)."""
        if self._all_closed:
            return None
        got = self.get(timeout)
        if got is CHANNEL_TIMEOUT:
            return CHANNEL_TIMEOUT
        if got is None:
            self._all_closed = True
            return None
        out = [got]
        while len(out) < max_n and self.qsize() > 0:
            nxt = self.get(timeout=0.001)
            if nxt is CHANNEL_TIMEOUT:
                break  # the visible entry was an unresolved EOS token
            if nxt is None:
                self._all_closed = True
                break
            out.append(nxt)
        return out

    def get(self, timeout: Optional[float] = None):
        handle = ctypes.c_size_t()
        cid = ctypes.c_int()
        if timeout is None:
            rc = self.lib.wfn_channel_get(self.ptr, ctypes.byref(handle),
                                          ctypes.byref(cid))
        else:
            rc = self.lib.wfn_channel_get_timed(
                self.ptr, ctypes.byref(handle), ctypes.byref(cid),
                max(1, int(timeout * 1000)))
        if rc < 0:
            from ..resilience.cancel import GraphCancelled
            raise GraphCancelled("native channel poisoned")
        if rc == 2:
            return CHANNEL_TIMEOUT
        if not rc:
            return None
        obj = ctypes.cast(handle.value, ctypes.py_object).value
        ctypes.pythonapi.Py_DecRef(ctypes.py_object(obj))
        self.gets += 1
        return cid.value, obj

    def poison(self) -> None:
        """Graph-cancellation sentinel: wake and fail all blocked ends."""
        self.poisoned = True
        self.lib.wfn_channel_poison(self.ptr)

    def qsize(self) -> int:
        return self.lib.wfn_channel_size(self.ptr)

    @property
    def depth(self) -> int:
        """Depth gauge (monitoring/elastic samplers): the C++ size read
        is already lock-cheap, so this just mirrors the pure-Python
        channel's surface."""
        return self.lib.wfn_channel_size(self.ptr)

    def __del__(self):
        try:
            lib, ptr = getattr(self, "lib", None), getattr(self, "ptr", None)
            if lib is not None and ptr:
                # drain remaining handles to avoid leaking references
                # (drain works on poisoned channels too, unlike get)
                handle = ctypes.c_size_t()
                while lib.wfn_channel_drain(ptr, ctypes.byref(handle)):
                    obj = ctypes.cast(handle.value, ctypes.py_object).value
                    ctypes.pythonapi.Py_DecRef(ctypes.py_object(obj))
                lib.wfn_channel_free(ptr)
        except (TypeError, AttributeError):
            pass  # interpreter shutdown: ctypes globals already torn down


def mask_to_rows(mask, take):
    """``np.nonzero(mask)[0]`` of a boolean (or 0/1 byte) mask from one
    branch-free pass with the GIL released, into ``take(len(mask))``'s
    int64 buffer (a pool's: the answer is a view of it).  None
    where this process has not loaded the library (nothing is built for
    a filter's sake) or the mask is not one byte a row in a row: the
    caller then asks numpy."""
    lib = _lib
    if not lib or mask.itemsize != 1 or not mask.flags.c_contiguous:
        return None
    out = take(len(mask))
    return out[:lib.wfn_mask_to_rows(mask.ctypes.data, len(mask),
                                     out.ctypes.data)]


def pane_prereduce(keys, tss, values, pane: int):
    """Fused ingest-plane pane pre-reduction (ingest/coalesce.py):
    collapse a columnar chunk to per-(key, pane) sum partials in one
    native pass.  Returns (keys, pane_starts, sums) arrays or None when
    the library is unavailable / the domain is too sparse for the
    dense-grid kernel (callers fall back to numpy or pass-through)."""
    import numpy as np
    lib = get_lib()
    if lib is None:
        return None
    keys = np.ascontiguousarray(keys, np.int64)
    tss = np.ascontiguousarray(tss, np.int64)
    if values.dtype == np.float32:
        values = np.ascontiguousarray(values)
        fn = lib.wfn_pane_prereduce_f32
        vp = values.ctypes.data_as(ctypes.POINTER(ctypes.c_float))
    else:
        values = np.ascontiguousarray(values, np.float64)
        fn = lib.wfn_pane_prereduce
        vp = values.ctypes.data_as(ctypes.POINTER(ctypes.c_double))
    n = len(keys)
    cap = min(n, 1 << 16)
    while True:
        out_k = np.empty(cap, np.int64)
        out_p = np.empty(cap, np.int64)
        out_s = np.empty(cap, np.float64)
        m = fn(keys.ctypes.data_as(ctypes.POINTER(ctypes.c_longlong)),
               tss.ctypes.data_as(ctypes.POINTER(ctypes.c_longlong)),
               vp, n, pane, cap,
               out_k.ctypes.data_as(ctypes.POINTER(ctypes.c_longlong)),
               out_p.ctypes.data_as(ctypes.POINTER(ctypes.c_longlong)),
               out_s.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))
        if m == -1:
            return None  # sparse domain: dense grid refused
        if m == -2:
            cap = n      # partials cannot outnumber tuples
            continue
        return out_k[:m], out_p[:m], out_s[:m]


def pane_reduce(values, pos, kind: str):
    """Native pane partial reduction; returns None if lib unavailable."""
    import numpy as np
    lib = get_lib()
    if lib is None:
        return None
    values = np.ascontiguousarray(values, np.float64)
    pos = np.ascontiguousarray(pos, np.int64)
    n = len(pos) - 1
    out = np.empty(n, np.float64)
    vp = values.ctypes.data_as(ctypes.POINTER(ctypes.c_double))
    pp = pos.ctypes.data_as(ctypes.POINTER(ctypes.c_longlong))
    op = out.ctypes.data_as(ctypes.POINTER(ctypes.c_double))
    if kind == "sum":
        lib.wfn_pane_sum(vp, pp, n, op)
    elif kind == "max":
        lib.wfn_pane_max(vp, pp, n, float("-inf"), op)
    elif kind == "min":
        lib.wfn_pane_min(vp, pp, n, float("inf"), op)
    else:
        return None
    return out


class NativeRecordPipeline:
    """ctypes wrapper over the native record-at-a-time pipeline engine
    (native/record_pipeline.cpp).

    ``mode="threaded"`` is the reference-architecture baseline (one
    thread per operator stage over SPSC rings -- the FastFlow design,
    SURVEY.md L0); ``mode="fused"`` is the chain-fused fast host path
    (multipipe.hpp:345-390 applied end-to-end) with ``shards``
    key-sharded workers.

    Stages are added in pipeline order with the expression-descriptor
    helpers; the source is either native-synthetic (``set_synth``) or
    Python-fed columnar batches (``set_feed`` + ``feed``/``feed_eos``).
    """

    __slots__ = ("lib", "ptr", "_started", "_waited", "_store")

    FIELDS = {"key": 0, "id": 1, "ts": 2, "value": 3}
    WKINDS = {"sum": 0, "count": 1, "max": 2, "min": 3, "mean": 4}
    _FILTER_OPS = {"mod_eq": 0, "lt": 1, "gt": 2, "le": 3, "ge": 4, "eq": 5}

    def __init__(self, mode: str = "fused", shards: int = 1,
                 store_results: bool = False):
        self.lib = get_lib()
        if self.lib is None:
            raise RuntimeError("native runtime unavailable")
        self.ptr = self.lib.wfn_rp_new(
            {"threaded": 0, "fused": 1}[mode], shards,
            1 if store_results else 0)
        self._started = False
        self._waited = False
        self._store = store_results

    # -- stage construction -------------------------------------------
    def add_filter(self, field: str, op: str, *, m: int = 0, r: int = 0,
                   const: float = 0.0) -> "NativeRecordPipeline":
        """op in mod_eq (keep when field % m == r) | lt|gt|le|ge|eq
        (compare field against const)."""
        self.lib.wfn_rp_add_stage(self.ptr, 1, self.FIELDS[field],
                                  self._FILTER_OPS[op], m, r, 0, 0,
                                  const, 0.0)
        return self

    def add_map_affine(self, scale: float, offset: float = 0.0,
                       square: bool = False) -> "NativeRecordPipeline":
        """value = value*scale + offset (or value^2*scale + offset)."""
        self.lib.wfn_rp_add_stage(self.ptr, 2, 3, 2 if square else 0,
                                  0, 0, 0, 0, scale, offset)
        return self

    def add_map_load(self, field: str, scale: float = 1.0,
                     offset: float = 0.0) -> "NativeRecordPipeline":
        """value = field*scale + offset."""
        self.lib.wfn_rp_add_stage(self.ptr, 2, self.FIELDS[field], 1,
                                  0, 0, 0, 0, scale, offset)
        return self

    def add_accumulator(self) -> "NativeRecordPipeline":
        """Keyed rolling sum (the reference Accumulator)."""
        self.lib.wfn_rp_add_stage(self.ptr, 3, 3, 0, 0, 0, 0, 0, 0.0, 0.0)
        return self

    def add_window(self, win_len: int, slide_len: int, is_tb: bool,
                   kind: str = "sum",
                   renumber: bool = False) -> "NativeRecordPipeline":
        self.lib.wfn_rp_add_stage(self.ptr, 4, 3, 1 if renumber else 0,
                                  win_len, slide_len,
                                  1 if is_tb else 0, self.WKINDS[kind],
                                  0.0, 0.0)
        return self

    # -- source -------------------------------------------------------
    def set_synth(self, n_events: int, n_keys: int, vmod: int = 97,
                  vscale: float = 1.0, voff: float = 0.0) -> None:
        """Native synthetic source: key=i%K, id=ts=i//K,
        value=(i%vmod)*vscale+voff (the bench/test fixture shape)."""
        self.lib.wfn_rp_set_synth(self.ptr, n_events, n_keys, vmod,
                                  vscale, voff)

    def set_feed(self) -> None:
        self.lib.wfn_rp_set_feed(self.ptr)

    def feed(self, keys, ids, ts, vals) -> None:
        import numpy as np
        LL = ctypes.c_longlong
        keys = np.ascontiguousarray(keys, np.int64)
        ids = np.ascontiguousarray(ids, np.int64)
        ts = np.ascontiguousarray(ts, np.int64)
        vals = np.ascontiguousarray(vals, np.float64)
        self.lib.wfn_rp_feed(
            self.ptr, keys.ctypes.data_as(ctypes.POINTER(LL)),
            ids.ctypes.data_as(ctypes.POINTER(LL)),
            ts.ctypes.data_as(ctypes.POINTER(LL)),
            vals.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            len(keys))

    def feed_eos(self) -> None:
        self.lib.wfn_rp_feed_eos(self.ptr)

    # -- execution ----------------------------------------------------
    def start(self) -> None:
        self._started = True
        self.lib.wfn_rp_start(self.ptr)

    def poll(self, max_n: int = 65536):
        """Blocking poll of stored results; returns (keys, wids, ts,
        vals, done). Requires store_results=True."""
        import numpy as np
        LL = ctypes.c_longlong
        keys = np.empty(max_n, np.int64)
        wids = np.empty(max_n, np.int64)
        ts = np.empty(max_n, np.int64)
        vals = np.empty(max_n, np.float64)
        done = ctypes.c_int()
        n = self.lib.wfn_rp_poll(
            self.ptr, max_n, keys.ctypes.data_as(ctypes.POINTER(LL)),
            wids.ctypes.data_as(ctypes.POINTER(LL)),
            ts.ctypes.data_as(ctypes.POINTER(LL)),
            vals.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            ctypes.byref(done))
        return (keys[:n], wids[:n], ts[:n], vals[:n], bool(done.value))

    def wait(self):
        """Join all pipeline threads; returns (n_results, result_sum,
        dropped)."""
        LL = ctypes.c_longlong
        count, dropped = LL(), LL()
        total = ctypes.c_double()
        self.lib.wfn_rp_wait(self.ptr, ctypes.byref(count),
                             ctypes.byref(total), ctypes.byref(dropped))
        self._waited = True
        return count.value, total.value, dropped.value

    def __del__(self):
        lib, ptr = getattr(self, "lib", None), getattr(self, "ptr", None)
        if lib is not None and ptr:
            if self._started and not self._waited:
                # joining requires the feed to be closed; best effort
                try:
                    self.feed_eos()
                except Exception:
                    pass
            lib.wfn_rp_free(ptr)


class NativeWindowEngine:
    """ctypes wrapper over the C++ columnar window engine
    (native/window_engine.cpp).  TB windows on real stamps fire on the
    engine's stream time (docs/RUNTIME.md "When a window fires"); such
    an engine emits a row for every window that holds a tuple of the
    key and evicts a key whose last window has been staged.  ``dense``
    is for callers whose output ids count a key's windows (role PLQ) or
    whose host twin emits the empty ones (graph/native_lowering.py):
    every window from the key's anchor on is emitted and no key is
    evicted.

    The window operator (operators/tpu/win_seq_tpu.py) stages from this
    engine or from its Python twin (operators/tpu/window_store.py)
    through the same calls."""

    __slots__ = ("lib", "ptr", "_stats", "_stats_p", "engine_kind", "tb",
                 "dense", "plq_counters", "key_intern", "key_extern",
                 "_staged", "copy_out_ns", "argmax", "tuples")

    # ``tuples``: a pane keeps its tuples, for a user's window function
    # (docs/RUNTIME.md 5d)
    KINDS = {"sum": 0, "count": 1, "max": 2, "min": 3, "mean": 4,
             "argmax": 5, "tuples": 6}
    # what ``stats()`` returns, in order: nanoseconds creating key
    # states, finding and queueing fired windows, evicting; then
    # ``spans.ENGINE_STATS``, in the slots ``wfn_engine_stats`` fills but
    # ``copy_out_ns``: ``flush`` keeps that clock in an attribute and
    # mirrors it into its slot, which the engine leaves alone, so that
    # ``stats()`` hands it over with the rest
    STATS = ("open_ns", "trigger_ns", "evict_ns",
             *(s.name for s in ENGINE_STATS))
    _COPY_OUT = STATS.index("copy_out_ns")
    # the slots of ``spans.ENGINE_COUNTERS``, in their order
    COUNTED = tuple(map(STATS.index, ENGINE_COUNTERS))

    def __init__(self, win_len: int, slide_len: int, is_tb: bool,
                 delay: int = 0, renumber: bool = False, kind: str = "sum",
                 dense: bool = False):
        self.lib = get_lib()
        if self.lib is None:
            raise RuntimeError("native runtime unavailable")
        self.ptr = self.lib.wfn_engine_new(win_len, slide_len,
                                           1 if is_tb else 0, delay,
                                           1 if renumber else 0,
                                           self.KINDS[kind],
                                           1 if dense else 0)
        self._stats = (ctypes.c_longlong * len(self.STATS))()
        self._stats_p = ctypes.cast(self._stats,
                                    ctypes.POINTER(ctypes.c_longlong))
        self.copy_out_ns = 0
        # the helper engine a flushed buffer needs: count windows sum
        # their per-pane counts; mean windows divide pane-sum totals by
        # pane-count totals (pair program); sum, max and min fold
        # partials through the engine of their own kind
        self.engine_kind = {"count": "sum", "mean": "mean_panes"}.get(kind)
        self.tb, self.dense = is_tb, dense
        self.argmax = kind == "argmax"
        self.tuples = kind == "tuples"
        # where ``flush`` copies a launch's pane partials to: buffers
        # that come back once the launch has read them (by refcount, no
        # release call).  A fresh array as large as a wide launch's (62 MB
        # a column in SABER's SG2) costs its page faults every time: 66 ms
        # on the chip's host against 10 for the copy (PR 34)
        from ..core.tuples import ColumnPool
        self._staged = ColumnPool()
        self.plq_counters: dict = {}
        # non-integral record keys (the reference's templated key types)
        # are interned into a reserved negative int64 range and
        # translated back on emission
        self.key_intern: dict = {}
        self.key_extern: dict = {}

    def stats(self):
        """The engine's churn clock and counters (:data:`STATS`) as a
        ctypes array the engine refills on every call: read it before
        the next one."""
        self.lib.wfn_engine_stats(self.ptr, self._stats_p)
        return self._stats

    def snapshot(self) -> dict:
        """:data:`STATS` by name, through a buffer of its own: for a
        thread that is not the one that feeds the engine."""
        buf = (ctypes.c_longlong * len(self.STATS))()
        self.lib.wfn_engine_stats(self.ptr, buf)
        buf[self._COPY_OUT] = self.copy_out_ns
        return dict(zip(self.STATS, buf))

    def ingest(self, keys, ids, ts, vals, sel=None) -> int:
        """One chunk's columns.  With ``sel`` (the rows of a selected
        batch, ``TupleBatch.selection``) the chunk is ``len(sel)`` rows:
        a column as long as ``sel`` is compact, a longer one is a base
        column whose rows ``sel[j]`` the engine reads in its walks
        (nothing is gathered here; a selection is shorter than its
        base, core/tuples.py).  An ``argmax`` engine's rows are keyed by
        the tuples' own ids: on TB, where ``ids`` are the stamps that
        place them, the ids come in ``ts``'s place, which a TB engine
        reads nowhere else (docs/RUNTIME.md 5c)."""
        import numpy as np
        vals = np.asarray(vals)
        through = 0
        if sel is not None:
            n = len(sel)
            sel = np.ascontiguousarray(sel, np.int64)
            if len(vals) != n and not (
                    vals.dtype.kind == "f" and vals.flags.c_contiguous
                    and vals.itemsize in (4, 8)):
                vals = np.take(vals, sel, axis=0)  # to be converted: its
                #                                    rows alone
            # Engine::Sel's bits: 1 keys, 2 ids, 4 stamps, 8 values; the
            # engine checks the rows against the shortest base column
            n_base = None
            for bit, col in ((1, keys), (2, ids), (4, ts), (8, vals)):
                if len(col) != n:
                    through |= bit
                    if n_base is None or len(col) < n_base:
                        n_base = len(col)
            if not through:
                sel = None      # every column compact already
        keys = np.ascontiguousarray(keys, np.int64)
        ids = np.ascontiguousarray(ids, np.int64)
        ts = np.ascontiguousarray(ts, np.int64)
        # f32 lane: no widening copy; the engine widens per element
        f32 = vals.dtype == np.float32 and vals.flags.c_contiguous
        if not f32:
            vals = np.ascontiguousarray(vals, np.float64)
        lib = self.lib
        if sel is None:
            fn = lib.wfn_engine_ingest_f32 if f32 else lib.wfn_engine_ingest
            return fn(self.ptr, keys.ctypes.data, ids.ctypes.data,
                      ts.ctypes.data, vals.ctypes.data, len(keys))
        fn = lib.wfn_engine_ingest_sel_f32 if f32 \
            else lib.wfn_engine_ingest_sel
        ready = fn(self.ptr, keys.ctypes.data, ids.ctypes.data,
                   ts.ctypes.data, vals.ctypes.data, sel.ctypes.data, n,
                   through, n_base)
        if ready < 0:
            raise IndexError(f"a selection's row lies outside its base "
                             f"columns of {n_base} rows")
        return ready

    # interned ids live below _INTERN_CEIL, far outside any plausible
    # user key, so a result batch can be tested for them vectorized
    _INTERN_BASE = -(1 << 62)
    _INTERN_CEIL = -(1 << 61)

    def intern_key(self, key) -> int:
        iid = self.key_intern.get(key)
        if iid is None:
            iid = self._INTERN_BASE + len(self.key_intern)
            self.key_intern[key] = iid
            self.key_extern[iid] = key
        return iid

    def ingest_record(self, t, val) -> int:
        """One record ``t`` of value ``val`` as a 1-row chunk, so mixed
        record/batch streams share one state store.  ``val`` None is an
        EOS marker, which is nothing to this engine (it fires on
        ``eos()``): it answers 0, so the caller launches nothing.  A TB
        ``argmax`` engine takes the record's id in the stamp's place
        (:meth:`ingest`)."""
        if val is None:
            return 0
        import numpy as np
        key, tid, ts = t.get_control_fields()
        if not isinstance(key, (int, np.integer)):
            key = self.intern_key(key)
        return self.ingest(np.array([key], np.int64),
                           np.array([ts if self.tb else tid], np.int64),
                           np.array([tid if self.argmax and self.tb else ts],
                                    np.int64),
                           np.array([val], np.float64))

    def output_ids(self, keys, gwids):
        """The keys and output ids of one flushed batch's rows, asked
        as the batch is emitted.  A ``dense`` engine numbers a key's
        windows 0, 1, 2, ... in firing order (win_seq.hpp:484 with an
        identity config: role PLQ); interned keys come back as they
        were given, in a list (a key column cannot carry them)."""
        import numpy as np
        ids = gwids
        if self.dense:
            from ..core.tuples import key_groups
            ids = np.empty(len(keys), np.int64)
            order, keys_s, bounds = key_groups(keys)
            for j in range(len(bounds) - 1):
                lo, hi = int(bounds[j]), int(bounds[j + 1])
                key = int(keys_s[lo])
                start = self.plq_counters.get(key, 0)
                run = np.arange(start, start + (hi - lo))
                if order is None:
                    ids[lo:hi] = run
                else:
                    ids[order[lo:hi]] = run
                self.plq_counters[key] = start + (hi - lo)
        if self.key_extern and len(keys) \
                and bool((keys < self._INTERN_CEIL).any()):
            ext = self.key_extern
            keys = [ext.get(k, k) for k in keys.tolist()]
        return keys, ids

    def synth_ingest(self, start: int, n: int, n_keys: int,
                     vmod: int = 97, vscale: float = 1.0,
                     voff: float = 0.0, mask=None, vtab=None) -> int:
        """Fused generate+fold of the declared synthetic law
        (operators/synth.py): events [start, start+n) never materialize
        as host arrays.  ``mask`` (uint8[vmod], optional) drops events
        whose mask[e % vmod] entry is 0 -- the folded form of a
        declared value-predicate Filter; a dropped event neither folds
        nor advances triggering.  ``vtab`` (float64[vmod], optional)
        overrides the affine law with a per-residue value table (the
        sequentially-applied declared map chain).  Returns the
        ready-window count.  An ``argmax`` engine keeps ids, which the
        fused lane does not walk: its slice is materialized, as the
        Python store does (the graph's lowering fuses no filter or map
        into an argmax window).  A ``tuples`` engine keeps its tuples'
        ids too, and is fed so as well."""
        if self.argmax or self.tuples:
            from ..core.tuples import SynthChunk
            b = SynthChunk(start, n, n_keys, vmod, vscale,
                           voff).materialize()
            return self.ingest(b.key, b.id, b.ts, b["value"])
        if mask is None and vtab is None:
            return self.lib.wfn_engine_synth_ingest(
                self.ptr, start, n, n_keys, vmod, vscale, voff)
        import numpy as np
        PD = ctypes.POINTER(ctypes.c_double)
        mp = None
        if mask is not None:
            mask = np.ascontiguousarray(mask, np.uint8)
            assert len(mask) == vmod
            mp = mask.ctypes.data_as(ctypes.POINTER(ctypes.c_ubyte))
        vp = None
        if vtab is not None:
            vtab = np.ascontiguousarray(vtab, np.float64)
            assert len(vtab) == vmod
            vp = vtab.ctypes.data_as(PD)
        return self.lib.wfn_engine_synth_ingest_masked(
            self.ptr, start, n, n_keys, vmod, vscale, voff, mp, vp)

    def ready(self) -> int:
        return self.lib.wfn_engine_ready(self.ptr)

    def ignored(self) -> int:
        """Tuples dropped behind the fired frontier (the acceptance
        rule of win_seq.hpp:417-428)."""
        return self.lib.wfn_engine_ignored(self.ptr)

    def eos(self) -> None:
        self.lib.wfn_engine_eos(self.ptr)

    def flush(self, max_windows: int):
        """Returns (cols, starts, ends, keys, gwids, rts, engine_kind)
        as numpy copies, or None when nothing is ready: ``cols`` holds
        the pane partials under ``value`` (f64) and, for the 'mean'
        kind only, the per-pane tuple counts in the same layout under
        ``count``; a ``tuples`` engine's ``value`` holds the windows' own
        values, each key's span once (docs/RUNTIME.md 5d); ``engine_kind``
        is the helper engine they need."""
        import numpy as np
        LL = ctypes.c_longlong
        PD = ctypes.POINTER(ctypes.c_double)
        PLL = ctypes.POINTER(LL)
        vals_p, n_vals = PD(), LL()
        cnts_p, n_cnts = PD(), LL()
        sp, ep, kp, gp, rp = PLL(), PLL(), PLL(), PLL(), PLL()
        b = self.lib.wfn_engine_flush(
            self.ptr, max_windows, ctypes.byref(vals_p),
            ctypes.byref(n_vals), ctypes.byref(cnts_p),
            ctypes.byref(n_cnts), ctypes.byref(sp), ctypes.byref(ep),
            ctypes.byref(kp), ctypes.byref(gp), ctypes.byref(rp))
        if b == 0:
            return None
        t_staged = time.perf_counter_ns()
        nv = n_vals.value

        def arr(p, n, dt):
            return np.ctypeslib.as_array(p, shape=(n,)).astype(dt, copy=True)

        def partials(p, n):
            out = self._staged.take(n, np.float64)
            if n:
                np.copyto(out, np.ctypeslib.as_array(p, shape=(n,)))
            return out

        cols = {"value": partials(vals_p, nv)}
        if n_cnts.value:
            cols["count"] = partials(cnts_p, n_cnts.value)
        out = (cols, arr(sp, b, np.int64), arr(ep, b, np.int64),
               arr(kp, b, np.int64), arr(gp, b, np.int64),
               arr(rp, b, np.int64), self.engine_kind)
        if self.argmax:
            # beside the pane maxima, the ids at each (docs/RUNTIME.md 5c)
            fp, tp, ip = PLL(), PLL(), PLL()
            n_ties = self.lib.wfn_engine_argmax_staged(
                self.ptr, ctypes.byref(fp), ctypes.byref(tp),
                ctypes.byref(ip))
            out += (ArgmaxStaged(
                out[1], out[2], cols["value"], arr(fp, nv, np.int64),
                arr(tp, nv + 1, np.int64) if n_ties else None,
                arr(ip, n_ties, np.int64) if n_ties else None),)
        # the copies out of the engine, handed over with its clocks
        self.copy_out_ns += time.perf_counter_ns() - t_staged
        self._stats[self._COPY_OUT] = self.copy_out_ns
        return out

    def serialize(self) -> dict:
        """All mutable state, as the checkpoint envelope has always
        named it (``WinSeqTPULogic.state_dict``): the C++ engine's
        versioned binary snapshot under ``native``, the dense output
        counters and the interned keys."""
        n = self.lib.wfn_engine_serialize(self.ptr, None, 0)
        buf = ctypes.create_string_buffer(n)
        got = self.lib.wfn_engine_serialize(self.ptr, buf, n)
        if got != n:
            raise RuntimeError("engine snapshot size changed mid-call")
        state = {"native": buf.raw[:n],
                 "plq_counters": dict(self.plq_counters)}
        if self.key_intern:
            state["key_intern"] = dict(self.key_intern)
        return state

    def deserialize(self, state: dict) -> None:
        """Restore a snapshot into an identically-configured engine."""
        blob = state["native"]
        ok = self.lib.wfn_engine_deserialize(self.ptr, blob, len(blob))
        if not ok:
            raise ValueError("malformed or mismatched engine snapshot")
        self.plq_counters = dict(state.get("plq_counters", {}))
        self.key_intern = dict(state.get("key_intern", {}))
        self.key_extern = {v: k for k, v in self.key_intern.items()}

    def __del__(self):
        lib, ptr = getattr(self, "lib", None), getattr(self, "ptr", None)
        if lib is not None and ptr:
            lib.wfn_engine_free(ptr)
