"""Cost-based placement planner: device vs host lane per window operator.

Runs inside ``PipeGraph.start`` (right after the LEVEL2 fusion pass,
before any replica thread starts).  The VERDICT-round-5 embarrassment
it exists to fix: device placement used to be a *structural* choice --
build a ``WinSeqTPU`` and every launch pays the launch round trip,
whether or not the batch amortizes it, and small-window application
configs ran *faster on the CPU backend than on device*.

The planner decides per engine replica, from **measured** quantities:

* ``rtt_floor_ms`` -- median round trip of one tiny launch, probed
  once per process at the first auto-placed graph start (override:
  ``WINDFLOW_RTT_FLOOR_MS``);
* ``host_rate_tps`` -- the host/native engine's sustained fold rate,
  micro-calibrated once per box (~1M synthetic tuples through
  ``NativeWindowEngine``; numpy fallback) and cached in
  ``host_calibration.json`` beside the compile cache
  (``ops/backend.compile_cache_dir``); override:
  ``WINDFLOW_HOST_RATE_TPS``;
* ``tuples_per_launch`` / ``bytes_per_launch`` -- derived from the
  operator's window parameters (batch_len windows x slide tuples each;
  pane-partial staging bytes), the same arithmetic the engine's
  staging uses.

Decision rule (pure; deterministic; unit-tested): the device lane's
projected rate is ``tuples_per_launch / (rtt_floor + transfer_time)``;
it wins only when it beats the measured host rate by ``DEVICE_MARGIN``
(ties go to the host lane -- its rate was measured, the device's is
projected).  ``.with_placement('device'|'host')`` on the TPU builders
pins a lane and bypasses the model; ``'auto'`` opts in.  Decisions are
recorded on the graph and surfaced in the stats JSON (``Placements``).

The same module owns the *strategy* half of the decision table:
:func:`select_strategy` maps (win_kind, win_len, slide_len, key
cardinality) to the parallelization pattern (win_seq / win_farm /
pane_farm / ffat / key_farm) the reference makes the user pick by hand
(builders_gpu.hpp), and :func:`plan_window_operator` builds the chosen
operator.  docs/PLANNER.md has the full table.
"""
from __future__ import annotations

import json
import math
import os
import socket
import sys
import threading
import time
from dataclasses import dataclass
from typing import List, Optional

from ..ops.backend import compile_cache_dir
from ..ops.host_compute import HOST_KINDS

# device must beat the measured host rate by this factor to win an
# 'auto' placement: the host number is measured on this box, the device
# number is a projection
DEVICE_MARGIN = 1.2

# assumed effective host->device transfer bandwidth when none was
# measured (MB/s); deliberately conservative
DEFAULT_TRANSFER_MBPS = 200.0

# the two per-box calibrations live beside the compile cache: both are
# what this box measured, kept for its next process
_CALIB_PATH = os.path.join(compile_cache_dir(), "host_calibration.json")
_DEV_CALIB_PATH = os.path.join(compile_cache_dir(),
                               "device_calibration.json")

_probe_lock = threading.Lock()
_rtt_floor_ms: Optional[float] = None
_host_rate_tps: Optional[float] = None
_device_compute_ms: Optional[float] = None


# ---------------------------------------------------------------------------
# measured inputs
# ---------------------------------------------------------------------------

def rtt_floor_ms() -> float:
    """Measured device round-trip floor (ms), probed once per process:
    the latency any single launch pays on this backend.  A backend
    that cannot run the probe is an error, not a nominal floor."""
    global _rtt_floor_ms
    env = os.environ.get("WINDFLOW_RTT_FLOOR_MS")
    if env:
        try:
            return float(env)
        except ValueError:
            pass  # malformed override: fall back to the probe
    with _probe_lock:
        if _rtt_floor_ms is not None:
            return _rtt_floor_ms
        import numpy as np
        from ..ops.backend import jax_modules
        jax, jnp = jax_modules()
        f = jax.jit(lambda v: jnp.cumsum(v))
        v = np.zeros(2048, np.float32)
        np.asarray(f(v))  # compile outside the timed reps
        lats = []
        for _ in range(5):
            t0 = time.perf_counter()
            np.asarray(f(v))
            lats.append((time.perf_counter() - t0) * 1e3)
        lats.sort()
        _rtt_floor_ms = max(0.01, lats[len(lats) // 2])
        return _rtt_floor_ms


def _calibrate_host_rate() -> float:
    """Sustained host-engine fold rate (tuples/s) over ~1M synthetic
    tuples -- the native columnar engine when built, else a numpy
    cumsum proxy for the pure-Python plane."""
    import numpy as np
    n = 1 << 20
    try:
        from ..runtime.native import NativeWindowEngine, native_available
        if native_available():
            eng = NativeWindowEngine(4096, 2048, True, kind="sum")
            t0 = time.perf_counter()
            eng.synth_ingest(0, n, 64)
            eng.eos()
            while eng.ready():
                eng.flush(1 << 14)
            return n / max(1e-9, time.perf_counter() - t0)
    except Exception:
        pass
    vals = np.random.default_rng(0).random(n)
    t0 = time.perf_counter()
    np.cumsum(vals)
    np.add.reduceat(vals, np.arange(0, n, 2048))
    return n / max(1e-9, time.perf_counter() - t0)


def host_rate_tps() -> float:
    """Host-engine sustained rate, cached per box in
    ``host_calibration.json`` beside the compile cache (keyed by
    hostname + core count, so a checkout moved between boxes
    re-calibrates)."""
    global _host_rate_tps
    env = os.environ.get("WINDFLOW_HOST_RATE_TPS")
    if env:
        try:
            return float(env)
        except ValueError:
            pass  # malformed override: fall back to the calibration
    with _probe_lock:
        if _host_rate_tps is not None:
            return _host_rate_tps
        key = f"{socket.gethostname()}/{os.cpu_count()}"
        try:
            with open(_CALIB_PATH) as f:
                cached = json.load(f)
            if cached.get("box") == key:
                _host_rate_tps = float(cached["host_rate_tps"])
                return _host_rate_tps
        except (OSError, ValueError, KeyError):
            pass
        _host_rate_tps = _calibrate_host_rate()
        try:
            os.makedirs(os.path.dirname(_CALIB_PATH), exist_ok=True)
            with open(_CALIB_PATH, "w") as f:
                json.dump({"box": key,
                           "host_rate_tps": round(_host_rate_tps, 1),
                           "calibrated_at": time.time()}, f, indent=1)
        except OSError:
            pass  # read-only checkout: keep the in-process cache
        return _host_rate_tps


def device_compute_ms_per_launch() -> float:
    """Measured on-device compute per launch (ms), from a prior
    attribution capture cached per box -- the PR 6 MEASURED note's
    exact miss: the original model treated on-device compute as FREE
    (false on the CPU backend, where the "device" program shares the
    host's cores), so boxes on the CPU backend kept resolving 'device'
    against the evidence.  Sources, in priority order: the
    ``WINDFLOW_DEVICE_COMPUTE_MS`` env override, the in-process value
    the re-planner recorded this run, the per-box cache file
    (``device_calibration.json``, written alongside
    host_calibration.json whenever a device lane's attribution is
    measured).  0.0 when never measured -- the original free-compute
    projection, unchanged."""
    env = os.environ.get("WINDFLOW_DEVICE_COMPUTE_MS")
    if env:
        try:
            return max(0.0, float(env))
        except ValueError:
            pass  # malformed override: fall back to the cache
    global _device_compute_ms
    with _probe_lock:
        if _device_compute_ms is not None:
            return _device_compute_ms
        key = f"{socket.gethostname()}/{os.cpu_count()}"
        try:
            with open(_DEV_CALIB_PATH) as f:
                cached = json.load(f)
            if cached.get("box") == key:
                # cache the file value in-process (the EWMA of any
                # later measurement folds onto it) so the monitor-
                # cadence callers never re-read the file
                _device_compute_ms = max(
                    0.0, float(cached["device_compute_ms"]))
                return _device_compute_ms
        except (OSError, ValueError, KeyError, TypeError):
            pass
        return 0.0


def record_device_compute(ms_per_launch: float,
                          persist: bool = True) -> None:
    """Feed a measured device-compute figure back into the cost model
    (called by the online re-planner when it attributes a device
    lane's launches).  EWMA-folded into the in-process value; with
    ``persist`` also mirrored to the per-box cache so the NEXT
    process's start-time planner already projects with evidence (the
    re-planner records per tick with persist=False and flushes once
    at stop)."""
    global _device_compute_ms
    ms = max(0.0, float(ms_per_launch))
    with _probe_lock:
        if _device_compute_ms is None:
            _device_compute_ms = ms
        else:
            _device_compute_ms += 0.25 * (ms - _device_compute_ms)
    if persist:
        flush_device_calibration()


def flush_device_calibration() -> None:
    """Write the in-process device-compute EWMA to the per-box cache
    file (one durable write, best-effort)."""
    with _probe_lock:
        value = _device_compute_ms
    if value is None:
        return
    try:
        os.makedirs(os.path.dirname(_DEV_CALIB_PATH), exist_ok=True)
        with open(_DEV_CALIB_PATH, "w") as f:
            json.dump({"box": f"{socket.gethostname()}/{os.cpu_count()}",
                       "device_compute_ms": round(value, 4),
                       "calibrated_at": time.time()}, f, indent=1)
    except OSError:
        pass  # read-only checkout: keep the in-process value


# ---------------------------------------------------------------------------
# the cost model (pure functions of measured inputs)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class PlacementInputs:
    """Everything the placement decision reads (so tests can pin it)."""

    rtt_floor_ms: float
    host_rate_tps: float
    tuples_per_launch: float
    bytes_per_launch: float
    transfer_mbps: float = DEFAULT_TRANSFER_MBPS
    # measured on-device compute per launch (ms); 0.0 = never measured
    # (the legacy free-compute projection)
    device_compute_ms: float = 0.0


def device_rate_tps(inp: PlacementInputs) -> float:
    """Projected device-lane throughput: one launch amortizes
    ``tuples_per_launch`` ingested tuples over (RTT floor + transfer
    time + measured on-device compute).  Pipelining (inflight_depth)
    overlaps launches, but the floor still bounds the *per-launch*
    cost, so the projection is deliberately un-pipelined --
    conservative toward the host lane."""
    transfer_ms = inp.bytes_per_launch / (inp.transfer_mbps * 1e3)
    period_ms = inp.rtt_floor_ms + transfer_ms + inp.device_compute_ms
    return inp.tuples_per_launch / max(1e-9, period_ms / 1e3)


def decide_placement(inp: PlacementInputs) -> dict:
    """'device' | 'host' plus the projections that led there.
    Deterministic: same inputs, same decision."""
    dev = device_rate_tps(inp)
    host = inp.host_rate_tps
    placement = "device" if dev > host * DEVICE_MARGIN else "host"
    out = {
        "placement": placement,
        "device_rate_tps": round(dev, 1),
        "host_rate_tps": round(host, 1),
        "rtt_floor_ms": round(inp.rtt_floor_ms, 3),
        "tuples_per_launch": round(inp.tuples_per_launch, 1),
        "bytes_per_launch": round(inp.bytes_per_launch, 1),
    }
    if inp.device_compute_ms > 0:
        out["device_compute_ms"] = round(inp.device_compute_ms, 3)
    return out


def launch_profile(logic) -> tuple:
    """(tuples_per_launch, bytes_per_launch) from window parameters:
    a full batch of ``batch_len`` windows advances the stream by
    ``slide_len`` tuples each; staging ships pane partials (f32) plus
    packed extents, results come back one f32 per window.

    For TB windows ``slide_len`` is in *timestamp units*, so this
    assumes dense timestamps (~one tuple per unit, what every synth /
    bench source produces).  A sparse timestamped stream carries fewer
    tuples per launch than projected, flattering the device lane --
    pin ``.with_placement('host')`` or set ``WINDFLOW_RTT_FLOOR_MS``
    for such feeds (docs/PLANNER.md, "cost-model assumptions")."""
    b = max(1, int(logic.batch_len))
    tuples = float(b) * max(1, int(logic.slide_len))
    pane = max(1, math.gcd(int(logic.win_len), int(logic.slide_len)))
    panes_per_window = max(1, int(logic.win_len) // pane)
    # staged flat buffer: ~one new pane per fired window plus the
    # window-spanning carry; extents 2 x int32; results f32
    staged = b + panes_per_window
    bytes_ = 4.0 * staged + 8.0 * b + 4.0 * b
    return tuples, bytes_


# ---------------------------------------------------------------------------
# graph pass
# ---------------------------------------------------------------------------

def _what(kind) -> str:
    """A combine as a placement's reason names it."""
    if isinstance(kind, str):
        return kind
    return "user window function" if callable(kind) else "custom combine"


def plan_graph(graph) -> List[dict]:
    """Resolve every window engine replica's placement.  Pinned lanes
    ('device'/'host') pass through; 'auto' consults the cost model.
    Each resolved engine gets the measured RTT floor (feeding the
    adaptive batch resize) and -- tracing or not -- a stats record, so
    per-launch device timing is always observable for placed
    operators.  Returns the recorded decision list (also stored on
    ``graph.placements`` and in the stats JSON)."""
    from ..operators.tpu.ffat_resident import WinSeqFFATResidentLogic
    from ..operators.tpu.win_seq_tpu import WinSeqTPULogic
    from ..runtime.node import FusedLogic

    decisions: List[dict] = []
    placed: List[tuple] = []
    seen: set = set()
    replica_ids: dict = {}  # per-operator-name counter for stats keys

    # tenant-aware device placement (scheduler/devices.py): under a
    # device-scheduling Server, every lane resolved to the device
    # acquires a lease from the worker's registry.  Leases are
    # grant-and-record (the graph still runs), but the grant's
    # contention bit is annotated into the decision and the arbiter
    # reads the registry to demote a low-priority co-lessee when a
    # higher-priority tenant breaches on the contended chip.
    dev_leases = getattr(graph, "device_leases", None)
    lease_tenant = getattr(graph, "tenant_name", None) or graph.name
    lease_prio = getattr(graph, "tenant_priority", 0)

    def _lease(entry: dict, name: str, resident: bool) -> None:
        if dev_leases is None or entry["placement"] != "device":
            return
        entry["lease"] = dev_leases.acquire(
            lease_tenant, name, priority=lease_prio, resident=resident)

    for node in graph._all_nodes():
        if isinstance(node.logic, FusedLogic):
            pairs = [(seg.name, seg.logic, seg) for seg in
                     node.logic.segments]
        else:
            pairs = [(node.name, node.logic, node)]
        for name, logic, holder in pairs:
            if id(logic) in seen:
                continue
            if isinstance(logic, WinSeqFFATResidentLogic):
                # the resident FFAT engine is structurally
                # device-bound; it is recorded (and given a stats
                # record, so per-launch device timing + the resident
                # byte gauges are observable untraced) but never
                # lane-planned
                seen.add(id(logic))
                rid = replica_ids.get(name, 0)
                replica_ids[name] = rid + 1
                if holder.stats is None:
                    holder.stats = graph.stats.register(name, str(rid))
                entry = {"placement": "device",
                         "reason": "resident ffat: device only",
                         "resident": True, "operator": name}
                _lease(entry, name, resident=True)
                decisions.append(entry)
                continue
            if not isinstance(logic, WinSeqTPULogic):
                continue
            seen.add(id(logic))
            pinned = getattr(logic, "placement", "device")
            kind = logic.engine.kind
            device_only = not isinstance(kind, str) or kind not in HOST_KINDS
            if pinned == "host" and device_only:
                # custom / FFAT combines and argmax have no host program:
                # never run somewhere other than where they were asked to
                raise ValueError(
                    f"{name}: {_what(kind)} has no host program and cannot "
                    "be placed on the host lane")
            if pinned == "auto":
                if device_only:
                    entry = {"placement": "device",
                             "reason": f"{_what(kind)}: device only"}
                else:
                    tuples, bytes_ = launch_profile(logic)
                    entry = decide_placement(PlacementInputs(
                        rtt_floor_ms=rtt_floor_ms(),
                        host_rate_tps=host_rate_tps(),
                        tuples_per_launch=tuples,
                        bytes_per_launch=bytes_,
                        device_compute_ms=device_compute_ms_per_launch()))
                logic.apply_placement(entry["placement"],
                                      rtt_floor_ms=entry.get(
                                          "rtt_floor_ms"))
            else:
                entry = {"placement": pinned, "reason": "pinned"}
                logic.apply_placement(pinned)
            rid = replica_ids.get(name, 0)
            replica_ids[name] = rid + 1
            if holder.stats is None:
                holder.stats = graph.stats.register(name, str(rid))
            entry["operator"] = name
            if callable(kind):
                # a user's window function: where it runs and which store
                # stages its windows, said where the run is watched
                entry["store"] = ("native" if logic._native is not None
                                  else "python")
                print(f"[windflow] {name}: the user's window function "
                      f"runs on the device ({entry['reason']}), its windows "
                      f"staged by the {entry['store']} store",
                      file=sys.stderr)
            # the lease's Resident bit marks NON-demotable lanes: a
            # custom/FFAT combine has no host program, so the arbiter
            # must never pick it for a device->host demotion.
            _lease(entry, name,
                   resident=not isinstance(
                       getattr(logic.engine, "kind", None), str))
            decisions.append(entry)
            placed.append((name, logic, entry))
    graph.placements = decisions
    # live registry for the online re-planner (graph/replanner.py):
    # decision entries paired with their engine objects
    graph.placed_engines = placed
    graph.stats.set_placements(decisions)
    return decisions


# ---------------------------------------------------------------------------
# strategy selection (the decision table of docs/PLANNER.md)
# ---------------------------------------------------------------------------

# pane length below which pane decomposition stops paying (matches
# ingest/wiring.MIN_PREREDUCE_PANE)
MIN_PANE = 16
# window/slide overlap ratio from which an incremental FlatFAT tree
# beats per-window recomputation when panes are too short to pre-reduce
FFAT_OVERLAP = 8
# key cardinality from which key-sharded farms beat a single engine
KEY_FARM_MIN_KEYS = 2

_PANE_KINDS = ("sum", "count", "max", "min")
_FFAT_KINDS = ("sum", "max", "min")


def select_strategy(win_kind, win_len: int, slide_len: int,
                    key_cardinality: int = 1) -> str:
    """Deterministic parallelization-strategy choice from window
    parameters (the decision table in docs/PLANNER.md):

    1. associative builtin + long panes + a genuine slide (slide <
       win; tumbling windows share no panes) -> 'pane_farm' (ship
       partials, not tuples: transfer shrinks by the pane length);
    2. heavy overlap (win/slide >= 8) on a semigroup combine whose
       panes are too short to pre-reduce -> 'ffat' (incremental tree
       amortizes the recompute the overlap would otherwise multiply);
    3. many keys -> 'key_farm' (key-sharded engines; the emitter hash
       is the parallelism);
    4. single key, long windows -> 'win_farm' (round-robin window
       parallelism is the only axis left);
    5. otherwise -> 'win_seq' (one engine; batching alone).
    """
    if win_len <= 0 or slide_len <= 0:
        raise ValueError("win_len and slide_len must be > 0")
    pane = math.gcd(win_len, slide_len)
    builtin = isinstance(win_kind, str)
    # pane decomposition needs a genuine slide (PaneFarm rejects
    # tumbling shapes): tumbling windows have no pane sharing to win
    if builtin and win_kind in _PANE_KINDS and pane >= MIN_PANE \
            and slide_len < win_len:
        return "pane_farm"
    if builtin and win_kind in _FFAT_KINDS and pane < MIN_PANE \
            and win_len // slide_len >= FFAT_OVERLAP:
        return "ffat"
    if key_cardinality >= KEY_FARM_MIN_KEYS:
        return "key_farm"
    if win_len >= (1 << 16):
        return "win_farm"
    return "win_seq"


def plan_window_operator(win_kind, win_len: int, slide_len: int,
                         win_type, key_cardinality: int = 1,
                         parallelism: int = 2, **kwargs):
    """Build the operator :func:`select_strategy` picks (the planner's
    builder-level entry point; every knob in ``kwargs`` reaches the
    chosen operator's constructor)."""
    from ..operators.tpu.farms_tpu import (KeyFarmTPU, PaneFarmTPU,
                                           WinFarmTPU, WinSeqFFATTPU)
    from ..operators.tpu.win_seq_tpu import WinSeqTPU

    strategy = select_strategy(win_kind, win_len, slide_len,
                               key_cardinality)
    if strategy == "pane_farm":
        return PaneFarmTPU(win_kind, win_kind, win_len, slide_len,
                           win_type, **kwargs)
    if strategy == "ffat":
        # the FFAT tree is device-pinned (no host twin of the
        # incremental combine): reject lane knobs loudly, like the
        # builders' _check_placement_supported, instead of a
        # data-dependent TypeError from the constructor
        if kwargs.pop("placement", "device") != "device" \
                or kwargs.pop("adaptive_batch", False):
            raise ValueError(
                "strategy 'ffat' is device-pinned: placement/"
                "adaptive_batch are not supported for this window shape")
        lift = (lambda t: t.value)
        return WinSeqFFATTPU(lift, win_kind, win_len, slide_len,
                             win_type, **kwargs)
    if strategy == "key_farm":
        return KeyFarmTPU(win_kind, win_len, slide_len, win_type,
                          parallelism=parallelism, **kwargs)
    if strategy == "win_farm":
        return WinFarmTPU(win_kind, win_len, slide_len, win_type,
                          parallelism=parallelism, **kwargs)
    return WinSeqTPU(win_kind, win_len, slide_len, win_type, **kwargs)
