"""Per-replica statistics records and JSON aggregation.

Re-design of reference ``wf/stats_record.hpp`` (:45-165) and the
JSON aggregation spread across operators (source.hpp:399-427) and
PipeGraph (pipegraph.hpp:791-851).  Counters kept per replica, updated
inline by the runtime node loop, aggregated into the same JSON shape
the reference ships to its dashboard; device-era metrics replace the
CUDA ones (kernels launched / bytes H2D/D2H -> program launches /
bytes staged to device, stats_record.hpp:77-79).
"""
from __future__ import annotations

import json
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from ..telemetry import spans
from ..telemetry.histogram import LogHistogram

# Stats-JSON schema version (the top-level ``Schema_version`` field).
# 3 = the diagnosis-plane layout (adds Topology / Diagnosis / History /
# optional Flight on top of the PR 7 telemetry and PR 9 audit blocks).
# 4 = adds the optional Durability block (epoch coordinator gauges).
# 5 = adds the optional Worker id + Wire block (distributed runtime's
# per-edge wire delivery books; distributed/observe.py merges them).
# 6 = adds the optional Slo block (burn-rate tracker gauges,
# slo/plane.py) and the Pool block (ColumnPool arena occupancy).
# 7 = adds the optional Tenant block (serving plane identity: name,
# state, priority/weight, live credit lease, arbitration count --
# serving/server.py publishes it per tenant graph).
# 8 = the Durability block gains Delta / Last_commit_bytes (delta
# snapshot sizing) and the optional Replica_restarts counter
# (supervised self-healing, durability/supervision.py).
# 9 = Skew.Census rows may carry tiered keyed-state extras (per-tier
# "tiers" key/byte splits plus spills / spill_bytes / promotions /
# demotions / sheds counters -- state/tiers.py census()) and
# Skew.Hot_keys entries may name each hot key's tier ("tiers").
# 10 = replica records may carry event-time plane gauges
# (eventtime/; docs/EVENTTIME.md): Late_tuples (allowed-lateness
# misses quarantined to dead letters), Sessions_open (live gap
# sessions) and Join_state_keys (keys with buffered join state) --
# emitted only when nonzero.
# 11 = adds the optional Scheduler block (global-scheduler plane,
# scheduler/: tenant->worker placement, fair-share leases, device
# leases -- serving/server.py publishes it per tenant graph when the
# plane is on) and replica records may carry Sched_wait_s (seconds a
# consume loop spent gated by the fair-share lease; emitted only when
# nonzero).
# 12 = adds the optional Spans block (the span layer,
# telemetry/spans.py: seconds and shares busy / idle / blocked per
# operator replica and thread, launch stages per window operator);
# the replica records' roofline fraction is gone (it divided bytes by a
# host wall under a device's name).
# 13 = Spans.Launches rows gain Collected (how many launches were
# collected ready / waited / forced / flushed) and their Slowest row
# its own Collected.
# 14-26 = Spans.Operators rows of a window operator on the native lane
# gain Counters (telemetry/spans.ENGINE_STATS, SELECTION_COUNTERS) and
# their Phases the engine's open / trigger / evict; Spans.Launches rows
# and summaries gain the counts of spans.LAUNCH_COUNTS and the three
# parts of dispatch (pack, call, handoff; None where the lane takes no
# stamps for them).  From 26 on, a name added to a by-name block
# (Counters, a Launches row or summary) leaves the version as it is:
# readers look names up.
# Readers (doctor CLI, dashboard /explain, tests) must tolerate MISSING
# blocks rather than dispatch on this number: older dumps carry no
# version field at all, and every block is optional by contract.
SCHEMA_VERSION = 26


@dataclass
class StatsRecord:
    """Per-replica counters (stats_record.hpp:45-165)."""

    operator_name: str = ""
    replica_id: str = "0"
    start_time: float = field(default_factory=time.time)
    terminated: bool = False
    inputs_received: int = 0
    bytes_received: int = 0
    outputs_sent: int = 0
    bytes_sent: int = 0
    inputs_ignored: int = 0
    # tuples whose svc raised under a skip/dead_letter error policy
    # (resilience/policies.py); the replica stayed alive
    svc_failures: int = 0
    # EWMA service times (microseconds), updated inline like
    # win_seq.hpp:499-509.  Since the batched-stats amortization
    # (graph compile pass PR) observations are SAMPLED -- stride 1 for
    # the first 64, then 1/16 (or once per get_many batch) -- so the
    # mean runs over ``samples``, not ``inputs_received``; tracing no
    # longer costs a perf_counter pair per tuple
    service_time_us: float = 0.0
    eff_service_time_us: float = 0.0
    samples: int = 0
    # device metrics (TPU analogues of stats_record.hpp:77-79)
    num_launches: int = 0
    bytes_to_device: int = 0
    bytes_from_device: int = 0
    # per-launch timing (docs/PLANNER.md): a HOST WALL, not device
    # time -- from the dispatcher picking a launch up to its result on
    # the host (dispatch + ready wait + block of the span layer's launch
    # record, telemetry/spans.py), summed over launches.  JAX gives the
    # host no device timestamp outside a profiler session.  With the
    # launch RTT floor this splits a launch's fixed cost from its
    # compute: est. fixed = launches x floor, est. compute = the rest.
    device_time_ms: float = 0.0
    # resident gauge (operators/tpu/ffat_resident.py): bytes of per-key
    # window state living in device memory ACROSS launches (the FFAT
    # forest).  Separate from the shipped byte counters above, which on
    # that lane count only NEW bytes per launch (events in + results
    # out).
    device_state_bytes: int = 0
    # ingest-plane metrics (ingest/; zero outside ingest sources):
    # admission-shed tuples, live credit level, tuples parked in outlet
    # channels, the controller's current coalesced batch size and its
    # recent (time, batch_size) decision trace
    tuples_shed: int = 0
    credits_available: int = 0
    ingest_queue_depth: int = 0
    ingest_batch_size: int = 0
    # DEFENSIVE bound only: the ingest reporter REBINDS this attribute
    # with the controller's <=32-entry trace tail each report
    # (ingest/sources.py), and the real rolling bound on long-running
    # sources lives in MicrobatchController.trace; the deque caps any
    # direct appender so the record can never become a slow leak
    controller_trace: deque = field(
        default_factory=lambda: deque(maxlen=64))
    # standalone gauges refreshed by PipeGraph.refresh_gauges before
    # every report: tuples parked in this replica's inbound channel and
    # cumulative seconds its source gate spent blocked on credits.
    # Useful to operators on their own and the raw inputs of the
    # elastic signal plane (elastic/signals.py)
    queue_depth: int = 0
    credit_wait_s: float = 0.0
    # cumulative seconds this replica's consume loop spent blocked in
    # the worker's fair-share gate (scheduler/leases.py) -- lets the
    # diagnosis plane name SCHEDULING, not queueing or credits, as the
    # bottleneck.  Zero (and not emitted) when the plane is off.
    sched_wait_s: float = 0.0
    # peak inbound-channel depth, measured by both channel planes since
    # PR 1 (runtime/queues.py:73 / native.py:209) and exported here
    queue_high_watermark: int = 0
    # audit plane (audit/progress.py): the replica's low-watermark
    # frontier (per-source position units) and how long it has been
    # held back while work was pending
    frontier: float = 0.0
    frontier_lag_ms: float = 0.0
    # event-time plane gauges (eventtime/; docs/EVENTTIME.md), written
    # inline by the event-time logics: tuples behind the allowed-
    # lateness horizon (quarantined, never silently dropped), live gap
    # sessions, and keys holding buffered join state
    late_tuples: int = 0
    sessions_open: int = 0
    join_state_keys: int = 0
    # telemetry plane (telemetry/; docs/OBSERVABILITY.md): per-replica
    # single-writer log-bucketed latency histograms, merged across
    # replicas at report time.  ``service`` is fed by the sampled
    # observe() path below; ``residency`` and ``e2e`` by the trace
    # stamping in the runtime node loop (e2e on sink replicas only,
    # created lazily at the first trace closure)
    service_hist: Optional[LogHistogram] = None
    residency_hist: Optional[LogHistogram] = None
    e2e_hist: Optional[LogHistogram] = None

    def ensure_hists(self) -> None:
        """Create the service/residency histograms (idempotent);
        called when the graph's telemetry plane is enabled."""
        if self.service_hist is None:
            self.service_hist = LogHistogram()
        if self.residency_hist is None:
            self.residency_hist = LogHistogram()

    def observe(self, elapsed_us: float) -> None:
        self.samples += 1
        self.service_time_us += \
            (elapsed_us - self.service_time_us) / self.samples
        h = self.service_hist
        if h is not None:
            h.observe(elapsed_us)

    def set_terminated(self) -> None:
        self.terminated = True

    def to_dict(self) -> dict:
        d = {
            "Replica_id": self.replica_id,
            "Starting_time": self.start_time,
            "Terminated": self.terminated,
            "Inputs_received": self.inputs_received,
            "Bytes_received": self.bytes_received,
            "Outputs_sent": self.outputs_sent,
            "Bytes_sent": self.bytes_sent,
            "Inputs_ignored": self.inputs_ignored,
            "Svc_failures": self.svc_failures,
            "Shed_tuples": self.tuples_shed,
            "Service_time_usec": round(self.service_time_us, 3),
            "Eff_Service_time_usec": round(self.eff_service_time_us, 3),
            "Device_launches": self.num_launches,
            "Bytes_to_device": self.bytes_to_device,
            "Bytes_from_device": self.bytes_from_device,
            "Device_time_ms": round(self.device_time_ms, 3),
            "Queue_depth": self.queue_depth,
            "Queue_high_watermark": self.queue_high_watermark,
            "Credit_wait_s": round(self.credit_wait_s, 3),
            "Frontier": round(self.frontier, 1),
            "Frontier_lag_ms": round(self.frontier_lag_ms, 1),
        }
        if self.sched_wait_s:
            # fair-share gate wait (scheduler/leases.py): nonzero only
            # when co-resident tenants actually contended
            d["Sched_wait_s"] = round(self.sched_wait_s, 3)
        if self.device_state_bytes:
            d["Device_state_bytes_resident"] = self.device_state_bytes
        # event-time plane gauges: nonzero only on eventtime/ replicas
        if self.late_tuples:
            d["Late_tuples"] = self.late_tuples
        if self.sessions_open:
            d["Sessions_open"] = self.sessions_open
        if self.join_state_keys:
            d["Join_state_keys"] = self.join_state_keys
        if self.num_launches:
            # per-launch derivations (host wall and bytes a launch)
            d["Device_ms_per_launch"] = round(
                self.device_time_ms / self.num_launches, 3)
            d["Device_bytes_per_launch"] = int(
                (self.bytes_to_device + self.bytes_from_device)
                / self.num_launches)
        if self.ingest_batch_size:     # ingest source replicas only
            d["Ingest_credits"] = self.credits_available
            d["Ingest_queue_depth"] = self.ingest_queue_depth
            d["Ingest_batch_size"] = self.ingest_batch_size
            d["Controller_batch_trace"] = [
                [round(t, 3), b]
                for t, b in list(self.controller_trace)[-32:]]
        if self.service_hist is not None:
            lat = {"service": self.service_hist.to_dict(),
                   "residency": self.residency_hist.to_dict()}
            if self.e2e_hist is not None:
                lat["e2e"] = self.e2e_hist.to_dict()
            d["Latency"] = lat
        return d


def get_mem_usage_kb() -> int:
    """Process RSS in KiB (monitoring.hpp:49-68 reads /proc/self/status)."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1])
    except OSError:
        pass
    return 0


class GraphStats:
    """Aggregates per-operator replica records into the dashboard JSON
    (pipegraph.hpp:791-851 generate_JSONStats)."""

    def __init__(self, graph_name: str):
        self.graph_name = graph_name
        self.lock = threading.Lock()
        self.records: Dict[str, List[StatsRecord]] = {}
        # elastic scaling plane (elastic/): records of retired replicas
        # stay (terminated, history), so the LIVE parallelism of a
        # rescaled operator is an explicit override; plus the rescale
        # event log surfaced in the JSON
        self.current_parallelism: Dict[str, int] = {}
        self.rescale_events: List[dict] = []
        # placement planner decisions (graph/planner.py): one entry per
        # window engine replica, recorded at PipeGraph.start
        self.placements: List[dict] = []
        # telemetry plane (telemetry/; docs/OBSERVABILITY.md): once
        # enabled, every record (existing and future -- rescale-created
        # replicas register through register()) carries latency
        # histograms; closed traces land in the bounded recent-record
        # ring and, when a sink replica has no record, in the graph-
        # level e2e fallback histogram
        self.histograms = False
        self.e2e_extra: Optional[LogHistogram] = None
        self.trace_records: deque = deque(maxlen=16)
        # distributed plane: producer-side PARTIAL records of traces
        # that left this worker over a wire edge (the consumer closes
        # them; the merge stitches by id).  A separate ring so a busy
        # outbound edge can never evict this worker's own closed
        # records from the bounded ring above.
        self.trace_partials: deque = deque(maxlen=16)
        # audit plane (audit/; docs/OBSERVABILITY.md): the latest
        # Conservation and Skew blocks, published by the GraphAuditor
        # after every pass (and after the wait_end final check)
        self.audit_conservation: Optional[dict] = None
        self.audit_skew: Optional[dict] = None
        # diagnosis plane (diagnosis/; docs/OBSERVABILITY.md): the
        # operator-level topology (set once at start), and the latest
        # Diagnosis / History blocks published per tick
        self.topology: Optional[List[List[str]]] = None
        self.diagnosis: Optional[dict] = None
        self.history: Optional[dict] = None
        # durability plane (durability/; docs/RESILIENCE.md): the
        # latest epoch-coordinator gauges (committed epoch, lag,
        # commit wall time, stall flag), published per commit/tick
        self.durability: Optional[dict] = None
        # distributed runtime plane (distributed/; docs/DISTRIBUTED.md):
        # this process's worker id (None = single-process graph) and
        # the latest per-edge wire delivery books, refreshed per report
        self.worker: Optional[int] = None
        self.wire: Optional[dict] = None
        # SLO plane (slo/; docs/OBSERVABILITY.md "SLO plane"): the
        # burn-rate tracker's latest gauges, published per diagnosis
        # tick; and the ColumnPool arena occupancy gauges (memory-
        # pressure evidence for the SLO/doctor surfaces)
        self.slo: Optional[dict] = None
        self.pool: Optional[dict] = None
        # serving plane (serving/; docs/SERVING.md): this graph's
        # tenant identity under a multi-tenant Server -- name, state,
        # priority/weight standing, live credit lease, arbitration
        # count; None outside a served run
        self.tenant: Optional[dict] = None
        # global-scheduler plane (scheduler/; docs/SERVING.md "Global
        # scheduler"): which worker hosts this tenant, its fair-share
        # weight, its device leases; None when the plane is off
        self.scheduler: Optional[dict] = None
        # span layer (telemetry/spans.py): this graph's entry in the
        # span registry, set at PipeGraph.start
        self.span_graph = None

    def register(self, operator_name: str, replica_id: str) -> StatsRecord:
        rec = StatsRecord(operator_name, replica_id)
        with self.lock:
            if self.histograms:
                rec.ensure_hists()
            self.records.setdefault(operator_name, []).append(rec)
        return rec

    def enable_histograms(self) -> None:
        """Turn on the latency-histogram surface: backfills every
        already-registered record and marks future registrations."""
        with self.lock:
            self.histograms = True
            if self.e2e_extra is None:
                self.e2e_extra = LogHistogram()
            for replicas in self.records.values():
                for r in replicas:
                    r.ensure_hists()

    def add_trace_record(self, rec) -> None:
        """Append one closed end-to-end trace as a live ``(TraceContext,
        t_end)`` pair (deque append: no lock).  Serialization happens at
        report time so hop stamps that land just after closure -- fused
        upstream segments unwind outward through the closing sink --
        still make the record."""
        self.trace_records.append(rec)

    def add_trace_partial(self, rec) -> None:
        """Append one producer-side partial trace view (same live
        ``(view, t)`` contract as :meth:`add_trace_record`, separate
        bounded ring)."""
        self.trace_partials.append(rec)

    def set_parallelism(self, operator_name: str, n: int) -> None:
        with self.lock:
            self.current_parallelism[operator_name] = n

    def record_rescale(self, event) -> None:
        """Append a completed RescaleEvent (elastic/rescale.py)."""
        with self.lock:
            self.rescale_events.append(event.to_dict())

    def set_placements(self, decisions: List[dict]) -> None:
        """Record the planner's per-engine placement decisions
        (graph/planner.plan_graph)."""
        with self.lock:
            self.placements = list(decisions)

    def set_audit(self, conservation: dict, skew: dict) -> None:
        """Publish the auditor's latest Conservation/Skew blocks
        (audit/auditor.py)."""
        with self.lock:
            self.audit_conservation = conservation
            self.audit_skew = skew

    def set_topology(self, edges: List[List[str]]) -> None:
        """Record the operator-level edge list (diagnosis/topology.py)
        so the bottleneck walk works on serialized reports too."""
        with self.lock:
            self.topology = list(edges)

    def set_diagnosis(self, block: dict, history: Optional[dict]) -> None:
        """Publish the diagnosis plane's latest Diagnosis/History
        blocks (diagnosis/plane.py, once per tick)."""
        with self.lock:
            self.diagnosis = block
            self.history = history

    def set_durability(self, block: dict) -> None:
        """Publish the epoch coordinator's latest gauges
        (durability/coordinator.py, per commit/tick)."""
        with self.lock:
            self.durability = block

    def set_wire(self, block: dict) -> None:
        """Publish the distributed plane's per-edge wire books
        (distributed/wiring.DistRuntime.wire_block, per gauge
        refresh)."""
        with self.lock:
            self.wire = block

    def set_slo(self, block: dict) -> None:
        """Publish the SLO tracker's latest burn-rate gauges
        (slo/plane.py, once per diagnosis tick)."""
        with self.lock:
            self.slo = block

    def set_pool(self, block: Optional[dict]) -> None:
        """Publish the ColumnPool arena occupancy gauges
        (diagnosis/plane.py, once per tick)."""
        with self.lock:
            self.pool = block

    def set_tenant(self, block: Optional[dict]) -> None:
        """Publish the serving plane's tenant identity block
        (serving/server.py, at submit and on every state/lease
        change)."""
        with self.lock:
            self.tenant = block

    def set_scheduler(self, block: Optional[dict]) -> None:
        """Publish the global-scheduler plane's placement/lease block
        (serving/server.py, after start and on every lease change)."""
        with self.lock:
            self.scheduler = block

    def to_json(self, dropped_tuples: int = 0,
                dead_letter_tuples: int = 0,
                flight_events: Optional[List[dict]] = None) -> str:
        with self.lock:
            ops = []
            for name, replicas in self.records.items():
                op = {
                    "Operator_name": name,
                    "Operator_type": name.rsplit("/", 1)[-1],
                    "Parallelism": self.current_parallelism.get(
                        name, len(replicas)),
                    "Replicas": [r.to_dict() for r in replicas],
                }
                if self.histograms:
                    # report-time merge of the per-replica single-writer
                    # histograms (telemetry/histogram.py)
                    op["Latency"] = {
                        "service": LogHistogram.merged(
                            r.service_hist for r in replicas
                        ).to_dict(buckets=True),
                        "residency": LogHistogram.merged(
                            r.residency_hist for r in replicas
                        ).to_dict(buckets=True),
                    }
                ops.append(op)
            svc_failures = sum(r.svc_failures
                               for rs in self.records.values() for r in rs)
            shed_tuples = sum(r.tuples_shed
                              for rs in self.records.values() for r in rs)
            rescales = list(self.rescale_events)
            placements = list(self.placements)
            conservation = self.audit_conservation
            skew = self.audit_skew
            topology = self.topology
            diagnosis = self.diagnosis
            history = self.history
            durability = self.durability
            worker = self.worker
            wire = self.wire
            slo = self.slo
            pool = self.pool
            tenant = self.tenant
            scheduler = self.scheduler
            latency_e2e = None
            trace_records: List[dict] = []
            if self.histograms:
                e2e = LogHistogram.merged(
                    r.e2e_hist for rs in self.records.values() for r in rs)
                if self.e2e_extra is not None:
                    e2e.merge_from(self.e2e_extra)
                latency_e2e = e2e.to_dict(buckets=True)
                # snapshot FIRST: list(deque) is one C call (atomic
                # under the GIL), while comprehending over the live
                # deque would raise 'deque mutated during iteration'
                # when a sink thread closes a trace mid-report
                trace_records = [ctx.to_dict(t_end)
                                 for ctx, t_end in list(self.trace_records)]
                # wire-crossing partials ride the same JSON list (the
                # serialized dicts carry "partial": true; attribution
                # skips them, the cross-worker merge stitches by id)
                trace_records += [v.to_dict(t_end) for v, t_end
                                  in list(self.trace_partials)]
        payload = {
            "PipeGraph_name": self.graph_name,
            # report-shape version (see SCHEMA_VERSION above); loaders
            # must treat every block below as optional regardless
            "Schema_version": SCHEMA_VERSION,
            "Mode": "DEFAULT",
            "Backpressure": "ON",
            "Dropped_tuples": dropped_tuples,
            # failure-containment counters (resilience/): tuples whose
            # svc raised under a skip/dead_letter policy, and how many
            # of those were quarantined in the dead-letter store
            "Svc_failures": svc_failures,
            "Dead_letter_tuples": dead_letter_tuples,
            # ingest admission control (ingest/admission.py): tuples
            # shed under overload (also quarantined above)
            "Shed_tuples": shed_tuples,
            # elastic scaling plane (elastic/; docs/ELASTIC.md):
            # completed runtime rescales (timestamp, operator,
            # old -> new parallelism, trigger signal)
            "Rescales": len(rescales),
            "Rescale_events": rescales,
            # cost-based placement planner (graph/planner.py;
            # docs/PLANNER.md): resolved lane + the measured inputs
            # behind every 'auto' decision
            "Placements": placements,
            # audit plane (audit/; docs/OBSERVABILITY.md): the online
            # flow-conservation ledger (per-edge books + graph-wide
            # identity inputs + violations) and the keyed-state /
            # hot-key skew census; None when RuntimeConfig.audit is off
            "Conservation": conservation,
            "Skew": skew,
            # telemetry plane (telemetry/; docs/OBSERVABILITY.md):
            # graph-wide end-to-end latency histogram (merged across
            # sink replicas) and the most recent closed traces with
            # per-hop stamps; None / absent histograms when tracing
            # sampling is off
            "Latency_e2e": latency_e2e,
            "Trace_records": trace_records,
            # diagnosis plane (diagnosis/; docs/OBSERVABILITY.md):
            # operator-level topology edges, the latest critical-path /
            # bottleneck / anomaly diagnosis, and the rolling gauge
            # history ring; None until the first tick (or with the
            # plane disabled)
            "Topology": {"Edges": topology} if topology else None,
            "Diagnosis": diagnosis,
            "History": history,
            # durability plane (durability/; docs/RESILIENCE.md):
            # epoch-coordinator gauges -- committed/begun epoch ids,
            # lag of the oldest uncommitted epoch, last commit wall
            # time, stall flag; None with the plane disabled
            "Durability": durability,
            # distributed runtime plane (distributed/;
            # docs/DISTRIBUTED.md): this process's worker id and the
            # per-edge wire delivery books; None/absent outside
            # distributed runs.  distributed/observe.merge_stats folds
            # N such dumps into one graph view.
            "Worker": worker,
            "Wire": wire,
            # SLO plane (slo/; docs/OBSERVABILITY.md "SLO plane"):
            # burn-rate tracker gauges -- windows, fast/slow burn
            # rates, budget burned, open-breach flag; None with no
            # declared objectives.  The ColumnPool arena occupancy
            # rides next to it as memory-pressure evidence.
            "Slo": slo,
            "Pool": pool,
            # serving plane (serving/; docs/SERVING.md): tenant
            # identity + live lease under a multi-tenant Server; None
            # outside a served run
            "Tenant": tenant,
            # global-scheduler plane (scheduler/; docs/SERVING.md
            # "Global scheduler"): hosting worker, fair-share weight,
            # device leases; None when the plane is off
            "Scheduler": scheduler,
            # span layer (telemetry/spans.py; docs/OBSERVABILITY.md
            # "Spans"): per operator replica and thread seconds and
            # shares busy / idle / blocked since start and over the last
            # ten seconds, per window operator the mean and longest of
            # each launch stage; None before the graph has started
            "Spans": spans.report(self.span_graph),
            "Memory_usage_KB": get_mem_usage_kb(),
            "Operator_number": len(ops),
            "Operators": ops,
        }
        if flight_events is not None:
            # bounded FlightRecorder ring snapshot: ships with the
            # monitor reports so the dashboard's /flight endpoint (and
            # the doctor's offline path) can read recent events without
            # a stall/crash triggering a JSONL dump
            payload["Flight"] = flight_events
        return json.dumps(payload)
