"""Global scheduler: fleet-level control plane (windflow_tpu/scheduler/;
docs/SERVING.md "Global scheduler").

Covers the ISSUE-20 acceptance contract:

* the pure placement policy: priority-weighted bin-packing by credit
  reservation + declared device demand, hard credit refusal as a
  structured ``SchedulerError``, dead workers excluded from the live
  view;
* fair segment scheduling: weighted fair-share leases gate co-resident
  consume loops (a tenant alone NEVER waits -- scheduler-on/off is
  bitwise identical for a single-tenant graph), with ``Sched_wait_s``
  surfaced per lease;
* tenant-aware device placement: the planner acquires per-lane leases
  from the worker's ``DeviceLeaseRegistry``, oversubscription flips the
  contention bit, and the arbiter's device rung demotes a low-priority
  neighbour's lane device->host on a contended chip (chaos test: the
  victim's SLO recovers and its results stay bitwise equal to an
  uncontended run);
* the ``FleetServer``: >= 8 tenants placed over >= 2 worker processes,
  per-tenant crash isolation (one worker's death fails only its own
  tenants, which are re-placed under their original specs and
  complete), every decision a flight event;
* observability: ``merge_stats`` folds worker Scheduler blocks,
  /metrics exports the three scheduler families (strict-openmetrics
  clean), and the schema-11 doctor golden pins the report shape.
"""
import json
import os
import threading
import time
import warnings

import numpy as np
import pytest

import windflow_tpu as wf
from windflow_tpu.core.basic import RuntimeConfig
from windflow_tpu.core.tuples import TupleBatch
from windflow_tpu.diagnosis import build_report, render_text
from windflow_tpu.elastic import ElasticityConfig
from windflow_tpu.operators.basic_ops import Sink
from windflow_tpu.operators.batch_ops import BatchSource
from windflow_tpu.operators.tpu.win_seq_tpu import WinSeqTPU
from windflow_tpu.scheduler import (DeviceLeaseRegistry, FairShareRegistry,
                                    Placement, PlacementRequest,
                                    SchedulerError, WorkerCaps,
                                    plan_placement)
from windflow_tpu.serving import ArbiterConfig, Server, TenantSpec

WAIT_S = 120
N_KEYS = 8
WIN, SLIDE = 64, 32


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def batch_source(n, sb=2048, pace_s=0.0, stop_evt=None, vmod=97):
    state = {"i": 0}

    def fn(ctx):
        if stop_evt is not None and stop_evt.is_set():
            return None
        i = state["i"]
        if n is not None and i >= n:
            return None
        if pace_s:
            time.sleep(pace_s)
        m = sb if n is None else min(sb, n - i)
        idx = np.arange(i, i + m)
        ids = idx // N_KEYS
        state["i"] = i + m
        return TupleBatch({"key": idx % N_KEYS, "id": ids, "ts": ids,
                           "value": (idx % vmod).astype(np.float64)})

    return fn


def window_dict_sink():
    res = {}
    lock = threading.Lock()

    def sink(item):
        if item is None:
            return
        with lock:
            if isinstance(item, TupleBatch):
                for j in range(len(item)):
                    res[(int(item.key[j]), int(item.id[j]))] = \
                        float(item["value"][j])
            else:
                res[(item.key, item.id)] = item.value

    return res, sink


def record_source(n, pace_s=0.0, endless=False):
    state = {}

    def fn(shipper, ctx):
        i = state.setdefault("i", 0)
        if not endless and i >= n:
            return False
        if pace_s:
            time.sleep(pace_s)
        shipper.push(wf.BasicRecord(i % 4, i // 4, i, float(i)))
        state["i"] = i + 1
        return True

    return fn


def quiet_cfg(tmp_path, **kw):
    kw.setdefault("log_dir", str(tmp_path))
    kw.setdefault("elasticity", ElasticityConfig(enabled=False))
    return RuntimeConfig(**kw)


def device_window_pipe(g, n, sink, pace_s=0.0, stop_evt=None):
    """One device-pinned window lane (the chip-lease holder)."""
    op = WinSeqTPU("sum", WIN, SLIDE, wf.WinType.TB, batch_len=128,
                   emit_batches=True, placement="device")
    g.add_source(BatchSource(
        batch_source(n, pace_s=pace_s, stop_evt=stop_evt))) \
        .add(op).add_sink(Sink(sink))


# ---------------------------------------------------------------------------
# placement policy (pure)
# ---------------------------------------------------------------------------

def _caps(n=2, credits=1000, lanes=1):
    return [WorkerCaps(w, credits, lanes) for w in range(n)]


def test_plan_placement_spreads_by_normalized_load():
    reqs = [PlacementRequest(f"t{i}", credits=250) for i in range(4)]
    out = plan_placement(reqs, _caps())
    by_worker = {}
    for name, wid in out.items():
        by_worker.setdefault(wid, []).append(name)
    assert set(by_worker) == {0, 1}
    assert all(len(v) == 2 for v in by_worker.values()), out


def test_plan_placement_priority_first_then_reservation():
    # one slot per worker: the high-priority request must be placed
    # first (and so never be the one that fails)
    caps = _caps(2, credits=100)
    reqs = [PlacementRequest("low-a", credits=80, priority=0),
            PlacementRequest("low-b", credits=80, priority=0),
            PlacementRequest("vip", credits=80, priority=9)]
    with pytest.raises(SchedulerError) as ei:
        plan_placement(reqs, caps)
    err = ei.value
    assert err.tenant in ("low-a", "low-b")
    assert "no worker can host tenant" in str(err)
    assert err.hint
    # dropping one low request: everything fits, vip placed
    out = plan_placement(reqs[1:], caps)
    assert set(out) == {"low-b", "vip"}
    assert out["low-b"] != out["vip"]


def test_plan_placement_respects_existing_and_dead_workers():
    caps = _caps(2, credits=1000)
    placed = [Placement("old", worker=0, credits=900)]
    out = plan_placement([PlacementRequest("new", credits=500)],
                         caps, placed=placed)
    assert out["new"] == 1
    # dead worker 1: the request must squeeze onto 0 or fail loudly
    with pytest.raises(SchedulerError):
        plan_placement([PlacementRequest("new", credits=500)], caps,
                       placed=placed, live={0: True, 1: False})
    out = plan_placement([PlacementRequest("new", credits=50)], caps,
                         placed=placed, live={0: True, 1: False})
    assert out["new"] == 0
    with pytest.raises(SchedulerError, match="no live workers"):
        plan_placement([PlacementRequest("new", credits=1)], caps,
                       live={0: False, 1: False})


def test_plan_placement_spreads_device_demand():
    # same credits everywhere: without the device term both would
    # land by load alone; the dev_over key must separate them
    caps = _caps(2, credits=1000, lanes=1)
    reqs = [PlacementRequest("d1", credits=100, devices=1),
            PlacementRequest("d2", credits=100, devices=1)]
    out = plan_placement(reqs, caps)
    assert out["d1"] != out["d2"]
    # a third device tenant oversubscribes SOME chip -- placed, not
    # refused (lanes are a soft reservation)
    placed = [Placement("d1", out["d1"], 100, devices=1),
              Placement("d2", out["d2"], 100, devices=1)]
    out3 = plan_placement([PlacementRequest("d3", credits=100,
                                            devices=1)],
                          caps, placed=placed)
    assert out3["d3"] in (0, 1)


# ---------------------------------------------------------------------------
# fair-share executor leases
# ---------------------------------------------------------------------------

def test_fair_share_solo_never_waits():
    reg = FairShareRegistry(burst=64)
    ls = reg.lease("only", weight=1.0)
    for _ in range(50):
        assert ls.acquire(1000) == 0.0
    assert ls.wait_s == 0.0
    blk = reg.block()
    assert blk["Sched_wait_s"] == 0.0
    assert blk["Leases"][0]["Consumed"] == 50_000


def test_fair_share_weighted_contention_converges():
    reg = FairShareRegistry(burst=256)
    heavy = reg.lease("heavy", weight=2.0)
    light = reg.lease("light", weight=1.0)
    stop = threading.Event()

    def spin(ls):
        while not stop.is_set():
            ls.acquire(64)

    threads = [threading.Thread(target=spin, args=(ls,))
               for ls in (heavy, light)]
    for t in threads:
        t.start()
    time.sleep(0.8)
    stop.set()
    # poison unblocks whichever loop is parked in the gate
    heavy.poison()
    light.poison()
    for t in threads:
        t.join(10.0)
        assert not t.is_alive()
    ratio = heavy.consumed / max(1, light.consumed)
    assert 1.4 <= ratio <= 2.8, \
        f"weighted share diverged: {heavy.consumed}/{light.consumed}"
    blk = reg.block()
    assert blk["Sched_wait_s"] > 0.0, "contention never gated anyone"
    assert {r["Tenant"] for r in blk["Leases"]} == {"heavy", "light"}


def test_fair_share_idle_lease_ages_out_of_floor():
    reg = FairShareRegistry(burst=64, active_window_s=0.2)
    a = reg.lease("a")
    b = reg.lease("b")
    b.acquire(10)          # establishes a floor at 10/1.0
    t0 = time.monotonic()
    waited = a.acquire(10_000)   # way over burst vs b's floor
    took = time.monotonic() - t0
    # a was gated until b aged out, then released -- never parked
    # forever at a finished tenant's last position
    assert waited > 0.0
    assert took < 5.0
    assert a.consumed == 10_000


def test_fair_share_release_and_poison_unblock_waiters():
    reg = FairShareRegistry(burst=64)
    a = reg.lease("a")
    b = reg.lease("b")
    b.acquire(10)
    done = threading.Event()

    def blocked():
        a.acquire(100_000)
        done.set()

    t = threading.Thread(target=blocked)
    t.start()
    time.sleep(0.1)
    assert not done.is_set(), "gate never engaged"
    reg.release("b")       # the only other active lease leaves
    assert done.wait(5.0), "release did not unblock the waiter"
    t.join(5.0)
    assert a.wait_s > 0.0


def test_fair_share_late_joiner_seeded_at_floor():
    reg = FairShareRegistry(burst=64)
    a = reg.lease("a")
    a.acquire(9000)
    late = reg.lease("late", weight=2.0)
    # joined AT the floor (9000/1.0 * 2.0), not at zero -- so the
    # veteran is not parked waiting for the newcomer to catch up
    assert late.consumed == 18_000
    assert a.acquire(64) < 1.0


# ---------------------------------------------------------------------------
# device-lane leases
# ---------------------------------------------------------------------------

def test_device_leases_grant_and_record_contention():
    reg = DeviceLeaseRegistry(lanes=1, chip="tpu:0")
    g1 = reg.acquire("alpha", "pipe0/win", priority=2)
    assert g1 == {"chip": "tpu:0", "holders": 1, "contended": False}
    g2 = reg.acquire("beta", "pipe1/win", resident=True)
    assert g2["contended"] and g2["holders"] == 2
    assert reg.contended() and reg.holders() == 2
    rows = reg.rows()
    assert all(r["Contended"] for r in rows)
    resid = {r["Tenant"]: r["Resident"] for r in rows}
    assert resid == {"alpha": False, "beta": True}
    assert [r["Operator"] for r in reg.tenant_rows("alpha")] \
        == ["pipe0/win"]
    blk = reg.block()
    assert blk["Chip"] == "tpu:0" and blk["Lanes"] == 1
    assert blk["Holders"] == 2 and blk["Contended"]
    # release by (tenant, operator), then by tenant
    assert reg.release("alpha", "no/such") == 0
    assert reg.release("alpha", "pipe0/win") == 1
    assert not reg.contended()
    reg.acquire("beta", "pipe2/win")
    assert reg.release("beta") == 2
    assert reg.holders() == 0


# ---------------------------------------------------------------------------
# arbiter device rung (pure planner)
# ---------------------------------------------------------------------------

def _victim_view(**kw):
    from windflow_tpu.serving import TenantView
    kw.setdefault("name", "vic")
    kw.setdefault("priority", 5)
    kw.setdefault("breached", True)
    kw.setdefault("violating", ("throughput",))
    kw.setdefault("device_ops", [{"Tenant": "vic", "Operator": "v/win",
                                  "Chip": "tpu:0", "Contended": True,
                                  "Resident": False}])
    return TenantView(**kw)


def _donor_view(**kw):
    from windflow_tpu.serving import TenantView
    kw.setdefault("name", "noisy")
    kw.setdefault("priority", 0)
    kw.setdefault("breached", False)
    kw.setdefault("credits", 4096)
    kw.setdefault("device_ops", [{"Tenant": "noisy",
                                  "Operator": "n/win",
                                  "Chip": "tpu:0", "Contended": True,
                                  "Resident": False}])
    return TenantView(**kw)


def test_arbiter_device_rung_demotes_contended_neighbor():
    from windflow_tpu.serving import plan_arbitration
    cfg = ArbiterConfig(breach_ticks=2)
    d = plan_arbitration([_victim_view(), _donor_view()], cfg,
                         breach_runs={"vic": 2}, cooldowns={}, now=0.0)
    assert d is not None and d["victim"] == "vic"
    assert d["actions"] == [{"type": "device", "operator": "n/win",
                             "chip": "tpu:0", "to": "host"}]
    assert d["evidence"]["chip"] == "tpu:0"
    assert d["evidence"]["contended"] is True


def test_arbiter_device_rung_skips_resident_and_uncontended():
    from windflow_tpu.serving import plan_arbitration
    cfg = ArbiterConfig(breach_ticks=2)
    # resident donor lane: NOT demotable -> falls through to the
    # credit rung (the donor has spare credits)
    donor = _donor_view(device_ops=[{"Tenant": "noisy",
                                     "Operator": "n/win",
                                     "Chip": "tpu:0",
                                     "Contended": True,
                                     "Resident": True}])
    d = plan_arbitration([_victim_view(), donor], cfg,
                         breach_runs={"vic": 2}, cooldowns={}, now=0.0)
    assert d is not None
    assert all(a["type"] != "device" for a in d["actions"])
    # uncontended chip: the device rung never fires at all
    vic = _victim_view(device_ops=[{"Tenant": "vic",
                                    "Operator": "v/win",
                                    "Chip": "tpu:0",
                                    "Contended": False,
                                    "Resident": False}])
    d = plan_arbitration([vic, _donor_view()], cfg,
                         breach_runs={"vic": 2}, cooldowns={}, now=0.0)
    assert d is not None
    assert all(a["type"] != "device" for a in d["actions"])
    # a HIGHER-priority neighbour is never squeezed for the victim
    d = plan_arbitration([_victim_view(priority=0),
                          _donor_view(priority=5)], cfg,
                         breach_runs={"vic": 2}, cooldowns={}, now=0.0)
    assert d is None


# ---------------------------------------------------------------------------
# planner integration: device lanes acquire worker leases
# ---------------------------------------------------------------------------

def test_planner_acquires_device_lease():
    reg = DeviceLeaseRegistry(lanes=1)
    reg.acquire("hog", "other/win")      # the chip is already taken
    res, sink = window_dict_sink()
    g = wf.PipeGraph("lease_probe", wf.Mode.DEFAULT)
    g.device_leases = reg
    g.tenant_name = "t1"
    g.tenant_priority = 3
    device_window_pipe(g, 4096, sink)
    g.run()
    rows = reg.tenant_rows("t1")
    assert len(rows) == 1
    assert rows[0]["Priority"] == 3
    assert rows[0]["Resident"] is False
    assert rows[0]["Contended"] is True     # 2 holders > 1 lane
    leased = [p for p in g.placements if p.get("lease")]
    assert leased and leased[0]["lease"]["contended"]
    assert res, "window results lost through the leased lane"


# ---------------------------------------------------------------------------
# chaos: contended chip, arbiter demotes the low-priority neighbour
# ---------------------------------------------------------------------------

def burner_source(stop_evt):
    state = {}

    def fn(shipper, ctx):
        if stop_evt.is_set():
            return False
        i = state.setdefault("i", 0)
        shipper.push(wf.BasicRecord(i % 64, i, i, 1.0))
        state["i"] = i + 1
        return True

    return fn


def burn_10ms(t):
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < 0.01:
        pass
    return None


N_CHAOS = 40_000


def test_contended_chip_arbiter_demotes_neighbor_slo_recovers(tmp_path):
    """ISSUE-20 chaos acceptance: victim and noisy neighbour both pin
    a window lane onto the worker's single device lane (chip
    contended); the neighbour's CPU burners starve the victim's SLO;
    the arbiter's FIRST rung demotes the neighbour's lane device->host
    through replace_lane (flight-recorded with the arbiter trigger and
    chip evidence), escalation then restores the victim's SLO
    (slo_recovered), and the victim's window results are bitwise equal
    to an uncontended solo run."""
    # solo uncontended reference first (also warms the XLA cache)
    ref, ref_sink = window_dict_sink()
    gs = wf.PipeGraph("chaos_solo", wf.Mode.DEFAULT)
    device_window_pipe(gs, N_CHAOS, ref_sink)
    gs.run()
    assert ref

    stop = threading.Event()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        srv = Server(
            capacity=1 << 16, devices=1,
            arbiter=ArbiterConfig(interval_s=0.25, breach_ticks=2,
                                  cooldown_s=1.0,
                                  clear_ticks=10 ** 6))
        try:
            vres, vsink = window_dict_sink()

            def build_victim(g):
                # SLO driver lane: paced records starved by the
                # neighbour's burners
                g.add_source(wf.SourceBuilder(
                    record_source(10 ** 6, pace_s=0.001)).build()) \
                    .add(wf.MapBuilder(lambda t: None)
                         .with_name("vmap").build()) \
                    .add_sink(wf.SinkBuilder(lambda r: None).build())
                # device lane: holds the victim's chip lease and
                # produces the bitwise-compared window results
                device_window_pipe(g, N_CHAOS, vsink)

            def build_noisy(g):
                g.add_source(wf.SourceBuilder(
                    burner_source(stop)).build()) \
                    .add(wf.MapBuilder(burn_10ms).with_name("burn")
                         .with_key_by().with_parallelism(4)
                         .with_elasticity(1, 4).build()) \
                    .add_sink(wf.SinkBuilder(lambda r: None).build())
                # the demotable lease: a low-priority lane sharing the
                # victim's chip
                device_window_pipe(g, None, lambda item: None,
                                   pace_s=0.005, stop_evt=stop)

            hv = srv.submit(
                "vic", build_victim,
                TenantSpec(credits=1024, priority=5,
                           slo=dict(min_throughput_rps=60.0,
                                    target=0.9, fast_window_s=3.0,
                                    slow_window_s=30.0,
                                    warmup_ticks=1, fast_burn=2.0)),
                config=quiet_cfg(tmp_path, diagnosis_interval_s=0.2,
                                 audit_interval_s=0.1))
            hn = srv.submit(
                "noisy", build_noisy,
                TenantSpec(credits=4096, priority=0),
                config=quiet_cfg(tmp_path, queue_capacity=32))
            assert srv.devices.contended(), \
                "two device lanes on one chip must contend"

            # phase A: starvation opens the victim's breach episode
            deadline = time.monotonic() + WAIT_S
            while time.monotonic() < deadline:
                tr = hv.graph.diagnosis.slo
                if tr is not None and tr.breached:
                    break
                time.sleep(0.2)
            assert hv.graph.diagnosis.slo.breached, \
                "victim never breached under contention"

            # phase B: rung 1 demotes the neighbour's lane, the
            # ladder then squeezes until the episode closes
            recovered = False
            deadline = time.monotonic() + WAIT_S
            while time.monotonic() < deadline:
                kinds = [e["kind"] for e in hv.graph.flight.snapshot()]
                if "slo_recovered" in kinds:
                    recovered = True
                    break
                time.sleep(0.25)
            decisions = list(srv.arbiter.decisions)
            assert decisions, "arbiter never actuated"
            assert recovered, \
                (f"victim SLO never recovered "
                 f"({len(decisions)} decisions)")

            # the FIRST decision is the chip-targeted demotion
            dev_acts = [a for d in decisions for a in d["actions"]
                        if a["type"] == "device"]
            assert dev_acts and dev_acts[0].get("applied"), \
                f"no applied device demotion in {decisions}"
            assert dev_acts[0]["to"] == "host"
            first = decisions[0]
            assert any(a["type"] == "device" for a in first["actions"])
            assert first["donor"] == "noisy" \
                and first["victim"] == "vic"
            assert first["evidence"]["contended"] is True

            # the neighbour's lane really flipped through the quiesce
            # path with the arbiter trigger, and its lease is gone
            repl = [e for e in hn.graph.flight.snapshot()
                    if e["kind"] == "replacement"]
            assert any("arbiter:device->host for vic"
                       in (e.get("trigger") or "") for e in repl), repl
            assert not srv.devices.tenant_rows("noisy")
            assert not srv.devices.contended()
            assert srv.devices.tenant_rows("vic"), \
                "the victim must keep its lane"

            # the arbitration is flight-recorded on both graphs with
            # the demotion named
            for h in (hv, hn):
                evs = [e for e in h.graph.flight.snapshot()
                       if e["kind"] == "arbitration"]
                assert any("demoted" in (e.get("action") or "")
                           for e in evs), evs

            # bitwise identity: the victim's windows match the
            # uncontended solo run exactly
            deadline = time.monotonic() + WAIT_S
            while time.monotonic() < deadline \
                    and len(vres) < len(ref):
                time.sleep(0.2)
            assert vres == ref, \
                (f"victim results diverged under contention: "
                 f"{len(vres)} vs {len(ref)} windows")

            # the worker's Scheduler block carries the device books
            blk = srv.scheduler_block()
            assert blk["Devices"]["Holders"] == 1
            assert blk["Devices"]["Contended"] is False
        finally:
            stop.set()
            srv.close()


# ---------------------------------------------------------------------------
# FleetServer: placement, crash isolation, structured rejection
# ---------------------------------------------------------------------------

def fleet_build(g):
    """Worker-side tenant graph (must be importable by name)."""
    g.add_source(wf.SourceBuilder(
        record_source(1200, pace_s=0.003)).build()) \
        .add(wf.MapBuilder(lambda t: None).with_name("m").build()) \
        .add_sink(wf.SinkBuilder(lambda r: None).build())


def fleet_cfg():
    import tempfile
    return RuntimeConfig(log_dir=tempfile.gettempdir(),
                         elasticity=ElasticityConfig(enabled=False))


def test_fleet_places_8_tenants_and_survives_worker_death():
    """ISSUE-20 fleet acceptance: 8 tenants spread over 2 worker
    processes by the policy; killing one worker fails only its own
    tenants, which are re-placed onto the survivor under their
    original specs and complete; survivors are untouched; every
    decision (placement, death, re-placement, rejection) is a flight
    event."""
    from windflow_tpu.scheduler import FleetServer
    names = [f"t{i}" for i in range(8)]
    with FleetServer(workers=2, capacity=100_000,
                     push_interval_s=0.2) as fleet:
        for name in names:
            row = fleet.submit(name, fleet_build,
                               TenantSpec(credits=8000),
                               config_fn=fleet_cfg)
            assert row["State"] == "PLACED"
        st = fleet.stats()
        by_worker = {}
        for row in st["Placements"]:
            by_worker.setdefault(row["Worker"], []).append(row["Tenant"])
        assert set(by_worker) == {0, 1}, by_worker
        assert all(len(v) == 4 for v in by_worker.values()), by_worker
        assert len([e for e in st["Flight"]
                    if e["kind"] == "sched_place"]) == 8

        # structured refusal: nothing can host this reservation
        with pytest.raises(SchedulerError) as ei:
            fleet.submit("whale", fleet_build,
                         TenantSpec(credits=90_000),
                         config_fn=fleet_cfg)
        assert ei.value.tenant == "whale"
        assert ei.value.hint
        rej = [e for e in fleet.flight.snapshot()
               if e["kind"] == "sched_rejected"]
        assert rej and rej[-1]["tenant"] == "whale"

        # chaos: kill worker 0 while its tenants run
        victims = sorted(by_worker[0])
        survivors = sorted(by_worker[1])
        time.sleep(1.0)
        fleet.kill_worker(0)
        for name in names:
            row = fleet.wait(name, timeout=WAIT_S)
            assert row["State"] == "COMPLETED", (name, row)
            cons = row.get("Conservation")
            if cons:
                assert cons["Edges_balanced"], (name, cons)

        st = fleet.stats()
        rows = {r["Tenant"]: r for r in st["Placements"]}
        for name in victims:
            assert rows[name]["Worker"] == 1, rows[name]
            assert rows[name]["Attempts"] == 2, rows[name]
        for name in survivors:
            assert rows[name]["Worker"] == 1
            assert rows[name]["Attempts"] == 1, rows[name]
        deaths = [e for e in st["Flight"]
                  if e["kind"] == "worker_death"]
        assert len(deaths) == 1 and deaths[0]["worker"] == 0
        assert sorted(deaths[0]["tenants"]) == victims
        replaced = [e for e in st["Flight"]
                    if e["kind"] == "sched_replace"]
        assert sorted(e["tenant"] for e in replaced) == victims
        assert all(e["from_worker"] == 0 and e["worker"] == 1
                   for e in replaced)

        # the merged live cluster view folds the survivor's
        # Scheduler block (placements carried whole)
        deadline = time.monotonic() + 15
        merged = None
        while time.monotonic() < deadline:
            merged = fleet.cluster()
            if merged and merged.get("Scheduler"):
                break
            time.sleep(0.2)
        assert merged and merged.get("Scheduler"), \
            "worker Scheduler blocks never reached the observer"
        sched = merged["Scheduler"]
        assert any(b.get("Fair_share") for b in sched["Workers"])
        assert {p["Tenant"] for p in sched["Placements"]} \
            <= set(names)


def test_fleet_one_device_worker_per_visible_chip(monkeypatch):
    """A chip belongs to one process: the fleet counts the chips its
    children could open without touching JAX, hands each device worker
    one of them through the environment, and refuses to start more
    device workers than chips -- before spawning anything."""
    from windflow_tpu.scheduler import FleetServer
    from windflow_tpu.scheduler.fleet import chip_env, visible_chips
    # the CPU backend is shared by any number of processes
    assert visible_chips({"JAX_PLATFORMS": "cpu"}) is None
    assert visible_chips({"JAX_PLATFORMS": "tpu,cpu",
                          "TPU_VISIBLE_CHIPS": "2,3"}) == ["2", "3"]
    env = chip_env({"PATH": "/bin"}, "3", 1)
    assert env["TPU_VISIBLE_CHIPS"] == "3" and env["PATH"] == "/bin"
    assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "0")
    with pytest.raises(SchedulerError, match="2 workers with device "
                                             "lanes but 1 visible"):
        FleetServer(workers=2)


def test_fleet_single_tenant_completes_unthrottled(tmp_path):
    """A tenant alone on its worker runs under fair_share=True yet
    never waits in the gate (pay-for-what-you-use)."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        srv = Server(capacity=1 << 16, arbiter=False, fair_share=True,
                     worker_id=0)
        try:
            h = srv.submit("solo", fleet_build,
                           TenantSpec(credits=8000),
                           config=quiet_cfg(tmp_path))
            assert h.wait(WAIT_S) == "COMPLETED"
            blk = srv.scheduler_block()
            assert blk["Fair_share"] is True
            assert blk["Sched_wait_s"] == 0.0, blk
        finally:
            srv.close()


# ---------------------------------------------------------------------------
# distributed wiring: elastic graphs rejected with a structured error
# ---------------------------------------------------------------------------

def test_distributed_elastic_rejected_with_sched_event(tmp_path):
    from windflow_tpu.distributed.runtime import (DistributedSpec,
                                                  free_ports)
    p0, p1 = free_ports(2)
    cfg = quiet_cfg(tmp_path)
    cfg.distributed = DistributedSpec(0, 2, [("127.0.0.1", p0),
                                             ("127.0.0.1", p1)])
    g = wf.PipeGraph("dist_elastic", wf.Mode.DEFAULT, cfg)
    g.add_source(wf.SourceBuilder(record_source(100)).build()) \
        .add(wf.MapBuilder(lambda t: None).with_name("m")
             .with_key_by().with_parallelism(2)
             .with_elasticity(1, 4).build()) \
        .add_sink(wf.SinkBuilder(lambda r: None).build())
    try:
        with pytest.raises(SchedulerError) as ei:
            g.start()
    finally:
        try:
            g.cancel()
        except Exception:
            pass
    err = ei.value
    assert err.operators, "rejection must name the elastic operators"
    assert "FleetServer" in err.hint
    evs = [e for e in g.flight.snapshot()
           if e["kind"] == "sched_rejected"]
    assert len(evs) == 1
    assert evs[0]["operators"] == err.operators
    assert evs[0]["path"] == "scheduler.FleetServer"


# ---------------------------------------------------------------------------
# observability: merged stats, /metrics families, doctor
# ---------------------------------------------------------------------------

def _worker_stats(wid, wait_s, tenants):
    return {
        "Worker": wid,
        "PipeGraph_name": "fleet",
        "Scheduler": {
            "Worker": wid, "Capacity": 1 << 20,
            "Granted": sum(c for _, c in tenants),
            "Fair_share": True,
            "Placements": [{"Tenant": t, "Worker": wid,
                            "State": "RUNNING", "Credits": c,
                            "Priority": 0, "Weight": 1.0,
                            "Devices": 0} for t, c in tenants],
            "Sched_wait_s": wait_s,
        },
    }


def test_merge_stats_folds_scheduler_blocks():
    from windflow_tpu.distributed.observe import merge_stats
    merged = merge_stats([
        _worker_stats(0, 0.25, [("alpha", 1024), ("beta", 2048)]),
        _worker_stats(1, 0.5, [("gamma", 4096)]),
    ])
    sched = merged["Scheduler"]
    assert [b["Worker"] for b in sched["Workers"]] == [0, 1]
    assert sched["Sched_wait_s"] == 0.75
    assert [(p["Tenant"], p["Worker"])
            for p in sched["Placements"]] \
        == [("alpha", 0), ("beta", 0), ("gamma", 1)]
    # no worker runs the plane -> the block is absent entirely
    assert merge_stats([{"Worker": 0, "PipeGraph_name": "g"}]) \
        ["Scheduler"] is None


def test_openmetrics_scheduler_families():
    from windflow_tpu.telemetry.metrics import render_openmetrics
    apps = {1: {"active": True, "report": {
        "PipeGraph_name": "fleet",
        "Operators": [
            {"Operator_name": "pipe0/m", "Parallelism": 2,
             "Replicas": [{"Sched_wait_s": 0.2},
                          {"Sched_wait_s": 0.11}]},
            {"Operator_name": "pipe0/sink", "Parallelism": 1,
             "Replicas": [{"Outputs_sent": 5}]},
        ],
        "Scheduler": {
            "Worker": 0,
            "Placements": [{"Tenant": "alpha", "Worker": 0,
                            "State": "RUNNING"},
                           {"Tenant": "beta", "Worker": 0,
                            "State": "RUNNING"}],
            "Devices": {"Chip": "tpu:0", "Lanes": 1, "Holders": 2,
                        "Contended": True,
                        "Leases": [{"Tenant": "alpha",
                                    "Operator": "pipe0/w"},
                                   {"Tenant": "alpha",
                                    "Operator": "pipe1/w"},
                                   {"Tenant": "beta",
                                    "Operator": "pipe2/w"}]},
        },
    }}}
    text = render_openmetrics(apps)
    assert ('windflow_sched_wait_seconds_total{app="1",graph="fleet",'
            'operator="pipe0/m"} 0.31') in text
    assert ('windflow_sched_wait_seconds_total{app="1",graph="fleet",'
            'operator="pipe0/sink"}') not in text
    assert ('windflow_tenant_worker{app="1",graph="fleet",'
            'tenant="alpha",worker="0"} 1') in text
    assert ('windflow_tenant_worker{app="1",graph="fleet",'
            'tenant="beta",worker="0"} 1') in text
    assert ('windflow_device_lease{app="1",graph="fleet",'
            'tenant="alpha"} 2') in text
    assert ('windflow_device_lease{app="1",graph="fleet",'
            'tenant="beta"} 1') in text
    # scheduler-less report: the families stay sample-free
    bare = render_openmetrics({1: {"active": True, "report": {
        "PipeGraph_name": "g",
        "Operators": [{"Operator_name": "pipe0/m",
                       "Replicas": [{"Inputs_received": 1}]}]}}})
    for fam in ("windflow_sched_wait_seconds_total{",
                "windflow_tenant_worker{", "windflow_device_lease{"):
        assert fam not in bare
    # strict OpenMetrics syntax for the full render
    try:
        from prometheus_client.openmetrics import parser
    except ImportError:
        pytest.skip("prometheus_client not installed")
    list(parser.text_string_to_metric_families(text))


def test_doctor_golden_v11_scheduler():
    """Schema-11 dump (Scheduler block + fleet flight events) ->
    doctor --json report pinned by the committed golden pair."""
    golden_dir = os.path.join(os.path.dirname(__file__), "golden")
    import io
    from contextlib import redirect_stdout
    from windflow_tpu.doctor import main as doctor_main
    path = os.path.join(golden_dir, "doctor_stats_v11.json")
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = doctor_main([path, "--json"])
    assert rc == 0
    rep = json.loads(buf.getvalue())
    src = rep.pop("Source")
    assert src.endswith("doctor_stats_v11.json")
    with open(os.path.join(golden_dir, "doctor_report_v11.json")) as f:
        golden = json.load(f)
    assert rep == golden
    with open(path) as f:
        dump = json.load(f)
    assert dump["Schema_version"] == 11
    assert dump["Scheduler"]["Devices"]["Contended"] is True


def test_doctor_report_and_text_surface_scheduler():
    golden_dir = os.path.join(os.path.dirname(__file__), "golden")
    with open(os.path.join(golden_dir, "doctor_stats_v11.json")) as f:
        stats = json.load(f)
    rep = build_report(stats)
    sched = rep["Scheduler"]
    assert sched["Worker"] == 0 and sched["Fair_share"] is True
    assert sched["Device_contended"] is True
    assert sched["Device_holders"] == 2
    assert {e["kind"] for e in rep["Scheduler_events"]} \
        >= {"sched_place", "worker_death", "sched_replace",
            "sched_rejected"}
    assert "worker 1 DIED" in rep["Verdict"]
    assert "REJECTED" in rep["Verdict"]
    txt = render_text(rep)
    assert "scheduler: worker=0" in txt
    assert "CONTENDED" in txt
    assert "worker_death" in txt
    assert "hint:" in txt
