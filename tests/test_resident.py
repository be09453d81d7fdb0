"""Device-resident keyed window state + online re-planning
(docs/PLANNER.md "Online re-planning").

* the fused scatter+query forest program (one launch per chunk,
  donated carry) matches the sequential update/query pair;
* the FFAT resident lane ships >= 10x fewer bytes/launch than the
  rebuild lane on a sliding-window config;
* resident engines stay checkpoint-, rescale- (keyed_state_dict
  repartition) and epoch-compatible, including a mid-run lane flip
  between two epochs recovering exactly-once;
* the online re-planner flips a lane mid-run with zero lost tuples,
  records a ``replacement`` flight event and the doctor explains it.

Runs on the CPU backend; the same programs compile for TPU
unchanged.  Green on both channel planes (the
WINDFLOW_NATIVE=0 CI job).
"""
import collections
import threading
import time

import numpy as np
import pytest

import windflow_tpu as wf
from windflow_tpu.core import BasicRecord, Mode, WinType
from windflow_tpu.core.basic import Pattern, RoutingMode, RuntimeConfig
from windflow_tpu.core.tuples import TupleBatch
from windflow_tpu.operators.base import Operator, StageSpec
from windflow_tpu.operators.basic_ops import Sink
from windflow_tpu.operators.batch_ops import BatchSource
from windflow_tpu.operators.tpu.ffat_resident import (
    WinSeqFFATResident, WinSeqFFATResidentLogic)
from windflow_tpu.operators.tpu.win_seq_tpu import (WinSeqTPU,
                                                    WinSeqTPULogic)
from windflow_tpu.runtime.emitters import StandardEmitter
from windflow_tpu.runtime.node import SourceLoopLogic

N_KEYS = 3


@pytest.fixture(autouse=True)
def _pin_cost_model(monkeypatch, tmp_path):
    """Deterministic cost-model inputs: tiny RTT floor, pinned host
    rate, no compute calibration, and the calibration CACHE redirected
    to a tmp file so tests never write the per-box one."""
    from windflow_tpu.graph import planner
    monkeypatch.setenv("WINDFLOW_RTT_FLOOR_MS", "0.001")
    monkeypatch.setenv("WINDFLOW_HOST_RATE_TPS", "20000000")
    monkeypatch.setenv("WINDFLOW_DEVICE_COMPUTE_MS", "0")
    monkeypatch.setattr(planner, "_DEV_CALIB_PATH",
                        str(tmp_path / "device_calibration.json"))
    monkeypatch.setattr(planner, "_device_compute_ms", None)
    yield


def _int_batch(lo, hi, n_keys=N_KEYS):
    idx = np.arange(lo, hi)
    return TupleBatch({"key": idx % n_keys, "id": idx // n_keys,
                       "ts": idx // n_keys,
                       "value": (idx % 7).astype(np.float64)})


def _run_logic(lg, n, chunk=500, n_keys=N_KEYS):
    out = []
    for c in range(0, n, chunk):
        lg.svc(_int_batch(c, min(c + chunk, n), n_keys), 0, out.append)
    lg.eos_flush(out.append)
    flat = {}
    for r in out:
        if isinstance(r, TupleBatch):
            for i in range(len(r)):
                flat[(int(r.key[i]), int(r.id[i]))] = \
                    (float(r["value"][i]), int(r.ts[i]))
        else:
            flat[(r.key, r.id)] = (r.value, r.ts)
    return flat


# ---------------------------------------------------------------------------
# fused forest program
# ---------------------------------------------------------------------------

class TestFusedForest:
    def test_fused_matches_sequential(self):
        import jax.numpy as jnp
        from windflow_tpu.ops.flatfat_jax import BatchedFlatFAT
        rng = np.random.default_rng(0)
        a = BatchedFlatFAT(jnp.add, 0.0, 4, 32)
        b = BatchedFlatFAT(jnp.add, 0.0, 4, 32)
        for step in range(6):
            keys = rng.integers(0, 4, 12)
            ids = np.arange(step * 12, step * 12 + 12)
            vals = rng.integers(0, 100, 12).astype(np.float32)
            qk = np.arange(4)
            qs = np.full(4, max(0, step * 12 - 10))
            qe = np.full(4, step * 12 + 6)
            a.update(keys, ids, vals)
            r1 = a.query(qk, qs, qe)
            r2 = b.update_query(keys, ids, vals, qk, qs, qe)
            assert np.array_equal(r1, r2)

    def test_fused_ring_wrap_keeps_time_order(self):
        import jax.numpy as jnp
        from windflow_tpu.ops.flatfat_jax import BatchedFlatFAT
        # non-commutative combine: order proves the wrap pieces fold
        # oldest -> newest
        comb = lambda x, y: x * 0.5 + y  # noqa: E731
        f = BatchedFlatFAT(comb, 0.0, 2, 8)
        g = BatchedFlatFAT(comb, 0.0, 2, 8)
        vals = np.arange(1, 25, dtype=np.float32)
        for i in range(0, 24, 4):
            ids = np.arange(i, i + 4)
            f.update(np.zeros(4, int), ids, vals[i:i + 4])
            lo = max(0, i + 4 - 8)
            r1 = f.query([0], [lo], [i + 4])
            r2 = g.update_query(np.zeros(4, int), ids, vals[i:i + 4],
                                [0], [lo], [i + 4])
            assert np.array_equal(r1, r2)

    def test_state_bytes_gauge(self):
        import jax.numpy as jnp
        from windflow_tpu.ops.flatfat_jax import BatchedFlatFAT
        f = BatchedFlatFAT(jnp.add, 0.0, 4, 64)
        assert f.state_bytes == 4 * 2 * 64 * 4  # K x 2n x f32


def _counted_batches(n, sb, n_keys=N_KEYS, pace_s=0.0):
    state = {"i": 0}

    def fn():
        i = state["i"]
        if i * sb >= n:
            return None
        state["i"] = i + 1
        if pace_s:
            time.sleep(pace_s)
        return _int_batch(i * sb, min((i + 1) * sb, n), n_keys)

    return fn


# ---------------------------------------------------------------------------
# FFAT resident lane: bytes/launch + fused launches + mirror bound
# ---------------------------------------------------------------------------

def oracle(per_key, win, slide, agg=sum):
    out = {}
    g = 0
    while g * slide < per_key:
        vals = [float(v % 7) for v in range(per_key)
                if g * slide <= v < g * slide + win]
        out[g] = float(agg(vals)) if vals else 0.0
        g += 1
    return out


class TestResidentFFAT:
    def _resident(self, win=512, slide=16, tb=False):
        import jax.numpy as jnp
        return WinSeqFFATResidentLogic(
            lambda t: t.value, jnp.add, 0.0, win, slide,
            win_type=WinType.TB if tb else WinType.CB)

    def test_bytes_per_launch_10x_below_rebuild(self):
        """The acceptance ratio: on a sliding-window config the
        resident lane ships >= 10x fewer bytes per launch than the
        rebuild lane (which re-stages the window carry every launch),
        with identical results."""
        from windflow_tpu.monitoring.stats import StatsRecord
        import jax.numpy as jnp
        win, slide, n = 512, 16, 30_000
        rebuild = WinSeqTPULogic(("ffat", jnp.add, 0.0), win, slide,
                                 WinType.CB, batch_len=64,
                                 async_dispatch=False,
                                 value_of=lambda t: t.value)
        rebuild.stats = StatsRecord()
        a = _run_logic(rebuild, n)
        resident = self._resident(win, slide)
        resident.stats = StatsRecord()
        b = _run_logic(resident, n)
        # identical fired windows, bitwise (integer-valued f32 sums)
        assert a and {k: v[0] for k, v in a.items()} \
            == {k: v[0] for k, v in b.items()}
        per_rebuild = (rebuild.stats.bytes_to_device
                       + rebuild.stats.bytes_from_device) \
            / rebuild.stats.num_launches
        per_resident = (resident.stats.bytes_to_device
                        + resident.stats.bytes_from_device) \
            / resident.stats.num_launches
        assert per_rebuild >= 10 * per_resident, \
            (per_rebuild, per_resident)
        assert resident.stats.device_state_bytes > 0

    def test_one_fused_launch_per_chunk(self):
        lg = self._resident(64, 16)
        out = []
        before = lg.launched_batches
        # one chunk that both scatters AND fires windows: exactly ONE
        # fused launch, not an update launch plus a query launch
        lg.svc(_int_batch(0, 300, 1), 0, out.append)
        assert out  # windows fired
        assert lg.launched_batches == before + 1

    def test_tb_mirror_stays_bounded(self):
        """Satellite fix: the TB eviction proof resumes at the running
        cursor and the mirror is sliced there -- a long in-order
        stream keeps the host mirror O(live span), not O(history)."""
        lg = self._resident(64, 16, tb=True)
        out = []
        n, per_chunk = 40_000, 1000
        for c in range(0, n, per_chunk):
            idx = np.arange(c, c + per_chunk)
            lg.svc(TupleBatch({"key": np.zeros(per_chunk, np.int64),
                               "id": idx, "ts": idx,
                               "value": (idx % 7).astype(np.float64)}),
                   0, out.append)
        st = lg.keys[0]
        # live span = win + headroom-ish; the mirror must not have
        # accumulated the 40k-tuple history
        assert len(st.ts_vals) < 8192, len(st.ts_vals)
        assert st.ts_base > 30_000  # evicted at the proof
        lg.eos_flush(out.append)
        got = {r.get_control_fields()[1]: r.value for r in out}
        expect = oracle(n, 64, 16)
        assert got.keys() == expect.keys()
        for w in (0, 100, len(expect) - 1):
            assert got[w] == expect[w]

    def test_keyed_state_partitions_across_replicas(self):
        """The elastic contract: keyed_state_dict() splits by
        hash%n and load_keyed_state() rebuilds per-owner forests --
        a 1->2 repartition mid-stream matches the fixed run."""
        from windflow_tpu.elastic.rescale import (merge_keyed_states,
                                                  owner_of,
                                                  partition_keyed_state)
        n, n_keys = 12_000, 4
        ref = {}
        full = self._resident(128, 32)
        out = []
        for c in range(0, n, 600):
            full.svc(_int_batch(c, c + 600, n_keys), 0, out.append)
        full.eos_flush(out.append)
        ref = {(r.key, r.id): r.value for r in out}

        a = self._resident(128, 32)
        out = []
        for c in range(0, n // 2, 600):
            a.svc(_int_batch(c, c + 600, n_keys), 0, out.append)
        merged = a.keyed_state_dict()
        assert set(merged) == set(range(n_keys))
        parts = partition_keyed_state(merged, 2)
        reps = [self._resident(128, 32), self._resident(128, 32)]
        for part, rep in zip(parts, reps):
            rep.load_keyed_state(part)
        for c in range(n // 2, n, 600):
            batch = _int_batch(c, c + 600, n_keys)
            keys = batch.key
            for owner in (0, 1):
                mask = np.array([owner_of(int(k), 2) == owner
                                 for k in keys])
                if mask.any():
                    reps[owner].svc(batch.take(np.nonzero(mask)[0]),
                                    0, out.append)
        for rep in reps:
            rep.eos_flush(out.append)
        got = {(r.key, r.id): r.value for r in out}
        assert got == ref
        # and the merge invariant holds on the split replicas
        class _N:  # noqa: N801 - minimal RtNode stand-in
            def __init__(self, logic):
                self.logic = logic
                self.name = "ffat"
        merged2, stateful = merge_keyed_states([_N(r) for r in reps])
        assert stateful and set(merged2) == set(range(n_keys))


# ---------------------------------------------------------------------------
# online re-planning
# ---------------------------------------------------------------------------

class TestReplanDecision:
    def test_device_lane_measured_slow_flips_host(self):
        from windflow_tpu.graph.replanner import replan_decision
        v = replan_decision("device", measured_ms_per_launch=2.5,
                            tuples_per_launch=2048,
                            bytes_per_launch=1200, rtt_ms=0.01,
                            host_tps=20e6)
        assert v["placement"] == "host"
        assert v["measured_ms"] == 2.5
        assert v["device_compute_ms"] > 2.0

    def test_device_lane_measured_fast_stays(self):
        from windflow_tpu.graph.replanner import replan_decision
        v = replan_decision("device", measured_ms_per_launch=0.02,
                            tuples_per_launch=65536,
                            bytes_per_launch=1200, rtt_ms=0.01,
                            host_tps=20e6)
        assert v["placement"] == "device"

    def test_host_lane_wins_chip_back_with_cheap_calibration(self):
        from windflow_tpu.graph.replanner import replan_decision
        v = replan_decision("host", measured_ms_per_launch=None,
                            tuples_per_launch=65536,
                            bytes_per_launch=1200, rtt_ms=0.01,
                            host_tps=20e6, calibrated_compute_ms=0.01)
        assert v["placement"] == "device"
        v = replan_decision("host", measured_ms_per_launch=None,
                            tuples_per_launch=65536,
                            bytes_per_launch=1200, rtt_ms=0.01,
                            host_tps=20e6, calibrated_compute_ms=50.0)
        assert v["placement"] == "host"


def _window_count(n, n_keys, win, slide):
    per_key = n // n_keys
    c = 0
    while c * slide < per_key:
        c += 1
    return c * n_keys


class TestReplanFlip:
    def test_scripted_load_shift_flips_lane_zero_loss(self):
        """The acceptance scenario: auto resolves 'device' from the
        tiny pinned RTT floor, the launch walls measured on the CPU
        backend contradict the projection, and the re-planner flips the lane
        mid-run -- zero lost/duplicated windows (ledger balanced
        across the flip), values equal to the integer oracle on both
        sides of the flip, flip visible as a ``replacement`` flight
        event and explained by doctor.  The paced stream keeps
        flowing until the flip lands (bounded), so the proof is
        robust to a loaded box."""
        win, slide, sb, cap = 1024, 32, 1500, 800
        cfg = RuntimeConfig(mode=Mode.DEFAULT, replan=True,
                            replan_ticks=2, diagnosis_interval_s=0.15,
                            audit_interval_s=0.1)
        g = wf.PipeGraph("replan_flip", wf.Mode.DEFAULT, cfg)
        rows = []
        op = WinSeqTPU("sum", win, slide, WinType.CB, batch_len=64,
                       inflight_depth=1, placement="auto",
                       value_of=lambda t: t.value)
        state = {"i": 0, "tail": 0}

        def batch():
            i = state["i"]
            flipped = any(e["kind"] == "replacement"
                          for e in g.flight.snapshot())
            if flipped:
                state["tail"] += 1
            if i >= cap * sb or state["tail"] > 25:
                return None  # flip landed (plus a post-flip tail)
            state["i"] = i + sb
            time.sleep(0.004)
            return _int_batch(i, i + sb)

        g.add_source(BatchSource(batch)).add(op).add_sink(
            Sink(rows.append))
        g.run()
        n = state["i"]
        got = {}
        for r in rows:
            if r is None:  # EOS sentinel
                continue
            got[(r.key, r.id)] = got.get((r.key, r.id), []) + [r.value]
        entry = next(p for p in g.placements
                     if "win_seq_tpu" in p["operator"])
        assert entry["placement"] == "host" and entry.get("replanned")
        flips = [e for e in g.flight.snapshot()
                 if e["kind"] == "replacement"]
        assert flips and flips[0]["old"] == "device" \
            and flips[0]["new"] == "host"
        assert flips[0]["evidence"]["measured_ms"] > 0
        # zero lost / duplicated windows across the flip, values ==
        # the integer oracle on BOTH sides (host f64 and device f32
        # sums agree exactly on these magnitudes)
        assert all(len(v) == 1 for v in got.values())
        assert len(got) == _window_count(n, N_KEYS, win, slide)
        per_key = n // N_KEYS
        for key in range(N_KEYS):
            for w in (0, per_key // (2 * slide),
                      (per_key - 1) // slide):
                ids = range(w * slide, min(w * slide + win, per_key))
                want = float(sum((i * N_KEYS + key) % 7 for i in ids))
                assert got[(key, w)][0] == want, (key, w)
        # ledger balanced: a violation would have been flagged
        assert not [e for e in g.flight.snapshot()
                    if e["kind"] == "conservation_violation"]
        # doctor explains the flip
        rep = g.explain()
        assert rep["Replacements"] and \
            rep["Replacements"][0]["operator"] == flips[0]["operator"]
        from windflow_tpu.diagnosis.report import render_text
        txt = render_text(rep)
        assert "lane replacements (online re-planning):" in txt
        assert "device -> host" in txt


# ---------------------------------------------------------------------------
# durability: resident engines across epochs, crashes and lane flips
# ---------------------------------------------------------------------------

class _CkptSourceLogic(SourceLoopLogic):
    def __init__(self, n, pace_every=128, pace_s=0.001):
        self.i = 0
        self.n = n
        self.pace_every = pace_every
        self.pace_s = pace_s
        super().__init__(self._step)

    def _step(self, emit):
        i = self.i
        if i >= self.n:
            return False
        if self.pace_every and i % self.pace_every == 0:
            time.sleep(self.pace_s)
        emit(BasicRecord(i % N_KEYS, i // N_KEYS, i // N_KEYS,
                         float(i % 7)))
        self.i = i + 1
        return True

    def state_dict(self):
        return {"i": self.i}

    def load_state(self, st):
        self.i = st["i"]

    def progress_frontier(self):
        return self.i


class CkptSource(Operator):
    def __init__(self, n, name="ckpt_source", pace_every=128,
                 pace_s=0.001):
        super().__init__(name, 1, RoutingMode.NONE, Pattern.SOURCE)
        self.n = n
        self.pace_every = pace_every
        self.pace_s = pace_s

    def stages(self):
        logic = _CkptSourceLogic(self.n, self.pace_every, self.pace_s)
        return [StageSpec(self.name, [logic], StandardEmitter(),
                          self.routing)]


class TestResidentDurability:
    def _ffat_run(self, path, n, fault=None):
        from windflow_tpu.core import DurabilityConfig
        from windflow_tpu.durability import run_with_epochs
        from windflow_tpu.resilience.faults import FaultPlan
        wins = {}
        counts = collections.Counter()

        def sink(r):
            if r is None:
                return
            wins[(r.key, r.id)] = r.value
            counts[(r.key, r.id)] += 1

        graphs = []

        def factory(attempt):
            plan = fault if attempt == 0 else None
            cfg = wf.RuntimeConfig(
                durability=DurabilityConfig(epoch_interval_s=0.05,
                                            path=path),
                fault_plan=plan)
            g = wf.PipeGraph("dur_resident", wf.Mode.DEFAULT,
                             config=cfg)
            op = wf.WinSeqFFATTPUBuilder(lambda t: t.value, "sum") \
                .with_cb_windows(96, 16).build()
            assert isinstance(op, WinSeqFFATResident)  # default lane
            g.add_source(CkptSource(n, pace_every=64, pace_s=0.002)) \
                .add(op) \
                .add_sink(wf.SinkBuilder(sink).with_exactly_once()
                          .build())
            graphs.append(g)
            return g

        g = run_with_epochs(factory, max_restarts=2)
        return g, wins, counts

    def test_crash_restart_verify_resident_ffat(self, tmp_path):
        """Kill-restart-verify with the device-resident FFAT engine
        (on the CPU backend): epoch snapshots carry the resident forest,
        the restored run is bitwise equal to an uninterrupted one."""
        from windflow_tpu.resilience.faults import FaultPlan
        N = 5000
        _g, ref, ref_counts = self._ffat_run(str(tmp_path / "ref"), N)
        assert ref and max(ref_counts.values()) == 1
        # the builder names the op win_seqffat_tpu (the resident logic
        # rides the same builder); the crash clock binds per fused
        # segment, so the substring must match the SEGMENT name
        plan = FaultPlan(seed=9).crash_replica("win_seqffat_tpu",
                                               at_tuple=2500)
        g, wins, counts = self._ffat_run(str(tmp_path / "chaos"), N,
                                         fault=plan)
        assert getattr(g, "_epoch_restored", None) is not None
        assert max(counts.values()) == 1, "duplicate windows"
        assert wins == ref

    def test_lane_flip_between_epochs_exactly_once(self, tmp_path):
        """A scripted mid-run device->host lane flip lands between two
        epochs (replace_lane holds the epoch cadence like a rescale);
        a crash after the flip restarts from a committed epoch and the
        resolved results equal the uninterrupted no-flip run."""
        from windflow_tpu.core import DurabilityConfig
        from windflow_tpu.durability import run_with_epochs
        from windflow_tpu.resilience.faults import FaultPlan
        N, WIN, SLIDE = 6000, 64, 32

        def run(path, flip, fault):
            wins = {}
            counts = collections.Counter()

            def sink(r):
                if r is None:
                    return
                wins[(r.key, r.id)] = r.value
                counts[(r.key, r.id)] += 1

            flips = []

            def factory(attempt):
                plan = fault if attempt == 0 else None
                cfg = wf.RuntimeConfig(
                    durability=DurabilityConfig(epoch_interval_s=0.05,
                                                path=path),
                    fault_plan=plan)
                g = wf.PipeGraph("dur_flip", wf.Mode.DEFAULT,
                                 config=cfg)
                op = WinSeqTPU("sum", WIN, SLIDE, WinType.CB,
                               batch_len=32, placement="device",
                               value_of=lambda t: t.value)
                g.add_source(CkptSource(N, pace_every=32,
                                        pace_s=0.004)) \
                    .add(op) \
                    .add_sink(wf.SinkBuilder(sink).with_exactly_once()
                              .build())
                if flip and attempt == 0:
                    def flipper():
                        time.sleep(0.3)
                        try:
                            ev = g.replace_lane(
                                "pipe0/win_seq_tpu.0", "host",
                                trigger="script")
                            flips.append(ev)
                        except Exception:
                            pass  # graph already dead (late crash)
                    threading.Thread(target=flipper,
                                     daemon=True).start()
                return g

            g = run_with_epochs(factory, max_restarts=2)
            return g, wins, counts, flips

        _gr, ref, rc, _ = run(str(tmp_path / "ref"), False, None)
        assert ref and max(rc.values()) == 1
        # crash the ENGINE's tuple clock (a source's clock never ticks:
        # it consumes nothing), late enough to land after the flip
        plan = FaultPlan(seed=13).crash_replica("win_seq_tpu",
                                                at_tuple=5200)
        g, wins, counts, flips = run(str(tmp_path / "chaos"), True,
                                     plan)
        assert flips and flips[0] is not None  # the flip happened
        assert getattr(g, "_epoch_restored", None) is not None
        assert max(counts.values()) == 1, "duplicate windows"
        assert wins == ref
