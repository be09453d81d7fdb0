#!/usr/bin/env python3
"""chip_smoke.py: does the columnar window path still start on the chip?

One process drives the ordinary public path -- ``PipeGraph`` ->
``BatchSource`` -> window operator -> ``Sink`` -> ``g.run()`` -- and
compares EVERY window with a plain numpy recomputation from the same seeded columns.  Counts and
max/min must match exactly; f32 sums to ``SUM_RTOL``.  Nothing is
caught: any mismatch, a missing chip, or a placement that is not
``device`` ends the run with a non-zero exit code and no result line.

Stages (one chip, the default):

  A  the fed headline: numpy columns into ``WinSeqTPU("sum")``, TB
     4096/2048, 64 keys, 64 M events after an 8 M warm-up of the same
     graph (native C++ staging).
  B  the Python-staged XLA lanes the native engine bypasses: a JAX
     window function ``fn(gwid, cols, mask)`` (``_custom_program``) and
     a non-builtin FFAT lift+combine (``_ffat_program``), 8 M events.
  C  the Python staging lane (a ``value_of``) under the whole-partition
     device step: CB 1024/16, 8 keys, 8 M events, equal to
     ``device_step=False`` and to numpy, <= 2 launches per chunk.
  D  Yahoo as the repo has it: ``models/yahoo.build_pipeline``, 16 M
     events, 1,000 ads, 100 campaigns, tumbling count, against
     ``np.bincount``.
  K  both Pallas kernels compiled for the chip (no interpret) at the
     engine's floor shape and at the largest shape their gate admits,
     each against its XLA twin.

``--chips 4`` runs the mesh stage instead, on a four-chip host:
``KeyFarmMesh`` on ``make_mesh(4, win_axis=1)``, ``PaneFarmMesh`` and
``WinMapReduceMesh`` on ``make_mesh(4, win_axis=2)`` through
``PipeGraph``, and ``ShardedWindowEngine.step``, with every sharded
operand on four distinct devices.

Every stage prints counts and its cold (first run, compiles) and warm
wall time.  The script prints no rate.  The last line of stdout is
``{"ok": true, "device": {...}}`` with the device as JAX reports it.
"""
import argparse
import json
import os
import sys
import threading
import time

import numpy as np

# relative tolerance for f32 sums against the float64 reference
SUM_RTOL = 1e-5

# the headline shape
WIN, SLIDE, N_KEYS = 4096, 2048, 64
SOURCE_BATCH = 1_048_576
DEVICE_BATCH = 4096
MAX_BUFFER = 1 << 21
INFLIGHT = 8


# ---------------------------------------------------------------------------
# the stream, its reference, and the sink that collects what came out
# ---------------------------------------------------------------------------

def seeded_values(n_events, seed, ints=0):
    """The value column.  Event i of every stream here has key
    ``i % n_keys`` and id = ts = ``i // n_keys``.  ``ints`` > 0 draws
    small integers, whose f32 sums are exact."""
    rng = np.random.default_rng(seed)
    if ints:
        return rng.integers(0, ints, n_events).astype(np.float32)
    return rng.random(n_events, dtype=np.float32)


def column_source(values, n_keys, chunk):
    """BatchSource function replaying ``values`` in ``chunk``-event
    TupleBatches."""
    from windflow_tpu.core.tuples import TupleBatch
    assert chunk % n_keys == 0
    lane = np.arange(chunk, dtype=np.int64)
    keys_t, ids_t = lane % n_keys, lane // n_keys
    state = {"i": 0}

    def source():
        i = state["i"]
        if i >= len(values):
            return None
        n = min(chunk, len(values) - i)
        state["i"] = i + n
        ids = ids_t[:n] + i // n_keys
        return TupleBatch({"key": keys_t[:n], "id": ids, "ts": ids,
                           "value": values[i:i + n]})

    return source


def reference_windows(values, n_keys, win, slide, fold):
    """[n_windows, n_keys] float64: ``fold`` over every window's slice
    of every key's series.  A window that the stream's end cuts short
    folds what there is of it, as the engines' EOS flush does."""
    per_key = values.reshape(-1, n_keys).astype(np.float64)
    n_ids = per_key.shape[0]
    n_windows = (n_ids - 1) // slide + 1
    out = np.empty((n_windows, n_keys))
    for w in range(n_windows):
        out[w] = fold(per_key[w * slide:w * slide + win])
    return out


class WindowSink:
    """Collects (key, window id, value) from result batches or records."""

    def __init__(self):
        from windflow_tpu.core.tuples import TupleBatch
        self._batch = TupleBatch
        self.lock = threading.Lock()
        self.batches, self.records = [], []

    def __call__(self, item):
        if item is None:
            return
        with self.lock:
            if isinstance(item, self._batch):
                self.batches.append((item.key, item.id, item["value"]))
            else:
                self.records.append((item.key, item.id, item.value))

    def table(self, shape):
        """Results as a dense [n_windows, n_keys] table; every cell
        must have arrived exactly once."""
        cols = self.batches + [tuple(zip(*self.records)) or ((), (), ())]
        keys = np.concatenate([c[0] for c in cols]).astype(np.int64)
        ids = np.concatenate([c[1] for c in cols]).astype(np.int64)
        vals = np.concatenate([c[2] for c in cols]).astype(np.float64)
        if not (len(keys) == shape[0] * shape[1]
                and (0 <= ids).all() and (ids < shape[0]).all()
                and (0 <= keys).all() and (keys < shape[1]).all()):
            raise AssertionError(
                f"{len(keys)} windows out, expected {shape[0]}x{shape[1]}")
        seen = np.zeros(shape, np.int64)
        np.add.at(seen, (ids, keys), 1)
        if not (seen == 1).all():
            raise AssertionError("a window is missing or came twice")
        out = np.empty(shape)
        out[ids, keys] = vals
        return out


def check(name, got, want, rtol=0.0):
    """Every window equal to the reference: exactly, or within rtol."""
    if got.shape != want.shape:
        raise AssertionError(f"{name}: shape {got.shape} != {want.shape}")
    err = np.abs(got - want)
    bad = err > rtol * np.abs(want)
    if bad.any():
        w, k = np.argwhere(bad)[0]
        raise AssertionError(
            f"{name}: {int(bad.sum())} of {got.size} windows differ "
            f"(rtol {rtol}); first: window {w} key {k} got {got[w, k]!r} "
            f"want {want[w, k]!r}")
    worst = float((err / np.maximum(np.abs(want), 1e-30)).max()) \
        if rtol else 0.0
    print(f"  {name}: {got.size} windows equal to the numpy reference "
          + (f"(rtol {rtol}, worst {worst:.2e})" if rtol else "(exact)"))


# ---------------------------------------------------------------------------
# running one graph and reading its counters
# ---------------------------------------------------------------------------

def run_graph(name, source, make_op, config=None):
    """source -> make_op() -> WindowSink through a PipeGraph; returns
    (graph, sink, wall seconds)."""
    import windflow_tpu as wf
    from windflow_tpu.operators.basic_ops import Sink
    from windflow_tpu.operators.batch_ops import BatchSource
    g = wf.PipeGraph(name, wf.Mode.DEFAULT, config=config)
    sink = WindowSink()
    g.add_source(BatchSource(source)).add(make_op()).add_sink(Sink(sink))
    t0 = time.perf_counter()
    g.run()
    return g, sink, time.perf_counter() - t0


def device_counters(g):
    """Launches, bytes and resident state summed over the graph's
    replicas (the stats JSON's per-replica device fields)."""
    tot = {"Device_launches": 0, "Bytes_to_device": 0,
           "Bytes_from_device": 0, "Device_state_bytes_resident": 0}
    for op in json.loads(g.stats.to_json())["Operators"]:
        for rep in op["Replicas"]:
            for k in tot:
                tot[k] += rep.get(k) or 0
    return tot


def native_used(g):
    """Whether the graph's window engine staged through the C++ engine."""
    from windflow_tpu.graph.fuse import find_logic
    from windflow_tpu.operators.tpu.win_seq_tpu import WinSeqTPULogic
    logic = find_logic(g, lambda lg: isinstance(lg, WinSeqTPULogic))
    return logic is not None and logic._native is not None


def report(stage, g, n_events, n_windows, cold_s, warm_s, want_native):
    """Print one stage's counts and hold it to the device path."""
    from windflow_tpu.ops.backend import jax_modules
    jax, _ = jax_modules()
    c = device_counters(g)
    placements = [(p["operator"], p["placement"]) for p in g.placements]
    used = native_used(g)
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    print(f"  stage {stage}: tuples_in={n_events} windows_out={n_windows} "
          f"launches={c['Device_launches']} "
          f"bytes_to_device={c['Bytes_to_device']} "
          f"bytes_from_device={c['Bytes_from_device']} "
          f"state_bytes_resident={c['Device_state_bytes_resident']} "
          f"native_engine={used} placements={placements} "
          f"cold_s={cold_s:.2f} "
          f"warm_s={warm_s:.2f} peak_bytes_in_use={peak}")
    if not placements or any(p != "device" for _, p in placements):
        raise AssertionError(f"stage {stage}: placements {placements}")
    if c["Device_launches"] <= 0:
        raise AssertionError(f"stage {stage}: no device launch")
    if want_native and not used \
            and os.environ.get("WINDFLOW_NATIVE", "1") != "0":
        raise AssertionError(
            f"stage {stage}: the native engine is not in use "
            "(native library unavailable? see stderr)")
    return c


# ---------------------------------------------------------------------------
# stages A-D
# ---------------------------------------------------------------------------

def stage_a(n_events=64_000_000, n_warm=8_000_000, n_keys=N_KEYS, win=WIN,
            slide=SLIDE, chunk=SOURCE_BATCH):
    """The fed headline lane."""
    import windflow_tpu as wf
    from windflow_tpu.operators.tpu.win_seq_tpu import WinSeqTPU
    values = seeded_values(n_events, seed=1)

    def run(n):
        g, sink, wall = run_graph(
            "smoke_a", column_source(values[:n], n_keys, chunk),
            lambda: WinSeqTPU("sum", win, slide, wf.WinType.TB,
                              batch_len=DEVICE_BATCH, emit_batches=True,
                              max_buffer_elems=MAX_BUFFER,
                              inflight_depth=INFLIGHT))
        want = reference_windows(values[:n], n_keys, win, slide,
                                 lambda s: s.sum(axis=0))
        check(f"A sum, {n} events", sink.table(want.shape), want, SUM_RTOL)
        return g, want.size, wall

    _, _, cold = run(n_warm)
    g, n_windows, warm = run(n_events)
    report("A", g, n_events, n_windows, cold, warm, want_native=True)


def _sum_of_squares(gwid, cols, mask):
    from windflow_tpu.ops.backend import jax_modules
    _, jnp = jax_modules()
    v = cols["value"]
    return jnp.sum(jnp.where(mask, v * v, 0.0))


def stage_b(n_events=8_000_000, n_keys=N_KEYS, win=WIN, slide=SLIDE,
            chunk=SOURCE_BATCH):
    """The Python-staged XLA lanes: custom window function, custom FFAT."""
    import windflow_tpu as wf
    from windflow_tpu.operators.tpu.farms_tpu import WinSeqFFATTPU
    from windflow_tpu.operators.tpu.win_seq_tpu import WinSeqTPU
    from windflow_tpu.ops.backend import jax_modules
    _, jnp = jax_modules()
    values = seeded_values(n_events, seed=2)
    lanes = {
        # vmapped over gathered [B, W] tiles (_custom_program)
        "custom fn": (
            lambda: WinSeqTPU(_sum_of_squares, win, slide, wf.WinType.TB,
                              batch_len=DEVICE_BATCH, emit_batches=True,
                              max_buffer_elems=MAX_BUFFER,
                              inflight_depth=INFLIGHT),
            lambda s: (s * s).sum(axis=0)),
        # device FlatFAT build + range query (_ffat_program); hypot is
        # an associative combine no builtin covers
        "ffat hypot": (
            lambda: WinSeqFFATTPU(lambda t: t.value, (jnp.hypot, 0.0),
                                  win, slide, wf.WinType.TB,
                                  batch_len=DEVICE_BATCH,
                                  max_buffer_elems=MAX_BUFFER,
                                  inflight_depth=INFLIGHT),
            lambda s: np.sqrt((s * s).sum(axis=0))),
    }
    for name, (make_op, fold) in lanes.items():
        want = reference_windows(values, n_keys, win, slide, fold)
        walls = []
        for _ in range(2):
            g, sink, wall = run_graph(
                "smoke_b", column_source(values, n_keys, chunk), make_op)
            check(f"B {name}", sink.table(want.shape), want, SUM_RTOL)
            walls.append(wall)
        report(f"B/{name}", g, n_events, want.size, walls[0], walls[1],
               want_native=False)


def stage_c(n_events=8_000_000, n_keys=8, win=1024, slide=16, chunk=8192,
            batch_len=16):
    """The Python staging lane under the whole-partition device step."""
    import windflow_tpu as wf
    from windflow_tpu.graph.device_step import DeviceStepLogic
    from windflow_tpu.operators.tpu.win_seq_tpu import WinSeqTPU
    values = seeded_values(n_events, seed=3, ints=97)
    want = reference_windows(values, n_keys, win, slide,
                             lambda s: s.sum(axis=0))

    def run(step):
        g, sink, wall = run_graph(
            "smoke_c", column_source(values, n_keys, chunk),
            lambda: WinSeqTPU("sum", win, slide, wf.WinType.CB,
                              batch_len=batch_len,
                              max_buffer_elems=MAX_BUFFER,
                              inflight_depth=INFLIGHT,
                              value_of=lambda t: t.value),
            config=wf.RuntimeConfig(device_step=step))
        check(f"C device_step={step}", sink.table(want.shape), want)
        steps = [n.logic for n in g._all_nodes()
                 if isinstance(n.logic, DeviceStepLogic)]
        if bool(steps) != step:
            raise AssertionError(
                f"device step engaged={bool(steps)}, asked {step}")
        return g, steps, wall

    _, _, cold = run(True)
    g, steps, warm = run(True)
    run(False)
    c = report("C", g, n_events, want.size, cold, warm, want_native=False)
    chunks = sum(s.chunks_in for s in steps)
    boundary = sum(s.chunk_launches for s in steps)
    print(f"  stage C: chunks={chunks} boundary_launches={boundary}")
    if not (chunks > 0 and boundary <= 2 * chunks
            and c["Device_launches"] <= 2 * chunks):
        raise AssertionError("more than 2 launches per chunk")


def stage_d(n_events=16_000_000, n_ads=1000, n_campaigns=100,
            win=1 << 20, chunk=SOURCE_BATCH):
    """Yahoo: views -> campaign join -> tumbling count per campaign."""
    import windflow_tpu as wf
    from windflow_tpu.models import yahoo

    # the model's own seeded generator, replayed here without the graph
    pool = yahoo.synth_events(chunk, n_ads, seed=0)
    campaign_of_ad = yahoo.make_campaign_map(n_ads, n_campaigns)
    ts = np.arange(n_events)
    idx = ts % chunk
    views = pool["event_type"][idx] == yahoo.VIEW
    campaign = campaign_of_ad[pool["ad_id"][idx]][views]
    window = (ts // win)[views]
    n_windows = (n_events - 1) // win + 1
    want = np.bincount(window * n_campaigns + campaign,
                       minlength=n_windows * n_campaigns) \
        .reshape(n_windows, n_campaigns).astype(np.float64)

    walls = []
    for _ in range(2):
        g = wf.PipeGraph("smoke_d", wf.Mode.DEFAULT)
        sink = WindowSink()
        yahoo.build_pipeline(g, n_events, n_ads=n_ads,
                             n_campaigns=n_campaigns, win_len=win,
                             slide_len=win, batch_size=chunk,
                             device_batch=DEVICE_BATCH, sink=sink)
        t0 = time.perf_counter()
        g.run()
        walls.append(time.perf_counter() - t0)
        check("D view counts", sink.table(want.shape), want)
    report("D", g, n_events, want.size, walls[0], walls[1],
           want_native=True)


# ---------------------------------------------------------------------------
# stage K: the Pallas kernels against their XLA twins
# ---------------------------------------------------------------------------

def stage_kernels(shapes=None):
    """Compile both opt-in kernels at each (T_pad, B_pad) and compare
    with the XLA program the engine would otherwise run.  Integer
    values: both sides are exact."""
    from windflow_tpu.ops import window_compute as wc
    from windflow_tpu.ops.backend import jax_modules
    from windflow_tpu.ops.pallas.window_sum import (interpret_off_tpu,
                                                    window_sums_device)
    _, jnp = jax_modules()
    if shapes is None:
        # the engine's floor buckets, and the most its gate lets through
        shapes = [(2048, 2048), (wc._PALLAS_MAX_T, wc._PALLAS_MAX_B)]
    mode = "interpret" if interpret_off_tpu() else "compiled"
    for t_pad, b_pad in shapes:
        rng = np.random.default_rng(t_pad)
        vals = rng.integers(0, 8, t_pad).astype(np.float32)
        se = np.zeros((2, b_pad), np.int32)
        se[0] = rng.integers(0, t_pad, b_pad)
        se[1] = np.minimum(se[0] + rng.integers(0, 2 * WIN, b_pad), t_pad)
        # the XLA programs take the launch as the engine packs it
        packed = wc.pack_launch(
            np.empty(wc.packed_len(1, t_pad, b_pad), np.int32), (vals,), 0,
            se[0], se[1], t_pad, b_pad)
        t0 = time.perf_counter()
        got = np.asarray(window_sums_device(vals, se[0], se[1]))[:, 0]
        want = np.asarray(wc._block_sum_program(
            "sum", wc._block_levels(wc.next_pow2(2 * WIN)), t_pad,
            b_pad)(packed))
        if not (got == want).all():
            raise AssertionError(f"window_sum kernel != XLA at "
                                 f"T={t_pad} B={b_pad}")
        got = np.asarray(wc._ffat_pallas_program(
            jnp.maximum, -np.inf, t_pad, b_pad)(vals, se))
        want = np.asarray(wc._ffat_program(
            jnp.maximum, -np.inf, t_pad, b_pad)(packed))
        if not (got == want).all():
            raise AssertionError(f"flatfat_query kernel != XLA at "
                                 f"T={t_pad} B={b_pad}")
        print(f"  stage K: T_pad={t_pad} B_pad={b_pad} window_sum and "
              f"flatfat_query {mode}, equal to XLA (exact), "
              f"{time.perf_counter() - t0:.2f}s")


# ---------------------------------------------------------------------------
# the mesh stage (shared with __graft_entry__.dryrun_multichip)
# ---------------------------------------------------------------------------

def _on_all_devices(name, arrays, n_devices):
    for i, x in enumerate(arrays):
        devs = {s.device for s in x.addressable_shards}
        if len(devs) != n_devices:
            raise AssertionError(
                f"{name}[{i}] sits on {len(devs)} devices, not {n_devices}")


def stage_mesh(n_devices=4, n_events=8_000_000, n_keys=N_KEYS, win=WIN,
               slide=SLIDE, chunk=SOURCE_BATCH, batch_windows=1024,
               panes_per_epoch=16, step_shape=(1 << 16, 2048)):
    """The three mesh operators through PipeGraph plus the full sharded
    step, on the first ``n_devices`` devices JAX has.  An odd device
    count has no 'win' axis to split: only the key-sharded half runs."""
    import windflow_tpu as wf
    from windflow_tpu.graph.fuse import iter_logics
    from windflow_tpu.operators.tpu.mesh_farm import KeyFarmMesh
    from windflow_tpu.operators.tpu.pane_mesh import PaneFarmMesh
    from windflow_tpu.operators.tpu.wmr_mesh import WinMapReduceMesh
    from windflow_tpu.ops.backend import jax_modules
    from windflow_tpu.parallel.mesh import make_mesh
    from windflow_tpu.parallel.sharded import ShardedWindowEngine
    jax, _ = jax_modules()
    win_axis = 2 if n_devices % 2 == 0 else 1

    # one full sharded step: key-sharded sums, psum stripes, pane gather
    T, B = step_shape
    eng = ShardedWindowEngine(make_mesh(n_devices, win_axis=win_axis),
                              win_len=8, slide_len=4)
    args = eng.example_inputs(T=T, B=B, stripe_w=4, panes_per_shard=2,
                              pane_len=4)
    _on_all_devices("step operand", args, n_devices)
    out = eng.step(*args)
    jax.block_until_ready(out)
    _on_all_devices("step result", out, n_devices)
    v, s, e, stripe, pane = (np.asarray(a, np.float64) if a.dtype.kind == "f"
                             else np.asarray(a) for a in args)
    c = np.concatenate([np.zeros((len(v), 1)), np.cumsum(v, axis=1)], axis=1)
    # the key-sharded path differences an f32 prefix scan, so its error
    # scales with the prefix's magnitude, not the window's
    np.testing.assert_allclose(
        out[0], np.take_along_axis(c, e, 1) - np.take_along_axis(c, s, 1),
        rtol=1e-4, atol=1e-6 * np.abs(c).max())
    np.testing.assert_allclose(np.asarray(out[1])[:, 0],
                               stripe.sum(axis=(1, 3)), rtol=1e-4, atol=1e-5)
    partials = pane.sum(-1).reshape(len(pane), -1)
    np.testing.assert_allclose(
        out[2], np.stack([partials[:, w:w + 2].sum(-1) for w in
                          range(partials.shape[1] - 1)], axis=1),
        rtol=1e-4, atol=1e-5)
    print(f"  stage M: ShardedWindowEngine.step on a "
          f"{n_devices // win_axis}x{win_axis} mesh, T={T} B={B}: operands "
          f"and results on {n_devices} distinct devices, equal to numpy")

    values = seeded_values(n_events, seed=4, ints=8)
    want = reference_windows(values, n_keys, win, slide,
                             lambda s: s.sum(axis=0))
    ops = [("KeyFarmMesh", 1, lambda m: KeyFarmMesh(
        m, win, slide, wf.WinType.TB, batch_windows=batch_windows))]
    if win_axis > 1:
        ops += [
            ("PaneFarmMesh", win_axis, lambda m: PaneFarmMesh(
                m, win, slide, wf.WinType.TB,
                panes_per_epoch=panes_per_epoch)),
            ("WinMapReduceMesh", win_axis, lambda m: WinMapReduceMesh(
                m, win, slide, wf.WinType.TB, batch_windows=batch_windows)),
        ]
    for name, axis, make_op in ops:
        mesh = make_mesh(n_devices, win_axis=axis)
        walls = []
        for _ in range(2):
            g, sink, wall = run_graph(
                "smoke_mesh", column_source(values, n_keys, chunk),
                lambda: make_op(mesh))
            check(f"M {name}", sink.table(want.shape), want)
            walls.append(wall)
        launches = sum(getattr(lg, "launched_batches", 0)
                       for _, lg in iter_logics(g))
        print(f"  stage M/{name}: mesh={dict(mesh.shape)} "
              f"tuples_in={n_events} windows_out={want.size} "
              f"launches={launches} cold_s={walls[0]:.2f} "
              f"warm_s={walls[1]:.2f}")
        if launches <= 0:
            raise AssertionError(f"{name}: no mesh launch")


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: stages A-D and the kernels on one chip "
                         "(default); 4: the mesh stage on four")
    args = ap.parse_args(argv)
    from windflow_tpu.ops.backend import open_tpu
    t0 = time.perf_counter()
    device = open_tpu(args.chips)
    print(f"sum tolerance: rtol {SUM_RTOL}")
    if args.chips == 4:
        stage_mesh(4)
    else:
        from windflow_tpu.runtime import native
        if os.environ.get("WINDFLOW_NATIVE", "1") != "0" \
                and not native.native_available():
            raise SystemExit("chip_smoke: the native library did not "
                             "build on this host (see stderr)")
        print(f"native: {native.build_state}")
        for stage in (stage_a, stage_b, stage_c, stage_d, stage_kernels):
            stage()
    print(f"all stages passed in {time.perf_counter() - t0:.1f}s",
          file=sys.stderr)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
