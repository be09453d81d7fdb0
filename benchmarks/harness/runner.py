"""One run of one cell: build the graph, warm up, measure for
``seconds``, drain, check what the sink received, reduce the readings.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is found by its name in ``BENCHMARK.json``:

    configs/<config>/config.json, pipeline.py
    traffic/<mix>.json
    metrics/<metric>.py
"""
import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import threading
import time

import numpy as np

from . import check, window, xplane
from .generator import Generator

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class NoChip(SystemExit):
    """No accelerator, or fewer chips than the cell asks for."""


def seconds_since_process_start():
    """From the kernel's record of when this process was made (10 ms
    ticks): imports and the interpreter's own start count as set-up."""
    with open("/proc/self/stat") as f:
        ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - ticks / os.sysconf("SC_CLK_TCK")


def load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path):
    with open(path) as f:
        return json.load(f)


def stats_sum(graph, operator, field):
    """Sum of one field of the live stats records of the replicas of the
    operator whose name contains ``operator`` (``num_launches`` is the
    stats JSON's ``Device_launches``, ``device_time_ms`` its
    ``Device_time_ms``: a host wall, submit to result)."""
    return sum(getattr(r, field)
               for name, reps in graph.stats.records.items()
               if operator in name for r in reps)


class Cell:
    """A workload of the manifest, resolved to its files."""

    def __init__(self, manifest, workload, bench_dir=HERE):
        entry = next((w for w in manifest["workloads"]
                      if w["name"] == workload), None)
        if entry is None:
            raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
        self.name, self.chips = workload, entry["chips"]
        self.config_name, self.traffic_name = entry["config"], \
            entry["traffic"]
        conf = next(c for c in manifest["configs"]
                    if c["name"] == self.config_name)
        root = os.path.dirname(bench_dir)
        self.cfg = load_json(os.path.join(root, conf["file"]))
        self.pipeline = load_module(
            os.path.join(os.path.dirname(os.path.join(root, conf["file"])),
                         "pipeline.py"),
            f"benchmarks_pipeline_{self.config_name}")
        self.traffic = load_json(os.path.join(
            bench_dir, "traffic", self.traffic_name + ".json"))
        self.bench_dir = bench_dir

        def mine(m):
            return workload in m.get("workloads", [workload])
        self.end_to_end = [m for m in manifest["end_to_end"] if mine(m)]
        self.per_layer = [m for m in manifest["per_layer"] if mine(m)]

    def reader(self, metric):
        path = os.path.join(self.bench_dir, "metrics", metric + ".py")
        return load_module(path, f"benchmarks_metric_{metric}").read


@dataclasses.dataclass
class Doors:
    """What the tests open of a run, and a chip run leaves shut."""
    require_tpu: bool = True
    bench_dir: str = HERE
    out: object = None          # earlier lines; default standard output
    err: object = None          # the check's lines; default standard error
    tamper: object = None       # handed (graph, generator, sink) before start


class SinkRecorder:
    """The graph's sink function: stamps and keeps every result batch,
    and runs the configuration's fold, where it has one."""

    def __init__(self, clock, span, fold=None):
        self.clock, self.span, self.fold = clock, span, fold
        self.lock = threading.Lock()
        self.t_in, self.t_out, self.batches = [], [], []

    def __call__(self, item):
        if item is None:
            return
        t_in = self.clock()
        with self.span("bench/sink"), self.lock:
            self.batches.append((item.key, item.id, item["value"]))
            if self.fold is not None:
                self.fold.add(*self.batches[-1])
            self.t_in.append(t_in)
            self.t_out.append(self.clock())

    def n_batches(self):
        return len(self.t_in)

    def columns(self):
        """(receipt time per row, keys, window ids, values)."""
        if not self.batches:
            z = np.empty(0)
            return z, z.astype(np.int64), z.astype(np.int64), z
        t = np.repeat(np.asarray(self.t_in),
                      [len(b[0]) for b in self.batches])
        return (t, *(np.concatenate([np.asarray(b[j]) for b in self.batches])
                     for j in range(3)))


def open_device(chips, require_tpu):
    """JAX through the program's own door (which places the compile
    cache), and the device as JAX reports it."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    from windflow_tpu.ops.backend import jax_modules
    jax, _ = jax_modules()
    devs = jax.devices()
    if require_tpu and (jax.default_backend() != "tpu"
                        or len(devs) < chips):
        raise NoChip(f"benchmark: backend {jax.default_backend()!r} with "
                     f"{len(devs)} device(s); the cell needs {chips} TPU "
                     "chip(s)")
    return jax, {"platform": devs[0].platform, "kind": devs[0].device_kind,
                 "count": len(devs)}


def memory_peak_bytes(jax):
    return max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in jax.devices()), default=0)


def run_cell(manifest, workload, seed, seconds, trace, doors=None):
    """Run one cell and return its result line as a dict."""
    doors = doors or Doors()
    bench_dir = doors.bench_dir
    out, err = doors.out or sys.stdout, doors.err or sys.stderr
    parts = {"to_run_cell": seconds_since_process_start()}
    t_part = time.perf_counter()

    def part(name):
        nonlocal t_part
        parts[name] = time.perf_counter() - t_part
        t_part = time.perf_counter()

    cell = Cell(manifest, workload, bench_dir)
    cfg, traffic, pipeline = cell.cfg, cell.traffic, cell.pipeline
    jax, device = open_device(cell.chips, doors.require_tpu)
    part("jax_and_device")
    import windflow_tpu as wf
    from windflow_tpu.core.tuples import TupleBatch
    clock = time.perf_counter
    if trace:
        from jax.profiler import TraceAnnotation as span
    else:
        span = contextlib.nullcontext

    compiles = []
    import jax.monitoring
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append((clock(), name, secs))
        if name == BACKEND_COMPILE else None)

    collections = []            # (start, seconds, generation)

    def on_gc(phase, info):
        if phase == "start":
            collections.append([clock(), None, info["generation"]])
        elif collections:
            collections[-1][1] = clock() - collections[-1][0]

    gc.callbacks.append(on_gc)
    pool = pipeline.make_pool(cfg, seed)
    part("pool")
    sink = SinkRecorder(clock, span, pipeline.SinkFold(cfg)
                        if hasattr(pipeline, "SinkFold") else None)
    snaps = {}

    def snapshot(tag, now):
        with span("bench/window_" + tag):
            snaps[tag] = {
                "cpu_s": time.process_time(),
                "launches": pipeline.launches(graph),
                "device_time_ms": (pipeline.device_time_ms(graph)
                                   if hasattr(pipeline, "device_time_ms")
                                   else None)}

    gen = Generator(pool, traffic, seconds, TupleBatch, clock=clock,
                    results_seen=sink.n_batches,
                    on_open=lambda now: snapshot("open", now),
                    on_close=lambda now: snapshot("close", now))

    def body(ctx=None):
        with span("bench/source"):
            return gen(ctx)

    t_build = clock()
    graph = wf.PipeGraph("bench_" + cell.config_name, wf.Mode.DEFAULT)
    pipeline.build(graph, cfg, body, sink, seed)
    if doors.tamper is not None:
        doors.tamper(graph, gen, sink)
    graph.start()
    graph_start_s = clock() - t_build
    part("graph_start")

    trace_dir = os.path.join(bench_dir, "out", "trace",
                             f"{workload}-{seed}")
    if trace:
        # the profiler starts while the warm-up runs, so its start-up
        # stalls no chunk of the window
        time.sleep(traffic["warmup_s"])
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    gen.go.set()
    if not gen.closed.wait(seconds + traffic["warmup_s"]
                           + traffic.get("settle_max_s", 0) + 240):
        graph.cancel()
    setup_s = seconds_since_process_start() - (clock() - gen.t_open) \
        if gen.t_open is not None else None
    parts["start_to_open"] = gen.t_open - t_part if gen.t_open else None
    if trace:
        jax.profiler.stop_trace()
    graph.wait_end()
    gc.callbacks.remove(on_gc)
    if not gen.closed.is_set():
        raise SystemExit("benchmark: the window never closed")
    peak = memory_peak_bytes(jax)
    stats = json.loads(graph.stats.to_json(
        dropped_tuples=graph.get_num_dropped_tuples(),
        dead_letter_tuples=graph.dead_letters.count()))
    counters = {"dropped_tuples": stats["Dropped_tuples"],
                "dead_letters": stats["Dead_letter_tuples"],
                "svc_failures": stats["Svc_failures"],
                "shed_tuples": stats["Shed_tuples"]}
    n_offered = gen.sent
    del graph

    # -- the window's readings ---------------------------------------------
    t_open, t_close = gen.t_open, gen.t_close
    handoff_t, due_t, blocked_s = gen.window()
    events = len(handoff_t) * gen.chunk
    row_t, row_key, row_wid, row_val = sink.columns()
    in_win = (row_t > t_open) & (row_t <= t_close)
    latency_s = None
    if gen.paced:
        latency_s = window.result_latency_s(
            row_t[in_win], row_wid[in_win], cfg["win_events"],
            cfg["slide_events"], gen.created_at)
    lag_s = (handoff_t - due_t) if gen.paced else None
    rec = {
        "workload": workload, "seed": seed, "config": cfg,
        "traffic": traffic, "window_s": float(seconds),
        "events": events, "chunks": len(handoff_t),
        "chunk_events": gen.chunk,
        "setup_s": setup_s, "graph_start_s": graph_start_s,
        "blocked_s": blocked_s,
        "lag_s": lag_s,
        "cpu_s": snaps["close"]["cpu_s"] - snaps["open"]["cpu_s"],
        "launches": snaps["close"]["launches"] - snaps["open"]["launches"],
        "device_time_ms": (
            None if snaps["open"]["device_time_ms"] is None else
            snaps["close"]["device_time_ms"]
            - snaps["open"]["device_time_ms"]),
        "sink_busy_s": window.busy_s(sink.t_in, sink.t_out, t_open,
                                     t_close),
        "rows": int(in_win.sum()),
        "latency_s": latency_s,
        "bytes_per_row": pipeline.logical_bytes_per_row(cfg),
        "device": device, "peaks": load_json(os.path.join(
            bench_dir, "harness", "peaks.json")),
        "trace": None,
    }
    if trace:
        planes = xplane.read_planes(xplane.find_trace(trace_dir))
        shutil.rmtree(trace_dir)
        rec["trace"] = xplane.reduce_window(planes)

    metrics = {}
    for m in cell.end_to_end:
        metrics[m["name"]] = {"value": end_to_end(m["name"], rec),
                              "unit": m["unit"]}
    layer = {}
    for m in cell.per_layer:
        value = cell.reader(m["name"])(rec)
        if value is not None:
            layer[m["name"]] = {"value": float(value), "unit": m["unit"]}

    # -- the check: after the window, the peak and the graph ---------------
    t_chk = clock()
    want = pipeline.reference(cfg, seed, n_offered)
    numbers = check.compare((row_key, row_wid, row_val), want,
                            cfg["win_events"], cfg["slide_events"],
                            counters)
    if sink.fold is not None:
        numbers.update(check.compare_folds(
            sink.fold.result(), pipeline.reference_fold(want)))
    check_s = clock() - t_chk
    in_window_compiles = [c for c in compiles if t_open < c[0] <= t_close]
    notes = {
        "kind": "run", "workload": workload, "seed": seed, "trace": trace,
        "seconds": seconds, "events_offered": n_offered,
        "events_in_window": events, "rows_received": len(row_t),
        "rows_owed": len(want[0]), "warmup_s": t_open - gen.t0,
        "setup_parts": parts, "compiles": len(compiles),
        "compiles_in_window": len(in_window_compiles),
        "memory_peak_bytes": peak, "check_s": check_s,
        # where the window stood still: collections of the oldest
        # generation, and hand-offs the graph held for over 30 ms
        "gc_gen2_in_window": [
            [round(t - t_open, 3), round(d or 0.0, 4)]
            for t, d, g in collections if g == 2 and t_open < t <= t_close],
        "holds_over_30ms": [
            [round(t - t_open, 3), round(b, 4)]
            for t, b in zip(handoff_t, blocked_s) if b > 0.03][:50],
        "end_to_end": {k: v["value"] for k, v in metrics.items()},
        "per_layer": {k: v["value"] for k, v in layer.items()},
    }
    print(json.dumps(notes), file=out)
    write_out(bench_dir, workload, seed, notes,
              window.timeline(t_open, float(seconds), gen.chunk, handoff_t,
                              lag_s, row_t[in_win], latency_s))
    correct, compared = check.verdict(numbers, err)

    dev = dict(device, memory_peak_bytes=peak)
    result = {"correct": correct, "attempted": len(want[0]),
              "failed": int(numbers["rows_missing"] + numbers["rows_wrong"]
                            + numbers["rows_unexpected"])}
    if trace:
        tr = rec["trace"]
        dev.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        result["metrics"] = layer
        result["device"] = dev
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
    else:
        result["metrics"] = metrics
        result["device"] = dev
    result["compared"] = compared
    return result


def end_to_end(name, rec):
    """The end-to-end metrics, taken by the benchmark itself."""
    if name == "events_per_s":
        return rec["events"] / rec["window_s"]
    if name == "setup_s":
        return rec["setup_s"]
    if name == "result_latency_p50_ms":
        return 1e3 * window.quantile(rec["latency_s"], 0.5)
    raise KeyError(f"no end-to-end metric {name!r}")


def write_out(bench_dir, workload, seed, notes, rows):
    out_dir = os.path.join(bench_dir, "out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"{workload}-{seed}.jsonl"), "w") as f:
        for row in [notes] + rows:
            f.write(json.dumps(row) + "\n")
