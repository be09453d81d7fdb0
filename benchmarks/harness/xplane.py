"""Reduction of a profiler trace (``.xplane.pb``) to what the metrics
read: device busy time, device ops by total time, the longest idle gaps
by what the host was doing.

The window is cut out of the trace by two host annotations the generator
writes (``bench/window_open`` and ``bench/window_close``); the benchmark's
own host spans (``bench/source``, ``bench/sink``) attribute the gaps.
"""
import glob
import os

OPEN_MARK, CLOSE_MARK = "bench/window_open", "bench/window_close"
OPS_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
TOP = 10


def find_trace(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def read_planes(path):
    """{plane name: {line name: [(name, start_ns, end_ns), ...]}} with
    nothing but JAX."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            for ev in line.events:
                evs.append((ev.name, float(ev.start_ns),
                            float(ev.start_ns + ev.duration_ns)))
    return planes


def union_s(intervals):
    """Seconds covered by the union of (start_ns, end_ns) intervals, and
    the merged intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged) / 1e9, merged


def _clip(events, lo, hi):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def short_name(op, module=None):
    """``jit_run:fusion.3`` from the trace's ``%fusion.3 = (...) fusion(...),
    kind=kLoop, ...`` inside the module ``jit_run(1176...)``."""
    op = op.split(" = ", 1)[0].strip().lstrip("%")
    if module:
        op = module.split("(", 1)[0] + ":" + op
    return op


def _module_of(modules, t):
    """Name of the module event that covers time ``t`` (sorted input)."""
    import bisect
    i = bisect.bisect_right(modules, (t, "\uffff")) - 1
    if i >= 0 and modules[i][2] >= t:
        return modules[i][1]
    return None


def reduce(planes):
    """What the metrics read from one trace.  ``busy_s`` is averaged over
    the devices that ran anything; ``idle_share`` is the fullest device's.
    Without both marks the window is the whole trace and ``marks_found``
    is false: :func:`reduce_window` refuses such a trace."""
    host = [ev for pname, lines in planes.items()
            if not pname.startswith("/device:")
            for evs in lines.values() for ev in evs]
    opens = [s for n, s, _ in host if n == OPEN_MARK]
    closes = [s for n, s, _ in host if n == CLOSE_MARK]
    devices = {p: [ev for ln, evs in lines.items() if ln in OPS_LINES
                   for ev in evs]
               for p, lines in planes.items() if p.startswith("/device:")}
    devices = {p: evs for p, evs in devices.items() if evs}
    if not devices:
        raise ValueError("the trace holds no device operation")
    every = [ev for evs in devices.values() for ev in evs]
    lo = opens[0] if opens else min(s for _, s, _ in every)
    hi = closes[-1] if closes else max(e for _, _, e in every)
    window_s = (hi - lo) / 1e9
    busy, merged_of = {}, {}
    op_s = {}
    for p, evs in devices.items():
        evs = _clip(evs, lo, hi)
        busy[p], merged_of[p] = union_s([(s, e) for _, s, e in evs])
        modules = sorted((s, n, e) for ln, mevs in planes[p].items()
                         if ln in MODULE_LINES for n, s, e in mevs)
        for n, s, e in evs:
            d = (e - s) / 1e9
            n = short_name(n, _module_of(modules, s))
            op_s[n] = op_s.get(n, 0.0) + d
    fullest = max(busy, key=busy.get)
    spans = sorted((s, e, n) for n, s, e in _clip(host, lo, hi)
                   if n.startswith("bench/") and n not in (OPEN_MARK,
                                                           CLOSE_MARK))
    gaps = []
    edges = [lo] + [x for iv in merged_of[fullest] for x in iv] + [hi]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            gaps.append((b - a, (a + b) / 2))
    gaps.sort(reverse=True)
    idle_gaps = [[_host_was(spans, mid), d / 1e9] for d, mid in gaps[:TOP]]
    ops = sorted(op_s.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": window_s,
        "busy_s": sum(busy.values()) / len(busy),
        "idle_share": 1.0 - busy[fullest] / window_s,
        "n_devices": len(busy),
        "device_ops": [[n, s] for n, s in ops],
        "idle_gaps": idle_gaps,
        "marks_found": bool(opens and closes),
    }


def reduce_window(planes):
    """:func:`reduce`, for a run: a trace that lacks a mark would give the
    device's busy and idle time over warm-up and drain as well, under the
    window's name, so it is refused."""
    r = reduce(planes)
    if not r["marks_found"]:
        raise ValueError(f"the trace lacks {OPEN_MARK} or {CLOSE_MARK}: "
                         "no window to cut out of it")
    return r


def _host_was(spans, t):
    """The benchmark's span that covered ``t``, else the program, after
    the benchmark span that ended last before ``t``."""
    last = None
    for s, e, n in spans:
        if s > t:
            break
        if e >= t:
            return n
        if last is None or e > last[0]:
            last = (e, n)
    return f"program/after_{last[1]}" if last else "program"
