"""What the program's own span layer (``windflow_tpu/telemetry/spans.py``)
says about a run's window: the readings behind the ``program_span``
metrics, and the naming of device idle gaps by program span.

The run record has no absolute stamp of the window, so :func:`window_of`
finds it: ``rec["setup_s"]`` is the opening in seconds since the process
started, and ``runner.seconds_since_process_start()`` gives the same
origin now, good to the 10 ms ticks of ``/proc``.  The readers cut the
span timelines (100 ms buckets) and the launch rings at those two
instants.  The registry outlives the graph, which is gone by the time a
reader runs.

A program without the span layer (a parent commit) gives every reader
``None``: nothing is raised, and the result line leaves the metric out.
"""
import time

from . import xplane

NOT_OPERATORS = ("audit", "diagnosis")   # threads of the program's planes


def span_layer():
    try:
        from windflow_tpu.telemetry import spans
    except ImportError:
        return None
    return spans


def window_of(rec):
    """(t_open, t_close) of the measured window on ``time.perf_counter``,
    the clock of the program's spans (and of the generator)."""
    if "_window_of" not in rec:
        from . import runner
        t_open = (time.perf_counter() - runner.seconds_since_process_start()
                  + rec["setup_s"])
        rec["_window_of"] = (t_open, t_open + rec["window_s"])
    return rec["_window_of"]


def graph_of(rec):
    """The run's entry in the span registry (``runner.run_cell`` names
    its graph ``bench_<config>``)."""
    spans = span_layer()
    if spans is None:
        return None
    return spans.graph("bench_" + rec["config"]["name"])


def _threads(rec):
    """The run's operator threads: per thread the rows ``spans.triad``
    gives for the window (seconds busy / idle / blocked / in the body per
    operator, and every phase's self seconds)."""
    spans, g = span_layer(), graph_of(rec)
    if g is None:
        return None
    threads = {}
    for row in spans.triad(g, *window_of(rec)):
        if row["operator"] not in NOT_OPERATORS:
            threads.setdefault(row["track"], []).append(row)
    return list(threads.values())


def pacing_thread(rec):
    """(thread name, busy share, coverage share) of the operator thread
    that is busiest in the window.  Busy is the self time of ``svc`` and
    of its working children (``keyby``, ``fold``, ``flush``, ``stage``,
    ``dispatch``, ``emit``), never a wait and never a source's ``body``
    (its loop less everything under a child span: the load generator
    and the loop's own few microseconds a step).  Coverage is everything
    the thread's spans account for, waits and ``body`` included."""
    threads = _threads(rec)
    if not threads:
        return None

    def busy(rows):
        return sum(r["busy_s"] for r in rows)
    rows = max(threads, key=busy)
    covered = sum(r[k] for r in rows
                  for k in ("busy_s", "idle_s", "blocked_s", "body_s"))
    return (rows[0]["thread"], busy(rows) / rec["window_s"],
            covered / rec["window_s"])


def _launches(rec):
    g = graph_of(rec)
    if g is None:
        return None
    t0, t1 = window_of(rec)
    return [r for ring in list(g.rings.values())
            for r in ring.finished(t0, t1)]


def launch_mean_ms(rec, later, earlier):
    """Mean of ``later - earlier`` (two stamps of the launch record) over
    the launches whose result reached the host in the window."""
    recs = _launches(rec)
    if not recs:
        return None
    return 1e3 * sum(getattr(r, later) - getattr(r, earlier)
                     for r in recs) / len(recs)


def longest_stall_ms(rec):
    """The longest single wait span (``get_wait``, ``put_wait``,
    ``submit_wait``, ``ready_wait``) of any thread of the graph that
    ended in the window."""
    spans, g = span_layer(), graph_of(rec)
    if g is None:
        return None
    return spans.longest_wait_ms(g, *window_of(rec))


# -- device idle gaps by span ----------------------------------------------

def read_planes(path):
    """:func:`xplane.read_planes` with every thread line kept apart:
    the profiler names a Python thread's line after the process, so
    lines of one name are told apart by ``#<n>`` (``xplane.read_planes``
    merges them, which is right for its two ``bench/`` spans and wrong
    for spans of several threads)."""
    from jax.profiler import ProfileData
    planes = {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            name, n = line.name, 1
            while name in lines:
                n += 1
                name = f"{line.name}#{n}"
            lines[name] = [(ev.name, float(ev.start_ns),
                            float(ev.start_ns + ev.duration_ns))
                           for ev in line.events]
    return planes


def gaps_by_span(planes, prefixes=("bench/", "wf/"), top=xplane.TOP):
    """The longest idle gaps of the fullest device inside the window's
    marks, each named by what the host's threads had open at its middle:
    per thread line the innermost span whose name starts with one of
    ``prefixes``, joined with `` | ``; where none covers it, the program
    after the span that ended last before it.  Takes :func:`read_planes`'
    dict (a thread a line) and returns ``[[name, seconds], ...]``, the
    shape of ``reduce()['idle_gaps']``: what ``xplane._host_was`` is to
    be replaced with."""
    marks = (xplane.OPEN_MARK, xplane.CLOSE_MARK)
    host_lines = [evs for pname, lines in planes.items()
                  if not pname.startswith("/device:")
                  for evs in lines.values()]
    every = [ev for evs in host_lines for ev in evs]
    opens = [s for n, s, _ in every if n == marks[0]]
    closes = [s for n, s, _ in every if n == marks[1]]
    busy = {}
    for pname, lines in planes.items():
        if pname.startswith("/device:"):
            evs = [(s, e) for ln, levs in lines.items()
                   if ln in xplane.OPS_LINES for _n, s, e in levs]
            if evs:
                busy[pname] = evs
    if not busy:
        raise ValueError("the trace holds no device operation")
    lo = opens[0] if opens else min(s for evs in busy.values()
                                    for s, _ in evs)
    hi = closes[-1] if closes else max(e for evs in busy.values()
                                       for _, e in evs)
    merged = {p: xplane.union_s([(max(s, lo), min(e, hi)) for s, e in evs
                                 if e > lo and s < hi])
              for p, evs in busy.items()}
    fullest = max(merged, key=lambda p: merged[p][0])
    edges = [lo] + [x for iv in merged[fullest][1] for x in iv] + [hi]
    gaps = sorted(((b - a, (a + b) / 2)
                   for a, b in zip(edges[0::2], edges[1::2]) if b > a),
                  reverse=True)[:top]
    lines = [sorted((s, e, n) for n, s, e in evs
                    if n.startswith(tuple(prefixes)) and n not in marks)
             for evs in host_lines]
    lines = [ln for ln in lines if ln]
    out = []
    for d, mid in gaps:
        names, last = set(), None
        for ln in lines:
            inner = None
            for s, e, n in ln:
                if s > mid:
                    break
                if e >= mid:
                    inner = n           # sorted by start: the last is innermost
                elif last is None or e > last[0]:
                    last = (e, n)
            if inner is not None:
                names.add(inner)
        name = " | ".join(sorted(names)) if names else (
            f"program/after_{last[1]}" if last else "program")
        out.append([name, d / 1e9])
    return out
