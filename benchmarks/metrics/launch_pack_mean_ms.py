"""Dispatch: mean ``t_packed - t_picked`` of the launches whose result
reached the host in the window: the host preparing what it hands the
runtime (the launch's shape and program, the pooled buffer,
``pack_launch``), the first part of ``launch_dispatch_mean_ms``.  Over
the launches that carry the stamp; nothing on a program whose launch
records have none, or on a lane that takes none (host, mesh).  The
manifest has one ``moves`` a metric, ``events_per_s``, which every cell
reports and the dispatcher's thread bounds at saturation; in the two
open-loop cells (``nexmark_q5.paced``, ``nexmark_q5.burst``) the rate is
the schedule's and what this moves is ``result_latency_p50_ms``, as
``launch_dispatch_mean_ms`` does there."""
from benchmarks.harness import program_spans


def part_mean_ms(rec, later, earlier):
    """Mean of ``later - earlier`` over the window's launches that carry
    the compute engine's two stamps inside ``dispatch``."""
    recs = [r for r in program_spans._launches(rec) or ()
            if getattr(r, "t_packed", None) is not None]
    if not recs:
        return None
    return 1e3 * sum(getattr(r, later) - getattr(r, earlier)
                     for r in recs) / len(recs)


def read(rec):
    return part_mean_ms(rec, "t_packed", "t_picked")
