"""The program's ``repro.*`` spans in trace rows, for the per-layer readers.

The program marks its phases with ``jax.profiler.TraceAnnotation`` spans
named ``repro.<layer>.<what>``.  A ``--trace 1`` run keeps them as host
rows ``[plane, line, name, start_ns, dur_ns]`` beside the device
operations, on the trace's one clock.  Rows keep no event stats, so a
reader pairs spans by their order in time; that is sound where one task
is in flight at a time, as in the rebind load.  Every helper returns None
where the rows hold none of the spans it reads (a program without them).
"""

from __future__ import annotations

from benchkit import trace

PREFIX = "repro."
SUBMIT = "repro.repo.submit"
WEIGHTS = "repro.payload.weights"
ADMIT = "repro.engine.admit"
DECODE = "repro.engine.decode"
READBACK = "repro.engine.readback"


def spans(rows, name: str) -> list[tuple[float, float]]:
    """``(start_ns, end_ns)`` of every host event named ``name``, in order
    of start."""
    return sorted((st, st + d) for plane, _, n, st, d in rows or ()
                  if n == name and not trace.is_device_plane(plane))


def mean_ms(intervals) -> float | None:
    """Mean length of ``(start_ns, end_ns)`` intervals, in ms."""
    return (sum(e - s for s, e in intervals) / len(intervals) / 1e6
            if intervals else None)


def paired(rows, first: str, then: str,
           to_end: bool) -> list[tuple[float, float]]:
    """For each span ``first``: from its start to the start (or, with
    ``to_end``, the end) of the first span ``then`` that starts after it
    and before the next ``first``.  A ``first`` with no such ``then`` is
    left out."""
    heads = spans(rows, first)
    tails = spans(rows, then)
    out, j = [], 0
    for i, (s0, _) in enumerate(heads):
        nxt = heads[i + 1][0] if i + 1 < len(heads) else float("inf")
        while j < len(tails) and tails[j][0] < s0:
            j += 1
        if j < len(tails) and tails[j][0] < nxt:
            out.append((s0, tails[j][1] if to_end else tails[j][0]))
    return out


def unspanned_idle_share(rows) -> float | None:
    """Share, in %, of the device's idle time (the gaps between its busy
    intervals on the first device plane, as ``trace.idle_gaps`` takes
    them) that no ``repro.*`` span overlaps."""
    planes = trace.device_ops(rows or ())
    covered = trace.union((st, st + d) for plane, _, n, st, d in rows or ()
                          if n.startswith(PREFIX)
                          and not trace.is_device_plane(plane))
    if not planes or not covered:
        return None
    busy = trace.union((st, st + d) for _, st, d in planes[sorted(planes)[0]])
    gaps = [(a[1], b[0]) for a, b in zip(busy, busy[1:]) if b[0] > a[1]]
    idle = sum(g1 - g0 for g0, g1 in gaps)
    if idle <= 0:
        return None
    hidden, j = 0.0, 0
    for g0, g1 in gaps:                 # both lists sorted and disjoint
        while j < len(covered) and covered[j][1] <= g0:
            j += 1
        k = j
        while k < len(covered) and covered[k][0] < g1:
            hidden += min(g1, covered[k][1]) - max(g0, covered[k][0])
            k += 1
    return 100.0 * (idle - hidden) / idle
