"""Reduce a profiler trace to what the per-layer readers need.

A trace is reduced to a flat list of events ``[plane, line, name,
start_ns, dur_ns]`` (:func:`load`), kept as JSON for the recorded test
trace.  From those events:

* device busy time is the union of the intervals in which an operation
  ran on a device plane, averaged over the device planes;
* kernel time sums the device events whose name contains one of the
  kernel's stable names;
* each idle gap between busy intervals is attributed to the host event
  that covers most of it, so the longest gaps say what the host was
  doing while the chip waited.
"""

from __future__ import annotations

import collections
import glob
import os

OP_LINE = "XLA Ops"


def load(trace_dir: str) -> list[list]:
    """Flatten the ``.xplane.pb`` under ``trace_dir`` into event rows."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    rows = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        for line in plane.lines:
            for ev in line.events:
                rows.append([plane.name, line.name, ev.name,
                             float(ev.start_ns), float(ev.duration_ns)])
    return rows


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


def device_ops(rows) -> dict[str, list]:
    """Device plane -> its operation events.  The ``XLA Ops`` line where a
    plane has one (modules and steps on other lines would double-count),
    else every line of the plane."""
    by_plane: dict[str, dict[str, list]] = collections.defaultdict(
        lambda: collections.defaultdict(list))
    for plane, line, name, start, dur in rows:
        if is_device_plane(plane):
            by_plane[plane][line].append((name, start, dur))
    out = {}
    for plane, lines in by_plane.items():
        if OP_LINE in lines:
            out[plane] = lines[OP_LINE]
        else:
            out[plane] = [e for evs in lines.values() for e in evs]
    return out


def union(intervals) -> list[tuple[float, float]]:
    """Merge ``(start, end)`` intervals."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_s(rows) -> float:
    """Seconds in which an operation ran, averaged over device planes."""
    planes = device_ops(rows)
    if not planes:
        return 0.0
    tot = 0.0
    for evs in planes.values():
        tot += sum(e - s for s, e in union((st, st + d) for _, st, d in evs))
    return tot / len(planes) / 1e9


def kernel_s(rows, names) -> float:
    """Device seconds of events whose name contains any of ``names``,
    averaged over device planes; 0.0 when no such event ran."""
    planes = device_ops(rows)
    if not planes:
        return 0.0
    tot = sum(d for evs in planes.values() for n, _, d in evs
              if any(k in n for k in names))
    return tot / len(planes) / 1e9


def top_ops(rows, n: int = 10) -> list[list]:
    """The ``n`` device operations that took most time, ``[name, s]``."""
    acc: dict[str, float] = collections.Counter()
    planes = device_ops(rows)
    for evs in planes.values():
        for name, _, d in evs:
            acc[name] += d / 1e9 / len(planes)
    return [[k, v] for k, v in acc.most_common(n)]


def idle_gaps(rows, n: int = 10) -> list[list]:
    """The ``n`` longest gaps between busy intervals on the first device
    plane, each named by the host event that overlaps it most:
    ``[host event name, gap seconds]``."""
    planes = device_ops(rows)
    if not planes:
        return []
    evs = planes[sorted(planes)[0]]
    busy = union((st, st + d) for _, st, d in evs)
    gaps = sorted(((b[0] - a[1], a[1], b[0])
                   for a, b in zip(busy, busy[1:]) if b[0] > a[1]),
                  reverse=True)[:n]
    host = [(name, st, st + d) for plane, _, name, st, d in rows
            if not is_device_plane(plane) and d > 0]
    out = []
    for length, g0, g1 in gaps:
        best, best_ov = "(no host event)", 0.0
        for name, s, e in host:
            ov = min(e, g1) - max(s, g0)
            if ov > best_ov:
                best, best_ov = name, ov
        out.append([best, length / 1e9])
    return out
