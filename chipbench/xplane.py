"""The one reduction from a profiler trace (``.xplane.pb``) to intervals.

Reads the file with ``jax.profiler.ProfileData`` and nothing else. A
trace has planes (one per device, one for the host's threads), a plane
has lines, a line has events with a start and a duration in
nanoseconds. On a TPU the operations a device ran are the events of the
line ``XLA Ops`` of the plane ``/device:TPU:<n>``; the other lines of
that plane (modules, steps, names) cover the same time again and are not
added. Everything here is name-free about *which* operation ran.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # (start_s, end_s) on the trace's axis

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


def newest_trace(trace_dir: str) -> str:
    """The ``.xplane.pb`` that ``jax.profiler.stop_trace`` wrote last
    under ``trace_dir``."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str):
    import jax
    return jax.profiler.ProfileData.from_file(path)


def describe(profile, events_per_line: int = 3) -> List[str]:
    """Planes, lines and a few events of each: what one looks at by hand
    before trusting a reduction."""
    out = []
    for plane in profile.planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  line {line.name!r}: {len(evs)} events")
            for e in evs[:events_per_line]:
                out.append(f"    {e.name!r} start_ns={e.start_ns} "
                           f"duration_ns={e.duration_ns}")
    return out


def device_planes(profile, prefix: str = DEVICE_PLANE) -> Dict[int, object]:
    """Device planes by ordinal."""
    out = {}
    for plane in profile.planes:
        if plane.name.startswith(prefix):
            tail = plane.name[len(prefix):].split()[0]
            if tail.isdigit():
                out[int(tail)] = plane
    return out


def line_events(plane, line_name: str) -> List[Tuple[str, float, float]]:
    """``(name, start_s, end_s)`` of every event on the plane's lines
    called ``line_name``."""
    out = []
    for line in plane.lines:
        if line.name != line_name:
            continue
        for e in line.events:
            s = e.start_ns * 1e-9
            out.append((e.name, s, s + e.duration_ns * 1e-9))
    return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge overlapping or touching intervals; sorted, disjoint."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e < s:
            s, e = e, s
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of disjoint ``intervals`` inside ``[lo, hi]``."""
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals: Sequence[Interval]) -> float:
    return float(sum(e - s for s, e in intervals))


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle stretches of ``[lo, hi]`` between disjoint sorted
    ``busy`` intervals."""
    out, at = [], lo
    for s, e in clip(busy, lo, hi):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def time_by_name(events: Sequence[Tuple[str, float, float]], lo: float,
                 hi: float) -> List[Tuple[str, float]]:
    """Seconds per event name inside ``[lo, hi]``, largest first."""
    total: Dict[str, float] = {}
    for name, s, e in events:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            total[name] = total.get(name, 0.0) + d
    return sorted(total.items(), key=lambda kv: -kv[1])


def annotations(profile, name: str, plane_name: str = HOST_PLANE
                ) -> List[Tuple[float, float, dict]]:
    """``(start_s, end_s, stats)`` of the host events called ``name``
    (``jax.profiler.TraceAnnotation``), in time order."""
    out = []
    for plane in profile.planes:
        if plane.name != plane_name:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == name:
                    s = e.start_ns * 1e-9
                    out.append((s, s + e.duration_ns * 1e-9, dict(e.stats)))
    return sorted(out, key=lambda t: t[0])


def clock_offset(marks: Sequence[Tuple[float, float, dict]],
                 host_starts: Dict[int, float], key: str = "i"
                 ) -> Optional[float]:
    """Seconds to add to a host-clock reading to land on the trace's
    axis: the median, over the annotations whose ``key`` stat names an
    iteration the host also stamped, of trace start minus host start."""
    diffs = sorted(s - host_starts[int(st[key])] for s, _e, st in marks
                   if key in st and int(st[key]) in host_starts)
    if not diffs:
        return None
    return diffs[len(diffs) // 2]
