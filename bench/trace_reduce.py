"""Reduction of a JAX profiler trace to device numbers.

Works on the planes of ``jax.profiler.ProfileData`` (or any objects with the
same ``name`` / ``lines`` / ``events`` / ``start_ns`` / ``duration_ns``
shape).  Device planes are those named ``/device:TPU:<n>``; on them only the
``XLA Ops`` line counts, because a module's span covers its ops.  Each op is
named by the HLO instruction it defines (``%phase_row_max.3 = ...`` is
``phase_row_max``), the rule of ``benchmarks/probe_dispatch.py``: a layout
copy that only reads a kernel's result is busy time of its own, never the
kernel's.
"""

from __future__ import annotations

from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Tuple

DEVICE_PREFIX = "/device:TPU"
OPS_LINE = "XLA Ops"
HOST_PREFIX = "/host:"


def op_name(name: str) -> str:
    """``%phase_row_max.3 = s32[...] custom-call(...)`` -> ``phase_row_max``:
    the instruction an event defines, without its numeric suffix."""
    head = name.split("=", 1)[0].strip().lstrip("%")
    base, _, suffix = head.rpartition(".")
    return base if base and suffix.isdigit() else head


def device_intervals(planes) -> Dict[str, List[Tuple[int, int]]]:
    """``{device plane: [(start_ns, end_ns), ...]}`` of its XLA ops."""
    out: Dict[str, List[Tuple[int, int]]] = defaultdict(list)
    for plane in planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        out[plane.name]
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s = int(ev.start_ns)
                out[plane.name].append((s, s + int(ev.duration_ns)))
    return dict(out)


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def busy_seconds(planes) -> float:
    """Seconds in which an op ran, per device, averaged over the devices
    that appear in the trace (0.0 when none does)."""
    per = device_intervals(planes)
    if not per:
        return 0.0
    return sum(sum(e - s for s, e in union(iv)) for iv in per.values()) \
        / len(per) / 1e9


def idle_share(planes, window_s: float) -> float:
    """1 - busy / window; a trace with no device op reads 1.0."""
    if window_s <= 0:
        raise ValueError("window must be positive")
    return 1.0 - busy_seconds(planes) / window_s


def op_seconds(planes, top: int = 10) -> List[List]:
    """The ``top`` device ops by total device time, ``[[name, seconds]]``,
    summed over their events and averaged over devices."""
    per = device_intervals(planes)
    tot: Dict[str, float] = defaultdict(float)
    for plane in planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                tot[op_name(ev.name)] += int(ev.duration_ns) / 1e9
    n = max(1, len(per))
    rows = sorted(([k, v / n] for k, v in tot.items()), key=lambda r: -r[1])
    return rows[:top]


def host_spans(planes, prefix: str = "bench/") -> List[Tuple[str, int, int]]:
    """Host annotations whose name starts with ``prefix``, as
    ``(name, start_ns, end_ns)``."""
    out = []
    for plane in planes:
        if not plane.name.startswith(HOST_PREFIX):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    s = int(ev.start_ns)
                    out.append((ev.name, s, s + int(ev.duration_ns)))
    return out


def idle_gaps(planes, window: Tuple[int, int], top: int = 10) -> List[List]:
    """The ``top`` longest stretches of ``window`` (ns, on the trace's
    clock) in which no device op ran, each named by the ``bench/`` host
    span that overlaps it most: ``[[name, seconds]]``."""
    lo, hi = window
    busy = union(iv for ivs in device_intervals(planes).values() for iv in ivs)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        gaps.append((t, hi))
    spans = [sp for sp in host_spans(planes) if sp[0] != "bench/window"]
    rows = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        best, name = 0, "host (no bench span)"
        for nm, hs, he in spans:
            ov = min(e, he) - max(s, hs)
            if ov > best:
                best, name = ov, nm
        rows.append([name, (e - s) / 1e9])
    return rows


def window_of(planes, name: str = "bench/window") -> Tuple[int, int]:
    """The span of the host annotation ``name`` (ns), or the whole trace."""
    for nm, s, e in host_spans(planes):
        if nm == name:
            return s, e
    starts = [s for iv in device_intervals(planes).values() for s, _ in iv]
    ends = [e for iv in device_intervals(planes).values() for _, e in iv]
    return (min(starts), max(ends)) if starts else (0, 0)


@dataclass
class Event:
    name: str
    start_ns: int
    duration_ns: int


@dataclass
class Line:
    name: str
    events: List[Event] = field(default_factory=list)


@dataclass
class Plane:
    name: str
    lines: List[Line] = field(default_factory=list)


def load(path) -> List[Plane]:
    """The trace's device planes and its ``bench/`` host annotations as
    plain objects (the profiler's own plane objects live only as long as
    the file's ``ProfileData``)."""
    import jax
    data = jax.profiler.ProfileData.from_file(str(path))
    out = []
    for plane in data.planes:
        host = plane.name.startswith(HOST_PREFIX)
        if not (host or plane.name.startswith(DEVICE_PREFIX)):
            continue
        p = Plane(plane.name)
        for line in plane.lines:
            evs = [Event(ev.name, int(ev.start_ns), int(ev.duration_ns))
                   for ev in line.events
                   if not host or ev.name.startswith("bench/")]
            if evs:
                p.lines.append(Line(line.name, evs))
        out.append(p)
    return out
