"""Reduction from a traced run to numbers: span self time, the device
busy union and its gaps, read from the program's telemetry spans and
from the JAX profiler's trace (``.xplane.pb``).

Device planes are named ``/device:TPU:<n>``; on each, the line
``XLA Ops`` holds one event per executed operation and ``XLA Modules``
one per executed program.  Host planes carry the harness's own
``jax.profiler.TraceAnnotation`` events on the same clock.
"""

from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ANNOTATIONS = ("bench.build", "bench.dispatch", "bench.collect")
NAME_CHARS = 120           # an HLO op's event name is its whole text

Interval = Tuple[float, float]


# -- spans ------------------------------------------------------------------

def self_time(span) -> float:
    """A span's wall seconds less the part its child spans cover."""
    return span.wall_s - sum(c.wall_s for c in span.children)


def spans_self_time(roots: Iterable, keep: Callable) -> float:
    """Summed self time of every span under ``roots`` that ``keep``
    accepts."""
    return sum(self_time(s) for root in roots for s in root.walk()
               if keep(s))


# -- intervals --------------------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of ``intervals``."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(merged: Sequence[Interval]) -> float:
    return sum(e - s for s, e in merged)


def overlap(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Length of the intersection of two disjoint sorted covers."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def gaps(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of ``[lo, hi]`` that ``merged`` leaves uncovered."""
    out, t = [], lo
    for s, e in merged:
        if e <= lo or s >= hi:
            continue
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


# -- the profiler's trace ---------------------------------------------------

@dataclass
class Device:
    """One device plane: ``(name, start_ns, end_ns)`` per event."""

    name: str
    ops: List[Tuple[str, float, float]] = field(default_factory=list)
    modules: List[Tuple[str, float, float]] = field(default_factory=list)

    def busy(self) -> List[Interval]:
        return union((s, e) for _, s, e in (self.ops or self.modules))


@dataclass
class Profile:
    devices: List[Device]
    host: Dict[str, List[Interval]]     # annotation name -> intervals

    def window(self) -> Optional[Interval]:
        """From the first traced dispatch's start to the last one's end."""
        spans = self.host.get("bench.dispatch", [])
        if not spans:
            return None
        return min(s for s, _ in spans), max(e for _, e in spans)


def _events(line) -> List[Tuple[str, float, float]]:
    return [(ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns))
            for ev in line.events]


def from_profile_data(data,
                      annotations: Sequence[str] = ANNOTATIONS) -> Profile:
    """Devices and harness annotations of a ``ProfileData``."""
    devices, host = [], defaultdict(list)
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = Device(plane.name)
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev.ops = _events(line)
                elif line.name == MODULES_LINE:
                    dev.modules = _events(line)
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for name, s, e in _events(line):
                    if name in annotations:
                        host[name].append((s, e))
    devices.sort(key=lambda d: int(d.name.rsplit(":", 1)[1]))
    return Profile(devices, dict(host))


def load_profile(trace_dir: Path) -> Optional[Profile]:
    """The newest ``.xplane.pb`` under ``trace_dir``, reduced."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        return None
    return from_profile_data(ProfileData.from_file(str(files[-1])))


def busy_within(dev: Device, windows: Sequence[Interval]) -> float:
    """Nanoseconds in which ``dev`` ran an operation inside ``windows``."""
    return overlap(dev.busy(), union(windows))


def outermost(events: Sequence[Tuple[str, float, float]]
              ) -> List[Tuple[str, float, float]]:
    """The events not nested in another one of the same line: a loop's
    body operations lie inside the loop's own event."""
    out, end = [], float("-inf")
    for ev in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        if ev[1] >= end:
            out.append(ev)
            end = ev[2]
    return out


def breakdown(profile: Profile, top: int = 10) -> Dict[str, List]:
    """The outermost device operations that took most time (mean over
    devices) and the longest idle gaps of the first device in the traced window,
    each named by the harness annotation the host was in."""
    per_op: Dict[str, float] = defaultdict(float)
    for dev in profile.devices:
        for name, s, e in outermost(dev.ops):
            per_op[name[:NAME_CHARS]] += (e - s) * 1e-9 / len(profile.devices)
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    window = profile.window()
    idle: List[List] = []
    if window is not None and profile.devices:
        named = sorted((s, e, name) for name, spans in profile.host.items()
                       for s, e in spans)
        for s, e in gaps(profile.devices[0].busy(), *window):
            mid = (s + e) / 2
            inside = [n for a, b, n in named if a <= mid <= b]
            idle.append([inside[-1] if inside else "outside_annotations",
                         (e - s) * 1e-9])
        idle.sort(key=lambda g: -g[1])
    return {"device_ops": [[n, v] for n, v in ops], "idle_gaps": idle[:top]}
