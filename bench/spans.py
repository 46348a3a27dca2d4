"""The program's telemetry spans on the profiler's clock, and sums over
their attributes.

Spans time themselves on the host's ``time.perf_counter`` (seconds);
the profile's events carry nanoseconds on the profiler's clock.  The
harness wraps every traced ``dispatch`` in a ``bench.dispatch``
annotation, and every dispatch leaves one ``device.dispatch`` root span.
The i-th root pairs with the i-th annotation, and the root's start and
end map linearly onto the annotation's.  Mapping both ends, and not
only the start, keeps a span within the harness's slack around the root
(tens of microseconds) even where the two clocks run at rates that
differ by hundreds of parts per million over a query of seconds.
"""

from __future__ import annotations

from collections import defaultdict
from typing import Callable, Dict, Iterable, List, Optional, Tuple

from bench.trace import gaps, length, overlap, union

ROOT = "device.dispatch"
#: names under which a device gap is not explained by a stage: the
#: dispatch roots' own time, and dispatch time outside every span
UNNAMED = ("device.dispatch", "chip.dispatch", "bench.dispatch")

Segment = Tuple[float, float, str]


def walk(roots: Iterable, keep: Callable) -> List:
    """Every span under ``roots`` that ``keep`` accepts."""
    return [s for root in roots for s in root.walk() if keep(s)]


def attr_sum(roots: Iterable, name: str, key: str) -> Optional[float]:
    """The summed attribute ``key`` of every span named ``name``, or
    ``None`` where no such span carries it."""
    values = [s.attrs[key] for s in walk(roots, lambda s: s.name == name)
              if key in s.attrs]
    return float(sum(values)) if values else None


class Clock:
    """The linear map of one dispatch's ``perf_counter`` seconds onto
    the profile's nanoseconds: the root's ``[t0, t0 + wall_s]`` onto its
    annotation's ``[start, end]``."""

    def __init__(self, root, window: Tuple[float, float]):
        self.t0, self.start = root.t0, window[0]
        wall_ns = root.wall_s * 1e9
        self.rate = (window[1] - window[0]) / wall_ns if wall_ns > 0 else 1.0

    def __call__(self, t: float) -> float:
        return self.start + (t - self.t0) * 1e9 * self.rate


def aligned(run) -> List[Tuple[object, Clock, Tuple[float, float]]]:
    """``(root, clock, window)`` for every traced dispatch: its root
    span, the map onto the profile's clock and its ``bench.dispatch``
    interval.  Empty where the run has no profile or the roots and the
    annotations do not pair one to one."""
    if run.profile is None:
        return []
    roots = [r for r in run.spans if r.name == ROOT]
    windows = sorted(run.profile.host.get("bench.dispatch", []))
    if not roots or len(roots) != len(windows):
        return []
    return [(r, Clock(r, w), w) for r, w in zip(roots, windows)]


def interval(span, clock: Clock) -> Tuple[float, float]:
    """A span's start and end on the profile's clock."""
    return clock(span.t0), clock(span.t0 + span.wall_s)


def innermost(root, clock: Clock) -> List[Segment]:
    """The parts of the profile's clock during which each span of the
    tree is the innermost one open: a span's interval less its
    children's."""
    out: List[Segment] = []

    def visit(span) -> None:
        lo, hi = interval(span, clock)
        kids = union(interval(c, clock) for c in span.children)
        out.extend((s, e, span.name) for s, e in gaps(kids, lo, hi))
        for child in span.children:
            visit(child)

    visit(root)
    return out


def idle_by_span(run) -> Optional[Dict[str, float]]:
    """Device idle nanoseconds inside the traced dispatches, by the
    innermost program span the host was in at the time; idle time that
    no span of the dispatch covers falls under ``bench.dispatch``.
    Summed over devices; ``None`` where there is nothing to align."""
    pairs = aligned(run)
    if not pairs or not run.profile.devices:
        return None
    out: Dict[str, float] = defaultdict(float)
    for dev in run.profile.devices:
        busy = dev.busy()
        for root, clock, (lo, hi) in pairs:
            idle = gaps(busy, lo, hi)
            by_name: Dict[str, List] = defaultdict(list)
            for s, e, name in innermost(root, clock):
                by_name[name].append((max(s, lo), min(e, hi)))
            named = 0.0
            for name, segments in by_name.items():
                ns = overlap(idle, union(segments))
                out[name] += ns
                named += ns
            out["bench.dispatch"] += length(idle) - named
    return dict(out)
