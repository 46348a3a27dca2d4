"""Share of the device's idle time inside the traced dispatches that no
stage explains, in %: idle time during which the host's innermost open
program span is a dispatch root (``device.dispatch``,
``chip.dispatch``) or none at all, over all idle time inside dispatch.
Spans are put on the profile's clock by :mod:`bench.spans`."""

from bench.spans import UNNAMED, idle_by_span


def read(run):
    idle = idle_by_span(run)
    if not idle:
        return None
    total = sum(idle.values())
    if total <= 0:
        return None
    return sum(idle.get(name, 0.0) for name in UNNAMED) / total * 100.0
