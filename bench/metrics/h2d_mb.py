"""Bytes copied from the host to the device per queue, in MB (1e6 B):
the summed ``bytes`` of ``chip.h2d``, each round's packed state.  The
command tables are not counted: they are built once and stay on the
device."""

from bench.spans import attr_sum


def read(run):
    total = attr_sum(run.spans, "chip.h2d", "bytes")
    if total is None or not run.queues:
        return None
    return total / 1e6 / run.queues
