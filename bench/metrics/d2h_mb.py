"""Bytes copied from the device to the host per queue, in MB (1e6 B):
the summed ``bytes`` of ``chip.harvest.fetch``, each round's whole
state."""

from bench.spans import attr_sum


def read(run):
    total = attr_sum(run.spans, "chip.harvest.fetch", "bytes")
    if total is None or not run.queues:
        return None
    return total / 1e6 / run.queues
