"""Device-to-host copy per queue, in ms of host clock: the self time of
``chip.harvest.fetch``, which copies each finished round's whole state
to the host."""

from bench.spans import walk
from bench.trace import spans_self_time


def is_fetch(sp):
    return sp.name == "chip.harvest.fetch"


def read(run):
    if not run.queues or not walk(run.spans, is_fetch):
        return None
    return spans_self_time(run.spans, is_fetch) / run.queues * 1e3
