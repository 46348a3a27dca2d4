"""Host-to-device copy per queue, in ms of host clock: the self time of
``chip.h2d``, which puts each round's packed state on the device (on a
sharded replay, each bank slab on the chip that replays it)."""

from bench.spans import walk
from bench.trace import spans_self_time


def is_h2d(sp):
    return sp.name == "chip.h2d"


def read(run):
    if not run.queues or not walk(run.spans, is_h2d):
        return None
    return spans_self_time(run.spans, is_h2d) / run.queues * 1e3
