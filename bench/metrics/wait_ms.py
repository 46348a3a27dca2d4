"""Host time spent waiting for the device per queue, in ms: the self
time of every span of category ``wait`` (``chip.harvest.wait`` before a
round's state is copied back, ``chip.drain`` at the end of the queue)."""

from bench.spans import walk
from bench.trace import spans_self_time


def read(run):
    if not run.queues or not walk(run.spans, lambda sp: sp.cat == "wait"):
        return None
    return spans_self_time(run.spans, lambda sp: sp.cat == "wait") \
        / run.queues * 1e3
