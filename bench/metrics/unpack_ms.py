"""Host unpacking of the harvested rounds per queue, in ms of host
clock: the self time of every span of category ``unpack``
(``chip.unpack`` less its wait and copy, and ``bank.harvest_out``:
output planes read back to values, forwarded planes cached).  Read only
where the harvest is split, so that the wait and the copy are not in
it."""

from bench.spans import walk
from bench.trace import spans_self_time


def read(run):
    if not run.queues or not walk(
            run.spans, lambda sp: sp.name == "chip.harvest.fetch"):
        return None
    return spans_self_time(run.spans, lambda sp: sp.cat == "unpack") \
        / run.queues * 1e3
