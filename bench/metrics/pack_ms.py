"""Host layout conversion per queue, in ms of host clock: the self time
of every span of category ``pack`` (``chip.pack_round``,
``bank.pack_wave``: operand bits packed into round slabs, command tables
looked up)."""

from bench.trace import spans_self_time


def read(run):
    if not run.spans or not run.queues:
        return None
    return spans_self_time(run.spans, lambda sp: sp.cat == "pack") \
        / run.queues * 1e3
