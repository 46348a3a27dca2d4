"""Useful share of the replayed commands over the traced rounds, in %:
the μProgram commands of every packed slot (``cmds_useful`` of
``chip.pack_round``) over the commands the stacked scan steps through,
every bank and subarray at the round's padded command count
(``cmds_replayed``).  The rest is NOP padding: table buckets, the
round's longest program, idle subarrays and banks."""

from bench.spans import attr_sum


def read(run):
    useful = attr_sum(run.spans, "chip.pack_round", "cmds_useful")
    replayed = attr_sum(run.spans, "chip.pack_round", "cmds_replayed")
    if useful is None or not replayed:
        return None
    return useful / replayed * 100.0
