"""Dispatch front end per queue, in ms of host clock: the self time of
``device.dispatch`` (queue validation, the cost accounting after the
drain) plus every span of category ``plan`` (``device.validate``,
``chip.plan``, ``chip.schedule``: partitioning and wave building)."""

from bench.trace import spans_self_time


def read(run):
    if not run.spans or not run.queues:
        return None
    s = spans_self_time(
        run.spans, lambda sp: sp.name == "device.dispatch" or sp.cat == "plan")
    return s / run.queues * 1e3
