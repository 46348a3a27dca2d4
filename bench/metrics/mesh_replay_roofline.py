"""Share of the memory roofline of a replay spread over several
devices, in %: the least time the devices that replayed need to move
the queue's bytes, each at its HBM bandwidth, over the slowest device's
replay time.  The bytes are ``replay_roofline``'s: the non-``Ref``
operand bits plus every returned output bit, times lanes, over 8."""

from bench.metrics.replay_balance import device_replay_seconds


def read(run):
    per_device = device_replay_seconds(run)
    if per_device is None or not run.queue_bytes:
        return None
    used = sum(1 for s in per_device if s > 0)
    least = run.queue_bytes / (used * run.peaks["hbm_bytes_per_s"])
    return least / max(per_device) * 100.0
