"""Device time of the replay program per queue, in ms: the summed
duration of its executions on the profiler's ``XLA Modules`` line; over
several devices, the slowest device's."""

import re

#: module names of the chip replay executors (vmap and shard_map alike)
REPLAY_PROGRAM = re.compile(r"chip_replay")


def replay_seconds(run):
    """Summed replay seconds of the slowest device, or ``None``."""
    if run.profile is None:
        return None
    per_device = [sum(e - s for name, s, e in dev.modules
                      if REPLAY_PROGRAM.search(name)) * 1e-9
                  for dev in run.profile.devices]
    if not per_device or max(per_device) <= 0:
        return None
    return max(per_device)


def read(run):
    s = replay_seconds(run)
    if s is None or not run.queues:
        return None
    return s / run.queues * 1e3
