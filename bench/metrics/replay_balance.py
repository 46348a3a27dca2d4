"""Balance of the replay over the devices, in %: the mean over devices
of each device's summed replay time (``chip_replay`` on the ``XLA
Modules`` line) over the slowest device's.  100 is an even split; a
device that replayed nothing counts as 0."""

from bench.metrics.replay_ms import REPLAY_PROGRAM


def device_replay_seconds(run):
    """Summed replay seconds of each device of the profile, or ``None``
    where no device replayed."""
    if run.profile is None:
        return None
    per_device = [sum(e - s for name, s, e in dev.modules
                      if REPLAY_PROGRAM.search(name)) * 1e-9
                  for dev in run.profile.devices]
    if not per_device or max(per_device) <= 0:
        return None
    return per_device


def read(run):
    per_device = device_replay_seconds(run)
    if per_device is None:
        return None
    return sum(per_device) / len(per_device) / max(per_device) * 100.0
