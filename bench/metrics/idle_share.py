"""Device idle share while a queue is in ``dispatch``, in %: one minus
the device-busy union over the summed dispatch intervals of the traced
window; over several devices, the mean."""

from bench.trace import busy_within, length, union


def read(run):
    if run.profile is None or not run.profile.devices:
        return None
    windows = union(run.profile.host.get("bench.dispatch", []))
    total = length(windows)
    if total <= 0:
        return None
    shares = [1.0 - busy_within(dev, windows) / total
              for dev in run.profile.devices]
    return sum(shares) / len(shares) * 100.0
