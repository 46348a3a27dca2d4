"""Replay's share of its memory roofline, in %: the least time the
chip's HBM needs to move the queue's bytes, over the replay's device
time.  The bytes are the non-``Ref`` operand bits plus every returned
output bit, times lanes, over 8: any implementation has to read those
operands and write those results at least once.  No published peak
covers bitwise integer work, so the bound is bytes alone."""

from bench.metrics.replay_ms import replay_seconds


def read(run):
    s = replay_seconds(run)
    if s is None or not run.queue_bytes:
        return None
    least = run.queue_bytes / run.peaks["hbm_bytes_per_s"]
    return least / s * 100.0
