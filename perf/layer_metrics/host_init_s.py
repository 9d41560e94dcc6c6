"""Median seconds a job spends on the host before its first
host-to-device placement (initial centroids, dtype conversion)."""

import statistics


def read(run):
    d = run.rec.durations("host_init", *run.window)
    return statistics.median(d) if d else None
