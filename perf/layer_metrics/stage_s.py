"""Median seconds from a job's first host-to-device placement
(``mesh.shard_array``) to the first op of its step program on the device:
how long the input takes to arrive.  The placement call itself returns
at once, so the host's clock cannot see this; the trace can."""

import statistics


def read(run):
    t = run.trace
    if not t:
        return None
    waits = []
    for name, start, _ in t["spans"]:
        if name != "perf:stage":
            continue
        first = next((b0 for b0, _ in t["busy"] if b0 >= start), None)
        if first is not None:
            waits.append(first - start)
    return statistics.median(waits) if waits else None
