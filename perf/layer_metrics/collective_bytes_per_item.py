"""Bytes the collective verbs were asked to move (the program's
CommLedger: payload per call site times executions) per item.  A count
from shapes, not a measured transfer."""


def read(run):
    if run.comm_bytes is None or not run.items:
        return None
    return run.comm_bytes / run.items
