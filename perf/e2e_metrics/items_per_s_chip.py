"""Items a block completes / the median block's seconds / chips (host
clock; every block ends in a readback, and every block of a cell does the
same work).  The median, not the window's mean: one stall of the shared
host inside a 10 s window moved the mean by 0.85% in 4 runs of 12 and the
median by 0.02% (PR 22).  The ``info`` line carries items and window
seconds for the mean.  The configuration names its item."""

import statistics


def read(run):
    per_block = run.items / len(run.block_s)
    return per_block / statistics.median(run.block_s) / run.chips
