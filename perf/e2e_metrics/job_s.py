"""Median wall seconds of one whole job, host array in to host result
out (host clock).  Count and tail go on the earlier ``info`` line."""

import statistics


def read(run):
    return statistics.median(run.block_s)
