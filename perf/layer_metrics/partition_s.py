"""Seconds inside ``set_ratings`` in set-up: the program's host
partition of the ratings and their staging."""


def read(run):
    d = run.rec.durations("partition")
    return d[0] if d else None
