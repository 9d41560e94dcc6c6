"""A stand-in per-layer metric: reads what the stand-in driver adds."""


def read(run):
    return run.extra.get("answer")
