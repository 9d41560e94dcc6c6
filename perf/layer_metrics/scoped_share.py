"""Device busy self time in ops that lie under one of the program's
``jax.named_scope``s (``perf/scope_reduce.py``): whether the names still
reach the ops after a refactor.  A program without the op map, or a run
without a trace, reads as nothing."""

from perf import scope_reduce


def read(run):
    table = scope_reduce.by_scope(run)
    if not table or not table["busy_s"]:
        return None
    return 100.0 * (1.0 - table["unscoped_s"] / table["busy_s"])
