"""Device busy self time under the scope ``mlp.layer1``, forward and
backward: x·W1, W1's gradient and what fuses with them
(``perf/scope_reduce.py``)."""

from perf import scope_reduce


def read(run):
    return scope_reduce.share(run, "mlp.layer1")
