"""Device busy self time under the scope ``subgraph.padded``: the row
gathers by the padded part's neighbour ids, the mask product and the sum
over slots, both child shapes (``perf/scope_reduce.py``)."""

from perf import scope_reduce


def read(run):
    return scope_reduce.share(run, "subgraph.padded")
