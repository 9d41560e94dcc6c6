"""Device busy self time the degree order costs: the tiles' rows of
``nbr`` and ``msk`` taken by it (``subgraph.order.take``) and their sums
set back into vertex order (``subgraph.order.put``)
(``perf/scope_reduce.py``)."""

from perf import scope_reduce


def read(run):
    return scope_reduce.share(run, "subgraph.order.take",
                              "subgraph.order.put")
