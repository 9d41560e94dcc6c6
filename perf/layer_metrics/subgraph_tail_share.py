"""Device busy self time under the scope ``subgraph.tail``: the exact
sum over the adjacency entries past ``max_degree``, both child shapes
(``perf/scope_reduce.py``)."""

from perf import scope_reduce


def read(run):
    return scope_reduce.share(run, "subgraph.tail")
