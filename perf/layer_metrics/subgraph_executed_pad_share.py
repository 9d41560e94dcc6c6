"""1 - real adjacency entries / slots as staged (the skew ledger's
``subgraph.partition``, made at install: the padded part's ``n x
max_degree`` slots and the tail's, together): every slot is gathered in
every neighbour sum of every colouring.  A count."""

from perf import program_telemetry


def read(run):
    pad = program_telemetry.padding_frac("subgraph.partition")
    return None if pad is None else 100.0 * pad
