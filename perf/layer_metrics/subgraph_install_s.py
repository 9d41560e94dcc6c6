"""Seconds in set-up inside the program's ``subgraph.install`` span: the
padded CSR (``subgraph.pad_csr``: the host's stable sort of the adjacency
entries), the exact tail's partition (``subgraph.overflow``) and the
placement calls, as the host sees them.  A program without the span
reads as nothing."""

from perf import program_telemetry


def read(run):
    return program_telemetry.setup_span_seconds(run, "subgraph.install")
