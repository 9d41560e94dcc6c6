"""Seconds in set-up inside the partitioner's ``mfsgd.partition.sort``
span where it runs below the program's ``lda.pack_tokens``: tile ids of
every token, the stable argsort by tile, the gathers into tile order.
The grid partitioner is MF-SGD's; this is LDA's share of it."""

from perf import program_telemetry


def read(run):
    return program_telemetry.setup_span_seconds(
        run, "mfsgd.partition.sort", under="lda.pack_tokens")
