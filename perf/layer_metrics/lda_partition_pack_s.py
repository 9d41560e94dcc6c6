"""Seconds in set-up inside the partitioner's ``mfsgd.partition.pack``
span where it runs below the program's ``lda.pack_tokens``: the tiles'
tokens laid into entries as wide as the widest."""

from perf import program_telemetry


def read(run):
    return program_telemetry.setup_span_seconds(
        run, "mfsgd.partition.pack", under="lda.pack_tokens")
