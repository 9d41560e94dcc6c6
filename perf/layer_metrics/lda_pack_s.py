"""Seconds in set-up inside the program's ``lda.pack_tokens`` span (the
partition into tile entries, with ``mfsgd.partition.sort`` / ``.pack``
below it, and the initial count tables) plus its ``lda.install`` span
(the placement calls, as the host sees them).  Between the two,
``set_tokens`` takes the kernel's count bounds (row sums over both
tables) and, in a traced run, the skew records: under no span."""

from perf import program_telemetry


def read(run):
    parts = [program_telemetry.setup_span_seconds(run, name)
             for name in ("lda.pack_tokens", "lda.install")]
    return None if None in parts else sum(parts)
