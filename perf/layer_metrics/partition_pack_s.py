"""Seconds in set-up inside the program's ``mfsgd.partition.pack`` span:
tile counts, the entry arrays' allocation, the per-entry copy loop."""

from perf import program_telemetry


def read(run):
    return program_telemetry.setup_span_seconds(
        run, "mfsgd.partition.pack", under="mfsgd.set_ratings")
