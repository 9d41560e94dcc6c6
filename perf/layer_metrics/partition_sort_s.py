"""Seconds in set-up inside the program's ``mfsgd.partition.sort`` span:
owner and tile ids of every rating, the stable argsort by tile, the
gathers into tile order."""

from perf import program_telemetry


def read(run):
    return program_telemetry.setup_span_seconds(
        run, "mfsgd.partition.sort", under="mfsgd.set_ratings")
