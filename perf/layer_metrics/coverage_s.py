"""Seconds in set-up inside the program's ``mfsgd.coverage`` span:
``insert_coverage_entries`` rebuilding the entry arrays for the kernel."""

from perf import program_telemetry


def read(run):
    return program_telemetry.setup_span_seconds(
        run, "mfsgd.coverage", under="mfsgd.set_ratings")
