"""Seconds in set-up inside the program's ``mfsgd.coverage`` span:
``insert_coverage_entries`` building the kernel's chunk list from the
partition's entries."""

from perf import program_telemetry


def read(run):
    return program_telemetry.setup_span_seconds(
        run, "mfsgd.coverage", under="mfsgd.set_ratings")
