"""Seconds in set-up inside ``mesh.shard_array`` under
``mfsgd.set_ratings``, summed over the rating blocks: the placement
calls as the host sees them (they do not wait for the arrays)."""

from perf import program_telemetry


def read(run):
    return program_telemetry.setup_span_seconds(
        run, "mesh.shard_array", under="mfsgd.set_ratings")
