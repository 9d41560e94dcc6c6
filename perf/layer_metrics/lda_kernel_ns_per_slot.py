"""Device time inside Mosaic calls over the entry slots a chip executed
in the traced blocks, padding included: the traced token samples are the
valid slots, ``lda.kernel_slots`` says what share of all they are."""

from perf import program_telemetry


def read(run):
    t = run.trace
    pad = program_telemetry.padding_frac("lda.kernel_slots")
    if pad is None or not t or not t["class_s"].get("kernel") \
            or not run.trace_items:
        return None
    slots = run.trace_items / (1.0 - pad) / run.chips
    return 1e9 * t["class_s"]["kernel"] / slots
