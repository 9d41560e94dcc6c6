"""1 - valid / slots of the rating blocks as staged (the skew ledger's
``mfsgd.kernel_slots``): what the device program runs every epoch, after
the coverage entries and the rounding to the kernel's chunk.  A count."""

from perf import program_telemetry


def read(run):
    pad = program_telemetry.padding_frac("mfsgd.kernel_slots")
    return None if pad is None else 100.0 * pad
