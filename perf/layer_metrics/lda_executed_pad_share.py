"""1 - tokens / slots of the token entries as staged (the skew ledger's
``lda.kernel_slots``): NE x C a grid row, every one of which the kernel
runs every sweep.  A count."""

from perf import program_telemetry


def read(run):
    pad = program_telemetry.padding_frac("lda.kernel_slots")
    return None if pad is None else 100.0 * pad
