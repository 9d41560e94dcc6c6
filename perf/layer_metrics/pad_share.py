"""1 - valid / padded entry slots of the program's rating partition
(``skew.record_partition``): kernel work spent on padding.  A count."""


def read(run):
    pad = run.extra.get("padding_frac")
    return None if pad is None else 100.0 * pad
