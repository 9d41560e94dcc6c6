"""What the program's own telemetry recorded, for per-layer readers:
spans of ``telemetry.span`` (kept in memory by ``telemetry.tracer``) and
records of the skew ledger.  A program that lacks the span, the record
or the query (one from before the PR that added it) gives ``None``."""

from harp_tpu.utils import skew, telemetry


def setup_span_seconds(run, name, under=None):
    """Seconds inside the program's spans ``name`` that ended before the
    window (the check runs one more block with telemetry on, after it);
    with ``under``, only those with a span of that name above them."""
    durations = getattr(telemetry.tracer, "durations", None)
    if durations is None:
        return None
    found = durations(name, under=under, t1=run.window[0])
    return sum(found) if found else None


def padding_frac(phase):
    """``padding_frac`` of one ``skew.record_partition`` record: 1 -
    valid / padded slots."""
    return skew.ledger.summary().get(phase, {}).get("padding_frac")
