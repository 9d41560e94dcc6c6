"""Elastic-execution evidence — provenance-stamped ``kind:"elastic"``
rows (scripts/check_jsonl.py invariant 14).

One row per elastic ACTION, in the order they happened:

- ``rebalance`` — a consumed ``skew_trigger`` moved packs between
  workers mid-run: per-worker ``loads_before``/``loads_after`` (both
  summing to ``total`` — moves conserve work) and
  ``wasted_frac_before``/``wasted_frac_after`` (the SkewLedger
  imbalance model; after ≤ before, or the move is refused and no row
  lands);
- ``shrink`` — a permanent worker loss removed a worker:
  ``n_workers_after < n_workers_before``, the lost worker's index, the
  injection site/ordinal, and ``capacity_frac`` (the degraded-throughput
  statement: the run continues at survivors/pre-fault capacity);
- ``resume`` — a rebuild from a crash-atomic checkpoint completed:
  survivor count, the replayed per-worker ``loads`` (summing to
  ``total``), the resulting ``wasted_frac``, and whether a repartition
  plan was replayed (post-shrink) or the stored assignment reused
  (same-mesh restart).

Rows are recorded unconditionally (they describe ACTIONS, not
observations — the zero-cost-when-disabled contract governs the
sentinel that *triggers* them, not the evidence that they happened) and
exported through ``telemetry.export`` with the flight recorder's
provenance stamp, so a CPU-sim drill can never read as chip evidence
(the invariant-4 inversion guard).
"""

from __future__ import annotations

import json

#: frozen event vocabulary — check_jsonl KNOWN_ELASTIC_EVENTS mirrors
#: this tuple (drift fails tier-1 via tests/test_check_jsonl.py)
EVENTS = ("rebalance", "shrink", "resume")


class ElasticLedger:
    """Append-only action log; one dict per event (see module doc)."""

    def __init__(self):
        self.reset()

    def reset(self) -> None:
        self.rows: list[dict] = []

    def record(self, event: str, phase: str, **fields) -> dict:
        if event not in EVENTS:
            raise ValueError(f"event {event!r} not in {EVENTS}")
        # on_timeline stamps whether a steptrace run covered this event
        # (PR 18) — invariant 16 reconciles covered rows against the
        # timeline's elastic marks EXACTLY in both directions, while a
        # row recorded outside any run (e.g. a manual install() for a
        # bit-identity comparison) is legitimately unmarked
        from harp_tpu.utils import steptrace

        covered = steptrace.tracer._run is not None
        row = {"kind": "elastic", "event": event, "phase": phase,
               "on_timeline": covered, **fields}
        self.rows.append(row)
        if covered:
            steptrace.tracer.on_elastic(event, phase, row)
        return row

    def export_jsonl(self, fh, stamp: dict | None = None) -> None:
        for row in self.rows:
            fh.write(json.dumps({**row, **(stamp or {})}) + "\n")


# ---------------------------------------------------------------------------
# Module singleton + hooks (the other spines' shape)
# ---------------------------------------------------------------------------

ledger = ElasticLedger()


def reset() -> None:
    """Clear the ledger (telemetry.scope does this on entry)."""
    ledger.reset()


def record(event: str, phase: str, **fields) -> dict:
    """Module-level shorthand for :meth:`ElasticLedger.record`."""
    return ledger.record(event, phase, **fields)


def export_jsonl(fh) -> None:
    """Append elastic rows (telemetry.export calls this); stamped with
    the flight recorder's provenance triple."""
    if not ledger.rows:
        return
    from harp_tpu.utils import flightrec

    ledger.export_jsonl(fh, flightrec.provenance_stamp())
