"""Superstep skew profiler — per-worker load and straggler attribution.

Reference parity (SURVEY.md §3.1, §3.5): Harp's whole reason to exist is
*balanced* Map-Collective supersteps — the timer-bounded
``schdynamic.DynamicScheduler`` and the ``edu.iu.dymoro`` rotation
pipeline are load-balancing machinery, because a BSP superstep runs at
the pace of its slowest worker.  The first three telemetry spines
(CommLedger/SpanTracer, :mod:`harp_tpu.utils.flightrec`) are
worker-blind: they can say how many bytes moved and how many dispatches
ran, but not "worker 3 holds 1.6x the nonzeros and is the wall".  This
module is the fourth spine: a **SkewLedger** recording per-worker work
volume at the three places it is cheaply knowable, an imbalance model
turning max/mean load ratios into predicted wasted chip-seconds (composed
with :mod:`harp_tpu.utils.roofline` so waste reads in percent-of-peak),
and :func:`SkewLedger.suggest_rebalance` — the greedy repartition plan
:mod:`harp_tpu.schedule` / the partitioners can apply, bridging
observation back to Harp's dynamic-scheduler behavior.

The three record points:

- **ingest** (:func:`record_partition`) — the :mod:`harp_tpu.fileformat`
  readers and the lda/mfsgd/subgraph/rf partitioners report per-shard
  real rows/nonzeros and the padding fraction at partition time.  Pure
  host arithmetic over arrays the partitioner already built: zero device
  cost.  ``units`` optionally carries the movable grains (e.g. files
  with byte sizes) so the rebalance plan can move whole units.
- **execution** (:func:`record_execution`) — the kmeans/lda/mfsgd epoch
  drivers fold a tiny per-worker work counter (active rows / tokens
  touched) into their EXISTING stacked readback, so the flagship flight
  budgets stay at 1 dispatch / 1 readback per run (pinned in
  tests/test_flightrec.py).  KMeans folds its per-worker row count into
  the same [nw, 2] stats array as the inertia — no extra collective, so
  the hand-computed comm byte sheet (tests/test_telemetry.py) is
  untouched.
- **host phases** (:func:`record_host`) — ``scripts/scaling_sweep.py``
  subprocesses and the multiprocess (Gloo) path
  (:meth:`harp_tpu.mapper.CollectiveApp.run`) stamp per-process
  wall-clock per superstep, covering skew the device counters cannot
  see (file parsing, host prep).

Everything shares the telemetry enable switch (``HARP_TELEMETRY=1`` /
``telemetry.enable()``) and the zero-cost-when-disabled contract: the
module-level hooks return before touching arrays.  The per-worker device
counters themselves are *unconditionally* part of the traced epoch
programs (a telemetry-gated output would make the traced program differ
with the flag, breaking the bit-identical on/off contract the flight
recorder tests pin) — they cost O(num_workers) floats per superstep.

The imbalance model: for per-worker work ``w`` with ``r = max(w) /
mean(w)``, a barrier superstep finishes when the max-loaded worker does,
so the fraction of total chip-time spent idle-waiting is ``1 - mean/max``
and the predicted waste for a phase that took ``wall_s`` is ``wall_s *
n_workers * (1 - mean/max)`` chip-seconds.  :func:`wasted_pct_of_peak`
composes that with the roofline annotation: of the percent-of-peak the
config achieves, the points predicted lost to skew.
"""

from __future__ import annotations

import json
from typing import Any, Sequence

import numpy as np

from harp_tpu.utils import telemetry


class SkewLedger:
    """Per-phase, per-worker work accounting (see module docstring).

    One record per phase name; re-recording a phase overwrites its work
    vector (latest superstep wins — work is per-superstep, and a rerun
    re-measures the same corpus) while ``runs`` counts how often.
    """

    def __init__(self):
        self.reset()

    def reset(self) -> None:
        self._phases: dict[str, dict] = {}

    # -- recording ----------------------------------------------------------
    def _put(self, phase: str, source: str, work, unit: str, **extra) -> None:
        w = np.asarray(work, np.float64).reshape(-1)
        rec = self._phases.get(phase)
        if rec is None or rec["source"] != source or len(rec["work"]) != len(w):
            rec = self._phases[phase] = {
                "phase": phase, "source": source, "unit": unit,
                "work": w, "runs": 0, "padding_frac": None, "wall_s": None,
                "units": None}
        rec["work"] = w
        rec["unit"] = unit
        rec["runs"] += 1
        for k, v in extra.items():
            if v is not None:
                rec[k] = v

    def record_partition(self, phase: str, work, *, unit: str = "rows",
                         padded_total: int | None = None,
                         units: Sequence[Sequence[tuple]] | None = None
                         ) -> None:
        """Ingest-time record: ``work[w]`` = real items on worker ``w``.

        ``padded_total`` is the total slot count after shape padding
        (``padding_frac = 1 - sum(work)/padded_total``); ``units`` is an
        optional per-worker list of movable ``(unit_id, size)`` grains
        (e.g. files) that :meth:`suggest_rebalance` can move whole.
        """
        pf = None
        if padded_total:
            pf = max(0.0, min(1.0, 1.0 - float(np.sum(np.asarray(
                work, np.float64))) / float(padded_total)))
        self._put(phase, "ingest", work, unit, padding_frac=pf,
                  units=[list(u) for u in units] if units is not None
                  else None)

    def record_execution(self, phase: str, work, *, unit: str,
                         wall_s: float | None = None,
                         units: Sequence[Sequence[tuple]] | None = None
                         ) -> None:
        """Execution record: ``work[w]`` = work units worker ``w``
        actually processed this superstep (from the driver's stacked
        readback); ``wall_s`` is the measured host wall for the phase,
        the basis of the wasted-chip-seconds prediction.

        ``units`` (PR 15): optional per-worker movable ``(unit_id,
        size)`` grains, exactly as :meth:`record_partition` takes them.
        The elastic drivers attach their pack grains here so the health
        sentinel's ``skew_trigger`` carries a WHOLE-UNIT
        ``suggest_rebalance`` plan — the shape
        ``schedule.apply_rebalance`` replays mid-run."""
        self._put(phase, "execution", work, unit,
                  wall_s=None if wall_s is None else float(wall_s),
                  units=[list(u) for u in units] if units is not None
                  else None)

    def record_host(self, phase: str, worker: int, wall_s: float,
                    n_workers: int | None = None) -> None:
        """Host-phase record: process ``worker`` spent ``wall_s`` seconds
        in ``phase`` this superstep.  Each process stamps only its own
        column (the Gloo/multi-host path); single-process callers fill
        worker 0 of a width-``n_workers`` vector."""
        rec = self._phases.get(phase)
        n = n_workers or (len(rec["work"]) if rec else worker + 1)
        n = max(n, worker + 1)
        w = np.zeros(n, np.float64)
        if rec is not None and rec["source"] == "host":
            w[: len(rec["work"])] = rec["work"][:n]
        w[worker] = float(wall_s)
        self._put(phase, "host", w, "seconds", wall_s=float(wall_s))

    # -- the imbalance model ------------------------------------------------
    @staticmethod
    def _imbalance(rec: dict) -> dict:
        w = rec["work"]
        total = float(w.sum())
        mean = total / len(w) if len(w) else 0.0
        mx = float(w.max()) if len(w) else 0.0
        ratio = (mx / mean) if mean > 0 else None
        wasted = (1.0 - mean / mx) if mx > 0 else None
        out = {"max_mean_ratio": None if ratio is None else round(ratio, 4),
               "wasted_frac": None if wasted is None else round(wasted, 4)}
        if wasted is not None and rec.get("wall_s"):
            # a barrier superstep ends when the max-loaded worker does:
            # every other worker idles (1 - w_i/max) of the wall
            out["wasted_chip_s"] = round(
                rec["wall_s"] * len(w) * wasted, 6)
        return out

    def summary(self) -> dict:
        """{phase: {source, unit, work, total, n_workers, max_mean_ratio,
        wasted_frac, [wasted_chip_s], [padding_frac], runs, [wall_s]}},
        most-imbalanced phases first."""
        out = {}
        for phase, rec in self._phases.items():
            row = {"source": rec["source"], "unit": rec["unit"],
                   "work": [round(float(x), 4) for x in rec["work"]],
                   "total": round(float(rec["work"].sum()), 4),
                   "n_workers": len(rec["work"]),
                   "runs": rec["runs"]}
            row.update(self._imbalance(rec))
            for k in ("padding_frac", "wall_s"):
                if rec.get(k) is not None:
                    row[k] = round(rec[k], 6)
            out[phase] = row
        return dict(sorted(out.items(),
                           key=lambda kv: -(kv[1]["max_mean_ratio"] or 0)))

    # -- the scheduler bridge -----------------------------------------------
    def suggest_rebalance(self, phase: str) -> dict | None:
        """Greedy repartition plan toward equal per-worker load.

        With ``units`` recorded (movable grains), re-runs greedy
        longest-processing-time placement over every unit (the same rule
        :func:`harp_tpu.fileformat.multi_file_splits` applies to byte
        sizes, here on MEASURED loads) and emits whole-unit moves that
        :func:`harp_tpu.schedule.apply_rebalance` can apply.  Without
        units the plan is fractional: surplus flows from overloaded to
        underloaded workers until all sit at the mean — the target a
        finer-grained partitioner should aim for.  Returns ``{phase,
        unit, moves, ratio_before, ratio_after, work_after}`` or None
        when the phase is unknown/empty.
        """
        rec = self._phases.get(phase)
        if rec is None or not len(rec["work"]) or rec["work"].sum() <= 0:
            return None
        before = self._imbalance(rec)["max_mean_ratio"]
        n = len(rec["work"])
        moves: list[dict] = []
        if rec.get("units"):
            units = [(uid, float(sz), w)
                     for w, lst in enumerate(rec["units"])
                     for uid, sz in lst]
            loads = np.zeros(n)
            assign: dict[Any, int] = {}
            for uid, sz, _ in sorted(units, key=lambda t: -t[1]):
                tgt = int(loads.argmin())
                assign[uid] = tgt
                loads[tgt] += sz
            for uid, sz, src in units:
                if assign[uid] != src:
                    moves.append({"id": uid, "from": src,
                                  "to": assign[uid], "work": sz})
            after_w = loads
        else:
            w = rec["work"].copy()
            mean = w.mean()
            surplus = [(i, w[i] - mean) for i in range(n) if w[i] > mean]
            deficit = [(i, mean - w[i]) for i in range(n) if w[i] < mean]
            surplus.sort(key=lambda t: -t[1])
            deficit.sort(key=lambda t: -t[1])
            si = di = 0
            while si < len(surplus) and di < len(deficit):
                s_i, s_amt = surplus[si]
                d_i, d_amt = deficit[di]
                amt = min(s_amt, d_amt)
                if amt > 1e-12:
                    moves.append({"from": s_i, "to": d_i,
                                  "work": round(float(amt), 4)})
                    w[s_i] -= amt
                    w[d_i] += amt
                if s_amt <= d_amt:
                    si += 1
                    deficit[di] = (d_i, d_amt - amt)
                if d_amt <= s_amt:
                    di += 1
                    if s_amt > d_amt:
                        surplus[si] = (s_i, s_amt - amt)
            after_w = w
        mean = after_w.mean()
        after = round(float(after_w.max() / mean), 4) if mean > 0 else None
        return {"phase": phase, "unit": rec["unit"], "moves": moves,
                "ratio_before": before, "ratio_after": after,
                "work_after": [round(float(x), 4) for x in after_w]}

    # -- export -------------------------------------------------------------
    def export_jsonl(self, fh, stamp: dict | None = None) -> None:
        """One provenance-stamped row per phase (``kind: "skew"``) — the
        shape scripts/check_jsonl.py invariant 5 validates: per-worker
        ``work`` sums to ``total``, ``padding_frac`` in [0, 1]."""
        for phase, row in self.summary().items():
            out = {"kind": "skew", "phase": phase, **row, **(stamp or {})}
            fh.write(json.dumps(out) + "\n")


# ---------------------------------------------------------------------------
# Module singleton + zero-cost hooks
# ---------------------------------------------------------------------------

ledger = SkewLedger()


def reset() -> None:
    """Clear the ledger (telemetry.scope does this on entry)."""
    ledger.reset()


def record_partition(phase: str, work, *, unit: str = "rows",
                     padded_total: int | None = None,
                     units=None) -> None:
    """Ingest hook for readers/partitioners (no-op when telemetry off).

    Also feeds the health sentinel's skew trigger (PR 14): K consecutive
    records with ``wasted_frac`` over the threshold emit a
    ``kind:"health"`` finding carrying the ``suggest_rebalance`` plan
    inline — the elastic-execution hook the PR-15 drivers consume
    mid-run (:mod:`harp_tpu.elastic`)."""
    if telemetry.enabled():
        ledger.record_partition(phase, work, unit=unit,
                                padded_total=padded_total, units=units)
        from harp_tpu import health

        health.monitor.observe_skew(phase, ledger)


def record_execution(phase: str, work, *, unit: str,
                     wall_s: float | None = None, units=None) -> None:
    """Execution hook for the epoch drivers (no-op when telemetry off).
    Feeds the health sentinel's skew trigger like
    :func:`record_partition` — each call is one superstep's record.
    ``units`` carries the elastic drivers' movable pack grains (PR 15)
    so the fired trigger's inline plan is whole-unit replayable."""
    if telemetry.enabled():
        ledger.record_execution(phase, work, unit=unit, wall_s=wall_s,
                                units=units)
        from harp_tpu.utils import steptrace

        if steptrace.tracer._run is not None:
            # the per-worker lane for the covering superstep (PR 18)
            steptrace.tracer.on_execution(phase, work, unit=unit,
                                          wall_s=wall_s)
        from harp_tpu import health

        health.monitor.observe_skew(phase, ledger)


def record_host(phase: str, worker: int, wall_s: float,
                n_workers: int | None = None) -> None:
    """Host-phase hook (scaling sweep / Gloo path; no-op when off)."""
    if telemetry.enabled():
        ledger.record_host(phase, worker, wall_s, n_workers=n_workers)


def suggest_rebalance(phase: str) -> dict | None:
    """Module-level shorthand for :meth:`SkewLedger.suggest_rebalance`."""
    return ledger.suggest_rebalance(phase)


def wasted_pct_of_peak(config: str, result: dict, phase: str,
                       device_kind: str) -> float | None:
    """Skew waste stated in percent-of-peak (the roofline composition).

    ``roofline.annotate`` gives the percent of datasheet peak the
    measured rate achieves on ``device_kind``; the phase's wasted
    fraction says how much of that a balanced partition would reclaim.
    None when either half is unavailable (no work model, a CPU run,
    phase unknown, zero work).
    """
    from harp_tpu.utils import roofline

    rec = ledger._phases.get(phase)
    if rec is None:
        return None
    imb = SkewLedger._imbalance(rec)
    if not imb.get("wasted_frac"):
        return None
    ann = roofline.annotate(config, result, device_kind)
    pct = ann.get("pct_peak_flops")
    if pct is None:
        return None
    return round(pct * imb["wasted_frac"], 3)


def export_jsonl(fh) -> None:
    """Append skew rows (telemetry.export calls this); stamped with the
    flight recorder's provenance triple — a CPU-sim work sheet must never
    read as chip evidence (same inversion guard as invariant 4)."""
    if not ledger._phases:
        return
    from harp_tpu.utils import flightrec

    ledger.export_jsonl(fh, flightrec.provenance_stamp())
