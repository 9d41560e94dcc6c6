#!/usr/bin/env python
"""Validate the committed measurement JSONL files.

Two invariants, enforced as a tier-1 test (tests/test_check_jsonl.py) and
runnable standalone (``python scripts/check_jsonl.py [--repo DIR]``):

1. **Every line parses as JSON.**  The measurement run tees CLI stdout into
   these files; a Python dict repr or a line truncated by a killed sprint
   is a record every downstream reader silently skips — make it loud.

2. **Bench rows carry the provenance stamp** (``backend``, ``date``,
   ``commit`` — the fields :func:`harp_tpu.utils.metrics._provenance`
   writes).  This is the CPU-inversion guard from metrics.py: a
   config-keyed row WITHOUT ``backend`` can pass downstream TPU-evidence
   filters (``perfmodel.grade.latest_tpu_rows`` excludes only
   ``backend == "cpu"``), so an unstamped CPU record reads
   as silicon evidence.  Rows committed before the stamp existed are
   grandfathered BY LINE INDEX (the history is append-only), so every
   row appended after this check landed
   must comply — "my row has no date, so I look legacy" is not a loophole.

PROFILE_local.jsonl and FLIP_DECISIONS.jsonl rows are trace/decision rows,
not bench evidence: they get the parse check only — plus invariants 3/4:

3. **CommLedger rows carry a coherent wire dtype** (any file): a
   ``kind: "comm"`` row for a quantized verb must record ``wire_dtype``
   in {bfloat16, int8}, and an exact rotate/regroup row must not claim
   one — the report's bytes-on-wire claims scale by this field.

4. **Flight-recorder rows are coherent evidence** (any file): a ``kind:
   "compile"`` / ``kind: "transfer"`` row must parse, carry the
   backend/date/commit provenance stamp (a CPU-sim compile count must
   never read as chip evidence — the same inversion guard as check 2),
   and its counters (count/dur/total_s/bytes/calls) must be non-negative
   numbers, with a compile row's cumulative ``count``/``total_s``
   monotone non-decreasing down the file (a decrease means two runs'
   exports were interleaved — every downstream "N compiles this run"
   claim would be wrong).

5. **Skew rows are coherent load evidence** (any file): a ``kind:
   "skew"`` row (the SkewLedger export, :mod:`harp_tpu.utils.skew`) must
   carry the provenance stamp (a CPU-sim load sheet must never read as
   chip evidence), its per-worker ``work`` counts must be non-negative
   numbers that SUM to the row's ``total`` (a mismatch means the
   imbalance ratio describes a different workload than the total
   claims), and ``padding_frac`` — when present — must lie in [0, 1].

6. **Lint rows are coherent analysis evidence** (any file): a ``kind:
   "lint"`` row (``python -m harp_tpu lint``) must carry the provenance
   stamp (a lint verdict is about a specific commit — an unstamped
   "clean" can certify the wrong tree), every rule id it mentions (in
   ``rules`` or as a ``per_rule`` key) must come from the registered set
   (``KNOWN_LINT_RULES`` — kept in sync with
   ``harp_tpu.analysis.rules`` by tests/test_lint.py), and the
   per-file/per-rule violation counts must be non-negative integers.
   CommGraph extension (PR 9): a lint row's per-program ``byte_sheets``
   (the Layer-4 static collective schedule — the planner's future
   input) must name programs from the drivers registry
   (``KNOWN_LINT_PROGRAMS``), primitives/verbs from the frozen wire
   vocabulary (``KNOWN_COMM_PRIMITIVES`` / ``KNOWN_COMM_VERBS``), and
   carry non-negative byte/count fields — a sheet naming an unknown
   program or claiming negative bytes would poison every schedule
   decision built on it.

7. **Serve rows are coherent serving evidence** (any file): a ``kind:
   "serve"`` row (``harp_tpu.serve.bench`` / ``serve <app> --bench``)
   must carry the provenance stamp, its latency percentiles must be
   non-negative and monotone (``p50_ms <= p95_ms <= p99_ms`` — crossed
   percentiles mean the latency sample was mangled), ``qps`` must be a
   positive number, and ``steady_compiles`` must be EXACTLY 0 — the
   serving loop's whole contract is that the steady state never
   recompiles, so a row that measured throughput while silently
   compiling per batch is not serving evidence at all.  SUSTAINED serve
   rows (the continuous-batching A/B, ``serve.bench.benchmark_
   sustained`` — recognizable by ``offered_qps``/``achieved_qps`` or
   ``mode == "sustained"``) additionally must satisfy ``offered_qps >=
   achieved_qps > 0`` (achieved above offered means the latency origin
   was not the arrival trace — the burst-submit dishonesty this mode
   exists to fix) and carry non-negative queue-depth percentiles
   (``qdepth_p50``/``qdepth_p95``/``qdepth_p99``): a sustained row
   without queue evidence cannot support any claim about the
   padding-vs-latency tradeoff its knobs encode.

8. **Ingest rows are coherent streaming evidence** (any file): a ``kind:
   "ingest"`` row (``kmeans_stream.benchmark_ingest`` /
   ``scripts/bench_ingest.py``, PR 8) must carry the provenance stamp
   (a CPU host-chain rate must never read as chip evidence),
   its ``overlap_efficiency`` (the host pipeline's stage-overlap score)
   must lie in [0, 1], and its rates must be positive:
   ``host_gb_per_sec > 0`` and ``points_per_sec > 0`` — a zero or
   negative rate means the instrument block never ran, and such a row
   grading the ingest fast path would certify a measurement that did
   not happen.

9. **Degraded-mode serve rows balance their books** (any file): a serve
   row carrying the fault-plane fields (``serve.bench.
   benchmark_sustained`` under shedding/deadlines/chaos, PR 10 —
   recognizable by any of ``shed_frac`` / ``deadline_miss_frac`` /
   ``fault_retries`` / ``shed_requests``) must carry ALL of them
   coherently: ``shed_frac`` and ``deadline_miss_frac`` in [0, 1],
   ``fault_retries`` a non-negative integer, and the request ledger
   exact — ``served_requests + shed_requests + failed_requests ==
   offered_requests`` (every offered request came back as exactly one
   of served / structured-shed / hard-failed; a row where requests
   vanish is not degradation evidence, it is a dead server wearing a
   qps number).

10. **Plan rows are coherent schedule evidence** (any file): a ``kind:
    "plan"`` row (``python -m harp_tpu plan``, PR 11) must carry the
    provenance stamp (a schedule decision is about a specific commit's
    byte sheets), name a registered driver program
    (``KNOWN_LINT_PROGRAMS``) and a frozen topology tag
    (``KNOWN_PLAN_TOPOLOGIES``), choose every site's schedule from the
    frozen vocabulary (``KNOWN_PLAN_SCHEDULES``) — and today that
    chosen schedule must be ``"keep"``: the planner FAILS CLOSED, so a
    committed row claiming any other choice is evidence of a bypassed
    flip gate — with per-site ``predicted_bytes`` equal to the frozen
    schedule scaling of the site's ``sheet_bytes`` (for ``keep``,
    exactly the program's byte sheet: a plan whose predictions drift
    from the sheet is pricing a program this repo does not run).

11. **Trace rows are a complete causal timeline** (any file): a ``kind:
    "trace"`` row (``harp_tpu.utils.reqtrace`` — ``telemetry.export`` /
    ``export_timeline``, PR 12) must carry the provenance stamp (a
    CPU-sim request timeline must never read as chip latency
    evidence), declare a known row shape (``ev`` ∈
    ``KNOWN_TRACE_EVS``), and carry a numeric non-negative ``ts`` that
    is MONOTONE non-decreasing down the file (the exporters sort — a
    decrease means two runs' timelines were interleaved, and a
    "causally ordered" file that is not ordered is not a timeline).
    Every request id seen in an ``ev:"event"`` row must have a
    TERMINATED ``ev:"request"`` row whose ``outcome`` ∈
    ``KNOWN_TRACE_OUTCOMES`` (served / shed / failed — an offered
    request that simply vanishes from its own trace is the exact
    failure mode request tracing exists to make impossible), and when
    the same file carries exactly one invariant-9 degraded-mode serve
    row, the per-outcome request counts must reconcile with that
    ledger EXACTLY (served == served_requests, etc.): a trace and a
    bench row telling different stories about the same run means one
    of them is lying.

12. **Model rows are coherent prediction evidence** (any file): a
    ``kind: "model"`` row (``python -m harp_tpu predict``, PR 13 —
    :mod:`harp_tpu.perfmodel`) must carry the provenance stamp (a
    prediction is about a specific commit's byte sheets and work
    models), name a registered program (``KNOWN_LINT_PROGRAMS``)
    and/or a config from the frozen list ``KNOWN_MODEL_CONFIGS`` (the
    config names BENCH_local.jsonl's rows and the price list use), stamp
    ``rates_source`` and ``bound`` from the frozen vocabularies
    (``KNOWN_MODEL_RATES_SOURCES`` / ``KNOWN_MODEL_BOUNDS`` —
    sync-pinned against ``harp_tpu.perfmodel`` by
    tests/test_perfmodel.py), predict POSITIVE seconds, carry all four
    per-term entries summing to ``predicted_s`` within float
    tolerance, and name as ``bound`` the largest term — a breakdown
    that does not reconcile with its own total is a wrong prediction
    that cannot even be diagnosed, which is the one thing a model row
    exists to prevent.

13. **Health rows are coherent monitoring evidence** (any file): a
    ``kind: "health"`` row (the PR-14 sentinel — ``harp_tpu.health``,
    exported by ``telemetry.export`` / emitted by ``python -m harp_tpu
    health --grade-model``) must carry the provenance stamp (a CPU-sim
    finding must never read as chip degradation evidence), name a
    registered detector and severity (``KNOWN_HEALTH_DETECTORS`` /
    ``KNOWN_HEALTH_SEVERITIES`` — frozen standalone and sync-pinned
    against ``harp_tpu.health`` by tests), carry non-negative integer
    counts and non-negative burn/ratio numbers, and — per detector —
    an ``evidence_regression`` row MUST carry a ``verdict`` from
    ``KNOWN_HEALTH_VERDICTS`` (``model_invalidated`` is the one that
    fails ``health --grade-model`` closed), while a
    ``skew_trigger`` row MUST carry a structurally valid inline
    rebalance plan (``schedule.apply_rebalance``'s input shape:
    ``phase``, ``moves`` with non-negative worker ids and work, numeric
    before/after ratios) — the elastic-execution hook is only a hook if
    its payload is replayable.

14. **Elastic rows are coherent elasticity evidence** (any file): a
    ``kind:"elastic"`` row (the PR-15 acting half —
    :mod:`harp_tpu.elastic`, exported by ``telemetry.export``) must
    carry the provenance stamp (a CPU-sim drill must never read as
    chip elasticity evidence), name an event from the frozen
    vocabulary (``KNOWN_ELASTIC_EVENTS``: rebalance / shrink / resume —
    sync-pinned against ``harp_tpu.elastic.EVENTS`` by
    tests/test_check_jsonl.py), carry per-worker load lists of
    non-negative numbers that SUM to the row's ``total``, and per
    event: a ``rebalance`` row must carry ``wasted_frac_before``/
    ``wasted_frac_after`` in [0, 1] with after ≤ before (a "rebalance"
    that made the imbalance worse is not rebalance evidence), and a
    ``shrink`` row must show the survivor count strictly below the
    pre-fault count (``n_workers_after < n_workers_before``) — a
    shrink that lost no worker describes a fault that did not happen.

15. **Profile rows are coherent attribution evidence** (any file): a
    ``kind:"profile"`` row (the PR-16 wall-attribution observatory —
    ``python -m harp_tpu profile``, :mod:`harp_tpu.profile`) must carry
    the provenance stamp (a CPU-sim attribution must never read as
    silicon wall evidence), name an app and driver program from the
    frozen vocabularies (``KNOWN_PROFILE_APPS`` /
    ``KNOWN_LINT_PROGRAMS`` — sync-pinned against
    ``harp_tpu.profile.attribution.PROFILE_APPS`` by
    tests/test_check_jsonl.py), carry exactly the six frozen mechanism
    buckets (``KNOWN_PROFILE_BUCKETS``) as non-negative ``*_s`` terms
    that SUM to the measured ``wall_s`` (the whole contract: every
    wall second is attributed to a mechanism, residual in overhead),
    name as ``bound`` the largest bucket (the wall the row claims),
    keep ``sum_rel_err`` within ``PROFILE_SUM_REL_TOL`` (sync-pinned
    against ``attribution.SUM_REL_TOL``), and reconcile against the
    other spines fail-closed: ``dispatches == reps *
    dispatches_per_rep`` (flight recorder), ``compiles_in_window ==
    0`` (a row that compiled mid-capture timed the compiler),
    ``wire_unmatched == 0`` (every static collective site carries a
    CommLedger verb match), and ``reconciled`` literally true — an
    unreconciled attribution committed as evidence is exactly the
    hand-read-profile ritual this row type replaces.

16. **Steptrace rows are a complete causal training timeline** (any
    file): a ``kind:"steptrace"`` row (the PR-18 superstep flightpath —
    :mod:`harp_tpu.utils.steptrace`, exported by ``telemetry.export`` /
    ``export_timeline``) must carry the provenance stamp (a CPU-sim
    training timeline must never read as chip evidence), declare a
    known row shape (``ev`` ∈ ``KNOWN_STEPTRACE_EVS``), and carry a
    numeric non-negative ``ts`` MONOTONE non-decreasing across the
    file's steptrace rows.  Every superstep span must terminate with an
    outcome from ``KNOWN_STEPTRACE_OUTCOMES`` and attribute exactly the
    frozen flight counters (``KNOWN_STEPTRACE_FLIGHT_KEYS``); every
    run id seen in span/mark/lane rows must close in exactly one
    ``ev:"run"`` row, whose declared ``supersteps`` / per-outcome
    counts / ``span_flight`` sums / ``marks`` / ``lanes`` are
    re-derived from the rows and must match EXACTLY.  Cross-spine,
    fail closed: each run's ``flight.dispatches`` must equal its
    dispatch-mark count (the flightrec observer path vs the
    TransferLedger counters — two independent spines), the file's runs
    cannot attribute more dispatches than its ``kind:"transfer"``
    dispatch rows record, elastic marks must match the file's
    timeline-covered ``kind:"elastic"`` rows (``on_timeline: true``)
    event-for-event (a rebalance on the timeline that the elastic
    ledger never recorded — or vice versa — means one spine is lying;
    rows recorded outside any run are legitimately unmarked), every
    health mark must name a detector
    with a ``kind:"health"`` row, and every ``consume_skew_trigger``
    actuation mark must point at a CONSUMED ``skew_trigger`` finding —
    the exactly-once handshake leaves ledger evidence or it did not
    happen.

17. **Memory rows are a replayable device-memory ledger** (any file): a
    ``kind:"memory"`` row (the PR-19 memory spine —
    :mod:`harp_tpu.utils.memrec`, exported by ``telemetry.export``)
    must carry the provenance stamp (a CPU-sim footprint must never
    read as silicon HBM evidence), declare a known row shape (``ev`` ∈
    ``KNOWN_MEMORY_EVS``; buffer rows additionally ``event`` ∈
    ``KNOWN_MEMORY_EVENTS``) with a strictly increasing ``seq``, and
    the ledger must REPLAY: re-deriving the live set from the buffer
    event stream (staged/output add, freed/donated remove — a
    freed/donated buffer must BE live; ``restored`` is zero-delta by
    design), every row's ``live_bytes``/``peak_bytes`` must equal the
    derived watermark EXACTLY; a ``dispatch`` row's donated buffer ids
    must have left the live set (the runtime twin of the HL303
    donation audit); an ``executable`` row's four footprint components
    must sum to its ``exec_hbm_bytes``; a ``vmem_check`` row's
    ``fits``/``refused`` flags must agree with its own
    predicted-vs-budget bytes; and the export must terminate in
    EXACTLY one ``summary`` row whose staged/freed/donated/peak/live
    totals and ``headroom_frac`` (= 1 − peak/hbm) re-derive from the
    stream — buffer events after the summary, or a peak the events
    cannot reproduce, mean the watermark was asserted, not measured.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

# line counts at the commit where this check landed (2026-08-04); rows up
# to these indices predate the provenance stamp and are exempt from check
# 2 (never from check 1).  Bump ONLY when deliberately rewriting history
# (73 → 72 in PR 21, which removed legacy row 4, a record with no number).
GRANDFATHERED = {"BENCH_local.jsonl": 72}

PARSE_ONLY = ("PROFILE_local.jsonl", "FLIP_DECISIONS.jsonl",
              "PROFILE_attrib.jsonl")
PROVENANCE_FIELDS = ("backend", "date", "commit")

# CommLedger rows (telemetry exports, teed into committed JSONL by
# HARP_TELEMETRY runs): the quantized movement/reduce verbs MUST name a
# narrow wire, the exact rotate/regroup twins must NOT claim one — a
# wrong wire_dtype silently mis-scales every bytes-on-wire claim the
# report makes (the whole point of the quantized-rotate telemetry).
QUANT_WIRES = ("bfloat16", "int8")
QUANT_VERBS = ("rotate_quantized", "regroup_quantized",
               "allreduce_quantized", "push_quantized")
EXACT_MOVE_VERBS = ("rotate", "regroup")


def _check_comm_row(name: str, i: int, row: dict) -> list[str]:
    verb = row.get("verb")
    wd = row.get("wire_dtype")
    if verb in QUANT_VERBS and wd not in QUANT_WIRES:
        return [f"{name}:{i}: comm row verb={verb!r} has "
                f"wire_dtype={wd!r} — quantized verbs must record one of "
                f"{QUANT_WIRES}"]
    if verb in EXACT_MOVE_VERBS and wd:
        return [f"{name}:{i}: comm row verb={verb!r} claims "
                f"wire_dtype={wd!r} — the exact verbs have no narrow "
                "wire; use the *_quantized twin (or drop the field)"]
    return []


FLIGHT_COUNTER_FIELDS = ("count", "dur", "total_s", "bytes", "calls")
FLIGHT_MONOTONE_FIELDS = ("count", "total_s")  # cumulative per export


def _check_flight_row(name: str, i: int, row: dict,
                      state: dict) -> list[str]:
    """Invariant 4: compile/transfer rows must be coherent evidence.

    ``state`` carries the previous compile row's cumulative counters so
    monotonicity is checked per file in line order.
    """
    errs: list[str] = []
    kind = row.get("kind")
    missing = [f for f in PROVENANCE_FIELDS if f not in row]
    if missing:
        errs.append(
            f"{name}:{i}: {kind} row missing provenance field(s) "
            f"{missing} — export through telemetry.export / "
            "flightrec.export_jsonl, which stamp them")
    for k in FLIGHT_COUNTER_FIELDS:
        v = row.get(k)
        if v is None:
            continue
        if isinstance(v, bool) or not isinstance(v, (int, float)) or v < 0:
            errs.append(f"{name}:{i}: {kind} row counter {k}={v!r} must "
                        "be a non-negative number")
    if kind == "compile":
        for k in FLIGHT_MONOTONE_FIELDS:
            v = row.get(k)
            if not isinstance(v, (int, float)) or isinstance(v, bool):
                continue
            last = state.get(k)
            if last is not None and v < last:
                errs.append(
                    f"{name}:{i}: compile row {k}={v} decreased from "
                    f"{last} — cumulative counters must be monotone "
                    "(interleaved exports?)")
            state[k] = v
    return errs


def _check_skew_row(name: str, i: int, row: dict) -> list[str]:
    """Invariant 5: skew rows must be coherent load evidence."""
    errs: list[str] = []
    missing = [f for f in PROVENANCE_FIELDS if f not in row]
    if missing:
        errs.append(
            f"{name}:{i}: skew row missing provenance field(s) {missing} "
            "— export through telemetry.export / skew.export_jsonl, "
            "which stamp them")
    work = row.get("work")
    total = row.get("total")
    if (isinstance(work, list) and work
            and all(isinstance(x, (int, float)) and not isinstance(x, bool)
                    for x in work)):
        if any(x < 0 for x in work):
            errs.append(f"{name}:{i}: skew row has negative per-worker "
                        "work counts")
        if isinstance(total, (int, float)) and not isinstance(total, bool):
            s = sum(work)
            if abs(s - total) > 1e-6 * max(1.0, abs(total)):
                errs.append(
                    f"{name}:{i}: skew row per-worker work sums to {s} "
                    f"but total claims {total} — counts must sum to the "
                    "global total")
    else:
        errs.append(f"{name}:{i}: skew row work={work!r} must be a "
                    "non-empty list of numbers")
    pf = row.get("padding_frac")
    if pf is not None and (isinstance(pf, bool)
                           or not isinstance(pf, (int, float))
                           or not 0.0 <= pf <= 1.0):
        errs.append(f"{name}:{i}: skew row padding_frac={pf!r} must lie "
                    "in [0, 1]")
    return errs


# the registered harplint rule ids, FROZEN here so this script stays
# standalone (no harp_tpu import); tests/test_lint.py asserts equality
# with harp_tpu.analysis.rules.rule_ids() so drift fails tier-1
KNOWN_LINT_RULES = ("HL000", "HL001", "HL002", "HL003", "HL004", "HL005",
                    "HL101", "HL102", "HL201", "HL202", "HL203", "HL204",
                    "HL205", "HL301", "HL302", "HL303", "HL304",
                    "HL401", "HL402", "HL403", "HL404", "HL405")
LINT_COUNT_FIELDS = ("files_scanned", "violations", "allowlisted",
                     "stale_allowlist")

# the CommGraph byte-sheet vocabulary, FROZEN like the rule ids and
# sync-pinned by tests/test_lint.py: program names must come from the
# drivers registry (harp_tpu.analysis.drivers.DRIVERS), primitives from
# the verbs' wire surface (collective.PRIMITIVE_VERBS), verbs from the
# collective verb table — a sheet naming an unknown program or verb is
# not evidence about THIS repo's communication schedule.
KNOWN_LINT_PROGRAMS = (
    "collective.reshard", "collective.reshard_wire",
    "elastic.regather",
    "ingest.accum_chunk", "ingest.finish_epoch", "kmeans.fit",
    "kmeans.fit_hier", "lda.epoch",
    "mfsgd.epoch", "rf.grow", "rf.grow_pallas", "ring_attention",
    "rotate.pipeline_chunked",
    "serve.kmeans_assign", "serve.lda_infer", "serve.mfsgd_topk",
    "serve.mlp_logits", "serve.rf_vote", "serve.svm_scores",
    "subgraph.count", "svm.train", "svm.train_pallas",
    "wdamds.smacof", "wdamds.smacof_pallas")
KNOWN_COMM_PRIMITIVES = ("all_gather", "all_to_all", "pmax", "pmin",
                         "ppermute", "psum", "reduce_scatter")
KNOWN_COMM_VERBS = ("allgather", "allreduce", "allreduce_hier",
                    "allreduce_quantized",
                    "barrier", "broadcast", "pull", "push",
                    "push_quantized", "reduce", "regroup",
                    "regroup_quantized", "reshard", "rotate",
                    "rotate_quantized")
SHEET_BYTE_FIELDS = ("bytes_per_trace", "amplified_bytes")


def _check_lint_row(name: str, i: int, row: dict) -> list[str]:
    """Invariant 6: lint rows must be coherent analysis evidence."""
    errs: list[str] = []
    missing = [f for f in PROVENANCE_FIELDS if f not in row]
    if missing:
        errs.append(
            f"{name}:{i}: lint row missing provenance field(s) {missing} "
            "— print it through harp_tpu.analysis.cli (benchmark_json "
            "stamps them)")
    mentioned = list(row.get("rules") or []) + list(row.get("per_rule")
                                                   or {})
    unknown = sorted({r for r in mentioned if r not in KNOWN_LINT_RULES})
    if unknown:
        errs.append(
            f"{name}:{i}: lint row mentions unregistered rule id(s) "
            f"{unknown} — ids must come from harp_tpu.analysis.rules "
            "(update KNOWN_LINT_RULES in the same commit as the "
            "registry)")
    counts = dict(row.get("per_file") or {})
    counts.update(row.get("per_rule") or {})
    counts.update({k: row[k] for k in LINT_COUNT_FIELDS if k in row})
    for key, v in counts.items():
        if isinstance(v, bool) or not isinstance(v, int) or v < 0:
            errs.append(f"{name}:{i}: lint row count {key}={v!r} must be "
                        "a non-negative integer")
    for prog, sheet in (row.get("byte_sheets") or {}).items():
        errs += _check_byte_sheet(name, i, prog, sheet)
    return errs


def _check_byte_sheet(name: str, i: int, prog, sheet) -> list[str]:
    """Invariant 6, CommGraph extension: a lint row's per-program byte
    sheet (the Layer-4 static comm schedule the planner will consume)
    must name a registered driver program, registered primitives/verbs,
    and non-negative byte counts — a malformed sheet poisons every
    schedule decision built on it."""
    errs: list[str] = []
    if prog not in KNOWN_LINT_PROGRAMS:
        errs.append(
            f"{name}:{i}: byte sheet for unregistered program {prog!r} "
            "— program names must come from "
            "harp_tpu.analysis.drivers.DRIVERS (update "
            "KNOWN_LINT_PROGRAMS in the same commit as the registry)")
    if not isinstance(sheet, dict):
        return errs + [f"{name}:{i}: byte sheet for {prog!r} must be an "
                       "object"]
    for k in SHEET_BYTE_FIELDS:
        v = sheet.get(k)
        if isinstance(v, bool) or not isinstance(v, int) or v < 0:
            errs.append(f"{name}:{i}: byte sheet {prog!r} {k}={v!r} "
                        "must be a non-negative integer")
    for c in sheet.get("collectives") or []:
        if not isinstance(c, dict):
            errs.append(f"{name}:{i}: byte sheet {prog!r} has a "
                        "non-object collective entry")
            continue
        prim = c.get("primitive")
        if prim not in KNOWN_COMM_PRIMITIVES:
            errs.append(
                f"{name}:{i}: byte sheet {prog!r} names unknown "
                f"primitive {prim!r} (known: {KNOWN_COMM_PRIMITIVES})")
        verb = c.get("verb")
        if verb is not None and verb not in KNOWN_COMM_VERBS:
            errs.append(
                f"{name}:{i}: byte sheet {prog!r} names unknown verb "
                f"{verb!r} (known: {KNOWN_COMM_VERBS})")
        for k in ("per_shard_bytes", "calls_per_trace", "amplification"):
            v = c.get(k)
            if isinstance(v, bool) or not isinstance(v, int) or v < 0:
                errs.append(
                    f"{name}:{i}: byte sheet {prog!r} collective "
                    f"{k}={v!r} must be a non-negative integer")
    return errs


SERVE_PCTL_FIELDS = ("p50_ms", "p95_ms", "p99_ms")


def _check_serve_row(name: str, i: int, row: dict) -> list[str]:
    """Invariant 7: serve rows must be coherent serving evidence."""
    errs: list[str] = []
    missing = [f for f in PROVENANCE_FIELDS if f not in row]
    if missing:
        errs.append(
            f"{name}:{i}: serve row missing provenance field(s) "
            f"{missing} — print it through "
            "harp_tpu.utils.metrics.benchmark_json")
    pctls = []
    for k in SERVE_PCTL_FIELDS:
        v = row.get(k)
        if (isinstance(v, bool) or not isinstance(v, (int, float))
                or v < 0):
            errs.append(f"{name}:{i}: serve row {k}={v!r} must be a "
                        "non-negative number")
            pctls = None
            break
        pctls.append(v)
    if pctls is not None and not (pctls[0] <= pctls[1] <= pctls[2]):
        errs.append(
            f"{name}:{i}: serve row percentiles p50={pctls[0]} "
            f"p95={pctls[1]} p99={pctls[2]} are not monotone — the "
            "latency sample was mangled")
    qps = row.get("qps")
    if isinstance(qps, bool) or not isinstance(qps, (int, float)) \
            or qps <= 0:
        errs.append(f"{name}:{i}: serve row qps={qps!r} must be a "
                    "positive number")
    sc = row.get("steady_compiles")
    if isinstance(sc, bool) or not isinstance(sc, int) or sc != 0:
        errs.append(
            f"{name}:{i}: serve row steady_compiles={sc!r} must be "
            "exactly 0 — a serving loop that compiles in steady state "
            "violates its own contract (flightrec.SteadyState)")
    if ("offered_qps" in row or "achieved_qps" in row
            or row.get("mode") == "sustained"):
        errs += _check_sustained_serve_row(name, i, row)
    if any(k in row for k in DEGRADED_TRIGGER_FIELDS):
        errs += _check_degraded_serve_row(name, i, row)
    return errs


SERVE_QDEPTH_FIELDS = ("qdepth_p50", "qdepth_p95", "qdepth_p99")


def _num(v) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def _check_sustained_serve_row(name: str, i: int, row: dict) -> list[str]:
    """Invariant 7, sustained extension (continuous-batching rows)."""
    errs: list[str] = []
    off, ach = row.get("offered_qps"), row.get("achieved_qps")
    if not _num(off) or not _num(ach) or ach <= 0 or off < ach:
        errs.append(
            f"{name}:{i}: sustained serve row needs offered_qps >= "
            f"achieved_qps > 0, got offered={off!r} achieved={ach!r} — "
            "achieved above offered means latency was not measured "
            "from the arrival trace")
    for k in SERVE_QDEPTH_FIELDS:
        v = row.get(k)
        if not _num(v) or v < 0:
            errs.append(
                f"{name}:{i}: sustained serve row {k}={v!r} must be a "
                "non-negative number — queue-depth evidence is what "
                "grades the padding-vs-latency knobs")
    return errs


DEGRADED_TRIGGER_FIELDS = ("shed_frac", "deadline_miss_frac",
                           "fault_retries", "shed_requests")
DEGRADED_FRAC_FIELDS = ("shed_frac", "deadline_miss_frac")
DEGRADED_COUNT_FIELDS = ("offered_requests", "served_requests",
                         "shed_requests", "failed_requests",
                         "fault_retries")


def _check_degraded_serve_row(name: str, i: int, row: dict) -> list[str]:
    """Invariant 9: fault-plane serve rows must balance their books."""
    errs: list[str] = []
    for k in DEGRADED_FRAC_FIELDS:
        v = row.get(k)
        if not _num(v) or not 0.0 <= v <= 1.0:
            errs.append(
                f"{name}:{i}: degraded serve row {k}={v!r} must lie in "
                "[0, 1] — it is a fraction of offered requests")
    counts = {}
    for k in DEGRADED_COUNT_FIELDS:
        v = row.get(k)
        if isinstance(v, bool) or not isinstance(v, int) or v < 0:
            errs.append(
                f"{name}:{i}: degraded serve row {k}={v!r} must be a "
                "non-negative integer")
        else:
            counts[k] = v
    if all(k in counts for k in ("offered_requests", "served_requests",
                                 "shed_requests", "failed_requests")):
        total = (counts["served_requests"] + counts["shed_requests"]
                 + counts["failed_requests"])
        if total != counts["offered_requests"]:
            errs.append(
                f"{name}:{i}: degraded serve row served "
                f"{counts['served_requests']} + shed "
                f"{counts['shed_requests']} + failed "
                f"{counts['failed_requests']} = {total} != offered "
                f"{counts['offered_requests']} — every offered request "
                "must come back as exactly one of the three")
    return errs


# the plan-row vocabularies (invariant 10), FROZEN standalone like the
# lint rule ids and sync-pinned by tests/test_plan.py against
# harp_tpu.plan (topology.TOPOLOGY_NAMES / planner.SCHEDULES /
# planner.predicted_bytes)
KNOWN_PLAN_TOPOLOGIES = ("single_chip", "sim_ring_8", "v5e_2x2", "v4_32")
KNOWN_PLAN_SCHEDULES = ("keep", "hier_psum", "chunked_pipeline",
                        "wire_bf16", "wire_int8")


def _plan_predicted_bytes(schedule: str, sheet_bytes: int) -> int:
    """The frozen schedule→bytes scaling (mirror of
    harp_tpu.plan.planner.predicted_bytes; drift fails tests)."""
    if schedule in ("keep", "chunked_pipeline"):
        return int(sheet_bytes)
    if schedule == "hier_psum":
        return 2 * int(sheet_bytes)
    if schedule == "wire_bf16":
        return (int(sheet_bytes) + 1) // 2
    return (int(sheet_bytes) + 3) // 4  # wire_int8


def _check_plan_row(name: str, i: int, row: dict) -> list[str]:
    """Invariant 10: plan rows must be coherent schedule evidence."""
    errs: list[str] = []
    missing = [f for f in PROVENANCE_FIELDS if f not in row]
    if missing:
        errs.append(
            f"{name}:{i}: plan row missing provenance field(s) {missing} "
            "— print it through harp_tpu.plan.cli (benchmark_json stamps "
            "them)")
    prog = row.get("program")
    if prog not in KNOWN_LINT_PROGRAMS:
        errs.append(
            f"{name}:{i}: plan row for unregistered program {prog!r} — "
            "programs must come from harp_tpu.analysis.drivers.DRIVERS "
            "(update KNOWN_LINT_PROGRAMS in the same commit as the "
            "registry)")
    topo = row.get("topology")
    if topo not in KNOWN_PLAN_TOPOLOGIES:
        errs.append(
            f"{name}:{i}: plan row names unknown topology {topo!r} "
            f"(known: {KNOWN_PLAN_TOPOLOGIES})")
    for s in row.get("sites") or []:
        if not isinstance(s, dict):
            errs.append(f"{name}:{i}: plan row has a non-object site "
                        "entry")
            continue
        sched = s.get("schedule")
        if sched not in KNOWN_PLAN_SCHEDULES:
            errs.append(
                f"{name}:{i}: plan site {s.get('site')!r} chose unknown "
                f"schedule {sched!r} (known: {KNOWN_PLAN_SCHEDULES})")
            continue
        if sched != "keep":
            errs.append(
                f"{name}:{i}: plan site {s.get('site')!r} chose "
                f"{sched!r} — the planner fails closed (schedule is "
                "always 'keep'; alternatives ride flip candidates, "
                "never the chosen slot)")
        sb, pb = s.get("sheet_bytes"), s.get("predicted_bytes")
        for k, v in (("sheet_bytes", sb), ("predicted_bytes", pb)):
            if isinstance(v, bool) or not isinstance(v, int) or v < 0:
                errs.append(f"{name}:{i}: plan site {k}={v!r} must be a "
                            "non-negative integer")
        if (isinstance(sb, int) and isinstance(pb, int)
                and not isinstance(sb, bool) and not isinstance(pb, bool)
                and pb != _plan_predicted_bytes(sched, sb)):
            errs.append(
                f"{name}:{i}: plan site {s.get('site')!r} predicts "
                f"{pb} B under {sched!r} but the sheet says {sb} B — "
                f"expected {_plan_predicted_bytes(sched, sb)}; the "
                "prediction must equal the frozen scaling of the "
                "program's byte sheet")
    return errs


# the trace-row vocabularies (invariant 11), FROZEN standalone like the
# lint rule ids and sync-pinned by tests/test_reqtrace.py against
# harp_tpu.utils.reqtrace.OUTCOMES
KNOWN_TRACE_OUTCOMES = ("served", "shed", "failed")
KNOWN_TRACE_EVS = ("event", "request", "batch", "mark", "summary")


def _check_trace_row(name: str, i: int, row: dict,
                     state: dict) -> list[str]:
    """Invariant 11, per-row half: stamp, row shape, monotone ts.

    ``state`` accumulates the file-level evidence the end-of-file half
    (:func:`_finish_trace_checks`) reconciles: request ids seen in
    event rows, terminated request rows with their outcomes, and the
    previous row's timestamp for monotonicity.
    """
    errs: list[str] = []
    missing = [f for f in PROVENANCE_FIELDS if f not in row]
    if missing:
        errs.append(
            f"{name}:{i}: trace row missing provenance field(s) "
            f"{missing} — export through telemetry.export / "
            "telemetry.export_timeline, which stamp them")
    ev = row.get("ev")
    if ev not in KNOWN_TRACE_EVS:
        errs.append(f"{name}:{i}: trace row ev={ev!r} not in "
                    f"{KNOWN_TRACE_EVS}")
    ts = row.get("ts")
    if not _num(ts) or ts < 0:
        errs.append(f"{name}:{i}: trace row ts={ts!r} must be a "
                    "non-negative number — a timeline row without a "
                    "timestamp cannot be causally ordered")
    else:
        last = state.get("last_ts")
        if last is not None and ts < last:
            errs.append(
                f"{name}:{i}: trace row ts={ts} decreased from {last} — "
                "timeline rows must be monotone (interleaved exports?)")
        state["last_ts"] = ts
    if ev == "event" and "req" in row:
        state.setdefault("seen", set()).add(row["req"])
    if ev == "request":
        outcome = row.get("outcome")
        if outcome not in KNOWN_TRACE_OUTCOMES:
            errs.append(
                f"{name}:{i}: trace request row req={row.get('req')!r} "
                f"has outcome={outcome!r} — every request span must "
                f"terminate with one of {KNOWN_TRACE_OUTCOMES}")
        else:
            counts = state.setdefault(
                "outcomes", {o: 0 for o in KNOWN_TRACE_OUTCOMES})
            counts[outcome] += 1
        state.setdefault("terminated", set()).add(row.get("req"))
    return errs


def _finish_trace_checks(name: str, state: dict,
                         degraded: list[tuple[int, dict]]) -> list[str]:
    """Invariant 11, file-level half: span completeness + ledger
    reconciliation (runs after the whole file was scanned)."""
    errs: list[str] = []
    unterminated = sorted(state.get("seen", set())
                          - state.get("terminated", set()))
    if unterminated:
        errs.append(
            f"{name}: trace has {len(unterminated)} request span(s) with "
            f"events but no terminated outcome row: {unterminated[:8]} — "
            "every offered request must end served/shed/failed")
    counts = state.get("outcomes")
    if counts is not None and len(degraded) == 1:
        _, row = degraded[0]
        ledger = {"served": row.get("served_requests"),
                  "shed": row.get("shed_requests"),
                  "failed": row.get("failed_requests")}
        if all(isinstance(v, int) and not isinstance(v, bool)
               for v in ledger.values()) and counts != ledger:
            errs.append(
                f"{name}: trace outcome counts {counts} do not "
                f"reconcile with the file's invariant-9 serve ledger "
                f"{ledger} — the timeline and the bench row describe "
                "different runs")
    return errs


# the model-row vocabularies (invariant 12), FROZEN standalone like the
# plan vocabularies and sync-pinned by tests/test_perfmodel.py against
# harp_tpu.perfmodel (BOUNDS / RATES_SOURCES; CONFIG_MODELS and
# PROGRAM_CONFIGS name configs of this list only)
KNOWN_MODEL_BOUNDS = ("compute", "memory", "wire", "overhead")
KNOWN_MODEL_RATES_SOURCES = ("declared", "probed")
KNOWN_MODEL_CONFIGS = (
    "kmeans", "kmeans_hier_psum", "kmeans_ingest", "kmeans_ingest_int8",
    "kmeans_int8", "kmeans_int8_fused", "kmeans_stream",
    "kmeans_stream_int8", "lda", "lda_carry", "lda_exprace", "lda_fast",
    "lda_pallas", "lda_pallas_approx", "lda_pallas_approx_hot",
    "lda_pallas_carry", "lda_pallas_hot", "lda_planner_wire",
    "lda_rotate_int8", "lda_scale", "lda_scale_1m", "lda_scale_1m_pallas",
    "lda_scatter", "mfsgd", "mfsgd_carry", "mfsgd_chunked_rotate",
    "mfsgd_pallas", "mfsgd_scatter", "mlp", "mlp_grad_bf16",
    "mlp_grad_int8", "rf", "rf_dense_hist", "rf_hist_pallas",
    "rf_scatter_hist", "serve_kmeans", "serve_kmeans_sustained",
    "serve_mfsgd_sustained", "serve_mfsgd_topk", "subgraph",
    "subgraph_1m", "subgraph_1m_onehot", "subgraph_csr32",
    "subgraph_onehot", "subgraph_pl",
    "svm", "svm_kernel_pallas", "svm_sv_bf16",
    "svm_sv_int8", "svm_x_bf16", "wdamds",
    "wdamds_coord_bf16", "wdamds_coord_int8", "wdamds_delta_bf16",
    "wdamds_dist_pallas")
MODEL_TERM_FIELDS = ("compute_s", "memory_s", "wire_s", "overhead_s")


def _check_model_row(name: str, i: int, row: dict) -> list[str]:
    """Invariant 12: model rows must be coherent prediction evidence."""
    errs: list[str] = []
    missing = [f for f in PROVENANCE_FIELDS if f not in row]
    if missing:
        errs.append(
            f"{name}:{i}: model row missing provenance field(s) "
            f"{missing} — print it through harp_tpu.perfmodel.cli, "
            "which stamps them")
    prog, cfg = row.get("program"), row.get("config")
    if prog is None and cfg is None:
        errs.append(f"{name}:{i}: model row names neither a program nor "
                    "a config — a prediction about nothing prices "
                    "nothing")
    if prog is not None and prog not in KNOWN_LINT_PROGRAMS:
        errs.append(
            f"{name}:{i}: model row for unregistered program {prog!r} — "
            "programs must come from harp_tpu.analysis.drivers.DRIVERS")
    for c in ([cfg] if cfg is not None else []) + list(
            row.get("configs") or []):
        if c not in KNOWN_MODEL_CONFIGS:
            errs.append(
                f"{name}:{i}: model row references config {c!r} not in "
                "the frozen list KNOWN_MODEL_CONFIGS")
    rs = row.get("rates_source")
    if rs not in KNOWN_MODEL_RATES_SOURCES:
        errs.append(f"{name}:{i}: model row rates_source={rs!r} not in "
                    f"{KNOWN_MODEL_RATES_SOURCES} — a declared ranking "
                    "must never masquerade as a measured one")
    bound = row.get("bound")
    if bound not in KNOWN_MODEL_BOUNDS:
        errs.append(f"{name}:{i}: model row bound={bound!r} not in "
                    f"{KNOWN_MODEL_BOUNDS}")
    ps = row.get("predicted_s")
    if not _num(ps) or ps <= 0:
        errs.append(f"{name}:{i}: model row predicted_s={ps!r} must be "
                    "a positive number — zero predicted seconds is not "
                    "a prediction")
    terms = row.get("terms")
    if (not isinstance(terms, dict)
            or sorted(terms) != sorted(MODEL_TERM_FIELDS)
            or not all(_num(terms[k]) and terms[k] >= 0
                       for k in MODEL_TERM_FIELDS)):
        errs.append(
            f"{name}:{i}: model row terms={terms!r} must carry exactly "
            f"{MODEL_TERM_FIELDS} as non-negative numbers — the "
            "breakdown is what makes a wrong prediction diagnosable")
    elif _num(ps) and ps > 0:
        total = sum(terms.values())
        if abs(total - ps) > 1e-6 * max(abs(ps), 1e-12):
            errs.append(
                f"{name}:{i}: model row terms sum to {total} but "
                f"predicted_s claims {ps} — the per-term breakdown "
                "must sum to the total")
        if bound in KNOWN_MODEL_BOUNDS and \
                terms[f"{bound}_s"] < max(terms.values()) - 1e-12:
            errs.append(
                f"{name}:{i}: model row bound={bound!r} is not the "
                "largest term — the bound names the wall the "
                "prediction is against")
    return errs


# the health-row vocabularies (invariant 13), FROZEN standalone like the
# plan/model vocabularies and sync-pinned by tests/test_check_jsonl.py
# against harp_tpu.health (DETECTORS / SEVERITIES / VERDICTS)
KNOWN_HEALTH_DETECTORS = ("slo_burn", "skew_trigger", "budget_drift",
                          "evidence_regression", "profile_drift",
                          "memory_pressure")
KNOWN_HEALTH_SEVERITIES = ("info", "warn", "page")
KNOWN_HEALTH_VERDICTS = ("confirmed", "improved", "regressed",
                         "model_invalidated")
HEALTH_COUNT_FIELDS = ("offered", "served", "shed", "failed",
                       "deadline_missed", "breaches", "violations",
                       "supersteps", "consecutive", "failures")
HEALTH_RATIO_FIELDS = ("fast_burn", "slow_burn", "wasted_frac",
                       "max_mean_ratio", "ratio_vs_incumbent",
                       "model_factor", "error_budget")


def _check_health_row(name: str, i: int, row: dict) -> list[str]:
    """Invariant 13: health rows must be coherent monitoring evidence."""
    errs: list[str] = []
    missing = [f for f in PROVENANCE_FIELDS if f not in row]
    if missing:
        errs.append(
            f"{name}:{i}: health row missing provenance field(s) "
            f"{missing} — export through telemetry.export / the health "
            "CLI, which stamp them")
    det = row.get("detector")
    if det not in KNOWN_HEALTH_DETECTORS:
        errs.append(f"{name}:{i}: health row detector={det!r} not in "
                    f"{KNOWN_HEALTH_DETECTORS}")
    sev = row.get("severity")
    if sev not in KNOWN_HEALTH_SEVERITIES:
        errs.append(f"{name}:{i}: health row severity={sev!r} not in "
                    f"{KNOWN_HEALTH_SEVERITIES}")
    for k in HEALTH_COUNT_FIELDS:
        v = row.get(k)
        if v is None:
            continue
        if isinstance(v, bool) or not isinstance(v, int) or v < 0:
            errs.append(f"{name}:{i}: health row count {k}={v!r} must "
                        "be a non-negative integer")
    for k in HEALTH_RATIO_FIELDS:
        v = row.get(k)
        if v is None:
            continue
        if not _num(v) or v < 0:
            errs.append(f"{name}:{i}: health row {k}={v!r} must be a "
                        "non-negative number")
    verdict = row.get("verdict")
    if det == "evidence_regression":
        if verdict not in KNOWN_HEALTH_VERDICTS:
            errs.append(
                f"{name}:{i}: evidence_regression health row has "
                f"verdict={verdict!r} — every graded row must carry "
                f"one of {KNOWN_HEALTH_VERDICTS}")
    elif verdict is not None and verdict not in KNOWN_HEALTH_VERDICTS:
        errs.append(f"{name}:{i}: health row verdict={verdict!r} not "
                    f"in {KNOWN_HEALTH_VERDICTS}")
    if det == "skew_trigger":
        errs += _check_rebalance_plan(name, i, row.get("plan"))
    elif row.get("plan") is not None:
        errs += _check_rebalance_plan(name, i, row.get("plan"))
    return errs


def _check_rebalance_plan(name: str, i: int, plan) -> list[str]:
    """Invariant 13, skew-trigger extension: the inline plan must be
    apply_rebalance-shaped — the elastic-execution PR will replay it."""
    if not isinstance(plan, dict):
        return [f"{name}:{i}: skew_trigger health row plan={plan!r} "
                "must be a suggest_rebalance object (the inline "
                "elastic-execution payload)"]
    errs: list[str] = []
    if not isinstance(plan.get("phase"), str):
        errs.append(f"{name}:{i}: rebalance plan phase="
                    f"{plan.get('phase')!r} must be a string")
    moves = plan.get("moves")
    if not isinstance(moves, list):
        errs.append(f"{name}:{i}: rebalance plan moves={moves!r} must "
                    "be a list")
        moves = []
    for m in moves:
        if not isinstance(m, dict):
            errs.append(f"{name}:{i}: rebalance plan has a non-object "
                        "move entry")
            continue
        for k in ("from", "to"):
            v = m.get(k)
            if isinstance(v, bool) or not isinstance(v, int) or v < 0:
                errs.append(f"{name}:{i}: rebalance move {k}={v!r} "
                            "must be a non-negative worker index")
        w = m.get("work")
        if not _num(w) or w < 0:
            errs.append(f"{name}:{i}: rebalance move work={w!r} must "
                        "be a non-negative number")
    for k in ("ratio_before", "ratio_after"):
        v = plan.get(k)
        if v is not None and (not _num(v) or v < 0):
            errs.append(f"{name}:{i}: rebalance plan {k}={v!r} must be "
                        "a non-negative number")
    return errs


# the elastic-row vocabulary (invariant 14), FROZEN standalone like the
# health vocabularies and sync-pinned by tests/test_check_jsonl.py
# against harp_tpu.elastic.EVENTS
KNOWN_ELASTIC_EVENTS = ("rebalance", "shrink", "resume")
ELASTIC_LOAD_FIELDS = ("loads", "loads_before", "loads_after")
ELASTIC_COUNT_FIELDS = ("n_workers", "moves", "lost_worker", "ordinal",
                        "from_step", "trigger_supersteps",
                        "n_workers_before", "n_workers_after")


def _check_elastic_row(name: str, i: int, row: dict) -> list[str]:
    """Invariant 14: elastic rows must be coherent elasticity evidence."""
    errs: list[str] = []
    missing = [f for f in PROVENANCE_FIELDS if f not in row]
    if missing:
        errs.append(
            f"{name}:{i}: elastic row missing provenance field(s) "
            f"{missing} — export through telemetry.export, which "
            "stamps them")
    ev = row.get("event")
    if ev not in KNOWN_ELASTIC_EVENTS:
        errs.append(f"{name}:{i}: elastic row event={ev!r} not in "
                    f"{KNOWN_ELASTIC_EVENTS}")
    total = row.get("total")
    for k in ELASTIC_LOAD_FIELDS:
        v = row.get(k)
        if v is None:
            continue
        if not (isinstance(v, list) and v
                and all(_num(x) and x >= 0 for x in v)):
            errs.append(
                f"{name}:{i}: elastic row {k}={v!r} must be a non-empty "
                "list of non-negative per-worker loads")
        elif not _num(total):
            errs.append(
                f"{name}:{i}: elastic row carries {k} but "
                f"total={total!r} — per-worker loads must state the "
                "total they sum to")
        elif abs(sum(v) - total) > 1e-4 * max(1.0, abs(total)):
            errs.append(
                f"{name}:{i}: elastic row {k} sums to {sum(v)} but "
                f"total claims {total} — a move must conserve work")
    for k in ELASTIC_COUNT_FIELDS:
        v = row.get(k)
        if v is None:
            continue
        if isinstance(v, bool) or not isinstance(v, int) or v < 0:
            errs.append(f"{name}:{i}: elastic row count {k}={v!r} must "
                        "be a non-negative integer")
    wb, wa = row.get("wasted_frac_before"), row.get("wasted_frac_after")
    for k, v in (("wasted_frac_before", wb), ("wasted_frac_after", wa),
                 ("wasted_frac", row.get("wasted_frac")),
                 ("capacity_frac", row.get("capacity_frac"))):
        if v is not None and (not _num(v) or not 0.0 <= v <= 1.0):
            errs.append(f"{name}:{i}: elastic row {k}={v!r} must lie "
                        "in [0, 1]")
    if ev == "rebalance":
        if not (_num(wb) and _num(wa)):
            errs.append(
                f"{name}:{i}: rebalance elastic row must carry numeric "
                "wasted_frac_before AND wasted_frac_after — the whole "
                "point is before/after evidence")
        elif wa > wb + 1e-9:
            errs.append(
                f"{name}:{i}: rebalance elastic row wasted_frac_after="
                f"{wa} > before={wb} — a move that made the imbalance "
                "worse must be refused, not committed as evidence")
        for k in ("loads_before", "loads_after"):
            if row.get(k) is None:
                errs.append(f"{name}:{i}: rebalance elastic row "
                            f"missing {k}")
    if ev == "shrink":
        nb, na = row.get("n_workers_before"), row.get("n_workers_after")
        ok = (isinstance(nb, int) and isinstance(na, int)
              and not isinstance(nb, bool) and not isinstance(na, bool)
              and nb >= 1 and na >= 1)
        if not ok or na >= nb:
            errs.append(
                f"{name}:{i}: shrink elastic row needs survivor count "
                f"n_workers_after < n_workers_before (>= 1), got "
                f"{na!r} / {nb!r}")
    return errs


# the profile-row vocabularies (invariant 15), FROZEN standalone like
# the model/health vocabularies and sync-pinned by
# tests/test_check_jsonl.py against harp_tpu.profile.attribution
# (BUCKETS / PROFILE_APPS / SUM_REL_TOL)
KNOWN_PROFILE_BUCKETS = ("mxu", "elementwise", "gather_dus", "scatter",
                         "wire", "overhead")
KNOWN_PROFILE_APPS = ("kmeans", "mfsgd", "lda", "rf", "svm", "wdamds",
                      "subgraph", "serve", "rf_pallas", "svm_pallas",
                      "wdamds_pallas")
PROFILE_SUM_REL_TOL = 0.75
PROFILE_COUNT_FIELDS = ("reps", "n_devices", "wire_bytes", "wire_sites",
                        "wire_unmatched", "dispatches",
                        "dispatches_per_rep", "compiles_in_window")


def _check_profile_row(name: str, i: int, row: dict) -> list[str]:
    """Invariant 15: profile rows must be coherent attribution evidence."""
    errs: list[str] = []
    missing = [f for f in PROVENANCE_FIELDS if f not in row]
    if missing:
        errs.append(
            f"{name}:{i}: profile row missing provenance field(s) "
            f"{missing} — emit it through harp_tpu.profile.cli / "
            "attribution.capture, which stamp them")
    app = row.get("app")
    if app not in KNOWN_PROFILE_APPS:
        errs.append(f"{name}:{i}: profile row app={app!r} not in "
                    f"{KNOWN_PROFILE_APPS}")
    prog = row.get("program")
    if prog not in KNOWN_LINT_PROGRAMS:
        errs.append(
            f"{name}:{i}: profile row for unregistered program {prog!r} "
            "— programs must come from harp_tpu.analysis.drivers.DRIVERS")
    for k in PROFILE_COUNT_FIELDS:
        v = row.get(k)
        if isinstance(v, bool) or not isinstance(v, int) or v < 0:
            errs.append(f"{name}:{i}: profile row count {k}={v!r} must "
                        "be a non-negative integer")
    wall = row.get("wall_s")
    if not _num(wall) or wall <= 0:
        errs.append(f"{name}:{i}: profile row wall_s={wall!r} must be a "
                    "positive number — an attribution needs a wall to "
                    "attribute")
    term_keys = tuple(f"{b}_s" for b in KNOWN_PROFILE_BUCKETS)
    terms = row.get("terms")
    if (not isinstance(terms, dict)
            or sorted(terms) != sorted(term_keys)
            or not all(_num(terms[k]) and terms[k] >= 0
                       for k in term_keys)):
        errs.append(
            f"{name}:{i}: profile row terms={terms!r} must carry exactly "
            f"{term_keys} as non-negative numbers — the frozen mechanism "
            "vocabulary is what lets the perfmodel consume the row")
    else:
        if _num(wall) and wall > 0:
            total = sum(terms.values())
            # terms are rounded to 6 decimals per bucket in the exporter
            if abs(total - wall) > 1e-3 * wall + 1e-5:
                errs.append(
                    f"{name}:{i}: profile row buckets sum to {total} but "
                    f"wall_s claims {wall} — every wall second must be "
                    "attributed to a mechanism (residual in overhead)")
        bound = row.get("bound")
        if bound not in KNOWN_PROFILE_BUCKETS:
            errs.append(f"{name}:{i}: profile row bound={bound!r} not in "
                        f"{KNOWN_PROFILE_BUCKETS}")
        elif terms[f"{bound}_s"] < max(terms.values()) - 1e-12:
            errs.append(
                f"{name}:{i}: profile row bound={bound!r} is not the "
                "largest bucket — the bound names the wall the row "
                "claims the app is against")
    sre = row.get("sum_rel_err")
    if not _num(sre) or sre < 0 or sre > PROFILE_SUM_REL_TOL:
        errs.append(
            f"{name}:{i}: profile row sum_rel_err={sre!r} must lie in "
            f"[0, {PROFILE_SUM_REL_TOL}] — beyond the documented "
            "concurrency-blur tolerance the capture is broken, not blurry")
    reps, per = row.get("reps"), row.get("dispatches_per_rep")
    disp = row.get("dispatches")
    if (isinstance(reps, int) and isinstance(per, int)
            and isinstance(disp, int)
            and not any(isinstance(x, bool) for x in (reps, per, disp))
            and disp != reps * per):
        errs.append(
            f"{name}:{i}: profile row dispatches={disp} != reps={reps} * "
            f"dispatches_per_rep={per} — the attribution window "
            "disagrees with the flight recorder about what ran")
    for k in ("compiles_in_window", "wire_unmatched"):
        v = row.get(k)
        if isinstance(v, int) and not isinstance(v, bool) and v != 0:
            errs.append(
                f"{name}:{i}: profile row {k}={v} must be exactly 0 — "
                + ("a capture that compiled mid-window timed the "
                   "compiler, not the program"
                   if k == "compiles_in_window" else
                   "every static collective site must carry a "
                   "CommLedger verb match"))
    if row.get("reconciled") is not True:
        errs.append(
            f"{name}:{i}: profile row reconciled="
            f"{row.get('reconciled')!r} must be literally true — an "
            "unreconciled attribution is a hand-read profile wearing a "
            "row format")
    return errs


# the steptrace vocabularies (invariant 16), FROZEN standalone like the
# trace vocabularies and sync-pinned by tests/test_check_jsonl.py
# against harp_tpu.utils.steptrace (EVS / OUTCOMES / SOURCES /
# FLIGHT_KEYS)
KNOWN_STEPTRACE_EVS = ("run", "superstep", "mark", "lane")
KNOWN_STEPTRACE_OUTCOMES = ("completed", "faulted", "rebalanced",
                            "resumed")
KNOWN_STEPTRACE_SOURCES = ("flight", "wire", "ckpt", "fault", "elastic",
                           "health", "memory")
KNOWN_STEPTRACE_FLIGHT_KEYS = ("dispatches", "readbacks", "h2d_calls",
                               "compiles")


def _steptrace_flight_ok(fl) -> bool:
    """Exactly the frozen counter keys, all non-negative integers."""
    return (isinstance(fl, dict)
            and sorted(fl) == sorted(KNOWN_STEPTRACE_FLIGHT_KEYS)
            and all(isinstance(fl[k], int) and not isinstance(fl[k], bool)
                    and fl[k] >= 0 for k in KNOWN_STEPTRACE_FLIGHT_KEYS))


def _check_steptrace_row(name: str, i: int, row: dict,
                         state: dict) -> list[str]:
    """Invariant 16, per-row half: stamp, row shape, monotone ts.

    ``state`` accumulates the per-run evidence the end-of-file half
    (:func:`_finish_steptrace_checks`) re-derives: span/mark/lane
    counts, outcome tallies, span flight sums, dispatch-mark counts,
    and the elastic/health marks for the cross-spine reconciliation.
    """
    errs: list[str] = []
    missing = [f for f in PROVENANCE_FIELDS if f not in row]
    if missing:
        errs.append(
            f"{name}:{i}: steptrace row missing provenance field(s) "
            f"{missing} — export through telemetry.export / "
            "telemetry.export_timeline, which stamp them")
    ev = row.get("ev")
    if ev not in KNOWN_STEPTRACE_EVS:
        errs.append(f"{name}:{i}: steptrace row ev={ev!r} not in "
                    f"{KNOWN_STEPTRACE_EVS}")
    ts = row.get("ts")
    if not _num(ts) or ts < 0:
        errs.append(f"{name}:{i}: steptrace row ts={ts!r} must be a "
                    "non-negative number — a timeline row without a "
                    "timestamp cannot be causally ordered")
    else:
        last = state.get("last_ts")
        if last is not None and ts < last:
            errs.append(
                f"{name}:{i}: steptrace row ts={ts} decreased from "
                f"{last} — timeline rows must be monotone (interleaved "
                "exports?)")
        state["last_ts"] = ts
    rid = row.get("run")
    if isinstance(rid, bool) or not isinstance(rid, int) or rid < 1:
        errs.append(f"{name}:{i}: steptrace row run={rid!r} must be a "
                    "positive integer run id")
        return errs
    per = state.setdefault("per", {}).setdefault(rid, {
        "spans": 0,
        "outcomes": {o: 0 for o in KNOWN_STEPTRACE_OUTCOMES},
        "span_flight": {k: 0 for k in KNOWN_STEPTRACE_FLIGHT_KEYS},
        "marks": 0, "lanes": 0, "dispatch_marks": 0,
        "elastic_marks": {}, "health_marks": [], "consume_marks": []})
    if ev == "run":
        runs = state.setdefault("runs", {})
        if rid in runs:
            errs.append(f"{name}:{i}: duplicate steptrace run row for "
                        f"run {rid} — every run terminates exactly once")
        runs[rid] = (i, row)
        outcomes = row.get("outcomes")
        if (not isinstance(outcomes, dict)
                or sorted(outcomes) != sorted(KNOWN_STEPTRACE_OUTCOMES)
                or not all(isinstance(outcomes[o], int)
                           and not isinstance(outcomes[o], bool)
                           and outcomes[o] >= 0
                           for o in KNOWN_STEPTRACE_OUTCOMES)):
            errs.append(
                f"{name}:{i}: steptrace run row outcomes={outcomes!r} "
                f"must carry exactly {KNOWN_STEPTRACE_OUTCOMES} as "
                "non-negative integers")
        for k in ("supersteps", "marks", "lanes"):
            v = row.get(k)
            if isinstance(v, bool) or not isinstance(v, int) or v < 0:
                errs.append(f"{name}:{i}: steptrace run row {k}={v!r} "
                            "must be a non-negative integer")
        for fname in ("flight", "span_flight"):
            if not _steptrace_flight_ok(row.get(fname)):
                errs.append(
                    f"{name}:{i}: steptrace run row {fname}="
                    f"{row.get(fname)!r} must carry exactly "
                    f"{KNOWN_STEPTRACE_FLIGHT_KEYS} as non-negative "
                    "integers")
        t0 = row.get("t0")
        if not _num(t0) or (_num(ts) and t0 > ts):
            errs.append(f"{name}:{i}: steptrace run row t0={t0!r} must "
                        "be a number not after its close ts")
    elif ev == "superstep":
        per["spans"] += 1
        outcome = row.get("outcome")
        if outcome not in KNOWN_STEPTRACE_OUTCOMES:
            errs.append(
                f"{name}:{i}: steptrace span run={rid} seq="
                f"{row.get('seq')!r} has outcome={outcome!r} — every "
                f"opened superstep must terminate with one of "
                f"{KNOWN_STEPTRACE_OUTCOMES}")
        else:
            per["outcomes"][outcome] += 1
        for k in ("seq", "step"):
            v = row.get(k)
            if isinstance(v, bool) or not isinstance(v, int) or v < 0:
                errs.append(f"{name}:{i}: steptrace span {k}={v!r} "
                            "must be a non-negative integer")
        t0 = row.get("t0")
        if not _num(t0) or (_num(ts) and t0 > ts):
            errs.append(f"{name}:{i}: steptrace span t0={t0!r} must be "
                        "a number not after its close ts")
        fl = row.get("flight")
        if not _steptrace_flight_ok(fl):
            errs.append(
                f"{name}:{i}: steptrace span flight={fl!r} must carry "
                f"exactly {KNOWN_STEPTRACE_FLIGHT_KEYS} as non-negative "
                "integers")
        else:
            for k in KNOWN_STEPTRACE_FLIGHT_KEYS:
                per["span_flight"][k] += fl[k]
    elif ev == "mark":
        per["marks"] += 1
        src = row.get("source")
        if src not in KNOWN_STEPTRACE_SOURCES:
            errs.append(f"{name}:{i}: steptrace mark source={src!r} not "
                        f"in {KNOWN_STEPTRACE_SOURCES}")
        nm = row.get("name")
        if src == "flight" and nm == "dispatch":
            per["dispatch_marks"] += 1
        elif src == "elastic":
            per["elastic_marks"][nm] = per["elastic_marks"].get(nm, 0) + 1
        elif src == "health":
            if nm == "consume_skew_trigger":
                per["consume_marks"].append((i, row.get("phase")))
            else:
                per["health_marks"].append((i, nm))
    elif ev == "lane":
        per["lanes"] += 1
        work = row.get("work")
        if not (isinstance(work, list) and work
                and all(_num(x) and x >= 0 for x in work)):
            errs.append(
                f"{name}:{i}: steptrace lane work={work!r} must be a "
                "non-empty list of non-negative per-worker loads")
    return errs


def _finish_steptrace_checks(name: str, state: dict,
                             elastic_counts: dict,
                             health_rows: list[dict],
                             transfer_dispatches: int | None
                             ) -> list[str]:
    """Invariant 16, file-level half: run termination, re-derived run
    summaries, and the cross-spine reconciliations (runs after the
    whole file was scanned)."""
    per = state.get("per") or {}
    if not per:
        return []
    errs: list[str] = []
    runs = state.get("runs") or {}
    unterminated = sorted(r for r in per if r not in runs)
    if unterminated:
        errs.append(
            f"{name}: steptrace has {len(unterminated)} run(s) with "
            f"spans/marks but no terminating run row: "
            f"{unterminated[:8]} — every opened run must close")
    total_dispatch = 0
    for rid, (i, rrow) in sorted(runs.items()):
        agg = per[rid]
        ss = rrow.get("supersteps")
        if isinstance(ss, int) and agg["spans"] != ss:
            errs.append(
                f"{name}:{i}: steptrace run {rid} claims {ss} "
                f"superstep(s) but the file carries {agg['spans']} span "
                "row(s)")
        outcomes = rrow.get("outcomes")
        if (isinstance(outcomes, dict)
                and sorted(outcomes) == sorted(KNOWN_STEPTRACE_OUTCOMES)
                and agg["outcomes"] != outcomes):
            errs.append(
                f"{name}:{i}: steptrace run {rid} span outcomes "
                f"{agg['outcomes']} do not match the run row's "
                f"{outcomes}")
        sf, fl = rrow.get("span_flight"), rrow.get("flight")
        if _steptrace_flight_ok(sf) and agg["span_flight"] != sf:
            errs.append(
                f"{name}:{i}: steptrace run {rid} span flight sums "
                f"{agg['span_flight']} do not match the run row's "
                f"span_flight {sf}")
        if _steptrace_flight_ok(sf) and _steptrace_flight_ok(fl):
            over = [k for k in KNOWN_STEPTRACE_FLIGHT_KEYS
                    if sf[k] > fl[k]]
            if over:
                errs.append(
                    f"{name}:{i}: steptrace run {rid} span_flight "
                    f"exceeds the run's flight delta for {over} — spans "
                    "cannot own more ops than the run recorded")
        for k in ("marks", "lanes"):
            v = rrow.get(k)
            if isinstance(v, int) and not isinstance(v, bool) \
                    and v != agg[k]:
                errs.append(
                    f"{name}:{i}: steptrace run {rid} claims {v} "
                    f"{k} but the file carries {agg[k]}")
        if _steptrace_flight_ok(fl):
            total_dispatch += fl["dispatches"]
            if agg["dispatch_marks"] != fl["dispatches"]:
                errs.append(
                    f"{name}:{i}: steptrace run {rid} has "
                    f"{agg['dispatch_marks']} dispatch mark(s) but its "
                    f"flight delta counted {fl['dispatches']} — the "
                    "observer spine and the TransferLedger must agree "
                    "EXACTLY")
    if transfer_dispatches is not None \
            and total_dispatch > transfer_dispatches:
        errs.append(
            f"{name}: steptrace runs attribute {total_dispatch} "
            f"dispatch(es) but the file's transfer rows record only "
            f"{transfer_dispatches} — a timeline cannot own more "
            "dispatches than the flight recorder counted")
    emarks: dict = {}
    for agg in per.values():
        for nm, n in agg["elastic_marks"].items():
            emarks[nm] = emarks.get(nm, 0) + n
    for evn in KNOWN_ELASTIC_EVENTS:
        if emarks.get(evn, 0) != elastic_counts.get(evn, 0):
            errs.append(
                f"{name}: steptrace carries {emarks.get(evn, 0)} "
                f"elastic {evn!r} mark(s) but the file has "
                f"{elastic_counts.get(evn, 0)} timeline-covered "
                f"kind:'elastic' {evn!r} row(s) — the timeline and the "
                "elastic ledger must tell one story")
    detectors = {r.get("detector") for r in health_rows}
    for agg in per.values():
        for i, nm in agg["health_marks"]:
            if nm not in detectors:
                errs.append(
                    f"{name}:{i}: steptrace health mark names detector "
                    f"{nm!r} with no kind:'health' row in the file — a "
                    "finding on the timeline must exist in the "
                    "sentinel export")
        for i, phase in agg["consume_marks"]:
            if not any(r.get("detector") == "skew_trigger"
                       and r.get("phase") == phase
                       and r.get("consumed") is True
                       for r in health_rows):
                errs.append(
                    f"{name}:{i}: steptrace consume_skew_trigger mark "
                    f"for phase {phase!r} has no consumed skew_trigger "
                    "health row — the exactly-once handshake leaves "
                    "ledger evidence or it did not happen")
    return errs


# the memory-row vocabularies (invariant 17), FROZEN standalone like the
# steptrace vocabularies and sync-pinned by tests/test_check_jsonl.py
# against harp_tpu.utils.memrec (EVS / BUFFER_EVENTS)
KNOWN_MEMORY_EVS = ("buffer", "dispatch", "executable", "vmem_check",
                    "summary")
KNOWN_MEMORY_EVENTS = ("staged", "restored", "output", "freed",
                       "donated")
MEMORY_EXEC_COMPONENTS = ("argument_bytes", "output_bytes", "temp_bytes",
                          "generated_code_bytes")
MEMORY_SUMMARY_DERIVED = ("peak_hbm_bytes", "live_hbm_bytes",
                          "staged_bytes", "freed_bytes", "donated_bytes",
                          "vmem_checks", "vmem_refusals")


def _check_memory_row(name: str, i: int, row: dict,
                      state: dict) -> list[str]:
    """Invariant 17, per-row half: stamp, row shape, and the live-set
    replay.

    ``state`` carries the re-derived ledger the end-of-file half
    (:func:`_finish_memory_checks`) closes out: the live set (buf id →
    bytes), running live/peak watermarks, staged/freed/donated totals,
    vmem check/refusal counts, and the summary row once seen — the
    IDENTICAL replay :func:`harp_tpu.utils.memrec.summarize_rows` runs,
    so the CLI and the repo gate cannot disagree about a file.
    """
    errs: list[str] = []
    missing = [f for f in PROVENANCE_FIELDS if f not in row]
    if missing:
        errs.append(
            f"{name}:{i}: memory row missing provenance field(s) "
            f"{missing} — export through telemetry.export, which stamps "
            "them (a CPU-sim footprint must never read as silicon HBM "
            "evidence)")
    ev = row.get("ev")
    if ev not in KNOWN_MEMORY_EVS:
        errs.append(f"{name}:{i}: memory row ev={ev!r} not in "
                    f"{KNOWN_MEMORY_EVS}")
        return errs
    seq = row.get("seq")
    if isinstance(seq, bool) or not isinstance(seq, int) or seq < 1:
        errs.append(f"{name}:{i}: memory row seq={seq!r} must be a "
                    "positive integer")
    else:
        last = state.get("last_seq", 0)
        if seq <= last:
            errs.append(
                f"{name}:{i}: memory row seq={seq} did not increase "
                f"from {last} — the ledger is an ordered event stream")
        state["last_seq"] = seq
    if state.get("summary") is not None and ev != "summary":
        errs.append(
            f"{name}:{i}: memory {ev} row after the summary row — the "
            "summary terminates the export; a late event means the "
            "watermark was asserted, not measured")
    live = state.setdefault("live", {})
    if ev == "buffer":
        errs += _replay_memory_buffer(name, i, row, state, live)
    elif ev == "dispatch":
        for b in row.get("donated") or []:
            if b in live:
                errs.append(
                    f"{name}:{i}: memory dispatch donated buf {b} is "
                    "still in the live set — a donated buffer must "
                    "leave at dispatch (runtime twin of HL303)")
        if row.get("live_bytes") != state.get("live_bytes", 0):
            errs.append(
                f"{name}:{i}: memory dispatch live_bytes="
                f"{row.get('live_bytes')!r} != derived "
                f"{state.get('live_bytes', 0)}")
    elif ev == "executable":
        parts = []
        for k in MEMORY_EXEC_COMPONENTS:
            v = row.get(k)
            if isinstance(v, bool) or not isinstance(v, int) or v < 0:
                errs.append(f"{name}:{i}: memory executable row "
                            f"{k}={v!r} must be a non-negative integer")
            else:
                parts.append(v)
        if (len(parts) == len(MEMORY_EXEC_COMPONENTS)
                and row.get("exec_hbm_bytes") != sum(parts)):
            errs.append(
                f"{name}:{i}: memory executable row exec_hbm_bytes="
                f"{row.get('exec_hbm_bytes')!r} != component sum "
                f"{sum(parts)} — the four memory_analysis components "
                "must add up")
        if row.get("source") not in ("compile", "cache"):
            errs.append(
                f"{name}:{i}: memory executable row source="
                f"{row.get('source')!r} must be 'compile' or 'cache'")
    elif ev == "vmem_check":
        pb, bb = row.get("predicted_bytes"), row.get("budget_bytes")
        for k, v in (("predicted_bytes", pb), ("budget_bytes", bb)):
            if isinstance(v, bool) or not isinstance(v, int) or v < 0:
                errs.append(f"{name}:{i}: memory vmem_check row "
                            f"{k}={v!r} must be a non-negative integer")
        if (isinstance(pb, int) and isinstance(bb, int)
                and not isinstance(pb, bool) and not isinstance(bb, bool)):
            fits = pb <= bb
            if bool(row.get("fits")) != fits:
                errs.append(
                    f"{name}:{i}: memory vmem_check fits="
                    f"{row.get('fits')!r} contradicts predicted={pb} "
                    f"vs budget={bb} — the gate's verdict must follow "
                    "its own bytes")
            if bool(row.get("refused")) == bool(row.get("fits")):
                errs.append(
                    f"{name}:{i}: memory vmem_check refused="
                    f"{row.get('refused')!r} must be the negation of "
                    f"fits={row.get('fits')!r}")
        state["vmem_checks"] = state.get("vmem_checks", 0) + 1
        if row.get("refused"):
            state["vmem_refusals"] = state.get("vmem_refusals", 0) + 1
    elif ev == "summary":
        if state.get("summary") is not None:
            errs.append(f"{name}:{i}: second memory summary row — the "
                        "export terminates exactly once")
        state["summary"] = (i, row)
    return errs


def _replay_memory_buffer(name: str, i: int, row: dict, state: dict,
                          live: dict) -> list[str]:
    """Invariant 17, buffer-event half of the live-set replay."""
    errs: list[str] = []
    e = row.get("event")
    if e not in KNOWN_MEMORY_EVENTS:
        errs.append(f"{name}:{i}: memory buffer row event={e!r} not in "
                    f"{KNOWN_MEMORY_EVENTS}")
        return errs
    nb = row.get("bytes")
    if isinstance(nb, bool) or not isinstance(nb, int) or nb < 0:
        errs.append(f"{name}:{i}: memory buffer row bytes={nb!r} must "
                    "be a non-negative integer")
        return errs
    b = row.get("buf")
    if e in ("staged", "output"):
        live[b] = nb
        state["live_bytes"] = state.get("live_bytes", 0) + nb
        state["peak_bytes"] = max(state.get("peak_bytes", 0),
                                  state["live_bytes"])
        if e == "staged":
            state["staged_bytes"] = state.get("staged_bytes", 0) + nb
    elif e in ("freed", "donated"):
        if b not in live:
            errs.append(
                f"{name}:{i}: memory buffer row {e} buf {b!r} is not "
                "in the live set — a buffer must be staged/output "
                "before it can leave")
        else:
            state["live_bytes"] = state.get("live_bytes", 0) - live.pop(b)
        key = "freed_bytes" if e == "freed" else "donated_bytes"
        state[key] = state.get(key, 0) + nb
    # e == "restored" is zero-delta by design (restore lands in host
    # RAM; the H2D that follows is its own staged event)
    if row.get("live_bytes") != state.get("live_bytes", 0):
        errs.append(
            f"{name}:{i}: memory buffer row live_bytes="
            f"{row.get('live_bytes')!r} != derived "
            f"{state.get('live_bytes', 0)} — the watermark must "
            "re-derive from the event stream EXACTLY")
    if row.get("peak_bytes") != state.get("peak_bytes", 0):
        errs.append(
            f"{name}:{i}: memory buffer row peak_bytes="
            f"{row.get('peak_bytes')!r} != derived "
            f"{state.get('peak_bytes', 0)}")
    return errs


def _finish_memory_checks(name: str, state: dict) -> list[str]:
    """Invariant 17, file-level half: exactly one terminating summary
    whose totals re-derive from the stream (runs after the whole file
    was scanned)."""
    if not state:
        return []
    errs: list[str] = []
    if state.get("summary") is None:
        return [f"{name}: memory rows with no terminating summary row — "
                "the export is unterminated (telemetry.export writes "
                "exactly one)"]
    i, row = state["summary"]
    derived = {"peak_hbm_bytes": state.get("peak_bytes", 0),
               "live_hbm_bytes": state.get("live_bytes", 0),
               "staged_bytes": state.get("staged_bytes", 0),
               "freed_bytes": state.get("freed_bytes", 0),
               "donated_bytes": state.get("donated_bytes", 0),
               "vmem_checks": state.get("vmem_checks", 0),
               "vmem_refusals": state.get("vmem_refusals", 0)}
    for k in MEMORY_SUMMARY_DERIVED:
        if row.get(k) != derived[k]:
            errs.append(
                f"{name}:{i}: memory summary {k}={row.get(k)!r} != "
                f"derived {derived[k]} — a peak the events cannot "
                "reproduce was asserted, not measured")
    hbm, peak = row.get("hbm_bytes"), row.get("peak_hbm_bytes")
    hf = row.get("headroom_frac")
    if isinstance(hbm, bool) or not isinstance(hbm, int) or hbm <= 0:
        errs.append(f"{name}:{i}: memory summary hbm_bytes={hbm!r} must "
                    "be a positive integer (the topology's declared "
                    "HBM capacity)")
    elif isinstance(peak, int) and not isinstance(peak, bool):
        want = round(max(0.0, 1.0 - peak / hbm), 6)
        if not _num(hf) or abs(hf - want) > 1e-6:
            errs.append(
                f"{name}:{i}: memory summary headroom_frac={hf!r} != "
                f"1 - peak/hbm = {want} — headroom must be computed, "
                "not asserted")
    return errs


INGEST_RATE_FIELDS = ("host_gb_per_sec", "points_per_sec")


def _check_ingest_row(name: str, i: int, row: dict) -> list[str]:
    """Invariant 8: ingest rows must be coherent streaming evidence."""
    errs: list[str] = []
    missing = [f for f in PROVENANCE_FIELDS if f not in row]
    if missing:
        errs.append(
            f"{name}:{i}: ingest row missing provenance field(s) "
            f"{missing} — print it through "
            "harp_tpu.utils.metrics.benchmark_json")
    oe = row.get("overlap_efficiency")
    if not _num(oe) or not 0.0 <= oe <= 1.0:
        errs.append(
            f"{name}:{i}: ingest row overlap_efficiency={oe!r} must lie "
            "in [0, 1] — it is the host pipeline's stage-overlap score "
            "(harp_tpu.ingest.IngestStats)")
    for k in INGEST_RATE_FIELDS:
        v = row.get(k)
        if not _num(v) or v <= 0:
            errs.append(
                f"{name}:{i}: ingest row {k}={v!r} must be a positive "
                "number — a non-positive rate means the instrumented "
                "epoch loop never ran")
    return errs


def check_file(path: str, grandfathered: int = 0,
               provenance: bool = False) -> list[str]:
    """Return a list of violation messages (empty = clean)."""
    errors: list[str] = []
    name = os.path.basename(path)
    try:
        lines = open(path).read().splitlines()
    except OSError as e:
        return [f"{name}: unreadable: {e}"]
    flight_state: dict = {}
    trace_state: dict = {}
    degraded_rows: list[tuple[int, dict]] = []
    steptrace_state: dict = {}
    elastic_counts: dict = {}
    health_rows: list[dict] = []
    memory_state: dict = {}
    transfer_dispatches: int | None = None
    for i, line in enumerate(lines, 1):
        if not line.strip():
            continue
        try:
            row = json.loads(line)
        except ValueError as e:
            errors.append(f"{name}:{i}: unparseable JSON ({e})")
            continue
        if isinstance(row, dict) and row.get("kind") == "comm":
            errors += _check_comm_row(name, i, row)
        if isinstance(row, dict) and row.get("kind") in ("compile",
                                                         "transfer"):
            errors += _check_flight_row(name, i, row, flight_state)
            if (row.get("kind") == "transfer"
                    and row.get("op") == "dispatch"
                    and isinstance(row.get("calls"), int)
                    and not isinstance(row.get("calls"), bool)):
                transfer_dispatches = ((transfer_dispatches or 0)
                                       + row["calls"])
        if isinstance(row, dict) and row.get("kind") == "skew":
            errors += _check_skew_row(name, i, row)
        if isinstance(row, dict) and row.get("kind") == "lint":
            errors += _check_lint_row(name, i, row)
        if isinstance(row, dict) and row.get("kind") == "serve":
            errors += _check_serve_row(name, i, row)
            if any(k in row for k in DEGRADED_TRIGGER_FIELDS):
                degraded_rows.append((i, row))
        if isinstance(row, dict) and row.get("kind") == "ingest":
            errors += _check_ingest_row(name, i, row)
        if isinstance(row, dict) and row.get("kind") == "plan":
            errors += _check_plan_row(name, i, row)
        if isinstance(row, dict) and row.get("kind") == "trace":
            errors += _check_trace_row(name, i, row, trace_state)
        if isinstance(row, dict) and row.get("kind") == "model":
            errors += _check_model_row(name, i, row)
        if isinstance(row, dict) and row.get("kind") == "health":
            errors += _check_health_row(name, i, row)
            health_rows.append(row)
        if isinstance(row, dict) and row.get("kind") == "elastic":
            errors += _check_elastic_row(name, i, row)
            # only timeline-covered rows enter the invariant-16 mark
            # reconciliation — a row recorded outside any steptrace run
            # (manual install, pre-PR-18 evidence) is legitimately
            # unmarked
            if row.get("on_timeline") is True:
                evn = row.get("event")
                elastic_counts[evn] = elastic_counts.get(evn, 0) + 1
        if isinstance(row, dict) and row.get("kind") == "profile":
            errors += _check_profile_row(name, i, row)
        if isinstance(row, dict) and row.get("kind") == "steptrace":
            errors += _check_steptrace_row(name, i, row, steptrace_state)
        if isinstance(row, dict) and row.get("kind") == "memory":
            errors += _check_memory_row(name, i, row, memory_state)
        if not provenance or i <= grandfathered:
            continue
        if not isinstance(row, dict) or "config" not in row:
            continue  # not a bench row (e.g. a raw verb-sweep record)
        missing = [f for f in PROVENANCE_FIELDS if f not in row]
        if missing:
            errors.append(
                f"{name}:{i}: bench row config={row.get('config')!r} "
                f"missing provenance field(s) {missing} — print it "
                "through harp_tpu.utils.metrics.benchmark_json")
    errors += _finish_trace_checks(name, trace_state, degraded_rows)
    errors += _finish_steptrace_checks(name, steptrace_state,
                                       elastic_counts, health_rows,
                                       transfer_dispatches)
    errors += _finish_memory_checks(name, memory_state)
    return errors


def check_repo(repo: str) -> list[str]:
    errors: list[str] = []
    for name, legacy in GRANDFATHERED.items():
        p = os.path.join(repo, name)
        if os.path.exists(p):
            errors += check_file(p, grandfathered=legacy, provenance=True)
    for name in PARSE_ONLY:
        p = os.path.join(repo, name)
        if os.path.exists(p):
            errors += check_file(p)
    return errors


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--repo", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    args = p.parse_args(argv)
    errors = check_repo(args.repo)
    for e in errors:
        print(e, file=sys.stderr)
    if errors:
        print(f"check_jsonl: {len(errors)} violation(s)", file=sys.stderr)
        return 1
    print("check_jsonl: clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
