"""The collective planner: byte sheets × topology → an explicit Plan.

Reference parity (SURVEY.md §3.6, ROADMAP "topology-aware collective
planner"): Harp hard-codes one algorithm per collective call site;
TACCL (PAPERS.md arXiv:2111.04867) instead synthesizes the schedule
from a communication sketch plus a profiled topology.  harp-tpu's
sketch already ships: PR 9's CommGraph emits every registered driver
program's static collective schedule as byte-exact ``byte_sheets`` in
the lint row (HL301/HL302-gated against trace evidence, so the
planner's input cannot silently rot).  This module is the decision
side: for each site it prices today's schedule against the
alternatives the codebase can actually execute —

- ``hier_psum``        — :func:`collective.allreduce_hier`'s two-stage
  grouped psum (crosses the inter-host class once per host group);
- ``chunked_pipeline`` — the chunked ppermute pipeline
  (``rotate_pipeline(n_chunks=…)`` / ``reshard(n_chunks=…)``);
- ``wire_bf16`` / ``wire_int8`` — the EQuARX-style quantized wires
  (``reshard(wire=…)`` / ``*_quantized``, PAPERS.md arXiv:2506.17615)

— and emits a serializable :class:`Plan`.  **Every choice fails
closed**: the chosen ``schedule`` is always ``"keep"`` (bit-identical
to today's lowering); a cheaper-priced alternative only *names its
flip candidate* (the name of the config that would measure it), per
the repo's rule that no default changes without a chip measurement.  ``Plan.row()`` is the ``kind: "plan"``
JSONL record ``scripts/check_jsonl.py`` invariant 10 validates —
provenance-stamped, topology tag and schedules from frozen
vocabularies, and per-site predicted bytes equal to the program's byte
sheet (exactly, for the fail-closed ``keep``).
"""

from __future__ import annotations

import dataclasses

from harp_tpu.plan.topology import Topology, detect

#: the frozen schedule vocabulary (check_jsonl invariant 10 pins it);
#: "keep" is today's exact lowering — the only schedule a fail-closed
#: Plan ever *chooses*, the rest are priced alternatives.
SCHEDULES = ("keep", "hier_psum", "chunked_pipeline", "wire_bf16",
             "wire_int8")

#: per-schedule predicted per-site bytes, as a function of the sheet's
#: amplified site bytes (frozen math, mirrored standalone in
#: scripts/check_jsonl.py and sync-pinned by tests/test_plan.py):
#: keep/chunked move the same payload (chunking re-times hops, it does
#: not shrink them); hier_psum pays both stages; the narrow wires are
#: the EQuARX byte fractions (ceil — a byte sheet is integers).
def predicted_bytes(schedule: str, sheet_bytes: int) -> int:
    if schedule in ("keep", "chunked_pipeline"):
        return int(sheet_bytes)
    if schedule == "hier_psum":
        return 2 * int(sheet_bytes)
    if schedule == "wire_bf16":
        return (int(sheet_bytes) + 1) // 2
    if schedule == "wire_int8":
        return (int(sheet_bytes) + 3) // 4
    raise ValueError(f"unknown schedule {schedule!r}")


#: which alternatives each verb can legally lower to (the executable
#: surface, not a wish list: hier needs an ADD reduction; pipeline and
#: wires need a data-movement verb — reshard or its quantized twins)
_VERB_ALTERNATIVES = {
    "allreduce": ("hier_psum", "wire_bf16", "wire_int8"),
    "push": ("wire_bf16", "wire_int8"),
    "reshard": ("chunked_pipeline", "wire_bf16", "wire_int8"),
    "rotate": ("chunked_pipeline", "wire_bf16", "wire_int8"),
    "regroup": ("wire_bf16", "wire_int8"),
    "pull": (),           # replication has no narrower legal wire here
    "allgather": (),
    "broadcast": (),
    "reduce": (),
    "barrier": (),
}

#: (program, verb, schedule) → the name of the config that measures
#: the alternative on silicon.  Only mapped sites can ever carry a
#: flip_candidate — an alternative with no measurement path stays a
#: priced row, never a recommendation (fail closed all the way down).
FLIP_CANDIDATE_CONFIGS = {
    ("kmeans.fit", "allreduce", "hier_psum"): "kmeans_hier_psum",
    ("mfsgd.epoch", "reshard", "chunked_pipeline"): "mfsgd_chunked_rotate",
    ("lda.epoch", "reshard", "wire_bf16"): "lda_planner_wire",
    ("lda.epoch", "reshard", "wire_int8"): "lda_rotate_int8",
    # PR 12: the last two per-app wires gain byte sheets + measurement
    # paths (ROADMAP planner item) — svm's per-round SV exchange and
    # wdamds's per-iteration coordinate exchange, both reshard
    # blocked→replicated sites gated on train_acc / final_stress
    ("svm.train", "reshard", "wire_bf16"): "svm_sv_bf16",
    ("svm.train", "reshard", "wire_int8"): "svm_sv_int8",
    ("wdamds.smacof", "reshard", "wire_bf16"): "wdamds_coord_bf16",
    ("wdamds.smacof", "reshard", "wire_int8"): "wdamds_coord_int8",
}


#: an alternative must price at least this much below "keep" before the
#: planner names its flip candidate — a ranking model's float noise (or
#: a genuinely-equal schedule like hier on a one-host ring) must never
#: read as a predicted win
CANDIDATE_MARGIN = 0.95


@dataclasses.dataclass
class SiteDecision:
    """One collective site's schedule decision (serialized per site in
    the plan row)."""

    site: str               # telemetry.site_key shape ("mfsgd.py:535")
    primitive: str
    verb: str | None
    sheet_bytes: int        # amplified per-site bytes FROM the byte sheet
    schedule: str = "keep"  # fail-closed: always "keep" today
    predicted_bytes: int = 0
    cost_s: float = 0.0     # topology price of the chosen schedule
    alternatives: dict = dataclasses.field(default_factory=dict)
    #: schedule -> config name, one entry per alternative that
    #: both prices under the margin AND has a measurement path
    candidates: dict = dataclasses.field(default_factory=dict)
    flip_candidate: str | None = None   # the cheapest of `candidates`

    def row(self) -> dict:
        return {
            "site": self.site, "primitive": self.primitive,
            "verb": self.verb, "schedule": self.schedule,
            "sheet_bytes": self.sheet_bytes,
            "predicted_bytes": self.predicted_bytes,
            "cost_s": round(self.cost_s, 9),
            "alternatives": {k: round(v, 9)
                             for k, v in sorted(self.alternatives.items())},
            "candidates": dict(sorted(self.candidates.items())),
            "flip_candidate": self.flip_candidate,
        }


@dataclasses.dataclass
class Plan:
    """One program's explicit, serializable schedule plan."""

    program: str
    topology: str
    rates_source: str
    sites: list

    def predicted_bytes_total(self) -> int:
        return sum(s.predicted_bytes for s in self.sites)

    def flip_candidates(self) -> list:
        out: set = set()
        for s in self.sites:
            out.update(s.candidates.values())
        return sorted(out)

    def row(self) -> dict:
        """The ``kind: "plan"`` record (check_jsonl invariant 10)."""
        return {
            "kind": "plan",
            "program": self.program,
            "topology": self.topology,
            "rates_source": self.rates_source,
            "sites": [s.row() for s in self.sites],
            "predicted_bytes_total": self.predicted_bytes_total(),
            "flip_candidates": self.flip_candidates(),
        }


def _site_cost(topo: Topology, primitive: str, schedule: str,
               sheet_bytes: int) -> float:
    """Price one (site, schedule) pair — delegates to the SHARED wire
    oracle (PR 13): the Plan rows' cost column and the perfmodel's wire
    term are one function (``perfmodel.model.wire_cost_s``), so the
    planner and the predictor can never price the same site
    differently.  The sheet's bytes are already amplification-folded,
    so the topology sees amplification=1 here."""
    from harp_tpu.perfmodel.model import wire_cost_s

    return wire_cost_s(topo, primitive, schedule, sheet_bytes)


def decide_site(program: str, entry: dict, topo: Topology) -> SiteDecision:
    """One byte-sheet collective entry → its fail-closed decision.

    ``entry`` is a row of ``sheet["collectives"]`` (commgraph
    CommSite.row()): per_shard_bytes × amplification is the site's
    per-run payload.  The chosen schedule is ALWAYS "keep"; cheaper
    alternatives only attach their flip candidate, and only when
    a) the verb can legally lower to them, b) the site's wire is still
    exact (a quantized site already took its trade), and c) a
    config is named that would measure them.
    """
    sheet_bytes = int(entry["per_shard_bytes"]) * max(
        int(entry.get("amplification") or 1), 1)
    prim = entry["primitive"]
    verb = entry.get("verb")
    dec = SiteDecision(site=entry["site"], primitive=prim, verb=verb,
                       sheet_bytes=sheet_bytes)
    dec.predicted_bytes = predicted_bytes("keep", sheet_bytes)
    dec.cost_s = _site_cost(topo, prim, "keep", sheet_bytes)
    already_quantized = bool(entry.get("ledger_wire")) or (
        verb or "").endswith("_quantized")
    for alt in _VERB_ALTERNATIVES.get(verb or "", ()):
        if already_quantized and alt.startswith("wire_"):
            continue
        cost = _site_cost(topo, prim, alt, sheet_bytes)
        dec.alternatives[alt] = cost
        if cost < dec.cost_s * CANDIDATE_MARGIN:
            cfg = FLIP_CANDIDATE_CONFIGS.get((program, verb, alt))
            if cfg is not None:
                dec.candidates[alt] = cfg
    if dec.candidates:
        dec.flip_candidate = dec.candidates[
            min(dec.candidates, key=lambda a: dec.alternatives[a])]
    return dec


def plan_sheet(program: str, sheet: dict,
               topo: Topology | None = None) -> Plan:
    """Plan one program from its (already extracted) byte sheet — the
    pure-decision core, usable straight off a committed lint row."""
    topo = topo or detect()
    sites = [decide_site(program, e, topo)
             for e in sheet.get("collectives") or []]
    return Plan(program=program, topology=topo.name,
                rates_source=topo.rates_source, sites=sites)


def plan_program(name: str, topo: Topology | None = None) -> Plan:
    """Extract the registered driver program's CommGraph (the same
    walk the lint row ships) and plan it."""
    from harp_tpu.analysis import commgraph
    from harp_tpu.analysis.drivers import DRIVERS

    if name not in DRIVERS:
        raise KeyError(
            f"{name!r} is not a registered driver program "
            f"(analysis/drivers.py has: {sorted(DRIVERS)})")
    fn, args = DRIVERS[name]()
    graph = commgraph.extract(name, fn, args)
    # carry each site's matched ledger wire into the sheet rows so
    # decide_site can skip re-quantizing an already-narrow wire
    rows = []
    for s in graph.sites:
        row = s.row()
        row["ledger_wire"] = s.ledger_wire
        rows.append(row)
    return plan_sheet(name, {"collectives": rows}, topo)


def plan_all(topo: Topology | None = None) -> dict:
    """Plan every registered driver program — the acceptance check that
    planner-predicted per-site bytes match the CommGraph byte sheets
    exactly rides this (tests/test_plan.py)."""
    from harp_tpu.analysis.drivers import DRIVERS

    topo = topo or detect()
    return {name: plan_program(name, topo) for name in sorted(DRIVERS)}
