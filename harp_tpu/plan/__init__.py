"""Topology-aware collective planner (PR 11).

The decision side of PR 9's CommGraph byte sheets: a mesh topology
model (:mod:`harp_tpu.plan.topology`) prices each registered program's
collective sites per link class, and the planner
(:mod:`harp_tpu.plan.planner`) emits an explicit, serializable
:class:`~harp_tpu.plan.planner.Plan` whose every choice FAILS CLOSED —
the chosen schedule is today's exact lowering, and cheaper-priced
alternatives name their flip candidate (a config name) instead of
flipping anything themselves.  ``python -m harp_tpu plan`` is the front
door; ``scripts/check_jsonl.py`` invariant 10 validates the rows.
"""

from harp_tpu.plan.planner import (
    FLIP_CANDIDATE_CONFIGS,
    Plan,
    SCHEDULES,
    SiteDecision,
    decide_site,
    plan_all,
    plan_program,
    plan_sheet,
    predicted_bytes,
)
from harp_tpu.plan.topology import (
    TOPOLOGY_NAMES,
    Topology,
    detect,
    probed,
    sim_ring,
    single_chip,
    v4_32,
    v5e_2x2,
)

__all__ = [
    "FLIP_CANDIDATE_CONFIGS",
    "Plan",
    "SCHEDULES",
    "SiteDecision",
    "TOPOLOGY_NAMES",
    "Topology",
    "decide_site",
    "detect",
    "plan_all",
    "plan_program",
    "plan_sheet",
    "predicted_bytes",
    "probed",
    "sim_ring",
    "single_chip",
    "v4_32",
    "v5e_2x2",
]
