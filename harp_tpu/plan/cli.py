"""``python -m harp_tpu plan`` — plan registered programs' collectives.

Extracts each registered driver program's CommGraph byte sheet (the
same Layer-4 walk the lint row ships), prices every site against the
selected topology, and prints a human schedule table plus ONE
provenance-stamped ``kind: "plan"`` JSON line per program (through
:func:`harp_tpu.utils.metrics.benchmark_json`, so the rows carry the
same backend/date/commit stamp as every bench row —
``scripts/check_jsonl.py`` invariant 10 validates the shape).

The jax-touching extraction forces the CPU backend (8 simulated
workers) before first backend use, exactly like the lint CLI — a
*planner* only traces and must not take the chip; the topology being
priced is a model, not the backend the extraction runs on.
"""

from __future__ import annotations

import argparse
import sys


def _topology(name: str):
    from harp_tpu import plan as P

    if name == "auto":
        return P.detect()
    if name == "single_chip":
        return P.single_chip()
    if name == "sim_ring_8":
        return P.sim_ring(8)
    if name == "v4_32":
        return P.v4_32()
    raise ValueError(name)


def render(plan) -> str:
    lines = [f"== plan: {plan.program} on {plan.topology} "
             f"({plan.rates_source} rates) =="]
    if not plan.sites:
        lines.append("  (no collectives — nothing to schedule)")
    for s in plan.sites:
        alts = ", ".join(f"{k}={v:.3g}s" for k, v in
                         sorted(s.alternatives.items())) or "-"
        flip = f" -> flip candidate {s.flip_candidate}" \
            if s.flip_candidate else ""
        lines.append(
            f"  {s.site:24s} {s.primitive:14s} {s.verb or '?':18s} "
            f"{s.sheet_bytes:>12d} B  keep={s.cost_s:.3g}s  "
            f"[{alts}]{flip}")
    lines.append(f"  total predicted: {plan.predicted_bytes_total()} B; "
                 f"flip candidates: {plan.flip_candidates() or 'none'}")
    return "\n".join(lines)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m harp_tpu plan",
        description="topology-aware collective planner over the "
                    "registered drivers' byte sheets (fail-closed: "
                    "decisions name flip candidates, never change "
                    "defaults)")
    p.add_argument("--program", action="append", default=None,
                   metavar="NAME",
                   help="plan only these registered driver programs "
                        "(default: all of analysis/drivers.py)")
    p.add_argument("--topology",
                   choices=("auto", "single_chip", "sim_ring_8", "v4_32"),
                   default="auto",
                   help="price list to plan against (auto = the active "
                        "mesh; v4_32 = the north-star slice with its "
                        "declared inter-host class)")
    p.add_argument("--json", action="store_true",
                   help="print only the machine-readable lines")
    args = p.parse_args(argv)

    from harp_tpu.analysis.cli import _force_cpu_backend

    _force_cpu_backend()

    from harp_tpu.analysis.drivers import DRIVERS
    from harp_tpu.plan import plan_program
    from harp_tpu.utils.metrics import benchmark_json

    names = args.program or sorted(DRIVERS)
    unknown = [n for n in names if n not in DRIVERS]
    if unknown:
        print(f"unknown program(s) {unknown}; registered: "
              f"{sorted(DRIVERS)}", file=sys.stderr)
        return 2
    topo = _topology(args.topology)
    for name in names:
        plan = plan_program(name, topo)
        if not args.json:
            print(render(plan))
        print(benchmark_json("plan", plan.row()), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
