"""``python -m harp_tpu predict`` — price configs and programs offline.

Three modes, all CPU-only (a *predictor* never touches the chip,
exactly like the lint and plan CLIs):

- default / ``--json``: one provenance-stamped ``kind: "model"`` row
  per registered byte-sheet program (the CommGraph extraction the lint
  row ships, priced wire+overhead) AND one per priceable config (full
  compute/memory/wire/overhead breakdown at the graded shape) —
  ``scripts/check_jsonl.py`` invariant 12 validates every row.
- ``--top N``: the flip-candidate ranking (predicted speedup over each
  candidate's incumbent); unpriceable candidates are listed loudly,
  never silently dropped.
- ``--grade``: replay the model against ALL committed BENCH_local /
  FLIP_DECISIONS / SWEEP_pallas evidence it can price; exit 1 with the
  term breakdowns on any disagreement (the honesty gate — see
  :mod:`harp_tpu.perfmodel.grade`).
"""

from __future__ import annotations

import argparse
import json
import os
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


def candidate_ranking(topo, bench_rows=None) -> tuple:
    """(ranked [(candidate, speedup)...] desc, unpriced [names...]) over
    the grading harness's family table."""
    from harp_tpu.perfmodel import grade as G
    from harp_tpu.perfmodel import model as M

    pairs = {c: inc for c, (inc, _, _) in G.FAMILY_PAIRS.items()}
    speedups = M.rank_candidates(pairs, topo, bench_rows)
    ranked = sorted(speedups.items(), key=lambda kv: (-kv[1], kv[0]))
    unpriced = sorted(set(pairs) - set(speedups))
    return ranked, unpriced


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m harp_tpu predict",
        description="offline predictive cost model over the byte "
                    "sheets, rooflines, and calibrated flight deltas "
                    "(ranking model; self-graded against the committed "
                    "bench rows)")
    p.add_argument("--topology",
                   choices=("auto", "single_chip", "sim_ring_8", "v4_32"),
                   default="v4_32",
                   help="price list to predict against (default: the "
                        "north-star v4_32 slice — wire terms matter "
                        "there; committed evidence grades at "
                        "single_chip)")
    p.add_argument("--json", action="store_true",
                   help="print only the machine-readable rows")
    p.add_argument("--top", type=int, default=None, metavar="N",
                   help="print only the top-N flip-candidate ranking")
    p.add_argument("--grade", action="store_true",
                   help="replay the model against the committed "
                        "evidence; exit 1 on any disagreement")
    p.add_argument("--repo", default=None,
                   help="repo root for --grade evidence files "
                        "(default: cwd)")
    args = p.parse_args(argv)

    from harp_tpu.analysis.cli import _force_cpu_backend

    _force_cpu_backend()

    from harp_tpu.perfmodel import grade as G
    from harp_tpu.perfmodel import model as M

    topo = _topology(args.topology)

    if args.grade:
        repo = args.repo or os.getcwd()
        report = G.grade(repo, topo=None)  # evidence is 1x v5e
        print(json.dumps({"kind": "model_grade", "ok": report["ok"],
                          "pairs": report["pairs"],
                          "sweeps": report["sweeps"]}))
        if not report["ok"]:
            for f in report["failures"]:
                print(f"GRADE FAIL: {json.dumps(f)}", file=sys.stderr)
            return 1
        n_ok = sum(1 for e in report["pairs"]
                   if e.get("status") == "agrees")
        print(f"model grade: OK ({n_ok} ranking agreements, "
              f"{len(report['sweeps'])} sweeps, "
              f"{len(report['magnitude'])} rows in band)",
              file=sys.stderr)
        return 0

    if args.top is not None:
        bench = G.latest_tpu_rows(
            os.path.join(args.repo or os.getcwd(), "BENCH_local.jsonl"))
        ranked, unpriced = candidate_ranking(topo, bench)
        for cand, speedup in ranked[:args.top]:
            print(json.dumps({"kind": "model_rank", "candidate": cand,
                              "predicted_speedup": speedup,
                              "topology": topo.name,
                              "rates_source": topo.rates_source}))
        if unpriced:
            print(f"unpriced candidates (no cost model — measure, "
                  f"don't guess): {unpriced}", file=sys.stderr)
        return 0

    from harp_tpu.analysis import commgraph
    from harp_tpu.analysis.drivers import DRIVERS
    from harp_tpu.utils.flightrec import provenance_stamp

    # NOT metrics.benchmark_json: its top-level float rounding (4 dp)
    # would zero a nanosecond-scale predicted_s — stamp the same
    # backend/date/commit triple at full precision instead
    def emit(row):
        print(json.dumps({**row, **provenance_stamp()}), flush=True)

    # program rows: byte sheet (the same Layer-4 walk the lint row
    # ships) x topology
    for name in sorted(DRIVERS):
        fn, prog_args = DRIVERS[name]()
        graph = commgraph.extract(name, fn, prog_args)
        sheet = {"collectives": [s.row() for s in graph.sites]}
        price = M.price_sheet(name, sheet, topo)
        row = M.model_row(price, topo, program=name)
        if not args.json:
            print(f"== {name}: wire {price.wire_s:.3g}s/run "
                  f"({len(graph.sites)} sites), bound {row['bound']}")
        emit(row)

    # config rows: full compute/memory/wire/overhead breakdown
    for cfg in sorted(M.CONFIG_MODELS):
        price = M.price(cfg, None, topo)
        row = M.model_row(price, topo, config=cfg)
        if not args.json:
            t = price.terms()
            print(f"== {cfg}: {price.predicted_rate:.4g} {price.metric} "
                  f"predicted, bound {row['bound']} "
                  f"(c={t['compute_s']:.3g} m={t['memory_s']:.3g} "
                  f"w={t['wire_s']:.3g} o={t['overhead_s']:.3g})")
        emit(row)
    return 0


if __name__ == "__main__":
    sys.exit(main())
