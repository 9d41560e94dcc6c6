"""``python -m harp_tpu health`` — the sentinel's offline half.

Two modes, both CPU-only (like the lint/plan/predict CLIs, a health
check reads files and never touches the chip):

- ``health run.jsonl [--json]``: read a JSONL file (a telemetry export,
  a sprint's BENCH output, or a committed evidence file), summarize its
  ``kind:"health"`` rows, and GRADE the freshest bench row per config
  against the committed incumbents + the perfmodel
  (:func:`harp_tpu.health.grade.grade_bench_row`; ``--no-grade-bench``
  skips).  Exit 0 healthy, 1 actionable findings (severity warn/page or
  a regressed/model_invalidated verdict), 2 unreadable input.
- ``health --grade-model``: run the fail-closed pruning gate
  (:func:`harp_tpu.health.grade.model_gate`) and print ONE
  provenance-stamped ``kind:"health"`` row, to be appended to the
  evidence file right after a measurement run lands new rows.  Exit 0
  confirmed, 1 model_invalidated.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from harp_tpu.health import sentinel


def _stamped(row: dict) -> dict:
    from harp_tpu.utils.flightrec import provenance_stamp

    return {**row, **provenance_stamp()}


def _render(rows: list[dict], summary: dict) -> str:
    lines = ["== harp-tpu health =="]
    lines.append(
        f"{summary['findings']} finding(s), "
        f"{summary['actionable']} actionable"
        + (f", worst severity {summary['worst_severity']}"
           if summary.get("worst_severity") else ""))
    for r in rows:
        det, sev = r.get("detector", "?"), r.get("severity", "?")
        who = r.get("tag") or r.get("phase") or r.get("config") or "?"
        bits = []
        if det == "slo_burn":
            bits.append(f"burn fast {r.get('fast_burn')} / slow "
                        f"{r.get('slow_burn')}; offered "
                        f"{r.get('offered')} = {r.get('served')} served"
                        f" + {r.get('shed')} shed + {r.get('failed')} "
                        f"failed ({r.get('deadline_missed')} missed "
                        "deadline)")
        elif det == "skew_trigger":
            plan = r.get("plan") or {}
            bits.append(f"wasted_frac {r.get('wasted_frac')} for "
                        f"{r.get('consecutive')} superstep(s); inline "
                        f"plan: {len(plan.get('moves') or [])} move(s), "
                        f"ratio {plan.get('ratio_before')} -> "
                        f"{plan.get('ratio_after')}")
        elif det == "budget_drift":
            bits.append(f"{r.get('violations')} violation(s); worst: "
                        f"{r.get('worst')}")
        elif det == "evidence_regression":
            bits.append(f"verdict {r.get('verdict')}"
                        + (f" (measured {r.get('measured')} vs "
                           f"incumbent {r.get('incumbent')})"
                           if r.get("incumbent") is not None else "")
                        + (f" [model factor {r.get('model_factor')}x]"
                           if r.get("model_factor") is not None else ""))
        elif det == "profile_drift":
            who = r.get("app") or who
            bits.append(
                ("bound FLIPPED "
                 f"{r.get('committed_bound')} -> {r.get('bound')}; "
                 if r.get("bound_flipped") else
                 f"bound {r.get('bound')} unchanged; ")
                + f"worst bucket {r.get('worst_bucket')} moved "
                  f"{r.get('share_delta')} of the wall vs committed "
                  "attribution")
        lines.append(f"  [{sev:<4s}] {det:<20s} {who}: "
                     + "; ".join(bits))
    if not rows:
        lines.append("  no findings — healthy")
    return "\n".join(lines)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m harp_tpu health",
        description="health sentinel, offline: summarize kind:'health' "
                    "rows, grade fresh bench rows against the committed "
                    "incumbents + the perfmodel, and run the "
                    "fail-closed --predicted-top model gate")
    p.add_argument("jsonl", nargs="?", default=None,
                   help="JSONL to check (telemetry export / sprint "
                        "output / committed evidence file)")
    p.add_argument("--json", action="store_true",
                   help="print one machine-readable summary line")
    p.add_argument("--grade-model", action="store_true",
                   help="run the perfmodel self-grade gate and print "
                        "one kind:'health' row (exit 1 on "
                        "model_invalidated)")
    p.add_argument("--no-grade-bench", action="store_true",
                   help="only summarize health rows; skip grading the "
                        "file's bench rows against the incumbents")
    p.add_argument("--repo", default=None,
                   help="repo root for the committed evidence files "
                        "(default: cwd)")
    args = p.parse_args(argv)

    from harp_tpu.analysis.cli import _force_cpu_backend

    _force_cpu_backend()
    repo = args.repo or os.getcwd()

    if args.grade_model:
        from harp_tpu.health import grade as HG

        ok, row = HG.model_gate(repo)
        print(json.dumps(_stamped(row)), flush=True)
        if not ok:
            print("health: perfmodel INVALIDATED by committed evidence "
                  "— do not rank by it until the "
                  "model is re-calibrated (python -m harp_tpu predict "
                  "--grade for the term breakdowns)", file=sys.stderr)
            return 1
        return 0

    if not args.jsonl:
        p.error("need a JSONL file (or --grade-model)")
    try:
        lines = open(args.jsonl).read().splitlines()
    except OSError as e:
        print(f"health: cannot read {args.jsonl}: {e}", file=sys.stderr)
        return 2

    health_rows: list[dict] = []
    latest_bench: dict[str, dict] = {}
    latest_profile: dict[str, dict] = {}
    for line in lines:
        line = line.strip()
        if not line:
            continue
        try:
            row = json.loads(line)
        except ValueError:
            continue  # check_jsonl owns parseability; summarize the rest
        if not isinstance(row, dict):
            continue
        if row.get("kind") == "health":
            health_rows.append(row)
        elif row.get("kind") == "profile" and row.get("app"):
            latest_profile[row["app"]] = row  # last row per app wins
        elif "config" in row:
            latest_bench[row["config"]] = row  # last row per config wins

    graded: list[dict] = []
    if (latest_bench or latest_profile) and not args.no_grade_bench:
        from harp_tpu.health import grade as HG

        for cfg in sorted(latest_bench):
            f = HG.grade_bench_row(latest_bench[cfg], repo)
            if f is not None:
                graded.append(f)
        committed = HG.committed_profiles(repo) if latest_profile else {}
        for app in sorted(latest_profile):
            f = HG.grade_profile_row(latest_profile[app], repo,
                                     committed=committed)
            if f is not None:
                graded.append(f)

    rows = health_rows + graded
    summary = sentinel.summarize_rows(rows)
    summary["graded_configs"] = len(graded)
    if args.json:
        from harp_tpu.utils.metrics import benchmark_json

        print(benchmark_json("health", summary))
    else:
        print(_render(rows, summary))
    return 1 if summary["actionable"] else 0


if __name__ == "__main__":  # pragma: no cover - python -m harp_tpu health
    sys.exit(main())
