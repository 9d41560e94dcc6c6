"""``python -m harp_tpu profile <app|--all>`` — the wall-attribution CLI.

Captures each requested app's registered driver under the device-trace
hook, attributes every op into the frozen mechanism buckets, and prints
a human attribution table (or, with ``--json``, one provenance-stamped
``kind:"profile"`` row per app — the exact shape check_jsonl invariant
15 validates, so ``profile --all --json > PROFILE_attrib.jsonl``
regenerates the committed baseline).

Exit codes: 0 every row reconciled; 1 any row failed a cross-check
(bucket sum, flightrec dispatch count, compile in the timed window, or
an unmatched CommLedger wire site); 2 unknown app / capture error.

Selects the 8-worker CPU backend before first backend use: this is the
dev-loop attribution tool, and it must not take the chip from a process
that is measuring on it.
Silicon attribution rows arrive through the bench/PROFILE_local path,
graded against this CPU baseline by the health sentinel's
``profile_drift`` detector.
"""

from __future__ import annotations

import argparse
import json
import sys


def _render(row: dict) -> str:
    terms = row["terms"]
    wall = row["wall_s"] or 1e-12
    parts = "  ".join(
        f"{k[:-2]} {v:.4f}s ({100.0 * v / wall:4.1f}%)"
        for k, v in sorted(terms.items(), key=lambda kv: -kv[1])
        if v > 0)
    flag = "ok" if row["reconciled"] else "FAILED"
    return (f"{row['app']:9s} {row['program']:20s} wall {wall:.4f}s  "
            f"bound={row['bound']:11s} [{flag}]\n"
            f"          {parts}\n"
            f"          wire {row['wire_bytes']} B over "
            f"{row['wire_sites']} site(s)  dispatches "
            f"{row['dispatches']} ({row['dispatches_per_rep']}/rep)  "
            f"compiles {row['compiles_in_window']}  "
            f"sum_rel_err {row['sum_rel_err']}")


def main(argv=None) -> int:
    from harp_tpu.analysis.cli import _force_cpu_backend

    p = argparse.ArgumentParser(
        prog="python -m harp_tpu profile",
        description="capture one driver run per app and attribute its "
                    "wall to the frozen mechanism buckets")
    p.add_argument("app", nargs="?", help="app to profile "
                   "(kmeans/mfsgd/lda/rf/svm/wdamds/subgraph/serve)")
    p.add_argument("--all", action="store_true",
                   help="profile every registered app")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="one kind:'profile' JSONL row per app")
    p.add_argument("--reps", type=int, default=4,
                   help="timed repetitions inside the trace (default 4)")
    args = p.parse_args(argv)

    from harp_tpu.profile.attribution import PROFILE_APPS, capture

    if args.all:
        apps = list(PROFILE_APPS)
    elif args.app:
        if args.app not in PROFILE_APPS:
            print(f"unknown app {args.app!r}; known: "
                  f"{', '.join(PROFILE_APPS)}", file=sys.stderr)
            return 2
        apps = [args.app]
    else:
        p.print_usage(sys.stderr)
        return 2

    _force_cpu_backend()
    rows = []
    for app in apps:
        try:
            rows.append(capture(app, reps=args.reps))
        except Exception as e:  # noqa: BLE001 - a broken capture is loud
            print(f"profile: capture failed for {app!r}: "
                  f"{type(e).__name__}: {e}", file=sys.stderr)
            return 2

    for row in rows:
        if args.as_json:
            print(json.dumps(row), flush=True)
        else:
            print(_render(row))
    return 0 if all(r["reconciled"] for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
