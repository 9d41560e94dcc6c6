#!/usr/bin/env python
"""1→N simulated-worker scaling curves for the graded apps.

VERDICT r4 item 5: the framework targets a v4-32 pod but had no scaling
evidence at all.  This script produces the half that needs no chip:
weak- and strong-scaling sweeps of every graded app over 1/2/4/8
simulated CPU workers, with the collective share of each run measured
from an XLA trace (`utils.profiling.op_breakdown` self-times, classified
by op name).  One JSON row per (app, mode, n_workers) → SCALING_local.jsonl.
Each row also carries per-worker SKEW columns (skew_work / skew_max_mean /
skew_wasted_frac, from the utils/skew.py ledger the instrumented drivers
feed during the telemetry-enabled warmup run), so a reader can
attribute efficiency loss to load imbalance separately from collective
overhead.

The device count is baked into XLA at backend init, so the parent spawns
one child subprocess per worker count (`--child`), each with its own
``--xla_force_host_platform_device_count=N`` and ``JAX_PLATFORMS=cpu``
in its ``env=`` (a CPU simulation must never take the chip).

Reading the rows (CPU-sim caveat, recorded in every row): absolute CPU
rates are non-predictive of TPU (BASELINE.md's onehot 7.8× CPU
inversion).  What transfers is (a) the SHAPE of the weak/strong curves —
how collective overhead grows with worker count under a fixed-bandwidth
memory system — and (b) the measured collective-op share, which bounds
any comm-byte model of a larger slice (BASELINE.md scaling section).

Usage:
  python scripts/scaling_sweep.py [--out SCALING_local.jsonl]
      [--workers 1 2 4 8] [--apps kmeans ...] [--modes strong weak]
"""

import argparse
import datetime
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "scripts"))

APPS = ("kmeans", "mfsgd", "lda", "mlp", "subgraph", "rf")

#: substrings identifying collective ops in XLA span names (CPU and TPU
#: use the same HLO names: all-reduce.3, collective-permute.1, ...)
COMM_MARKERS = ("all-reduce", "all-gather", "all-to-all",
                "collective-permute", "reduce-scatter", "collective")

#: headline rate key per app; *_per_chip keys
#: are multiplied by N for the total-rate scaling curves
RATE_KEYS = {
    "kmeans": "iters_per_sec",
    "mfsgd": "updates_per_sec_per_chip",
    "lda": "tokens_per_sec_per_chip",
    "mlp": "samples_per_sec",
    "subgraph": "vertices_per_sec",
    "rf": "trees_per_sec",
}


def shapes(app: str, mode: str, n: int) -> dict:
    """Benchmark kwargs for one (app, mode, n_workers) cell.

    strong: total problem fixed (divisible by 8) — speedup curve.
    weak: per-worker work fixed — efficiency curve.  Shapes are sized so
    the slowest cell stays tens of seconds on this 1-core CPU host.
    """
    w = n if mode == "weak" else 8  # weak grows with n; strong is fixed
    if app == "kmeans":
        return {"n": 16384 * w, "d": 64, "k": 64, "iters": 5}
    if app == "mfsgd":
        # rotation app: users+ratings shard; item factors rotate
        return {"n_users": 256 * w, "n_items": 512, "nnz": 32768 * w,
                "rank": 16, "epochs": 1, "u_tile": 32, "i_tile": 32,
                "entry_cap": 256}
    if app == "lda":
        # rotation+pushpull app: docs shard; word-topic slices rotate
        return {"n_docs": 256 * w, "vocab_size": 512, "n_topics": 16,
                "tokens_per_doc": 32, "epochs": 1, "d_tile": 32,
                "w_tile": 32, "entry_cap": 128}
    if app == "mlp":
        return {"n": 1024 * w, "batch": 128 * w, "steps": 10}
    if app == "subgraph":
        return {"n_vertices": 2048 * w, "avg_degree": 8}
    if app == "rf":
        return {"n": 2048 * w, "f": 32, "max_depth": 4, "n_trees": 8}
    raise ValueError(app)


def skew_columns():
    """Per-worker skew columns for a sweep row, from the SkewLedger the
    instrumented drivers fed during the (telemetry-enabled) warmup run.
    Picks the heaviest EXECUTION phase — the superstep the app's barrier
    actually waits on; apps without instrumented drivers yield the
    ingest view instead, and apps recording nothing yield one null
    marker so downstream readers see "not measured", not "balanced"."""
    from harp_tpu.utils import skew

    s = skew.ledger.summary()
    execs = {k: v for k, v in s.items() if v["source"] == "execution"} \
        or {k: v for k, v in s.items() if v["source"] == "ingest"}
    if not execs:
        return {"skew_max_mean": None}
    phase = max(execs, key=lambda k: execs[k]["total"])
    v = execs[phase]
    return {"skew_phase": phase, "skew_unit": v["unit"],
            "skew_work": v["work"],
            "skew_max_mean": v["max_mean_ratio"],
            "skew_wasted_frac": v["wasted_frac"]}


def child(app: str, mode: str, n: int, emit=print) -> None:
    """Run one cell in THIS process (device count fixed at init)."""
    import jax  # on the CPU: the parent put JAX_PLATFORMS=cpu in env=
    import tempfile
    import time

    from harp_tpu.models import kmeans, lda, mfsgd, mlp, rf, subgraph
    from harp_tpu.utils import skew, telemetry
    from harp_tpu.utils.profiling import op_breakdown, trace

    mod = {"kmeans": kmeans, "mfsgd": mfsgd, "lda": lda, "mlp": mlp,
           "subgraph": subgraph, "rf": rf}[app]
    kw = shapes(app, mode, n)
    assert jax.device_count() == n, (jax.device_count(), n)
    # warmup/compile OUTSIDE the trace; telemetry on for THIS run only,
    # so the drivers feed the skew ledger while the traced (timed) run
    # stays instrumentation-free — the host-phase stamp per subprocess
    # plus per-worker device counters, zero cost in the timed region
    telemetry.enable(True)
    t_warm = time.perf_counter()
    mod.benchmark(**kw)
    skew.record_host(f"{app}.child", 0, time.perf_counter() - t_warm,
                     n_workers=1)
    skew_cols = skew_columns()
    telemetry.enable(False)
    logdir = tempfile.mkdtemp(prefix=f"harp_scale_{app}_{n}_")
    t0 = time.perf_counter()
    with trace(logdir):
        result = mod.benchmark(**kw)
    wall = time.perf_counter() - t0
    ops = op_breakdown(logdir, top=10 ** 6)  # every span, self-time
    traced = sum(t for _, t in ops)
    comm = sum(t for name, t in ops
               if any(m in name.lower() for m in COMM_MARKERS))
    rate_key = RATE_KEYS[app]
    rate = float(result[rate_key])
    total = rate * n if rate_key.endswith("_per_chip") else rate
    emit(json.dumps({
        "app": app, "mode": mode, "n_workers": n,
        "rate": round(rate, 4), "rate_key": rate_key,
        "total_rate": round(total, 4),
        "wall_sec": round(wall, 4),
        "traced_sec": round(traced, 5),
        "comm_sec": round(comm, 5),
        "comm_fraction": round(comm / traced, 4) if traced else None,
        **skew_cols,
        "backend": "cpu", "cpu_sim": True,
        "date": datetime.date.today().isoformat(),
    }), flush=True)


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--out", default=os.path.join(REPO, "SCALING_local.jsonl"))
    p.add_argument("--workers", nargs="+", type=int, default=[1, 2, 4, 8])
    p.add_argument("--apps", nargs="+", choices=APPS, default=list(APPS))
    p.add_argument("--modes", nargs="+", choices=["strong", "weak"],
                   default=["strong", "weak"])
    p.add_argument("--child", nargs=3, metavar=("APP", "MODE", "N"),
                   default=None, help="internal: run one cell in-process")
    args = p.parse_args(argv)
    if args.child:
        child(args.child[0], args.child[1], int(args.child[2]))
        return 0
    sink = open(args.out, "a")
    failures = 0
    for app in args.apps:
        for mode in args.modes:
            for n in args.workers:
                env = dict(os.environ, JAX_PLATFORMS="cpu")
                env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                                    + f" --xla_force_host_platform_device"
                                      f"_count={n}")
                row = None
                try:
                    r = subprocess.run(
                        [sys.executable, os.path.abspath(__file__),
                         "--child", app, mode, str(n)],
                        capture_output=True, text=True, env=env, cwd=REPO,
                        timeout=1800)
                except subprocess.TimeoutExpired:
                    # a hung cell must cost only itself, like the
                    # returncode path below (review finding, round 5)
                    r = None
                    err = "timeout after 1800s (hung cell)"
                else:
                    for line in reversed(r.stdout.strip().splitlines()):
                        if line.startswith("{"):
                            row = line
                            break
                    err = (r.stderr.strip().splitlines() or ["?"])[-1]
                if r is None or r.returncode != 0 or row is None:
                    failures += 1
                    row = json.dumps({
                        "app": app, "mode": mode, "n_workers": n,
                        "error": err,
                        "backend": "cpu", "cpu_sim": True})
                print(row, flush=True)
                sink.write(row + "\n")
                sink.flush()
    sink.close()
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
