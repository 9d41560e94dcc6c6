#!/usr/bin/env python
"""v4-32 scaling projection from measured 1-chip rates + comm-byte models.

VERDICT r4 item 5, second half: the defensible multi-chip projection.
Inputs, per graded app:
  * the MEASURED 1-chip TPU rate (the last full-shape TPU row per
    bench.py cell in BENCH_local.jsonl — `measured_rates` below; 1× v5e,
    2026-08-01 until a newer row lands);
  * an ANALYTIC per-sync-quantum collective byte model at the graded
    shape — the same collective patterns the CPU-sim sweep traced
    (SCALING_local.jsonl), whose measured collective-op fractions grow
    with worker count the way these byte models predict;
  * stated ICI assumptions (below).

Per app the model defines one SYNC QUANTUM (an iteration, an epoch, a
step, a tree) and computes, at N workers:
  t_comp = per-chip compute time for the quantum at the measured rate;
  t_comm = wire_bytes/ICI_BW + hops·LAT for the quantum's collectives;
  - synchronous allreduce patterns:  eff = t_comp / (t_comp + t_comm)
  - double-buffered rotation rings (parallel/rotate.py; the reference's
    dymoro makes the identical bet, SURVEY.md §3.5): comm hides under
    compute until one slice hop outruns one compute step,
    eff = step_comp / max(step_comp, step_comm).

ICI assumptions (conservative, stated once here and in BASELINE.md):
  * ICI_BW_GBS = 90  — a 1-D ring uses 2 of a v4 chip's 6 links; public
    v4 figures put a link around 45 GB/s/direction; 2 × 45 = 90 GB/s of
    ring bandwidth per chip.
  * LAT_US = 1 per hop.
  * v4-32 = 32 workers (north star: "one Harp worker per chip via a
    pjit mesh"; if the slice name counts TensorCores, read the N=16
    row instead — both are emitted).

No chip needed; run anytime:  python scripts/project_scaling.py
One JSON line per (app, N); pipe into BASELINE.md's scaling section.
"""

import datetime
import importlib.util
import json
import math
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

ICI_BW_GBS = 90.0
LAT_US = 1.0


def measured_skew(path=None):
    """Latest measured per-app load skew from SCALING_local.jsonl's skew
    columns (scripts/scaling_sweep.py; utils/skew.py ledger): app →
    max/mean work ratio at the HIGHEST worker count that recorded one.
    The projection multiplies its comm-model efficiency by the measured
    ``1/ratio`` — a barrier superstep ends when the max-loaded worker
    does, so imbalance stacks multiplicatively with collective overhead
    — and emits both, so BASELINE.md's scaling section can state how
    much efficiency loss is attributable to skew vs the wire."""
    path = path or os.path.join(REPO, "SCALING_local.jsonl")
    best: dict = {}
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    row = json.loads(line)
                except ValueError:
                    continue
                r = row.get("skew_max_mean")
                app, n = row.get("app"), row.get("n_workers")
                if r and app and isinstance(n, int):
                    cur = best.get(app)
                    if cur is None or n >= cur[0]:
                        best[app] = (n, float(r))
    except OSError:
        pass
    return {app: ratio for app, (n, ratio) in best.items()}


def ring_bytes(payload_bytes, n):
    """Wire bytes per chip for a ring ALLREDUCE of `payload` bytes
    (reduce-scatter + allgather: 2(n-1)/n of the payload)."""
    return 2.0 * (n - 1) / n * payload_bytes


def allgather_bytes(shard_bytes, n):
    """Wire bytes per chip for a ring ALLGATHER of per-chip shards:
    each chip forwards every other chip's shard once — (n-1)·S, NOT the
    allreduce 2(n-1)/n formula (review finding, round 5)."""
    return (n - 1.0) * shard_bytes


def ring_hops(n):
    """Sequential neighbor steps in a ring allreduce: reduce-scatter is
    n-1 hops, allgather another n-1 (review finding, round 5)."""
    return 2 * (n - 1)


def t_wire(nbytes, hops):
    return nbytes / (ICI_BW_GBS * 1e9) + hops * LAT_US * 1e-6


def sync_eff(t_comp, t_comm):
    """Synchronous collective after each quantum (allreduce patterns)."""
    return t_comp / (t_comp + t_comm) if t_comp else 0.0


def rotate_eff(t_comp_quantum, slice_bytes, n):
    """Double-buffered ring: N steps/quantum, one slice hop per step."""
    if n == 1:
        return 1.0
    step_comp = t_comp_quantum / n
    step_comm = t_wire(slice_bytes, 1)
    return step_comp / max(step_comp, step_comm) if step_comp else 0.0


def measured_rates():
    """config → {value, unit, date}: the last full-shape TPU row of
    BENCH_local.jsonl per bench.py cell (``flip_decision.latest_rows``
    skips smoke, error and CPU rows), read at the cell's declared
    headline key."""
    import flip_decision

    spec = importlib.util.spec_from_file_location(
        "bench_mod", os.path.join(REPO, "bench.py"))
    b = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(b)
    rows = flip_decision.latest_rows(os.path.join(REPO, "BENCH_local.jsonl"))
    return {cfg: {"value": round(float(rows[cfg][key]), 2),
                  "unit": b.UNITS[key], "date": rows[cfg].get("date")}
            for cfg, key in b._CONFIG_KEYS
            if rows.get(cfg, {}).get(key) is not None}


def project(n_workers=(4, 8, 16, 32)):
    """Emit rows for every graded app at each worker count.

    Shapes mirror measure_all.py's full-mode configs; `per_chip` marks
    rates already divided by chip count (their projected value is the
    per-chip rate × efficiency; aggregate = × N).
    """
    lm = measured_rates()
    skew_by_app = measured_skew()

    rows = []

    def emit(app, rate_key, n, eff, t_comp, wire, pattern, quantum,
             per_chip, note, projected=None):
        rate1 = lm[rate_key]["value"]
        if projected is None:
            projected = rate1 * eff if per_chip else rate1 * n * eff
        # measured load skew stacks multiplicatively on the comm model:
        # the straggler sets the superstep, the wire sets the rest
        sk = skew_by_app.get(app)
        skew_cols = {}
        if sk:
            skew_cols = {
                "skew_max_mean": round(sk, 4),
                "eff_skew": round(1.0 / sk, 4),
                "efficiency_with_skew": round(eff / sk, 4),
                "projected_with_skew": round(projected / sk, 2),
            }
        rows.append({
            "app": app, "n_workers": n, "pattern": pattern,
            "quantum": quantum,
            "measured_rate_1chip": rate1,
            "measured_unit": lm[rate_key]["unit"],
            "measured_date": lm[rate_key]["date"],
            "wire_bytes_per_chip": round(wire),
            "compute_sec_per_chip_per_quantum": round(t_comp, 9),
            "efficiency": round(eff, 4),
            "projected": round(projected, 2),
            "projected_unit": (lm[rate_key]["unit"] if per_chip else
                               lm[rate_key]["unit"] + " aggregate"),
            "note": note,
            **skew_cols,
            "assumptions": f"ICI {ICI_BW_GBS:.0f} GB/s ring, "
                           f"{LAT_US:.0f}us/hop",
        })

    for n in n_workers:
        # kmeans 1M×300 k=100 f32: data shards, one psum of [k, d+1]/iter
        t_comp = 1.0 / (lm["kmeans"]["value"] * n)
        wire = ring_bytes(4 * 100 * 301, n)
        emit("kmeans", "kmeans", n,
             sync_eff(t_comp, t_wire(wire, ring_hops(n))), t_comp, wire,
             "allreduce", "iteration", False,
             "graded 1M points shard across chips; projected = iters/s "
             "on the SAME 1M-point problem")

        # north star: kmeans 1B pts k=1000 — measured rate is iter/s at
        # 100M on one chip, so per-chip work scales by (1e9/N)/1e8
        r = lm["kmeans_stream"]["value"]
        t_comp = (1e9 / n) / 1e8 / r   # measured rate is iter/s at 100M
        wire = ring_bytes(4 * 1000 * 301, n)
        t_comm = t_wire(wire, ring_hops(n))
        emit("kmeans_stream_1b", "kmeans_stream", n,
             sync_eff(t_comp, t_comm), t_comp, wire,
             "allreduce", "iteration(1B pts)", False,
             "north-star 1B×300 k=1k iter/s, e2e basis incl. the "
             "measured host-gen floor; the 10x-more-work-than-measured "
             "shape means projected is ABSOLUTE, not rate1-scaled",
             projected=1.0 / (t_comp + t_comm))

        # MF-SGD MovieLens-20M: epoch = 20M updates; H [26744, 64] f32
        # rotates in N double-buffered slices.  Rate = the DEFAULT stack
        # (fused kernel since the 2026-08-01 flip): ~3× the dense rate
        # shrinks the compute window the ring hides under — the honest
        # projection must use the shipped default, not the slower arm
        r = lm["mfsgd_pallas"]["value"]  # updates/s/chip
        t_comp = 20e6 / n / r
        slice_b = 4 * 26_744 * 64 / n
        emit("mfsgd", "mfsgd_pallas", n, rotate_eff(t_comp, slice_b, n),
             t_comp, slice_b * n, "rotate", "epoch", True,
             "projected updates/s/chip (fused-kernel default); rotation "
             "comm double-buffers under compute")

        # LDA enwiki-1M: epoch = 100M tokens; Nwk [50k, 1000] f32 rotates.
        # Rate = the default stack (kernel + exprace + rbg + Db-carry)
        r = lm["lda_pallas_carry"]["value"]  # tokens/s/chip
        t_comp = 100e6 / n / r
        slice_b = 4 * 50_000 * 1000 / n
        emit("lda", "lda_pallas_carry", n, rotate_eff(t_comp, slice_b, n),
             t_comp, slice_b * n, "rotate", "epoch", True,
             "projected tokens/s/chip (default stack); the 200 MB Nwk "
             "ring is the heaviest wire in the suite")

        # MLP MNIST: DP step at per-chip batch 8192; grads psum
        r = lm["mlp"]["value"]  # samples/s (1 chip)
        params = 784 * 512 + 512 * 256 + 256 * 10 + 512 + 256 + 10
        t_comp = 8192 / r
        wire = ring_bytes(4 * params, n)
        emit("mlp", "mlp", n, sync_eff(t_comp, t_wire(wire, ring_hops(n))),
             t_comp, wire, "allreduce", "step(batch 8192/chip)", False,
             "weak-scaled batch; projected = aggregate samples/s")

        # Subgraph u5-tree @1M powerlaw: per color-coding trial, one
        # allgather of the child's COMPACT table [V/N, cols] per template
        # edge (subgraph.py:199; u5-tree: 4 edges, compact cols avg ~4)
        r = lm["subgraph"]["value"]  # vertices/s
        t_comp = 1e6 / n / r
        wire = 4 * allgather_bytes(4 * (1e6 / n) * 4, n)
        emit("subgraph", "subgraph", n,
             sync_eff(t_comp, t_wire(wire, 4 * (n - 1))), t_comp, wire,
             "allgather", "color-coding trial", False,
             "4 compact-table allgathers per trial ((n-1)·shard wire "
             "each); projected = aggregate vertices/s, same 1M graph")

        # RF 32 trees depth 6 on 200k×64: per level, one-hot histogram
        # [nodes≤2^l, feat, bins, classes] psum; Σ_l 2^l ≈ 2^7
        r = lm["rf"]["value"]  # trees/s
        t_comp = 1.0 / r
        wire = ring_bytes(4 * (2 ** 7) * 64 * 32 * 2, n)
        emit("rf", "rf", n, sync_eff(t_comp, t_wire(wire, ring_hops(n))),
             t_comp, wire, "allreduce", "tree", False,
             "per-tree histogram psums; projected = aggregate trees/s "
             "with data sharded")
    return rows


def main():
    for row in project():
        print(json.dumps({**row,
                          "date": datetime.date.today().isoformat()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
