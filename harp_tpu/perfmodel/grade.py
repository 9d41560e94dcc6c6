"""Self-grading: replay the model against the committed evidence.

The honesty layer (ROADMAP autotuning item: "validated against the
committed BENCH_local rows and the PROFILE_local traces"): before the
model is allowed to prune a measurement run, it must agree with every
measurement this repo already paid for.  Three machine checks, all
CPU-only, all fail-closed (a row the harness cannot price is reported,
never silently skipped into a pass):

1. **Family ranking** — for every flip-candidate pair in
   :data:`FAMILY_PAIRS` whose candidate AND incumbent have committed
   full-shape TPU rows, the model's predicted winner must match the
   measured speedup direction.  Pairs whose measured speedup sits
   inside the ``DEAD_BAND`` (±10% — the flip threshold's own margin)
   are recorded as ``too_close`` and not direction-graded: the
   evidence itself calls them a coin flip.  Additionally every
   measured ``FLIP`` verdict in FLIP_DECISIONS.jsonl that the model
   can price must be predicted ≥ even — a model that would have pruned
   a measured winner is broken in the one way that costs real windows.

2. **Sweep rank correlation** — the committed knob sweeps (the
   SWEEP_pallas MF-SGD tile and LDA d_tile rows; the kmeans int8 tile
   sweep recorded in ``_tile_rows_int8``'s docstring, measured
   2026-08-01) must rank identically under the model: Spearman rho ≥
   :data:`RANK_FLOOR` per sweep.

3. **Magnitude band** — every committed full-shape TPU row the model
   prices must land within ``MAGNITUDE_TOL``× of the measured rate.  A
   ranking model is allowed to be wrong by a factor; it is not allowed
   to be wrong by three orders of magnitude and still call itself a
   model of this hardware.

``grade()`` returns a report dict; any failure flips ``ok`` to False
and carries the full term breakdown of both sides, so a wrong
prediction is diagnosable, not just wrong (tests/test_perfmodel.py
pins ``ok`` on the committed evidence — model drift fails tier-1).
"""

from __future__ import annotations

import json
import os

from harp_tpu.perfmodel import model as M

#: |measured speedup - 1| at or below this is "the evidence calls it a
#: tie" — the 10% a default change had to buy (FLIP_DECISIONS.jsonl).
DEAD_BAND = 0.10

#: predicted rate must land within this factor of the measured rate.
MAGNITUDE_TOL = 50.0

#: minimum Spearman rho per committed sweep.
RANK_FLOOR = 0.9

#: candidate -> (incumbent, metric, metric_fallback|None): who competes
#: with whom, and on which metric, for every pair the model can price
#: (the incumbents are those of FLIP_DECISIONS.jsonl's rows).
FAMILY_PAIRS = {
    "mfsgd_pallas": ("mfsgd", "updates_per_sec_per_chip", None),
    "mfsgd_carry": ("mfsgd", "updates_per_sec_per_chip", None),
    "mfsgd_chunked_rotate": ("mfsgd_pallas", "updates_per_sec_per_chip",
                             None),
    "lda_exprace": ("lda", "tokens_per_sec_per_chip", None),
    "lda_fast": ("lda", "tokens_per_sec_per_chip", None),
    "lda_pallas": ("lda", "tokens_per_sec_per_chip", None),
    "lda_pallas_approx": ("lda_pallas", "tokens_per_sec_per_chip", None),
    "lda_pallas_approx_hot": ("lda_pallas_hot", "tokens_per_sec_per_chip",
                              None),
    "lda_carry": ("lda", "tokens_per_sec_per_chip", None),
    "lda_pallas_carry": ("lda_pallas", "tokens_per_sec_per_chip", None),
    "lda_rotate_int8": ("lda_pallas_carry", "tokens_per_sec_per_chip",
                        None),
    "lda_planner_wire": ("lda_pallas_carry", "tokens_per_sec_per_chip",
                         None),
    "kmeans_hier_psum": ("kmeans", "iters_per_sec", None),
    "kmeans_int8_fused": ("kmeans_int8", "iters_per_sec", None),
    "kmeans_stream_int8": ("kmeans_stream", "iters_per_sec_ex_gen",
                           "iters_per_sec"),
    "mlp_grad_bf16": ("mlp", "samples_per_sec", None),
    "mlp_grad_int8": ("mlp", "samples_per_sec", None),
    # PR 16: the attribution observatory priced the remaining half —
    # these pairs were in CANDIDATES all along but unpriceable until
    # the profile pass named their walls (H2D staging + the rf
    # hist/subgraph overflow mechanisms).
    "svm_sv_bf16": ("svm", "samples_per_sec", None),
    "svm_sv_int8": ("svm", "samples_per_sec", None),
    "svm_x_bf16": ("svm", "samples_per_sec", None),
    "wdamds_coord_bf16": ("wdamds", "iters_per_sec", None),
    "wdamds_coord_int8": ("wdamds", "iters_per_sec", None),
    "wdamds_delta_bf16": ("wdamds", "iters_per_sec", None),
    "rf_dense_hist": ("rf_scatter_hist", "trees_per_sec", None),
    # PR 17: the kernelized arms — priced from birth (presize-predicted
    # tiles, no silicon rows yet, so they report "unmeasured" until a
    # chip run measures their flip candidates).
    "svm_kernel_pallas": ("svm", "samples_per_sec", None),
    "wdamds_dist_pallas": ("wdamds", "iters_per_sec", None),
    "rf_hist_pallas": ("rf_dense_hist", "trees_per_sec", None),
    "subgraph_csr32": ("subgraph", "vertices_per_sec", None),
    "subgraph_onehot": ("subgraph_pl", "vertices_per_sec", None),
    "subgraph_1m_onehot": ("subgraph_1m", "vertices_per_sec", None),
}

#: the committed knob sweeps: name -> (config, knob, [(value, measured
#: rate)]).  The kmeans int8 points are the OOM-window sweep recorded
#: in ops/kmeans_kernel._tile_rows_int8's docstring (2026-08-01, 1M×300
#: k=100, 1× v5e); the MF-SGD/LDA tile points are cross-checked against
#: the committed SWEEP_pallas.jsonl rows by load_sweep_points.
SWEEPS = {
    "kmeans_int8_tile": ("kmeans_int8_fused",
                         [({"tile": 8000}, 557.9), ({"tile": 4000}, 537.2),
                          ({"tile": 2000}, 521.5),
                          ({"tile": 1000}, 464.9)]),
    "mfsgd_pallas_tile": ("mfsgd_pallas",
                          [({"tile": 256}, 250233874.8),
                           ({"tile": 512}, 195512085.3),
                           ({"tile": 1024}, 163255187.4),
                           ({"tile": 128}, 147271764.4)]),
    "lda_pallas_tile": ("lda_pallas",
                        [({"d_tile": 512, "w_tile": 512}, 8018332.5),
                         ({"d_tile": 256, "w_tile": 256}, 4559994.0)]),
}


def latest_tpu_rows(path: str) -> dict:
    """config -> last full-shape non-error TPU row (CPU-sim speeds are
    explicitly non-predictive of TPU here and must not grade the
    model)."""
    rows: dict = {}
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
                cfg = row.get("config")
                if (not cfg or row.get("smoke") or "error" in row
                        or row.get("backend") == "cpu"):
                    continue
                rows[cfg] = row
    except OSError:
        pass
    return rows


def flip_verdicts(path: str) -> dict:
    """flip_decision name -> verdict row (FLIP_DECISIONS.jsonl)."""
    out: dict = {}
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
                if "flip_decision" in row:
                    out[row["flip_decision"]] = row
    except OSError:
        pass
    return out


def load_sweep_points(repo: str) -> dict:
    """The declared SWEEPS, with the tile points cross-checked against
    the committed SWEEP_pallas.jsonl rows: a declared point that
    disagrees with the file it cites is itself a grading failure."""
    sweeps = {k: (cfg, list(pts)) for k, (cfg, pts) in SWEEPS.items()}
    path = os.path.join(repo, "SWEEP_pallas.jsonl")
    measured: dict = {}
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
                t = row.get("tile")
                if t is None:
                    continue
                if row.get("updates_per_sec_per_chip") is not None:
                    measured[("mfsgd_pallas_tile", t)] = float(
                        row["updates_per_sec_per_chip"])
                elif row.get("tokens_per_sec_per_chip") is not None:
                    measured[("lda_pallas_tile", t)] = float(
                        row["tokens_per_sec_per_chip"])
    except OSError:
        pass
    errors = []
    for name in ("mfsgd_pallas_tile", "lda_pallas_tile"):
        for knobs, rate in sweeps[name][1]:
            v = knobs.get("tile") or knobs.get("d_tile")
            got = measured.get((name, v))
            if got is not None and abs(got - rate) > 0.01 * rate:
                errors.append(f"{name} tile={v}: declared {rate} but "
                              f"SWEEP_pallas.jsonl says {got}")
    return {"sweeps": sweeps, "errors": errors}


def spearman(xs, ys) -> float:
    """Spearman rank correlation (no-ties case — knob sweeps)."""
    def ranks(vals):
        order = sorted(range(len(vals)), key=lambda i: vals[i])
        r = [0] * len(vals)
        for rank_, i in enumerate(order):
            r[i] = rank_
        return r

    rx, ry = ranks(xs), ranks(ys)
    n = len(xs)
    if n < 2:
        return 1.0
    d2 = sum((a - b) ** 2 for a, b in zip(rx, ry))
    return 1.0 - 6.0 * d2 / (n * (n * n - 1))


def _metric_value(row, metric, fallback):
    v = row.get(metric)
    if v is None and fallback:
        v = row.get(fallback)
    return float(v) if v is not None else None


def grade(repo: str | None = None, topo=None) -> dict:
    """Run all three checks against the committed evidence files."""
    if repo is None:
        repo = os.getcwd()
    if topo is None:
        from harp_tpu.plan.topology import single_chip

        topo = single_chip()  # every committed row is 1× v5e
    bench = latest_tpu_rows(os.path.join(repo, "BENCH_local.jsonl"))
    verdicts = flip_verdicts(os.path.join(repo, "FLIP_DECISIONS.jsonl"))
    report = {"ok": True, "pairs": [], "sweeps": [], "magnitude": [],
              "failures": []}

    def fail(msg, **detail):
        report["ok"] = False
        report["failures"].append({"what": msg, **detail})

    # 1. family ranking ----------------------------------------------------
    for cand, (inc, metric, fb) in sorted(FAMILY_PAIRS.items()):
        crow, irow = bench.get(cand), bench.get(inc)
        entry = {"candidate": cand, "incumbent": inc}
        if crow is None or irow is None:
            entry["status"] = "unmeasured"
            report["pairs"].append(entry)
            continue
        cv, iv = (_metric_value(crow, metric, fb),
                  _metric_value(irow, metric, fb))
        if not cv or not iv:
            entry["status"] = "unmeasured"
            report["pairs"].append(entry)
            continue
        measured = cv / iv
        pc, pi = price(cand, crow, topo), price(inc, irow, topo)
        predicted = pi.predicted_s / pc.predicted_s
        entry.update({"measured": round(measured, 4),
                      "predicted": round(predicted, 4),
                      "candidate_terms": pc.terms(),
                      "incumbent_terms": pi.terms()})
        if abs(measured - 1.0) <= DEAD_BAND:
            entry["status"] = "too_close"
        elif (measured > 1.0) == (predicted > 1.0):
            entry["status"] = "agrees"
        else:
            entry["status"] = "DISAGREES"
            fail(f"ranking: {cand} vs {inc} measured {measured:.3f}x "
                 f"but model predicts {predicted:.3f}x", pair=entry)
        # a measured FLIP the model would have pruned is the costly
        # failure mode — check it even when the pair re-derives it
        v = verdicts.get(cand)
        if v is not None and v.get("flip") and predicted < 1.0:
            entry["status"] = "DISAGREES"
            fail(f"verdict: {cand} FLIPPED on silicon "
                 f"({v.get('speedup')}x) but the model predicts "
                 f"{predicted:.3f}x — pruning would have dropped a "
                 "measured winner", pair=entry)
        report["pairs"].append(entry)

    # 2. sweep rank correlation --------------------------------------------
    loaded = load_sweep_points(repo)
    for err in loaded["errors"]:
        fail(f"sweep points drifted from their committed file: {err}")
    for name, (cfg, pts) in sorted(loaded["sweeps"].items()):
        meas = [r for _, r in pts]
        pred = [price(cfg, knobs, topo).predicted_rate
                for knobs, _ in pts]
        rho = spearman(meas, pred)
        entry = {"sweep": name, "config": cfg, "points": len(pts),
                 "rho": round(rho, 4),
                 "measured_rates": meas, "predicted_rates":
                 [round(p, 2) for p in pred]}
        report["sweeps"].append(entry)
        if rho < RANK_FLOOR:
            fail(f"sweep {name}: rho {rho:.3f} < floor {RANK_FLOOR}",
                 sweep=entry)

    # 3. magnitude band ----------------------------------------------------
    for cfg, row in sorted(bench.items()):
        # *_cli rows (the app CLIs' committed 2026-08-01 evidence) grade
        # through their incumbent's model (PR 16)
        cfg_model = M.CLI_ROW_ALIASES.get(cfg, cfg)
        if cfg_model not in M.CONFIG_MODELS:
            continue
        p = price(cfg_model, row, topo)
        mv = _metric_value(row, p.metric, None)
        if mv is None or mv <= 0:
            continue
        factor = max(p.predicted_rate / mv, mv / p.predicted_rate)
        entry = {"config": cfg, "measured": round(mv, 2),
                 "predicted": round(p.predicted_rate, 2),
                 "factor": round(factor, 2)}
        report["magnitude"].append(entry)
        if factor > MAGNITUDE_TOL:
            fail(f"magnitude: {cfg} predicted {p.predicted_rate:.3g} vs "
                 f"measured {mv:.3g} ({factor:.0f}x off > "
                 f"{MAGNITUDE_TOL}x)", row=entry,
                 terms=p.terms())
    return report


def price(config, row, topo):
    """Module-level alias (kept here so grade-side callers and tests
    monkeypatch one surface)."""
    return M.price(config, row, topo)
