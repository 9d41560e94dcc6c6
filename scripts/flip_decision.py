#!/usr/bin/env python
"""Encode BASELINE.md's default-flip rule: ≥10% faster AT EQUAL QUALITY.

VERDICT r3 weak #5 / next #6: the decision rule existed only as prose —
a fast-but-degraded candidate kernel could become a default with nobody
noticing, because nothing in code compared the candidate's quality field
against the incumbent's.  This module is that comparison.

Each candidate config in CANDIDATES names its incumbent, its throughput
metric, its quality field, the direction quality improves, and the
tolerance inside which the two count as "equal quality".  ``decide``
takes the two measured rows and returns a verdict dict; the CLI reads
BENCH_local.jsonl (last non-error full-shape row per config wins),
prints one verdict JSON line per candidate, and exits 1 if any verdict
could not be computed (missing rows must block the flip, not pass it).

A flip verdict here authorizes the one-line default change listed in
BASELINE.md's candidates table (MFSGDConfig.algo, LDAConfig.sampler/
rng_impl/algo, KMeansConfig.use_pallas, SubgraphConfig.overflow_algo);
the BASELINE.md row and bench.py BASELINES update in the same commit.

Tolerances (stated, per VERDICT "within a stated tolerance"):
- rmse_final (lower better, rel 2%): the pallas kernel replays the dense
  update order, so real parity is ~bit-level; 2% allows accumulation-
  order noise only.
- log_likelihood (higher better, abs 0.05 nats/token): exprace/rbg draw
  from the identical distribution with a different stream; 2-epoch mean
  per-token LL jitters ~0.01 across seeds, while a biased sampler (e.g.
  the bf16-count rounding ADVICE r3 flags) shows up well above 0.05.
- inertia (lower better, rel 1%): int8 quantization measured 1.2e-4 rel
  on the graded shape (BENCH_local 2026-07-31); 1% is ~100× that.
- estimate (equal, rel 1e-3): segment/onehot reformulate the SAME sum
  over the SAME seed-0 coloring, but in f32 — and at the measured
  shapes the counts (1e16–1e18) are far beyond f32's 2^24 exact range,
  so the two summation ORDERS legitimately round differently (measured
  2026-08-01: 1.3e-4 rel at the powerlaw A/B shape, 3.7e-4 at graded
  1M, opposite signs).  1e-3 is ~3× the worst measured order-drift
  while a real counting bug (dropped overflow edges, wrong tail) moves
  the estimate by percents.  The original 1e-6 ("identical to 7
  digits") was calibrated on small exact-range shapes and can never
  pass at scale — it refused the round-5 A/B on rounding noise.
- train_acc (higher better, abs 0.005).
"""

import argparse
import json
import os
import sys

# candidate → how to judge it (see module doc for tolerance rationale)
CANDIDATES = {
    "mfsgd_pallas": {
        "incumbent": "mfsgd", "metric": "updates_per_sec_per_chip",
        "quality": "rmse_final", "sense": "lower", "rel_tol": 0.02,
        "flips": "MFSGDConfig.algo='pallas'"},
    "mfsgd_carry": {
        "incumbent": "mfsgd", "metric": "updates_per_sec_per_chip",
        "quality": "rmse_final", "sense": "lower", "rel_tol": 0.02,
        "flips": "MFSGDConfig.carry_w=True"},
    # PR 2: the chunked rotator at 4 chunks vs the incumbent 2-chunk
    # schedule, both on the flipped pallas stack.  The visit ORDER
    # changes (4n shorter steps instead of 2n), so rmse_final gates a
    # genuinely different-but-equal chain, not a bit-identical one.
    "mfsgd_chunked_rotate": {
        "incumbent": "mfsgd_pallas", "metric": "updates_per_sec_per_chip",
        "quality": "rmse_final", "sense": "lower", "rel_tol": 0.02,
        "flips": "MFSGDConfig.rotate_chunks=4"},
    "lda_exprace": {
        "incumbent": "lda", "metric": "tokens_per_sec_per_chip",
        "quality": "log_likelihood", "sense": "higher", "abs_tol": 0.05,
        "flips": "LDAConfig.sampler='exprace'"},
    "lda_fast": {
        "incumbent": "lda", "metric": "tokens_per_sec_per_chip",
        "quality": "log_likelihood", "sense": "higher", "abs_tol": 0.05,
        "flips": "LDAConfig.sampler='exprace', rng_impl='rbg'"},
    "lda_pallas": {
        "incumbent": "lda", "metric": "tokens_per_sec_per_chip",
        "quality": "log_likelihood", "sense": "higher", "abs_tol": 0.05,
        "flips": "LDAConfig.algo='pallas'"},
    # the ADVICE-r3 likelihood A/B in gate form: approx (single-dot bf16)
    # gathers may become the kernel default only by beating the exact
    # kernel ≥10% at equal chain likelihood
    "lda_pallas_approx": {
        "incumbent": "lda_pallas", "metric": "tokens_per_sec_per_chip",
        "quality": "log_likelihood", "sense": "higher", "abs_tol": 0.05,
        "flips": "LDAConfig.pallas_exact_gathers=False (ALSO requires the "
                 "lda_pallas_approx_hot LL gate)"},
    # VERDICT r4 item 7: the same knob gated at a >256-count shape where
    # bf16 gather rounding CAN show in the LL (default sweep counts are
    # double-digit — there the quality gate passes vacuously).  The knob
    # flips only if BOTH this and lda_pallas_approx say flip.
    "lda_pallas_approx_hot": {
        "incumbent": "lda_pallas_hot", "metric": "tokens_per_sec_per_chip",
        "quality": "log_likelihood", "sense": "higher", "abs_tol": 0.05,
        "flips": "LDAConfig.pallas_exact_gathers=False (hot-count LL gate; "
                 "flip only together with lda_pallas_approx)"},
    # VERDICT r3 item 2's Db-carry, bit-identical chain by construction
    # (same tile cores, tested) — the gate still demands the quality
    # field so a broken carry can't slip through on speed alone
    "lda_carry": {
        "incumbent": "lda", "metric": "tokens_per_sec_per_chip",
        "quality": "log_likelihood", "sense": "higher", "abs_tol": 0.05,
        "flips": "LDAConfig.carry_db=True"},
    "lda_pallas_carry": {
        "incumbent": "lda_pallas", "metric": "tokens_per_sec_per_chip",
        "quality": "log_likelihood", "sense": "higher", "abs_tol": 0.05,
        "flips": "LDAConfig.carry_db=True (pallas stack)"},
    # PR 2: int8 rotate wire vs the exact wire on the SAME default stack
    # (pallas+carry).  The narrow wire perturbs the word-topic counts a
    # chunk carries (≤ global_max/254 per element per hop), so the LL
    # gate is load-bearing here, not a formality — a degraded chain must
    # refuse the flip no matter the wire-byte saving.
    "lda_rotate_int8": {
        "incumbent": "lda_pallas_carry", "metric": "tokens_per_sec_per_chip",
        "quality": "log_likelihood", "sense": "higher", "abs_tol": 0.05,
        "flips": "LDAConfig.rotate_wire='int8'"},
    # PR 11: the planner-named bf16 reshard wire — same incumbent and
    # gate as the int8 twin (rotate_wire is ONE knob: the pair is
    # EXCLUSIVE below), half the ring bytes at one bf16 rounding per
    # hop.  The Plan row (python -m harp_tpu plan) prices this site;
    # only this gate can flip it.
    "lda_planner_wire": {
        "incumbent": "lda_pallas_carry", "metric": "tokens_per_sec_per_chip",
        "quality": "log_likelihood", "sense": "higher", "abs_tol": 0.05,
        "flips": "LDAConfig.rotate_wire='bf16'"},
    # PR 11: the planner's hierarchical two-stage psum on the graded
    # kmeans shape.  Quality gates on inertia at the int8 candidates'
    # tolerance: the two-stage reduce only reassociates float sums —
    # orders of magnitude below 1% — so a miss here means a broken
    # schedule, not noise.  A flat-ring measurement SHOULD read ~1.0x
    # and refuse; the flip is expected only from a multi-host window.
    "kmeans_hier_psum": {
        "incumbent": "kmeans", "metric": "iters_per_sec",
        "quality": "inertia", "sense": "lower", "rel_tol": 0.01,
        "flips": "KMeansConfig.psum_schedule='hier'"},
    # PR 8: the quantized gradient wire (ROADMAP decision-machinery
    # item; EQuARX-style bf16/int8 allreduce).  train_acc gates per the
    # module-doc tolerance (abs 0.005): a wire that degrades training
    # must refuse no matter the byte saving.  The pair is EXCLUSIVE
    # below — grad_wire has one default slot.
    "mlp_grad_bf16": {
        "incumbent": "mlp", "metric": "samples_per_sec",
        "quality": "train_acc", "sense": "higher", "abs_tol": 0.005,
        "flips": "MLPConfig.grad_wire='bf16'"},
    "mlp_grad_int8": {
        "incumbent": "mlp", "metric": "samples_per_sec",
        "quality": "train_acc", "sense": "higher", "abs_tol": 0.005,
        "flips": "MLPConfig.grad_wire='int8'"},
    # PR 12: the last per-app wires (planner-named; see
    # plan.planner.FLIP_CANDIDATE_CONFIGS).  svm gates on train_acc at
    # the mlp grad-wire tolerance — a quantized SV exchange that
    # degrades the ensemble must refuse.  wdamds gates on final_stress
    # (lower better) at the kernels' 2% band: SMACOF is a contraction,
    # so surviving wire noise shows as a small stress offset while a
    # broken exchange moves it by large factors.  Both pairs EXCLUSIVE
    # below (one wire slot per knob).
    "svm_sv_bf16": {
        "incumbent": "svm", "metric": "samples_per_sec",
        "quality": "train_acc", "sense": "higher", "abs_tol": 0.005,
        "flips": "SVMConfig.sv_wire='bf16'"},
    "svm_sv_int8": {
        "incumbent": "svm", "metric": "samples_per_sec",
        "quality": "train_acc", "sense": "higher", "abs_tol": 0.005,
        "flips": "SVMConfig.sv_wire='int8'"},
    "wdamds_coord_bf16": {
        "incumbent": "wdamds", "metric": "iters_per_sec",
        "quality": "final_stress", "sense": "lower", "rel_tol": 0.02,
        "flips": "MDSConfig.coord_wire='bf16'"},
    "wdamds_coord_int8": {
        "incumbent": "wdamds", "metric": "iters_per_sec",
        "quality": "final_stress", "sense": "lower", "rel_tol": 0.02,
        "flips": "MDSConfig.coord_wire='int8'"},
    "kmeans_int8_fused": {
        "incumbent": "kmeans_int8", "metric": "iters_per_sec",
        "quality": "inertia", "sense": "lower", "rel_tol": 0.01,
        "flips": "KMeansConfig.use_pallas=True (int8 path)"},
    "kmeans_stream_int8": {
        "incumbent": "kmeans_stream",
        # prefer the ex-gen rate when present (same rule as roofline.py:
        # synthetic chunk generation is scaffolding outside the work model)
        "metric": "iters_per_sec_ex_gen", "metric_fallback": "iters_per_sec",
        "quality": "inertia", "sense": "lower", "rel_tol": 0.01,
        "flips": "kmeans_stream default quantize='int8'"},
    # incumbent is the POWERLAW segment twin (subgraph_pl), not the
    # uniform graded config — the uniform graph's overflow share is ~0,
    # so comparing against it would read 1.0x at any truth
    "subgraph_onehot": {
        "incumbent": "subgraph_pl", "metric": "vertices_per_sec",
        "quality": "estimate", "sense": "equal", "rel_tol": 1e-3,
        "flips": "SubgraphConfig.overflow_algo='onehot'"},
    "subgraph_1m_onehot": {
        "incumbent": "subgraph_1m", "metric": "vertices_per_sec",
        "quality": "estimate", "sense": "equal", "rel_tol": 1e-3,
        "flips": "SubgraphConfig.overflow_algo='onehot' (graded scale)"},
    # PR 16: one flip candidate per app the attribution observatory
    # newly priced.  rf's pair makes CLAUDE.md's 25 GB/s scatter-wall
    # claim a measured verdict on THIS app (the dense one-hot MXU
    # histogram vs the scatter arm — same counts bit-identically, so
    # train_acc gates a genuinely equal chain); the svm/wdamds dtype
    # knobs halve the H2D staging the profile pass named as their
    # walls; subgraph_csr32 halves the padded-CSR ship on the graded
    # uniform shape (Poisson(16) degrees rarely exceed 32 — the
    # overflow path absorbs the tail, so estimate must hold).
    "rf_dense_hist": {
        "incumbent": "rf_scatter_hist", "metric": "trees_per_sec",
        "quality": "train_acc", "sense": "higher", "abs_tol": 0.005,
        "flips": "RFConfig.hist_algo='dense' (confirms the one-hot MXU "
                 "default against the scatter arm)"},
    "svm_x_bf16": {
        "incumbent": "svm", "metric": "samples_per_sec",
        "quality": "train_acc", "sense": "higher", "abs_tol": 0.005,
        "flips": "SVMConfig.x_dtype='bf16'"},
    "wdamds_delta_bf16": {
        "incumbent": "wdamds", "metric": "iters_per_sec",
        "quality": "final_stress", "sense": "lower", "rel_tol": 0.02,
        "flips": "MDSConfig.delta_dtype='bf16'"},
    "subgraph_csr32": {
        "incumbent": "subgraph", "metric": "vertices_per_sec",
        "quality": "estimate", "sense": "equal", "rel_tol": 1e-3,
        "flips": "subgraph benchmark default max_degree=32 (padded-CSR "
                 "width; the overflow path absorbs the tail)"},
    # PR 17: the kernelized arms of the newly priced half (presized
    # offline, Mosaic-proven via HL201 — no silicon rows yet).  svm
    # gates on train_acc at the wire-knob tolerance: the fused kernel
    # replays the same Pegasos sums, so a miss means a broken fusion.
    # wdamds gates on final_stress at the kernels' 2% band (the fused
    # D/ratio block reassociates float sums only).  rf's kernel is
    # bit-identical to the dense arm by construction (tests assert it),
    # so its incumbent is rf_dense_hist — the arm that HOLDS the
    # hist_algo slot — and the pair is EXCLUSIVE below.
    "svm_kernel_pallas": {
        "incumbent": "svm", "metric": "samples_per_sec",
        "quality": "train_acc", "sense": "higher", "abs_tol": 0.005,
        "flips": "SVMConfig.algo='pallas'"},
    "wdamds_dist_pallas": {
        "incumbent": "wdamds", "metric": "iters_per_sec",
        "quality": "final_stress", "sense": "lower", "rel_tol": 0.02,
        "flips": "MDSConfig.algo='pallas'"},
    "rf_hist_pallas": {
        "incumbent": "rf_dense_hist", "metric": "trees_per_sec",
        "quality": "train_acc", "sense": "higher", "abs_tol": 0.005,
        "flips": "RFConfig.hist_algo='pallas'"},
}

WIN_THRESHOLD = 1.10  # "wins >=10%" half of the rule

# candidate groups flipping the SAME knob: all must flip or none does
# (main() enforces this after per-candidate verdicts).  The subgraph
# pair gates overflow_algo at BOTH the controlled powerlaw A/B shape
# and the graded 1M scale — a knob that wins only off-scale must not
# print a FLIP line (round 5).
JOINT_GATES = [("lda_pallas_approx", "lda_pallas_approx_hot"),
               ("subgraph_onehot", "subgraph_1m_onehot")]

# alternatives for the same default slot: MFSGDConfig rejects
# carry_w=True with algo != "dense" (mfsgd.py __post_init__), so both
# FLIP lines applied together would crash the default config — if both
# pass, only the faster prints a FLIP line.  The grad-wire pair (PR 8)
# is the same shape: MLPConfig.grad_wire is one knob, bf16 and int8
# cannot both be its default.
EXCLUSIVE_GATES = [("mfsgd_pallas", "mfsgd_carry"),
                   ("mlp_grad_bf16", "mlp_grad_int8"),
                   # PR 11: LDAConfig.rotate_wire is one default slot —
                   # the int8 and planner-bf16 wires cannot both hold it
                   ("lda_rotate_int8", "lda_planner_wire"),
                   # PR 12: one wire slot per exchange knob
                   ("svm_sv_bf16", "svm_sv_int8"),
                   ("wdamds_coord_bf16", "wdamds_coord_int8")]

# stack-conditional: carry_db=True is one knob, but the evidence row
# that authorizes it depends on which algo the verdicts make default
CONDITIONAL_GATES = {
    "lda_pallas_carry": ("requires", "lda_pallas"),
    "lda_carry": ("requires_not", "lda_pallas"),
    # PR 17: the rf kernel's evidence row measures pallas against the
    # DENSE arm — it authorizes hist_algo='pallas' only on the stack
    # where dense itself held the slot against scatter (an EXCLUSIVE
    # gate would compare the two speedups raw, but they have different
    # incumbents — dense-vs-scatter would veto a winning pallas flip)
    "rf_hist_pallas": ("requires", "rf_dense_hist"),
}


def _metric_key(candidate_row, incumbent_row, spec):
    """Pick ONE metric key valid for BOTH rows, or None.

    The fallback applies only when BOTH rows lack the primary metric —
    dividing an ex-gen rate by an end-to-end rate (mixed basis) would
    overstate the speedup the gate authorizes (ADVICE r4), so a mixed
    pair refuses like the missing-quality path does.
    """
    primary = spec["metric"]
    has_c = candidate_row.get(primary) is not None
    has_i = incumbent_row.get(primary) is not None
    if has_c and has_i:
        return primary
    fb = spec.get("metric_fallback")
    if fb and not has_c and not has_i:
        return fb
    return None


def decide(candidate_row: dict, incumbent_row: dict, spec: dict) -> dict:
    """Apply the ≥10%-at-equal-quality rule to one candidate/incumbent pair.

    Returns {"flip": bool, "speedup": float|None, "quality_ok": bool|None,
    "reason": str, ...}.  Missing rows, error rows, or a missing quality
    field REFUSE the flip — the gate fails closed.
    """
    out = {"flip": False, "speedup": None, "quality_ok": None}
    for which, row in (("candidate", candidate_row),
                       ("incumbent", incumbent_row)):
        if row is None:
            out["reason"] = f"no measured row for {which} — refusing flip"
            return out
        if "error" in row:
            out["reason"] = f"{which} row is an error record — refusing flip"
            return out
    key = _metric_key(candidate_row, incumbent_row, spec)
    if key is None:
        out["reason"] = (f"metric {spec['metric']} missing or on mixed "
                         "basis across the pair — refusing flip")
        return out
    cv, iv = candidate_row.get(key), incumbent_row.get(key)
    if not cv or not iv:
        out["reason"] = f"metric {key} missing — refusing flip"
        return out
    out["speedup"] = round(float(cv) / float(iv), 4)
    cq, iq = candidate_row.get(spec["quality"]), incumbent_row.get(
        spec["quality"])
    if cq is None or iq is None:
        out["reason"] = (f"quality field {spec['quality']!r} missing — "
                         "refusing flip (gate fails closed)")
        return out
    cq, iq = float(cq), float(iq)
    sense = spec["sense"]
    if sense == "lower":
        ok = cq <= iq * (1.0 + spec["rel_tol"])
    elif sense == "higher":
        ok = cq >= iq - spec["abs_tol"]
    elif sense == "equal":
        ok = abs(cq - iq) <= spec["rel_tol"] * max(abs(iq), 1e-30)
    else:  # pragma: no cover — spec typo
        raise ValueError(f"unknown sense {sense!r}")
    out["quality_ok"] = bool(ok)
    out["quality_candidate"] = cq
    out["quality_incumbent"] = iq
    if not ok:
        out["reason"] = (f"QUALITY DEGRADED: {spec['quality']} "
                         f"{cq:.6g} vs incumbent {iq:.6g} — refusing flip "
                         f"regardless of {out['speedup']:.2f}x speed")
        return out
    if out["speedup"] >= WIN_THRESHOLD:
        out["flip"] = True
        out["reason"] = (f"FLIP: {out['speedup']:.2f}x at equal quality — "
                         f"apply {spec['flips']}")
    else:
        out["reason"] = (f"keep incumbent: {out['speedup']:.2f}x < "
                         f"{WIN_THRESHOLD:.2f}x threshold")
    return out


def latest_rows(path: str) -> dict:
    """config → last full-shape non-error TPU row (later lines win).

    CPU-sim rows are skipped: relative CPU speeds are explicitly non-predictive of TPU here
    (BASELINE.md's onehot-vs-segment 7.8× CPU inversion), so they must
    never authorize a flip.
    """
    rows = {}
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    row = json.loads(line)
                except ValueError:
                    continue  # sprint tee'd a non-JSON line; skip
                cfg = row.get("config")
                if (not cfg or row.get("smoke") or "error" in row
                        or row.get("backend") == "cpu"):
                    continue
                rows[cfg] = row
    except OSError:
        pass
    return rows


def main(argv=None):
    p = argparse.ArgumentParser()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    p.add_argument("--bench", default=os.path.join(repo, "BENCH_local.jsonl"))
    p.add_argument("--only", nargs="+", choices=sorted(CANDIDATES),
                   default=None)
    args = p.parse_args(argv)
    rows = latest_rows(args.bench)
    # evaluate every selected candidate PLUS every gate partner/anchor a
    # selected one depends on — "--only subgraph_onehot" must not bypass
    # the graded-scale half of its joint gate (fail open); partners are
    # evaluated but only selected names print (review finding, round 5)
    selected = set(args.only) if args.only else set(CANDIDATES)
    needed = set(selected)
    for group in JOINT_GATES + EXCLUSIVE_GATES:
        if needed & set(group):
            needed |= set(group)
    for name, (_, anchor) in CONDITIONAL_GATES.items():
        if name in needed:
            needed.add(anchor)
    verdicts = {}
    for name, spec in CANDIDATES.items():
        if name not in needed:
            continue
        verdicts[name] = decide(rows.get(name), rows.get(spec["incumbent"]),
                                spec)
    # gates IN CODE, not prose: "apply the FLIP lines above" must stay
    # safe to follow mechanically (round 5).  Veto reasons must NOT
    # contain the literal "FLIP:" marker — an operator grepping for it
    # must never match a vetoed line.
    # 1. joint: same knob, every gate must flip or none does (an
    #    unevaluated partner counts as refused — fail closed)
    blocked_by_unmeasured = False  # a partner's MISSING rows vetoed a
    #                                selected winner -> exit 1 (rerun)

    def _undecided(v):
        return v["speedup"] is None or v["quality_ok"] is None

    for group in JOINT_GATES:
        present = [n for n in group if n in verdicts]
        if not present:
            continue
        if not all(verdicts[n]["flip"] for n in present):
            for n in present:
                if verdicts[n]["flip"]:
                    verdicts[n]["flip"] = False
                    verdicts[n]["reason"] = (
                        "VETOED by joint gate: this half passed "
                        f"({verdicts[n]['speedup']:.2f}x at equal "
                        "quality) but partner gate(s) "
                        f"{[m for m in present if m != n]} refused; "
                        "the knob flips only if every gate flips")
                    if n in selected and any(
                            _undecided(verdicts[m]) for m in present
                            if m != n):
                        blocked_by_unmeasured = True
    # 2. exclusive: alternatives for the same default slot (applying
    #    both would violate the config's own validation) — keep the
    #    faster, veto the rest
    for group in EXCLUSIVE_GATES:
        flipping = sorted(
            (n for n in group if n in verdicts and verdicts[n]["flip"]),
            key=lambda n: -verdicts[n]["speedup"])
        for n in flipping[1:]:
            verdicts[n]["flip"] = False
            verdicts[n]["reason"] = (
                f"VETOED by exclusive gate: {flipping[0]} also flips and "
                f"is faster ({verdicts[flipping[0]]['speedup']:.2f}x vs "
                f"{verdicts[n]['speedup']:.2f}x); the two knobs cannot "
                "both be defaults")
    # 3. conditional: valid only on the stack the anchor verdict selects.
    #    An UNMEASURED anchor is not a verdict at all — both modes veto
    #    and signal exit 1, else requires_not would fail open (apply
    #    carry on the dense stack, then a later sprint flips the algo
    #    and the applied flip is exactly the off-stack evidence this
    #    gate exists to block — review finding, round 5)
    for name, (mode, anchor) in CONDITIONAL_GATES.items():
        if name not in verdicts or not verdicts[name]["flip"]:
            continue
        av = verdicts.get(anchor)
        if av is None or _undecided(av):
            verdicts[name]["flip"] = False
            verdicts[name]["reason"] = (
                "VETOED by conditional gate: this half passed "
                f"({verdicts[name]['speedup']:.2f}x) but its anchor "
                f"{anchor} is UNMEASURED — measure it, then re-decide")
            if name in selected:
                blocked_by_unmeasured = True
            continue
        if (av["flip"] if mode == "requires" else not av["flip"]):
            continue
        verdicts[name]["flip"] = False
        verdicts[name]["reason"] = (
            "VETOED by conditional gate: this half passed "
            f"({verdicts[name]['speedup']:.2f}x) but applies only when "
            f"{anchor} {'flips' if mode == 'requires' else 'does not flip'}"
            " — which is not the verdict")
    # exit 1 is the "rerun the benches" signal: any SELECTED verdict
    # that could not be computed, or a selected winner vetoed because a
    # gate partner's rows are MISSING (not because the partner measured
    # and refused — that is a genuine, fully-decided refusal).  An
    # unmeasured EXCLUSIVE partner never blocks, so it never signals.
    undecidable = 0
    for name, verdict in verdicts.items():
        if name not in selected:
            continue  # evaluated only as a gate partner
        if _undecided(verdict):
            undecidable += 1
        print(json.dumps({"flip_decision": name,
                          "incumbent": CANDIDATES[name]["incumbent"],
                          **verdict}))
    return 1 if (undecidable or blocked_by_unmeasured) else 0


if __name__ == "__main__":
    sys.exit(main())
