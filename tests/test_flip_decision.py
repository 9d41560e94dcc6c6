"""The ≥10%-at-equal-quality flip gate (VERDICT r3 weak #5 / next #6).

BASELINE.md's decision rule — "a candidate that wins ≥10% at equal
quality becomes the default" — must live in code: a fast-but-degraded
kernel may never flip a default silently, and missing quality evidence
must refuse the flip (fail closed), not pass it.
"""

import importlib.util
import json
import os
import sys

import pytest

_SPEC = importlib.util.spec_from_file_location(
    "flip_decision", os.path.join(os.path.dirname(__file__), "..",
                                  "scripts", "flip_decision.py"))
fd = importlib.util.module_from_spec(_SPEC)
_SPEC.loader.exec_module(fd)

MFSGD_SPEC = fd.CANDIDATES["mfsgd_pallas"]
LDA_SPEC = fd.CANDIDATES["lda_pallas"]
SG_SPEC = fd.CANDIDATES["subgraph_onehot"]


def test_flips_at_ten_percent_and_equal_quality():
    v = fd.decide({"updates_per_sec_per_chip": 120e6, "rmse_final": 0.366},
                  {"updates_per_sec_per_chip": 92.7e6, "rmse_final": 0.366},
                  MFSGD_SPEC)
    assert v["flip"] and v["quality_ok"]
    assert v["speedup"] == pytest.approx(120 / 92.7, rel=1e-3)
    assert "MFSGDConfig" in v["reason"]


def test_refuses_degraded_quality_regardless_of_speed():
    # 2x faster but rmse 10% worse → the gate must refuse
    v = fd.decide({"updates_per_sec_per_chip": 200e6, "rmse_final": 0.403},
                  {"updates_per_sec_per_chip": 92.7e6, "rmse_final": 0.366},
                  MFSGD_SPEC)
    assert not v["flip"] and v["quality_ok"] is False
    assert "QUALITY DEGRADED" in v["reason"]


def test_keeps_incumbent_below_threshold():
    v = fd.decide({"updates_per_sec_per_chip": 97e6, "rmse_final": 0.366},
                  {"updates_per_sec_per_chip": 92.7e6, "rmse_final": 0.366},
                  MFSGD_SPEC)
    assert not v["flip"] and v["quality_ok"]
    assert "keep incumbent" in v["reason"]


def test_fails_closed_on_missing_quality_field():
    v = fd.decide({"updates_per_sec_per_chip": 200e6},
                  {"updates_per_sec_per_chip": 92.7e6, "rmse_final": 0.366},
                  MFSGD_SPEC)
    assert not v["flip"] and v["quality_ok"] is None
    assert "fails closed" in v["reason"]


def test_fails_closed_on_missing_or_error_rows():
    good = {"updates_per_sec_per_chip": 92.7e6, "rmse_final": 0.366}
    assert not fd.decide(None, good, MFSGD_SPEC)["flip"]
    assert not fd.decide(good, {"error": "hang"}, MFSGD_SPEC)["flip"]


def test_log_likelihood_sense_handles_negative_values():
    # LL is negative; "higher" means closer to zero.  Candidate 0.02 nats
    # better → flip; 0.2 nats worse → refuse.
    inc = {"tokens_per_sec_per_chip": 6.58e6, "log_likelihood": -9.10}
    better = {"tokens_per_sec_per_chip": 8.0e6, "log_likelihood": -9.08}
    worse = {"tokens_per_sec_per_chip": 8.0e6, "log_likelihood": -9.30}
    assert fd.decide(better, inc, LDA_SPEC)["flip"]
    v = fd.decide(worse, inc, LDA_SPEC)
    assert not v["flip"] and v["quality_ok"] is False


def test_subgraph_estimates_match_within_order_drift():
    # rel_tol 1e-3 (round 5): the two formulations reorder an f32 sum
    # whose value exceeds 2^24, so ~3.7e-4 rel drift was MEASURED on
    # silicon between correct implementations (2026-08-01); a real
    # counting bug (dropped overflow edges) moves the estimate by
    # percents and must still refuse
    inc = {"vertices_per_sec": 117.3e3, "estimate": 4.37e18}
    same = {"vertices_per_sec": 150e3, "estimate": 4.37e18 * (1 + 3.7e-4)}
    diff = {"vertices_per_sec": 150e3, "estimate": 4.37e18 * 1.01}
    assert fd.decide(same, inc, SG_SPEC)["flip"]
    assert not fd.decide(diff, inc, SG_SPEC)["flip"]


def test_stream_metric_falls_back_to_end_to_end_rate():
    spec = fd.CANDIDATES["kmeans_stream_int8"]
    inc = {"iters_per_sec": 0.53, "iters_per_sec_ex_gen": 1.09,
           "inertia": 2.9e10}
    cand = {"iters_per_sec": 0.9, "iters_per_sec_ex_gen": 2.2,
            "inertia": 2.9e10}
    v = fd.decide(cand, inc, spec)
    assert v["speedup"] == pytest.approx(2.2 / 1.09, rel=1e-3)
    # ex_gen absent on both → falls back to end-to-end
    v2 = fd.decide({k: v_ for k, v_ in cand.items() if k != "iters_per_sec_ex_gen"},
                   {k: v_ for k, v_ in inc.items() if k != "iters_per_sec_ex_gen"},
                   spec)
    assert v2["speedup"] == pytest.approx(0.9 / 0.53, rel=1e-3)


def test_stream_metric_refuses_mixed_basis():
    # ADVICE r4: ex_gen on only ONE side would divide an ex-gen rate by an
    # end-to-end rate, overstating the speedup — must refuse, both ways.
    spec = fd.CANDIDATES["kmeans_stream_int8"]
    with_ex = {"iters_per_sec": 0.9, "iters_per_sec_ex_gen": 2.2,
               "inertia": 2.9e10}
    without = {"iters_per_sec": 0.53, "inertia": 2.9e10}
    for cand, inc in ((with_ex, without), (without, with_ex)):
        v = fd.decide(cand, inc, spec)
        assert not v["flip"] and v["speedup"] is None
        assert "mixed" in v["reason"]


def test_latest_rows_last_full_shape_non_error_wins(tmp_path):
    p = tmp_path / "bench.jsonl"
    p.write_text("\n".join([
        json.dumps({"config": "mfsgd", "updates_per_sec_per_chip": 1.0}),
        "{'config': 'subgraph_cli'}",  # old dict-repr tee line: skipped
        json.dumps({"config": "mfsgd", "updates_per_sec_per_chip": 2.0}),
        json.dumps({"config": "mfsgd", "smoke": True,
                    "updates_per_sec_per_chip": 99.0}),
        json.dumps({"config": "mfsgd", "error": "hang"}),
        # CPU-sim relative speeds are non-predictive of TPU (the repo's
        # own onehot 7.8x CPU inversion) — must never authorize a flip
        json.dumps({"config": "mfsgd", "backend": "cpu",
                    "updates_per_sec_per_chip": 500.0}),
    ]) + "\n")
    rows = fd.latest_rows(str(p))
    assert rows["mfsgd"]["updates_per_sec_per_chip"] == 2.0


def test_cli_exits_nonzero_when_undecidable(tmp_path, capsys):
    p = tmp_path / "bench.jsonl"
    p.write_text(json.dumps(
        {"config": "mfsgd", "updates_per_sec_per_chip": 92.7e6,
         "rmse_final": 0.366}) + "\n")
    rc = fd.main(["--bench", str(p), "--only", "mfsgd_pallas"])
    assert rc == 1  # candidate row missing → undecidable → nonzero
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1
    rec = json.loads(out[0])
    assert rec["flip_decision"] == "mfsgd_pallas" and not rec["flip"]


def test_cli_decides_all_candidates_when_rows_present(tmp_path, capsys):
    rows = [
        {"config": "mfsgd", "updates_per_sec_per_chip": 92.7e6,
         "rmse_final": 0.366},
        {"config": "mfsgd_pallas", "updates_per_sec_per_chip": 140e6,
         "rmse_final": 0.3661},
    ]
    p = tmp_path / "bench.jsonl"
    p.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    rc = fd.main(["--bench", str(p), "--only", "mfsgd_pallas"])
    assert rc == 0
    rec = json.loads(capsys.readouterr().out.strip())
    assert rec["flip"] and rec["quality_ok"]


def test_sprint_order_prices_scarcity():
    """VERDICT r4 weak #3: the sweep must measure every flip candidate
    BEFORE the first incumbent re-measure, and every name the gate needs
    (candidates + incumbents) must actually be in the sweep — a short
    chip run then yields verdicts, not re-confirmations."""
    spec = importlib.util.spec_from_file_location(
        "measure_all", os.path.join(os.path.dirname(__file__), "..",
                                    "scripts", "measure_all.py"))
    ma = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ma)
    order = ma.SPRINT_ORDER
    boundary = order.index(ma.FIRST_REMEASURE)
    for name, cspec in fd.CANDIDATES.items():
        assert name in order, name
        assert cspec["incumbent"] in order, cspec["incumbent"]
        assert order.index(name) < boundary, (
            f"{name} must run before the re-measure block")
    # host-bound ingest pair stays last (f16 then its int8-wire twin)
    assert order[-2:] == ["kmeans_ingest", "kmeans_ingest_int8"]


def test_joint_gate_vetoes_half_passed_knob(tmp_path, capsys):
    # the pallas_exact_gathers knob has TWO gates (default-shape speed,
    # hot-count LL); a FLIP line may only print if BOTH flip — prose in
    # the 'flips' string is not enforcement (review finding, round 5)
    rows = [
        {"config": "lda_pallas", "tokens_per_sec_per_chip": 6e6,
         "log_likelihood": -9.1},
        {"config": "lda_pallas_approx", "tokens_per_sec_per_chip": 7.5e6,
         "log_likelihood": -9.1},     # 1.25x at equal quality: flips
        {"config": "lda_pallas_hot", "tokens_per_sec_per_chip": 6e6,
         "log_likelihood": -7.0},
        {"config": "lda_pallas_approx_hot",
         "tokens_per_sec_per_chip": 7.5e6,
         "log_likelihood": -7.3},     # LL degraded: refuses
    ]
    p = tmp_path / "bench.jsonl"
    p.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    fd.main(["--bench", str(p),
             "--only", "lda_pallas_approx", "lda_pallas_approx_hot"])
    out = {json.loads(ln)["flip_decision"]: json.loads(ln)
           for ln in capsys.readouterr().out.strip().splitlines()}
    assert not out["lda_pallas_approx_hot"]["flip"]
    assert not out["lda_pallas_approx"]["flip"]          # vetoed
    assert "joint gate" in out["lda_pallas_approx"]["reason"]
    # an operator grepping for the FLIP: marker must not match a veto
    assert "FLIP:" not in out["lda_pallas_approx"]["reason"]
    # both flipping → the joint gate lets them through
    rows[3]["log_likelihood"] = -7.0
    p.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    rc = fd.main(["--bench", str(p),
                  "--only", "lda_pallas_approx", "lda_pallas_approx_hot"])
    assert rc == 0
    out = {json.loads(ln)["flip_decision"]: json.loads(ln)
           for ln in capsys.readouterr().out.strip().splitlines()}
    assert out["lda_pallas_approx"]["flip"]
    assert out["lda_pallas_approx_hot"]["flip"]


def test_subgraph_joint_gate_requires_both_scales(tmp_path, capsys):
    # overflow_algo flips only when onehot wins at BOTH the controlled
    # powerlaw shape and the graded 1M scale (round 5)
    rows = [
        {"config": "subgraph_pl", "vertices_per_sec": 100e3,
         "estimate": 1.0e12},
        {"config": "subgraph_onehot", "vertices_per_sec": 130e3,
         "estimate": 1.0e12},          # wins off-scale
        {"config": "subgraph_1m", "vertices_per_sec": 110e3,
         "estimate": 4.0e18},
        {"config": "subgraph_1m_onehot", "vertices_per_sec": 112e3,
         "estimate": 4.0e18},          # <10% at graded scale
    ]
    p = tmp_path / "bench.jsonl"
    p.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    fd.main(["--bench", str(p),
             "--only", "subgraph_onehot", "subgraph_1m_onehot"])
    out = {json.loads(ln)["flip_decision"]: json.loads(ln)
           for ln in capsys.readouterr().out.strip().splitlines()}
    assert not out["subgraph_onehot"]["flip"]      # vetoed by the pair
    assert not out["subgraph_1m_onehot"]["flip"]
    assert "FLIP:" not in out["subgraph_onehot"]["reason"]


def test_joint_gate_fails_closed_under_only(tmp_path, capsys):
    # --only with ONE half of a gated pair must still evaluate the
    # partner and veto when it refuses — selection must not bypass the
    # gate (fail open, review finding round 5)
    rows = [
        {"config": "subgraph_pl", "vertices_per_sec": 100e3,
         "estimate": 1.0e12},
        {"config": "subgraph_onehot", "vertices_per_sec": 130e3,
         "estimate": 1.0e12},  # wins — but the 1M half has no rows
    ]
    p = tmp_path / "bench.jsonl"
    p.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    fd.main(["--bench", str(p), "--only", "subgraph_onehot"])
    out = [json.loads(ln)
           for ln in capsys.readouterr().out.strip().splitlines()]
    assert len(out) == 1  # the partner is evaluated, not printed
    assert out[0]["flip_decision"] == "subgraph_onehot"
    assert not out[0]["flip"]
    assert "FLIP:" not in out[0]["reason"]


def test_exclusive_gate_keeps_only_the_faster(tmp_path, capsys):
    # both mfsgd candidates pass: applying both would crash
    # MFSGDConfig's own validation — only the faster prints FLIP
    rows = [
        {"config": "mfsgd", "updates_per_sec_per_chip": 92.7e6,
         "rmse_final": 0.366},
        {"config": "mfsgd_pallas", "updates_per_sec_per_chip": 150e6,
         "rmse_final": 0.366},
        {"config": "mfsgd_carry", "updates_per_sec_per_chip": 120e6,
         "rmse_final": 0.366},
    ]
    p = tmp_path / "bench.jsonl"
    p.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    fd.main(["--bench", str(p), "--only", "mfsgd_pallas", "mfsgd_carry"])
    out = {json.loads(ln)["flip_decision"]: json.loads(ln)
           for ln in capsys.readouterr().out.strip().splitlines()}
    assert out["mfsgd_pallas"]["flip"]
    assert not out["mfsgd_carry"]["flip"]
    assert "exclusive" in out["mfsgd_carry"]["reason"]
    assert "FLIP:" not in out["mfsgd_carry"]["reason"]


def test_conditional_gate_binds_carry_to_its_stack(tmp_path, capsys):
    # lda_carry's evidence is the DENSE stack: if lda_pallas flips the
    # default algo, lda_carry's row no longer describes the default and
    # must not print FLIP (lda_pallas_carry's would instead)
    rows = [
        {"config": "lda", "tokens_per_sec_per_chip": 6.58e6,
         "log_likelihood": -9.1},
        {"config": "lda_pallas", "tokens_per_sec_per_chip": 9e6,
         "log_likelihood": -9.1},   # flips the algo
        {"config": "lda_carry", "tokens_per_sec_per_chip": 7.5e6,
         "log_likelihood": -9.1},   # passed, but on the dense stack
    ]
    p = tmp_path / "bench.jsonl"
    p.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    fd.main(["--bench", str(p), "--only", "lda_carry"])
    out = [json.loads(ln)
           for ln in capsys.readouterr().out.strip().splitlines()]
    assert len(out) == 1 and not out[0]["flip"]
    assert "conditional" in out[0]["reason"]
    # and with lda_pallas NOT flipping, lda_carry's flip stands
    rows[1]["tokens_per_sec_per_chip"] = 6.6e6  # <10%: no algo flip
    p.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    fd.main(["--bench", str(p), "--only", "lda_carry"])
    out = [json.loads(ln)
           for ln in capsys.readouterr().out.strip().splitlines()]
    assert out[0]["flip"], out


def test_unmeasured_gate_partner_counts_as_undecidable(tmp_path, capsys):
    # exit 1 is the "rerun the benches" signal; a veto caused by a
    # MISSING partner row must carry it even though the partner's own
    # line never prints (round 5)
    rows = [
        {"config": "subgraph_pl", "vertices_per_sec": 100e3,
         "estimate": 1.0e12},
        {"config": "subgraph_onehot", "vertices_per_sec": 130e3,
         "estimate": 1.0e12},
    ]
    p = tmp_path / "bench.jsonl"
    p.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    rc = fd.main(["--bench", str(p), "--only", "subgraph_onehot"])
    capsys.readouterr()
    assert rc == 1  # the 1M partner is unmeasured -> undecidable


def test_conditional_gate_vetoes_on_unmeasured_anchor(tmp_path, capsys):
    # requires_not must NOT read an unmeasured anchor as "does not
    # flip" — carry applied on the dense stack today could be off-stack
    # evidence after the next sprint flips the algo (round 5)
    rows = [
        {"config": "lda", "tokens_per_sec_per_chip": 6.58e6,
         "log_likelihood": -9.1},
        {"config": "lda_carry", "tokens_per_sec_per_chip": 7.5e6,
         "log_likelihood": -9.1},
        # no lda_pallas row at all (e.g. the sprint --skip'd pallas)
    ]
    p = tmp_path / "bench.jsonl"
    p.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    rc = fd.main(["--bench", str(p), "--only", "lda_carry"])
    out = [json.loads(ln)
           for ln in capsys.readouterr().out.strip().splitlines()]
    assert rc == 1                       # rerun-the-benches signal
    assert not out[0]["flip"]
    assert "UNMEASURED" in out[0]["reason"]
    assert "FLIP:" not in out[0]["reason"]


def test_applied_flips_match_committed_verdicts():
    """The gate's contract: an authorized FLIP line is APPLIED (defaults
    follow verdicts, same commit).  This pins the coupling so an
    accidental default revert — or a FLIP line committed unapplied —
    fails loudly.  Reads the committed FLIP_DECISIONS.jsonl (round-5
    window verdicts, 2026-08-01)."""
    path = os.path.join(os.path.dirname(__file__), "..",
                        "FLIP_DECISIONS.jsonl")
    verdicts = {}
    with open(path) as f:
        for line in f:
            r = json.loads(line)
            verdicts[r["flip_decision"]] = r["flip"]
    # the five round-5 flips (lda_fast's edit is subsumed by lda_pallas)
    assert verdicts["mfsgd_pallas"] and verdicts["lda_pallas"]
    assert verdicts["lda_pallas_carry"] and verdicts["lda_fast"]
    assert verdicts["kmeans_int8_fused"]

    from harp_tpu.models.kmeans import KMeansConfig, _use_pallas
    from harp_tpu.models.lda import LDAConfig, carry_db_resolved
    from harp_tpu.models.mfsgd import MFSGDConfig

    assert MFSGDConfig().algo == "pallas"
    lcfg = LDAConfig()
    assert (lcfg.algo, lcfg.sampler, lcfg.rng_impl) == (
        "pallas", "exprace", "rbg")
    # carry_db resolves at READ time (ADVICE r5): None stays stored, the
    # resolver applies the verdict — ON for the pallas stack
    assert carry_db_resolved(lcfg) is True
    assert _use_pallas(KMeansConfig(quantize="int8"))
    # and the VETOED arms stayed un-applied
    assert not verdicts["lda_carry"] and not verdicts["mfsgd_carry"]
    assert carry_db_resolved(LDAConfig(algo="dense")) is False
    assert MFSGDConfig().carry_w is False
    assert not _use_pallas(KMeansConfig())  # f32 arm: XLA stays
