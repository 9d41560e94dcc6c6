"""The predictive performance observatory (harp_tpu/perfmodel, PR 13).

Three contracts, all tier-1:

1. **Self-grading passes on the committed evidence** — the model's
   ranking agrees with every BENCH_local / FLIP_DECISIONS pair and
   SWEEP_pallas sweep it can price (a model edit that drifts from the
   measurements fails HERE, before it can mis-prune a measurement run).
2. **Exported rows are invariant-12 evidence** — kind:"model" rows
   round-trip through scripts/check_jsonl.py, and the frozen
   vocabularies stay in sync.
3. **The kernel registry prices without fallbacks** — every registered
   kernel declares its work model, and the VMEM pre-sizer reproduces
   the tiles the 2026-08-01 window calibrated by hand.
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "scripts"))

import check_jsonl  # noqa: E402

from harp_tpu import perfmodel  # noqa: E402
from harp_tpu.perfmodel import grade as G  # noqa: E402
from harp_tpu.perfmodel import model as M  # noqa: E402


# -- 1. self-grading against the committed evidence -------------------------

def test_grading_passes_on_committed_evidence():
    """THE honesty gate: replay the model against every committed row
    it can price.  A disagreement ships the term breakdown in the
    failure, so a wrong prediction is diagnosable from the test log."""
    report = G.grade(ROOT)
    assert report["ok"], json.dumps(report["failures"], indent=2)
    # the evidence is rich enough to be a real gate, not a vacuous one:
    agreed = [p for p in report["pairs"] if p["status"] == "agrees"]
    assert len(agreed) >= 5, report["pairs"]
    assert len(report["sweeps"]) >= 3
    assert all(s["rho"] >= G.RANK_FLOOR for s in report["sweeps"])
    assert len(report["magnitude"]) >= 15  # priced committed rows


def test_grading_catches_an_inverted_model(monkeypatch):
    """Sabotage: invert one family's mechanism term (pretend the dense
    one-hot traffic is free) — the measured mfsgd_pallas FLIP must now
    disagree and flip ok to False (fail closed, like invariants 1-11)."""
    real = M.price

    def sabotaged(config, row=None, topo=None):
        p = real(config, row, topo)
        if config == "mfsgd":
            # dense suddenly prices as fast as the kernel
            return M.Price(p.config, p.metric, p.compute_s, 1e-12,
                           p.wire_s, p.overhead_s)
        return p

    monkeypatch.setattr(G, "price", sabotaged)
    report = G.grade(ROOT)
    assert not report["ok"]
    assert any("mfsgd_pallas" in f["what"] for f in report["failures"])


def test_measured_flips_are_never_predicted_losers():
    """Every measured FLIP verdict the model can price must be
    predicted at least even — pruning must never have dropped a
    measured winner (the costly failure mode)."""
    verdicts = G.flip_verdicts(os.path.join(ROOT, "FLIP_DECISIONS.jsonl"))
    bench = G.latest_tpu_rows(os.path.join(ROOT, "BENCH_local.jsonl"))
    checked = 0
    for name, v in verdicts.items():
        if not v.get("flip") or name not in M.CONFIG_MODELS:
            continue
        inc = G.FAMILY_PAIRS[name][0]
        shape = bench.get(inc)
        ratio = (M.price(inc, shape).predicted_s
                 / M.price(name, shape).predicted_s)
        assert ratio >= 1.0, (name, ratio)
        checked += 1
    assert checked >= 4  # mfsgd_pallas, lda_fast, lda_pallas, carry, fused


def test_sweep_points_match_their_committed_file():
    loaded = G.load_sweep_points(ROOT)
    assert loaded["errors"] == []


def test_spearman():
    assert G.spearman([1, 2, 3], [10, 20, 30]) == 1.0
    assert G.spearman([1, 2, 3], [30, 20, 10]) == -1.0
    assert abs(G.spearman([1, 2, 3, 4], [1, 2, 4, 3]) - 0.8) < 1e-9


# -- 2. model rows through the checker --------------------------------------

def _topo():
    from harp_tpu.plan.topology import v4_32

    return v4_32()


def test_config_model_rows_are_invariant_12_clean(tmp_path):
    from harp_tpu.utils.flightrec import provenance_stamp

    p = tmp_path / "rows.jsonl"
    with open(p, "w") as f:
        for cfg in sorted(M.CONFIG_MODELS):
            row = M.model_row(M.price(cfg, None, _topo()), _topo(),
                              config=cfg)
            f.write(json.dumps({**row, **provenance_stamp()}) + "\n")
    assert check_jsonl.check_file(str(p)) == []


def test_program_row_from_a_sheet_is_invariant_12_clean(tmp_path):
    from harp_tpu.utils.flightrec import provenance_stamp

    sheet = {"collectives": [
        {"site": "kmeans.py:346", "primitive": "psum",
         "per_shard_bytes": 2120, "amplification": 2}]}
    price = M.price_sheet("kmeans.fit", sheet, _topo())
    assert price.wire_s > 0          # v4_32 has a real wire
    row = M.model_row(price, _topo(), program="kmeans.fit")
    assert row["configs"]            # the sprint configs that run it
    p = tmp_path / "rows.jsonl"
    p.write_text(json.dumps({**row, **provenance_stamp()}) + "\n")
    assert check_jsonl.check_file(str(p)) == []


def test_model_row_terms_sum_and_bound():
    row = M.model_row(M.price("lda", None, _topo()), _topo(),
                      config="lda")
    assert row["predicted_s"] > 0
    assert abs(sum(row["terms"].values()) - row["predicted_s"]) \
        <= 1e-9 * row["predicted_s"]
    assert row["bound"] == max(M.BOUNDS,
                               key=lambda b: row["terms"][f"{b}_s"])


def test_vocabulary_sync():
    """Frozen vocab pins: perfmodel <-> check_jsonl."""
    assert tuple(perfmodel.BOUNDS) == check_jsonl.KNOWN_MODEL_BOUNDS
    assert tuple(perfmodel.RATES_SOURCES) == \
        check_jsonl.KNOWN_MODEL_RATES_SOURCES
    # every priced config and every program-mapped config is a name the
    # checker admits in a model row
    assert set(M.CONFIG_MODELS) <= set(check_jsonl.KNOWN_MODEL_CONFIGS)
    for prog, cfgs in M.PROGRAM_CONFIGS.items():
        assert prog in check_jsonl.KNOWN_LINT_PROGRAMS, prog
        assert set(cfgs) <= set(check_jsonl.KNOWN_MODEL_CONFIGS), prog
    # and the drivers registry maps completely (a new byte-sheeted
    # program must state its configs, even as an explicit ())
    from harp_tpu.analysis.drivers import DRIVERS

    assert set(M.PROGRAM_CONFIGS) == set(DRIVERS)


def test_unpriceable_config_raises_keyerror():
    # subgraph became priceable in PR 16; kmeans_ingest (host-link
    # bound, priced by bench_ingest itself) remains deliberately out
    with pytest.raises(KeyError, match="unpriceable"):
        M.price("kmeans_ingest", None, _topo())


def test_wire_cost_is_the_planner_cost():
    """One wire oracle: the planner's site cost and the model's wire
    term are the same function (the Plan rows' cost column re-pointed
    at the shared model, PR 13)."""
    from harp_tpu.plan import planner

    topo = _topo()
    for sched in planner.SCHEDULES:
        for b in (0, 1, 1024, 999_983):
            assert planner._site_cost(topo, "psum", sched, b) == \
                M.wire_cost_s(topo, "psum", sched, b), (sched, b)


# -- 3. kernel registry work models + the VMEM pre-sizer --------------------

def test_every_registered_kernel_prices_without_fallback():
    """A kernel in KERNELS without a work model cannot exist (the
    registration signature requires the fields); this pins the other
    half: the declared numbers are sane (positive, VMEM under the
    16 MiB ceiling) for every entry — loudly, at lint/test time."""
    from harp_tpu.ops.kernel_registry import KERNEL_WORK, KERNELS

    assert set(KERNEL_WORK) == set(KERNELS)
    for name, work in KERNEL_WORK.items():
        for field in ("flops", "min_hbm_bytes", "vmem_bytes"):
            v = work[field]
            assert isinstance(v, int) and v > 0, (name, field, v)
        assert work["vmem_bytes"] <= 16 << 20, name


def test_registering_without_a_work_model_fails_loudly():
    from harp_tpu.ops.kernel_registry import register_kernel

    with pytest.raises(TypeError):
        register_kernel("bogus.kernel")(lambda: None)  # no work fields
    with pytest.raises(ValueError, match="work field"):
        register_kernel("bogus.kernel", flops=0, min_hbm_bytes=1,
                        vmem_bytes=1)(lambda: None)
    from harp_tpu.ops.kernel_registry import KERNELS

    assert "bogus.kernel" not in KERNELS


def test_presizer_reproduces_the_oom_calibrated_int8_tile():
    """The 2026-08-01 window found 8000 rows by OOM-probing on silicon;
    the pre-sizer must reproduce it offline from the kernel's own
    calibrated byte model (graded shape 1M x 300, k=100)."""
    out = perfmodel.presize("kmeans.partials_int8",
                            n=1_000_000, d=300, k=100)
    assert out["tile"] == 8000, out


def test_presizer_picks_the_swept_mfsgd_tile():
    """256x256 measured fastest (SWEEP_pallas 2026-08-01); the
    pre-sizer must pick it from the model, not from 'largest fits'
    (512 and 1024 fit VMEM too — and measured slower)."""
    out = perfmodel.presize("mfsgd.sgd_tile_update",
                            rank=64, n_items=26_744)
    assert out["tile"] == 256, out
    assert set(out["fits"]) >= {256, 512, 1024}


def test_presizer_refuses_an_unbudgeted_kernel():
    with pytest.raises(KeyError, match="pre-size"):
        perfmodel.presize("made.up_kernel")


def test_presizer_reports_vmem_wall():
    out = perfmodel.presize("mfsgd.sgd_tile_update",
                            rank=256, i_shard=200_000)
    assert out["tile"] is None and "budget" in out["reason"]


# -- PR 17: the three newly kernelized arms are presized OFFLINE (no
# silicon evidence yet) — these pins are the tiles the sprint will try
# FIRST, and the ranking rationale in the config comments cites them.

def test_presizer_picks_the_svm_sample_tile():
    """Whole-d resident w/x-tile: the grid-overhead term is monotone in
    1/tn, so the largest VMEM-fitting sample tile must win (8192 at the
    graded 500k x 128 f32 shape)."""
    out = perfmodel.presize("svm.kernel_row", n=500_000, d=128)
    assert out["tile"] == 8192, out
    assert set(out["fits"]) >= {8192, 4096, 2048}


def test_presizer_picks_the_wdamds_column_tile():
    """X (all N rows) stays resident; the column tile only bounds the
    delta/dist working set — largest fitting tile (128 at the graded
    4096-point shape) wins on the same 1/tn overhead argument."""
    out = perfmodel.presize("wdamds.smacof_dist",
                            n=4096, num_workers=8, dim=3)
    assert out["tile"] == 128, out
    assert set(out["fits"]) >= {128, 64, 32}


def test_presizer_reports_wdamds_vmem_wall():
    """At 200k points the resident [N, dim] + [tn, N] blocks cannot fit
    any lane-aligned tile — the pre-sizer must say so offline instead of
    letting the sprint discover it as a Mosaic OOM."""
    out = perfmodel.presize("wdamds.smacof_dist",
                            n=200_000, num_workers=8, dim=3)
    assert out["tile"] is None and "budget" in out["reason"]


def test_presizer_picks_the_rf_row_tile():
    out = perfmodel.presize("rf.hist_bins", n=200_000, f=64, n_bins=32,
                            n_classes=2, depth=6, num_workers=8)
    assert out["tile"] == 2048, out
    assert set(out["fits"]) >= {2048, 1024, 512}
