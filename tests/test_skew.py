"""Superstep skew profiler (utils/skew.py) — per-worker load attribution.

Evidence layers, all on the 8-worker CPU sim:

1. numpy-golden skew stats for a deliberately imbalanced LDA corpus and
   an imbalanced MF-SGD rating matrix — the ingest records match the
   partitioners' ownership rule (``id // own``), and the execution
   counters folded into the stacked readbacks match them;
2. the flagship flight budgets are UNCHANGED with skew collection
   enabled (1 dispatch / 1 stacked readback per run, 0 post-warmup
   compiles) — the counters ride the EXISTING readback;
3. the imbalance model (max/mean → wasted chip-seconds, roofline
   composition) and ``suggest_rebalance`` → ``schedule.apply_rebalance``
   bridge;
4. export rows satisfy scripts/check_jsonl.py invariant 5, and the
   report CLI grows a ``skew`` section whose per-worker counts sum to
   the global total (the acceptance walkthrough);
5. ``op_breakdown(per_device=True)`` splits a synthetic multichip trace
   per device id with the default call unchanged.
"""

import gzip
import json
import os
import sys

import numpy as np
import pytest

from harp_tpu.utils import flightrec, skew, telemetry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "scripts"))

import check_jsonl  # noqa: E402

def _skewed_lda_corpus(seed=0):
    """64 docs, 48 vocab: docs 0-7 (worker 0's range at 8 workers) carry
    40 tokens each, the rest 4 — worker 0 holds ~4.7x the mean load."""
    rng = np.random.default_rng(seed)
    d_ids = np.concatenate([np.repeat(np.arange(8), 40),
                            np.repeat(np.arange(8, 64), 4)]).astype(np.int32)
    w_ids = rng.integers(0, 48, len(d_ids)).astype(np.int32)
    return d_ids, w_ids


# ---------------------------------------------------------------------------
# numpy-golden skew stats (ingest + execution)
# ---------------------------------------------------------------------------

def test_lda_skew_golden_imbalanced_corpus(mesh):
    """Ingest record == bincount by the partitioner's ownership rule
    (doc // d_own), and the execution counter folded into the stacked
    readback reproduces it exactly (every token touched once/sweep)."""
    import harp_tpu.models.lda as L

    cfg = L.LDAConfig(n_topics=8, algo="dense", d_tile=16, w_tile=16,
                      entry_cap=64)
    d_ids, w_ids = _skewed_lda_corpus()
    with telemetry.scope():
        model = L.LDA(64, 48, cfg, mesh, seed=0)
        model.set_tokens(d_ids, w_ids)
        expect = np.bincount(d_ids // model.d_own, minlength=8)
        ing = skew.ledger.summary()["lda.partition"]
        np.testing.assert_allclose(ing["work"], expect)
        assert ing["total"] == len(d_ids)
        assert 0.0 <= ing["padding_frac"] <= 1.0
        assert ing["source"] == "ingest"

        model.sample_epoch()
        ex = skew.ledger.summary()["lda.epochs"]
        np.testing.assert_allclose(ex["work"], expect)
        assert ex["total"] == len(d_ids) == model.n_tokens
        assert ex["source"] == "execution"
        golden_ratio = expect.max() / expect.mean()
        assert ex["max_mean_ratio"] == pytest.approx(golden_ratio, rel=1e-3)
        assert ex["wasted_frac"] == pytest.approx(
            1.0 - expect.mean() / expect.max(), rel=1e-3)
        assert ex["wall_s"] > 0 and ex["wasted_chip_s"] > 0


def test_mfsgd_skew_golden_imbalanced_ratings(mesh):
    """Same golden for MF-SGD: 70% of the ratings land on worker 0's
    user range; ingest and execution agree with numpy's bincount."""
    import harp_tpu.models.mfsgd as MF

    cfg = MF.MFSGDConfig(rank=4, algo="dense", u_tile=8, i_tile=8,
                         entry_cap=32)
    rng = np.random.default_rng(1)
    u = np.concatenate([rng.integers(0, 8, 700),
                        rng.integers(8, 64, 300)]).astype(np.int32)
    i = rng.integers(0, 48, 1000).astype(np.int32)
    v = rng.normal(size=1000).astype(np.float32)
    with telemetry.scope():
        m = MF.MFSGD(64, 48, cfg, mesh, seed=0)
        m.set_ratings(u, i, v)
        expect = np.bincount(u // m.u_own, minlength=8)
        ing = skew.ledger.summary()["mfsgd.partition"]
        np.testing.assert_allclose(ing["work"], expect)
        assert ing["total"] == 1000

        m.train_epoch()
        ex = skew.ledger.summary()["mfsgd.epochs"]
        np.testing.assert_allclose(ex["work"], expect)
        assert ex["unit"] == "ratings"
        assert ex["max_mean_ratio"] == pytest.approx(
            expect.max() / expect.mean(), rel=1e-3)

        # train_epochs (the multi-epoch program) records the same vector
        m.train_epochs(2)
        ex2 = skew.ledger.summary()["mfsgd.epochs"]
        np.testing.assert_allclose(ex2["work"], expect)


def test_kmeans_fit_records_balanced_execution_skew(mesh):
    """kmeans shards evenly by construction — its record pins the
    balanced baseline (ratio 1.0, zero predicted waste)."""
    import harp_tpu.models.kmeans as KM

    pts = np.random.default_rng(0).normal(size=(256, 8)).astype(np.float32)
    with telemetry.scope():
        KM.fit(pts, k=4, iters=2, mesh=mesh, seed=0)
        s = skew.ledger.summary()["kmeans.fit"]
        np.testing.assert_allclose(s["work"], [32.0] * 8)
        assert s["total"] == 256
        assert s["max_mean_ratio"] == pytest.approx(1.0)
        assert s["wasted_frac"] == pytest.approx(0.0)


# ---------------------------------------------------------------------------
# flagship budgets UNCHANGED with skew collection enabled (satellite pin)
# ---------------------------------------------------------------------------

def test_lda_flagship_budget_unchanged_with_skew_enabled(mesh):
    """The acceptance pin: with skew collection on (it rides the
    HARP_TELEMETRY switch), the lda flagship budget from
    tests/test_flightrec.py holds UNCHANGED — 1 dispatch + 1 stacked
    readback per sample_epochs run, 0 post-warmup compiles — because the
    per-worker counter rides the EXISTING readback."""
    import harp_tpu.models.lda as L

    cfg = L.LDAConfig(n_topics=8, algo="dense", d_tile=16, w_tile=16,
                      entry_cap=64)
    d_ids, w_ids = _skewed_lda_corpus()
    with telemetry.scope():
        lda = L.LDA(64, 48, cfg, mesh, seed=0)
        lda.set_tokens(d_ids, w_ids)
        lda.sample_epoch()  # warmup: the single-epoch compile
        lda.compile_epochs(2)
        keys_bytes = mesh.num_workers * 2 * 4
        for rerun in range(2):
            with flightrec.budget(compiles=0, dispatches=1, readbacks=1,
                                  h2d_bytes=keys_bytes,
                                  tag=f"lda.skew#{rerun}") as b:
                lda.sample_epochs(2)
            assert b.spent()["dispatches"] == 1
            assert b.spent()["readbacks"] == 1
        # and the counter it carried sums to the global token total
        ex = skew.ledger.summary()["lda.epochs"]
        assert sum(ex["work"]) == ex["total"] == lda.n_tokens


# ---------------------------------------------------------------------------
# the imbalance model + the scheduler bridge
# ---------------------------------------------------------------------------

def test_imbalance_model_and_roofline_composition():
    with telemetry.scope():
        skew.record_execution("p", [10, 2, 2, 2], unit="u", wall_s=2.0)
        s = skew.ledger.summary()["p"]
        assert s["max_mean_ratio"] == pytest.approx(2.5)  # 10 / 4
        assert s["wasted_frac"] == pytest.approx(0.6)     # 1 - 4/10
        # 4 chips idle 60% of a 2 s superstep
        assert s["wasted_chip_s"] == pytest.approx(4.8)
        # roofline composition: lda's work model at 1e9 tok/s/chip &
        # K=100 achieves 1.4e12/197e12 = 0.7107% of bf16 peak; skew
        # predicts 60% of that lost to the barrier
        from harp_tpu.utils.roofline import V5E

        res = {"n_topics": 100, "tokens_per_sec_per_chip": 1e9}
        pct = skew.wasted_pct_of_peak("lda", res, "p", V5E)
        assert pct == pytest.approx(0.7107 * 0.6, abs=1e-3)
        # unknown phase / config without a work model / a CPU run →
        # None, not garbage
        assert skew.wasted_pct_of_peak("lda", {}, "nope", V5E) is None
        assert skew.wasted_pct_of_peak("no_model", {}, "p", V5E) is None
        assert skew.wasted_pct_of_peak("lda", res, "p", "cpu") is None


def test_suggest_rebalance_fractional_plan():
    with telemetry.scope():
        skew.record_execution("p", [10, 2, 2, 2], unit="u")
        plan = skew.suggest_rebalance("p")
        assert plan["ratio_before"] == pytest.approx(2.5)
        assert plan["ratio_after"] == pytest.approx(1.0)
        assert all(m["from"] == 0 for m in plan["moves"])
        assert sum(m["work"] for m in plan["moves"]) == pytest.approx(6.0)
        np.testing.assert_allclose(plan["work_after"], [4.0] * 4)
        assert skew.suggest_rebalance("unknown") is None


def test_suggest_rebalance_units_applies_through_schedule(mesh):
    """The scheduler bridge: record per-worker loads WITH movable units
    (files), get a whole-unit greedy plan, replay it on the
    fileformat-shaped splits via schedule.apply_rebalance."""
    from harp_tpu import schedule

    with telemetry.scope():
        skew.record_partition(
            "files", [10, 1, 0, 1], unit="bytes",
            units=[[("a", 6), ("b", 4)], [("c", 1)], [], [("d", 1)]])
        plan = skew.suggest_rebalance("files")
        assert plan["ratio_after"] < plan["ratio_before"]
        assert all("id" in m for m in plan["moves"])
        new = schedule.apply_rebalance([["a", "b"], ["c"], [], ["d"]],
                                       plan)
        # greedy LPT on measured sizes: a→w0, b→w1, c→w2, d→w3
        assert sorted(map(sorted, new)) == [["a"], ["b"], ["c"], ["d"]]

        # a fractional plan must refuse to shuffle items
        skew.record_execution("frac", [4, 0], unit="u")
        with pytest.raises(ValueError, match="fractional"):
            schedule.apply_rebalance([["x"], []],
                                     skew.suggest_rebalance("frac"))


def test_record_host_stamps_per_process_columns():
    with telemetry.scope():
        skew.record_host("sweep", 0, 1.0, n_workers=4)
        skew.record_host("sweep", 2, 3.0, n_workers=4)
        s = skew.ledger.summary()["sweep"]
        assert s["source"] == "host" and s["unit"] == "seconds"
        np.testing.assert_allclose(s["work"], [1.0, 0.0, 3.0, 0.0])


def test_skew_zero_cost_when_disabled():
    with telemetry.scope(False):
        skew.record_execution("p", [1, 2], unit="u")
        skew.record_partition("q", [1, 2], unit="rows")
        skew.record_host("r", 0, 1.0)
        assert skew.ledger.summary() == {}


# ---------------------------------------------------------------------------
# export / checker / report round trips (acceptance walkthrough)
# ---------------------------------------------------------------------------

def test_skew_export_rows_pass_check_jsonl(mesh, tmp_path):
    with telemetry.scope():
        skew.record_execution("p", [3, 1], unit="u", wall_s=0.5)
        skew.record_partition("q", [4, 4], unit="rows", padded_total=10)
        p = tmp_path / "skew.jsonl"
        telemetry.export(str(p))
    rows = telemetry.load_rows(str(p))
    assert len(rows["skew"]) == 2
    for r in rows["skew"]:
        for f in ("backend", "date", "commit"):
            assert f in r, (f, r)
        assert sum(r["work"]) == pytest.approx(r["total"])
    assert check_jsonl.check_file(str(p)) == []


def test_lda_run_report_shows_skew_section_end_to_end(mesh, tmp_path,
                                                      capsys):
    """THE acceptance criterion: a telemetry-enabled lda run on the
    8-worker sim with a skewed corpus → ``python -m harp_tpu report``
    prints a skew section whose per-worker counts sum to the global
    token total, with a max/mean ratio and predicted wasted chip-s."""
    import harp_tpu.__main__ as cli
    import harp_tpu.models.lda as L

    cfg = L.LDAConfig(n_topics=8, algo="dense", d_tile=16, w_tile=16,
                      entry_cap=64)
    d_ids, w_ids = _skewed_lda_corpus()
    path = str(tmp_path / "run.jsonl")
    with telemetry.scope():
        model = L.LDA(64, 48, cfg, mesh, seed=0)
        model.set_tokens(d_ids, w_ids)
        model.sample_epochs(2)
        telemetry.export(path)
    rc = cli.main(["report", "--telemetry", path])
    assert rc == 0
    out = capsys.readouterr().out
    assert "skew (per-worker load" in out
    assert "lda.epochs" in out and "max/mean" in out
    rec = json.loads(out.strip().splitlines()[-1])
    sk = rec["skew"]["lda.epochs"]
    assert sum(sk["work"]) == pytest.approx(sk["total"])
    assert sk["total"] == model.n_tokens
    assert sk["max_mean_ratio"] > 1.5  # the corpus IS skewed
    assert sk["wasted_chip_s"] > 0
    # the ingest-side record travels too, with its padding fraction
    assert 0.0 <= rec["skew"]["lda.partition"]["padding_frac"] <= 1.0


def test_live_report_and_render_skew(mesh):
    from harp_tpu import report

    with telemetry.scope():
        skew.record_execution("phase.x", [8, 2, 2, 2, 2, 2, 2, 2],
                              unit="items", wall_s=1.0)
        row, spans = report.live_report()
    assert row["skew"]["phase.x"]["max_mean_ratio"] == pytest.approx(
        8 / 2.75, rel=1e-3)
    text = report.render(row, spans)
    assert "skew (per-worker load" in text
    assert "w0" in text and "#" in text  # the per-worker histogram


# ---------------------------------------------------------------------------
# scaling sweep carry-through (satellite)
# ---------------------------------------------------------------------------

def _load_script(name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "scripts", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_scaling_sweep_skew_columns_prefer_execution_phase():
    ss = _load_script("scaling_sweep")
    with telemetry.scope():
        skew.record_partition("x.partition", [9, 1], unit="tokens")
        skew.record_execution("x.epochs", [9, 1], unit="tokens",
                              wall_s=1.0)
        cols = ss.skew_columns()
    assert cols["skew_phase"] == "x.epochs"
    assert cols["skew_max_mean"] == pytest.approx(1.8)
    assert cols["skew_work"] == [9.0, 1.0]
    with telemetry.scope():
        assert ss.skew_columns() == {"skew_max_mean": None}  # nothing yet


# ---------------------------------------------------------------------------
# op_breakdown per-device split (small-fix satellite)
# ---------------------------------------------------------------------------

def test_op_breakdown_per_device_ids(tmp_path):
    """Synthetic multichip trace dump: per_device=True splits totals by
    the device ordinal from the process metadata; the default call keeps
    its old aggregated shape and numbers."""
    from harp_tpu.utils.profiling import op_breakdown

    d = tmp_path / "plugins" / "profile" / "0001"
    d.mkdir(parents=True)
    events = [
        {"ph": "M", "name": "process_name", "pid": 1,
         "args": {"name": "/device:TPU:0 (chip 0)"}},
        {"ph": "M", "name": "process_name", "pid": 2,
         "args": {"name": "/device:TPU:1 (chip 1)"}},
        {"ph": "X", "pid": 1, "tid": 0, "ts": 0, "dur": 100,
         "name": "fusion.1"},
        {"ph": "X", "pid": 2, "tid": 0, "ts": 0, "dur": 300,
         "name": "fusion.1"},
        {"ph": "X", "pid": 2, "tid": 0, "ts": 400, "dur": 50,
         "name": "copy.2"},
        # host track: filtered out once device tracks exist
        {"ph": "X", "pid": 7, "tid": 0, "ts": 0, "dur": 999,
         "name": "host_thing"},
    ]
    with gzip.open(d / "x.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": events}, f)

    agg = dict(op_breakdown(str(tmp_path)))
    assert agg["fusion.1"] == pytest.approx(400e-6)
    assert agg["copy.2"] == pytest.approx(50e-6)
    assert "host_thing" not in agg

    per = {(n, dev): t
           for n, dev, t in op_breakdown(str(tmp_path), per_device=True)}
    assert per[("fusion.1", 0)] == pytest.approx(100e-6)
    assert per[("fusion.1", 1)] == pytest.approx(300e-6)
    assert per[("copy.2", 1)] == pytest.approx(50e-6)
