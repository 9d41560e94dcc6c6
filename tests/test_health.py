"""Health sentinel (harp_tpu/health) — the sixth, derived telemetry spine.

Evidence layers, all on the 8-worker CPU sim:

1. SLO-burn math: multi-window burn rates, the two-floor breach rule,
   severity escalation, latch/hysteresis;
2. THE chaos acceptance pin (ISSUE 14): a seeded-chaos
   ``benchmark_sustained`` run fires SLO-burn AND budget-drift health
   rows whose counts reconcile EXACTLY with the invariant-9 ledger and
   the invariant-11 trace counts — and the full export (trace + health
   + the stamped bench row) passes scripts/check_jsonl.py as one file —
   while the identical healthy control run emits zero findings;
3. skew trigger: fires only after K consecutive over-threshold
   supersteps, carries the ``suggest_rebalance`` plan inline, and that
   plan replays through ``schedule.apply_rebalance`` (the
   elastic-execution handoff shape, pinned);
4. budget drift: warn-mode flightrec violations aggregate (count +
   worst offender per site); raise-mode stays loud-and-unrecorded;
5. zero-cost contract: every detector no-ops with telemetry off, the
   traced serve program is jaxpr-identical with the sentinel armed, and
   the flagship serve budgets (0 compiles / exact dispatch+readback
   totals) hold UNCHANGED with it armed;
6. evidence regression: tolerance verdicts vs a committed incumbent,
   model_invalidated on a magnitude-band breach, and the model gate
   (``health --grade-model``) passing on the real repo.
"""

import io
import json
import os
import sys
import warnings

import numpy as np
import pytest

from harp_tpu import health
from harp_tpu.utils import flightrec, skew, telemetry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "scripts"))

import check_jsonl  # noqa: E402


# ---------------------------------------------------------------------------
# SLO burn math
# ---------------------------------------------------------------------------

def test_slo_burn_two_floor_rule_and_severity():
    """Burn = bad_frac / budget; a breach needs fast >= 2 AND slow >= 1;
    slow >= PAGE_BURN escalates to page; recovery re-arms the latch."""
    with telemetry.scope(True):
        slo = health.SLOBurn("t", window_s=6.0, subwindows=6,
                             error_budget=0.10)
        # 9 good + 1 bad in one sub-window: fast burn = 0.1/0.1 = 1.0
        # (under the fast floor) -> no breach
        for _ in range(9):
            slo.observe(0.1, "served", latency_ms=1.0)
        slo.observe(0.1, "shed")
        assert slo.burn(0.1) == (pytest.approx(1.0), pytest.approx(1.0))
        assert slo.breaches == 0
        # next sub-window goes 50% bad: fast 5.0, slow ~2.3 -> breach,
        # but below PAGE_BURN -> warn
        for i in range(8):
            slo.observe(1.1, "served" if i % 2 else "failed")
        assert slo.breaches == 1
        row = health.monitor.findings()[-1]
        assert row["detector"] == "slo_burn" and row["severity"] == "warn"
        # an all-bad window pushes the slow burn past PAGE_BURN ->
        # severity escalates on the SAME row (one breach episode)
        for _ in range(30):
            slo.observe(2.1, "failed")
        assert health.monitor.findings()[-1]["severity"] == "page"
        # cumulative counts stay exact on the exported row
        assert row["offered"] == slo.counts["offered"] == 48
        assert row["failed"] == slo.counts["failed"]


def test_slo_burn_latency_objective_counts_slow_requests():
    with telemetry.scope(True):
        slo = health.SLOBurn("t", window_s=6.0, subwindows=6,
                             error_budget=0.5, latency_slo_ms=10.0)
        slo.observe(0.1, "served", latency_ms=5.0)    # good
        slo.observe(0.1, "served", latency_ms=50.0)   # over the SLO: bad
        fast, slow = slo.burn(0.1)
        assert fast == pytest.approx(1.0)  # 0.5 bad frac / 0.5 budget
        assert slo.counts["served"] == 2   # outcome counting unchanged


def test_slo_burn_zero_cost_when_disabled():
    slo = health.SLOBurn("t")
    slo.observe(0.0, "failed")
    slo.observe(0.0, "shed")
    assert slo.counts["offered"] == 0
    assert slo.snapshot(0.0)["fast_burn"] == 0.0
    assert health.monitor.findings() == []


# ---------------------------------------------------------------------------
# THE chaos acceptance pin
# ---------------------------------------------------------------------------

_CHAOS = dict(app="kmeans", n_requests=48, rows_per_request=1,
              burst_admit=8, ladder=(8,), offered_qps=1e5,
              state_shape={"k": 4, "d": 8})


def test_chaos_sustained_fires_and_reconciles(mesh, tmp_path):
    """Seeded chaos (exact dispatch ordinal + a bounded queue at 2x+
    offered load) fires SLO-burn + budget-drift rows that reconcile
    EXACTLY with the invariant-9 ledger and invariant-11 trace counts;
    the whole export passes the checker as one file."""
    from harp_tpu.serve.bench import benchmark_sustained
    from harp_tpu.utils import reqtrace
    from harp_tpu.utils.metrics import benchmark_json

    with telemetry.scope(True):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            res = benchmark_sustained(**_CHAOS, max_queue_rows=16,
                                      max_retries=2, fault_ordinals=(2,),
                                      mesh=mesh)
        # chaos actually ran, deterministically: dispatch event #2 fired
        assert res["faults_injected"] == 1
        assert res["fault_retries"] == 1
        assert res["shed_requests"] > 0

        rows = {r["detector"]: r for r in health.monitor.findings()}
        # (a) SLO burn fired and its cumulative counts ARE the ledger
        slo = rows["slo_burn"]
        assert slo["offered"] == res["offered_requests"]
        assert slo["served"] == res["served_requests"]
        assert slo["shed"] == res["shed_requests"]
        assert slo["failed"] == res["failed_requests"]
        # ... and the invariant-11 trace counts
        assert reqtrace.tracer.counts == {
            "served": slo["served"], "shed": slo["shed"],
            "failed": slo["failed"]}
        # (b) budget drift: exactly the retried window, worst offender
        # names the double staging
        bd = rows["budget_drift"]
        assert bd["violations"] == res["fault_retries"] == 1
        assert "h2d_calls used 2 > budget 1" in bd["worst"]
        assert res["health_budget_drift"] == 1
        # (c) the bench row's health fields summarize the findings
        assert res["health_findings"] == 2
        assert res["health_worst_severity"] == "page"
        assert res["health_breaches"] >= 1
        assert res["health_fast_burn"] >= health.FAST_BURN_MIN

        # (d) one file: trace + health export + the stamped bench row
        # passes EVERY checker invariant (9, 11, 13) together
        p = tmp_path / "chaos_run.jsonl"
        telemetry.export(str(p))
        with open(p, "a") as fh:
            fh.write(benchmark_json("serve_kmeans_sustained", res) + "\n")
    errs = check_jsonl.check_file(str(p), provenance=True)
    assert errs == [], errs


def test_healthy_control_run_emits_zero_findings(mesh):
    """The identical trace with the degradation knobs off: no faults,
    no bounds -> zero findings, zero burns, zero drift."""
    from harp_tpu.serve.bench import benchmark_sustained

    with telemetry.scope(True):
        res = benchmark_sustained(**{**_CHAOS, "offered_qps": 500.0},
                                  mesh=mesh)
        assert res["served_requests"] == res["offered_requests"]
        assert res["health_findings"] == 0
        assert res["health_worst_severity"] is None
        assert res["health_fast_burn"] == 0.0
        assert res["health_breaches"] == 0
        assert res["health_budget_drift"] == 0
        assert health.monitor.findings() == []


# ---------------------------------------------------------------------------
# Skew trigger -> the elastic-execution handoff
# ---------------------------------------------------------------------------

def test_skew_trigger_needs_k_consecutive_and_carries_plan():
    with telemetry.scope(True):
        for i in range(health.TRIGGER_SUPERSTEPS - 1):
            skew.record_execution("p", [10, 2, 2, 2], unit="u")
        assert health.monitor.findings() == []  # K-1 is not enough
        # a balanced superstep resets the consecutive counter
        skew.record_execution("p", [4, 4, 4, 4], unit="u")
        for i in range(health.TRIGGER_SUPERSTEPS - 1):
            skew.record_execution("p", [10, 2, 2, 2], unit="u")
        assert health.monitor.findings() == []
        skew.record_execution("p", [10, 2, 2, 2], unit="u")  # the K-th
        rows = health.monitor.findings()
        assert len(rows) == 1
        r = rows[0]
        assert r["detector"] == "skew_trigger" and r["phase"] == "p"
        assert r["wasted_frac"] == pytest.approx(0.6)
        assert r["consecutive"] == health.TRIGGER_SUPERSTEPS
        plan = r["plan"]
        assert plan["ratio_before"] == pytest.approx(2.5)
        assert plan["ratio_after"] == pytest.approx(1.0)
        # latched: further skewed supersteps do not spam new findings
        skew.record_execution("p", [10, 2, 2, 2], unit="u")
        assert len(health.monitor.findings()) == 1


def test_skew_trigger_plan_replays_through_apply_rebalance(mesh):
    """The acceptance pin for the handoff: the INLINE plan (recorded
    with movable units on the PR-4 skewed-corpus pattern) must be
    exactly what schedule.apply_rebalance accepts — the elastic
    execution PR acts on this payload, so its shape is contract."""
    from harp_tpu import schedule

    with telemetry.scope(True):
        for _ in range(health.TRIGGER_SUPERSTEPS):
            skew.record_partition(
                "files", [10, 1, 0, 1], unit="bytes",
                units=[[("a", 6), ("b", 4)], [("c", 1)], [], [("d", 1)]])
        r = health.monitor.findings()[0]
        assert r["detector"] == "skew_trigger"
        plan = r["plan"]
        assert all("id" in m for m in plan["moves"])
        new = schedule.apply_rebalance([["a", "b"], ["c"], [], ["d"]],
                                       plan)
        assert sorted(map(sorted, new)) == [["a"], ["b"], ["c"], ["d"]]
        # and the row round-trips the invariant-13 plan checks
        stamp = {"backend": "cpu", "date": "2026-08-05", "commit": "x"}
        assert check_jsonl._check_health_row("t", 1, {**r, **stamp}) == []


# ---------------------------------------------------------------------------
# Budget drift
# ---------------------------------------------------------------------------

def test_budget_drift_aggregates_warn_violations():
    with telemetry.scope(True):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            with flightrec.budget(readbacks=0, action="warn", tag="s"):
                flightrec.record_readback(4)
            with flightrec.budget(readbacks=1, h2d_bytes=0,
                                  action="warn", tag="s"):
                flightrec.record_readback(4)
                flightrec.record_readback(4)
                flightrec.record_h2d(1 << 20)
        rows = health.monitor.findings()
        assert len(rows) == 1  # one row per site, violations aggregated
        r = rows[0]
        assert r["detector"] == "budget_drift" and r["tag"] == "s"
        assert r["violations"] == 2
        # worst offender by overspend ratio: the 1 MiB h2d over budget 0
        assert "h2d_bytes" in r["worst"]


def test_budget_drift_raise_mode_stays_loud_not_recorded():
    with telemetry.scope(True):
        with pytest.raises(flightrec.BudgetExceeded):
            with flightrec.budget(readbacks=0, tag="s"):
                flightrec.record_readback(4)
        assert health.monitor.findings() == []


# ---------------------------------------------------------------------------
# Zero-cost contract
# ---------------------------------------------------------------------------

def test_detectors_noop_with_telemetry_off():
    telemetry.enable(False)
    try:
        health.monitor.reset()
        skew.record_execution("p", [10, 0, 0, 0], unit="u")
        health.monitor.observe_budget("t", [("readbacks", 2, 1)])
        health.monitor.observe_skew("p", skew.ledger)
        assert health.monitor.findings() == []
    finally:
        telemetry.enable(False)  # conftest default stays off


def test_serve_program_jaxpr_identical_with_sentinel_armed(mesh, tmp_path):
    """The PR-3 contract: arming the sentinel never touches a traced
    program — the serve engine's jaxpr is bit-identical with telemetry
    off vs on-with-the-sentinel-observing."""
    import jax

    from harp_tpu.serve.engines import make_engine

    rng = np.random.default_rng(0)
    from harp_tpu.serve.engines import ENGINES

    state = ENGINES["kmeans"].synthetic_state(rng, k=4, d=8)

    def trace():
        eng = make_engine("kmeans", state, mesh)
        return str(jax.make_jaxpr(eng.jitted().__wrapped__
                                  if hasattr(eng.jitted(), "__wrapped__")
                                  else eng.jitted())(
            *eng.trace_args(8)))

    telemetry.enable(False)
    off = trace()
    with telemetry.scope(True):
        slo = health.SLOBurn("t")
        slo.observe(0.0, "failed")  # sentinel actively observing
        on = trace()
    assert off == on


def test_flagship_serve_budget_unchanged_with_sentinel_armed(mesh,
                                                             tmp_path):
    """The acceptance pin: with the sentinel armed (it always is on the
    runner) and telemetry ON, the continuous plane still proves EXACT
    totals — one dispatch + one readback per batch, zero steady
    compiles — and a clean run records zero violations and findings."""
    from harp_tpu.serve.engines import ENGINES
    from harp_tpu.serve.server import Server

    rng = np.random.default_rng(7)
    with telemetry.scope(True):
        srv = Server("kmeans",
                     state=ENGINES["kmeans"].synthetic_state(rng, k=4,
                                                             d=8),
                     mesh=mesh, ladder=(1, 8),
                     cache_dir=str(tmp_path / "aot"))
        srv.startup()
        srv.process([srv.engine.synthetic_request(rng, n)
                     for n in (1, 8)])  # warm every rung
        srv.steady.reset()
        srv.steady.limits["h2d_calls"] = 1  # the staging discipline
        runner = srv.make_runner(clock=lambda: 0.0)
        for i in range(8):
            runner.submit(i, srv.engine.synthetic_request(rng, 3),
                          now=0.0)
            runner.step(0.0)
        runner.drain(0.0)
        runner.verify_exact()  # raises on any inexactness
        assert srv.steady.violations == 0
        assert runner.health.counts["served"] == 8
        assert runner.health.breaches == 0
        assert health.monitor.findings() == []
        # the sentinel is ON the stats surface
        assert runner.stats()["health"]["offered"] == 8


# ---------------------------------------------------------------------------
# Evidence regression + the fail-closed model gate
# ---------------------------------------------------------------------------

def _repo_with_incumbent(tmp_path, config, metric, value):
    row = {"config": config, metric: value, "backend": "tpu",
           "date": "2026-08-01", "commit": "abc1234"}
    (tmp_path / "BENCH_local.jsonl").write_text(json.dumps(row) + "\n")
    return str(tmp_path)


def test_grade_bench_row_tolerance_verdicts(tmp_path):
    """rf has deliberately no cost model (ROADMAP), so the verdict is
    the pure incumbent comparison at the +-10% dead band."""
    from harp_tpu.health import grade as HG

    repo = _repo_with_incumbent(tmp_path, "rf", "trees_per_sec", 10.0)
    health.monitor.reset()

    def fresh(v):
        return {"config": "rf", "trees_per_sec": v, "backend": "tpu",
                "date": "2026-08-05", "commit": "def5678"}

    assert HG.grade_bench_row(fresh(8.0), repo)["verdict"] == "regressed"
    assert HG.grade_bench_row(fresh(12.0), repo)["verdict"] == "improved"
    assert HG.grade_bench_row(fresh(10.2), repo)["verdict"] == "confirmed"
    # severity: regressions warn, the rest inform — but the upserted row
    # keeps the worst severity seen
    r = health.monitor.findings()[0]
    assert r["detector"] == "evidence_regression"
    assert r["severity"] == "warn"
    # smoke / CPU / error rows are never graded (CPU-inversion filter)
    assert HG.grade_bench_row({**fresh(1.0), "backend": "cpu"},
                              repo) is None
    assert HG.grade_bench_row({**fresh(1.0), "smoke": True},
                              repo) is None
    health.monitor.reset()


def test_grade_bench_row_magnitude_breach_invalidates_model(tmp_path):
    from harp_tpu.health import grade as HG

    repo = _repo_with_incumbent(tmp_path, "kmeans", "iters_per_sec",
                                381.2)
    health.monitor.reset()
    # a "measured" rate 6 orders of magnitude off the model's prediction
    # is outside MAGNITUDE_TOL: the model no longer describes this
    # hardware -> model_invalidated regardless of the incumbent verdict
    f = HG.grade_bench_row(
        {"config": "kmeans", "iters_per_sec": 1e-3, "n": 1_000_000,
         "d": 300, "k": 100, "backend": "tpu", "date": "2026-08-05",
         "commit": "def5678"}, repo)
    assert f["verdict"] == "model_invalidated"
    assert f["model_factor"] > 50.0
    health.monitor.reset()


def test_model_gate_passes_on_committed_evidence():
    """The real repo's committed evidence grades clean (tier-1 already
    pins perfmodel.grade ok), so the gate passes and emits a
    confirmed info row that passes invariant 13."""
    from harp_tpu.health import grade as HG

    health.monitor.reset()
    ok, finding = HG.model_gate(ROOT)
    assert ok is True
    assert finding["verdict"] == "confirmed"
    assert finding["failures"] == 0
    stamp = {"backend": "cpu", "date": "2026-08-05", "commit": "x"}
    assert check_jsonl._check_health_row("t", 1,
                                         {**finding, **stamp}) == []
    health.monitor.reset()


# ---------------------------------------------------------------------------
# Monitor mechanics + vocab
# ---------------------------------------------------------------------------

def test_monitor_upsert_escalates_severity_and_marks():
    health.monitor.reset()
    mark0 = health.monitor.mark()
    r = health.monitor.upsert("budget_drift", "k", severity="warn")
    r["violations"] = 1
    assert health.monitor.upsert("budget_drift", "k",
                                 severity="info") is r
    assert r["severity"] == "warn"  # never demotes
    health.monitor.upsert("budget_drift", "k", severity="page")
    assert r["severity"] == "page"
    assert [x["_seq"] for x in health.monitor.since(mark0)] == [1]
    assert health.monitor.since(health.monitor.mark()) == []
    with pytest.raises(ValueError):
        health.monitor.upsert("nope", "k")
    with pytest.raises(ValueError):
        health.monitor.upsert("slo_burn", "k", severity="meh")
    health.monitor.reset()


def test_summarize_rows_actionable_rule():
    rows = [{"detector": "slo_burn", "severity": "page"},
            {"detector": "evidence_regression", "severity": "info",
             "verdict": "confirmed"},
            {"detector": "evidence_regression", "severity": "info",
             "verdict": "model_invalidated"}]
    s = health.summarize_rows(rows)
    assert s["findings"] == 3
    assert s["actionable"] == 2  # the page + the invalidation
    assert s["worst_severity"] == "page"
    assert s["by_detector"]["evidence_regression"] == 2


def test_report_grows_health_section(mesh):
    """The live report carries the sentinel's findings (the report
    surface of the sixth spine)."""
    from harp_tpu import report

    with telemetry.scope(True):
        for _ in range(health.TRIGGER_SUPERSTEPS):
            skew.record_execution("p", [10, 2, 2, 2], unit="u")
        row, _ = report.live_report()
        assert row["health"]["findings"] == 1
        text = report.render(row)
        assert "health (sentinel findings)" in text
        assert "skew_trigger" in text
