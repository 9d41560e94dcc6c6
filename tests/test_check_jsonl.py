"""scripts/check_jsonl.py — committed measurement files stay parseable and
provenance-stamped (the CPU-inversion guard, tier-1)."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "scripts"))

import check_jsonl  # noqa: E402


def test_committed_files_are_clean():
    """THE tier-1 gate: every committed BENCH_local / PROFILE_local /
    FLIP_DECISIONS line parses, and post-grandfather bench rows carry
    backend/date/commit."""
    errors = check_jsonl.check_repo(ROOT)
    assert errors == [], "\n".join(errors)


def test_unparseable_line_is_loud(tmp_path):
    p = tmp_path / "BENCH_local.jsonl"
    p.write_text('{"config": "x", "backend": "cpu"}\n'
                 "{'config': 'dictrepr'}\n")  # the teed dict-repr bug
    errors = check_jsonl.check_file(str(p))
    assert len(errors) == 1 and "unparseable" in errors[0]
    assert ":2:" in errors[0]


def test_new_bench_row_must_carry_provenance(tmp_path):
    rows = [
        {"config": "legacy_row", "iters_per_sec": 1.0},   # grandfathered
        {"config": "new_row", "iters_per_sec": 2.0},      # must be stamped
    ]
    p = tmp_path / "BENCH_local.jsonl"
    p.write_text("".join(json.dumps(r) + "\n" for r in rows))
    errors = check_jsonl.check_file(str(p), grandfathered=1,
                                    provenance=True)
    assert len(errors) == 1
    assert "new_row" in errors[0] and "backend" in errors[0]


def test_stamped_row_passes(tmp_path):
    row = {"config": "ok", "iters_per_sec": 2.0, "backend": "tpu",
           "date": "2026-08-04", "commit": "abc1234"}
    p = tmp_path / "BENCH_local.jsonl"
    p.write_text(json.dumps(row) + "\n")
    assert check_jsonl.check_file(str(p), provenance=True) == []


def test_non_bench_rows_need_only_parse(tmp_path):
    # verb-sweep and metric-headline rows have no "config": parse-only
    rows = [{"verb": "pull_sparse_sweep", "sec": 0.1},
            {"metric": "kmeans_iters_per_sec", "value": 1.0}]
    p = tmp_path / "rows.jsonl"
    p.write_text("".join(json.dumps(r) + "\n" for r in rows))
    assert check_jsonl.check_file(str(p), provenance=True) == []


def test_comm_row_quantized_verb_must_name_wire(tmp_path):
    """PR-2 gate: a CommLedger row for a quantized verb without a valid
    wire_dtype mis-scales every bytes-on-wire claim downstream."""
    rows = [
        {"kind": "comm", "verb": "rotate_quantized", "wire_dtype": "int8",
         "payload_bytes": 64},                               # fine
        {"kind": "comm", "verb": "rotate_quantized",
         "payload_bytes": 64},                               # missing wire
        {"kind": "comm", "verb": "regroup_quantized",
         "wire_dtype": "float16", "payload_bytes": 64},      # bogus wire
    ]
    p = tmp_path / "rows.jsonl"
    p.write_text("".join(json.dumps(r) + "\n" for r in rows))
    errors = check_jsonl.check_file(str(p))
    assert len(errors) == 2
    assert ":2:" in errors[0] and "wire_dtype" in errors[0]
    assert ":3:" in errors[1] and "float16" in errors[1]


def test_comm_row_exact_move_verb_must_not_claim_wire(tmp_path):
    rows = [
        {"kind": "comm", "verb": "rotate", "payload_bytes": 64},  # fine
        {"kind": "comm", "verb": "rotate", "wire_dtype": "int8",
         "payload_bytes": 64},                                    # bogus
        # allreduce legitimately records no wire (exact by default)
        {"kind": "comm", "verb": "allreduce", "payload_bytes": 64},
    ]
    p = tmp_path / "rows.jsonl"
    p.write_text("".join(json.dumps(r) + "\n" for r in rows))
    errors = check_jsonl.check_file(str(p))
    assert len(errors) == 1 and ":2:" in errors[0]
    assert "_quantized twin" in errors[0]


def test_comm_rows_checked_even_in_bench_files(tmp_path):
    """A telemetry export teed into BENCH_local still gets invariant 3."""
    row = {"kind": "comm", "verb": "regroup_quantized",
           "payload_bytes": 64}
    p = tmp_path / "BENCH_local.jsonl"
    p.write_text(json.dumps(row) + "\n")
    errors = check_jsonl.check_file(str(p), provenance=True)
    assert len(errors) == 1 and "wire_dtype" in errors[0]


def test_exported_ledger_rows_satisfy_the_checker(tmp_path):
    """Round-trip: what telemetry.export writes for the quantized and
    exact movement verbs must pass invariant 3 as-is."""
    import jax.numpy as jnp
    import numpy as np

    from harp_tpu.utils import telemetry

    with telemetry.scope(True):
        telemetry.ledger.record("rotate", np.zeros((4, 2), np.float32),
                                axis="workers")
        telemetry.ledger.record("rotate_quantized",
                                np.zeros((4, 2), np.float32),
                                axis="workers", wire_dtype=jnp.int8)
        telemetry.ledger.record("regroup_quantized",
                                np.zeros((4, 2), np.float32),
                                axis="workers", wire_dtype=jnp.bfloat16)
        p = tmp_path / "telemetry.jsonl"
        telemetry.export(str(p))
    assert check_jsonl.check_file(str(p)) == []


def test_flight_row_must_carry_provenance(tmp_path):
    """Invariant 4: a compile/transfer row without backend/date/commit is
    ambiguous evidence — a CPU-sim compile count must never read as chip
    evidence (the same inversion guard as the bench-row check)."""
    stamp = {"backend": "cpu", "date": "2026-08-04", "commit": "abc1234"}
    rows = [
        {"kind": "compile", "count": 1, "dur": 0.1, "total_s": 0.1,
         "span": "epoch", **stamp},                          # fine
        {"kind": "compile", "count": 2, "dur": 0.1, "total_s": 0.2},
        {"kind": "transfer", "op": "h2d", "bytes": 64, "calls": 1},
    ]
    p = tmp_path / "rows.jsonl"
    p.write_text("".join(json.dumps(r) + "\n" for r in rows))
    errors = check_jsonl.check_file(str(p))
    assert len(errors) == 2
    assert ":2:" in errors[0] and "provenance" in errors[0]
    assert ":3:" in errors[1] and "provenance" in errors[1]


def test_flight_row_counters_must_be_nonnegative_numbers(tmp_path):
    stamp = {"backend": "cpu", "date": "2026-08-04", "commit": "abc1234"}
    rows = [
        {"kind": "transfer", "op": "readback", "bytes": -4, "calls": 1,
         **stamp},
        {"kind": "compile", "count": "three", "dur": 0.1, "total_s": 0.1,
         **stamp},
    ]
    p = tmp_path / "rows.jsonl"
    p.write_text("".join(json.dumps(r) + "\n" for r in rows))
    errors = check_jsonl.check_file(str(p))
    assert len(errors) == 2
    assert "bytes=-4" in errors[0]
    assert "count='three'" in errors[1]


def test_compile_rows_must_be_monotone_within_a_file(tmp_path):
    """A cumulative compile counter that DECREASES down the file means two
    runs' exports were interleaved — every "N compiles this run" claim
    downstream would be wrong."""
    stamp = {"backend": "cpu", "date": "2026-08-04", "commit": "abc1234"}
    rows = [
        {"kind": "compile", "count": 1, "dur": 0.2, "total_s": 0.2, **stamp},
        {"kind": "compile", "count": 2, "dur": 0.1, "total_s": 0.3, **stamp},
        {"kind": "compile", "count": 1, "dur": 0.1, "total_s": 0.1, **stamp},
    ]
    p = tmp_path / "rows.jsonl"
    p.write_text("".join(json.dumps(r) + "\n" for r in rows))
    errors = check_jsonl.check_file(str(p))
    assert len(errors) == 2  # count AND total_s both decreased on row 3
    assert all(":3:" in e and "monotone" in e for e in errors)


def test_exported_flight_rows_satisfy_the_checker(tmp_path):
    """Round-trip: what flightrec.export_jsonl writes must pass invariant
    4 as-is (stamped, non-negative, monotone) — even teed into a bench
    file where provenance checking is on."""
    from harp_tpu.utils import flightrec, telemetry

    with telemetry.scope(True):
        flightrec.compile_watch.on_compile(0.25)
        flightrec.compile_watch.on_compile(0.05)
        flightrec.record_h2d(1024)
        flightrec.record_readback(4)
        p = tmp_path / "BENCH_local.jsonl"
        telemetry.export(str(p))
    assert check_jsonl.check_file(str(p), provenance=True) == []


def test_skew_row_invariants(tmp_path):
    """Invariant 5: skew rows carry the provenance stamp, per-worker
    counts sum to the global total, padding fraction lies in [0, 1]."""
    stamp = {"backend": "cpu", "date": "2026-08-04", "commit": "abc1234"}
    rows = [
        {"kind": "skew", "phase": "ok", "work": [3, 1], "total": 4,
         "padding_frac": 0.25, **stamp},                       # fine
        {"kind": "skew", "phase": "p", "work": [2, 2], "total": 5,
         **stamp},                                             # bad sum
        {"kind": "skew", "phase": "p", "work": [2, 2], "total": 4,
         "padding_frac": 1.5, **stamp},                        # bad pad
        {"kind": "skew", "phase": "p", "work": [1, 1], "total": 2},
        {"kind": "skew", "phase": "p", "work": "oops", "total": 1,
         **stamp},                                             # bad work
        {"kind": "skew", "phase": "p", "work": [-1, 2], "total": 1,
         **stamp},                                             # negative
    ]
    p = tmp_path / "rows.jsonl"
    p.write_text("".join(json.dumps(r) + "\n" for r in rows))
    errors = check_jsonl.check_file(str(p))
    assert len(errors) == 5
    assert ":2:" in errors[0] and "sum" in errors[0]
    assert ":3:" in errors[1] and "padding_frac" in errors[1]
    assert ":4:" in errors[2] and "provenance" in errors[2]
    assert ":5:" in errors[3] and "work" in errors[3]
    assert ":6:" in errors[4] and "negative" in errors[4]


def test_exported_skew_rows_satisfy_the_checker(tmp_path):
    """Round-trip: what skew.export_jsonl writes (via telemetry.export)
    must pass invariant 5 as-is — even teed into a bench file."""
    from harp_tpu.utils import skew, telemetry

    with telemetry.scope(True):
        skew.record_execution("lda.epochs", [5, 1, 1, 1], unit="tokens",
                              wall_s=0.25)
        skew.record_partition("lda.partition", [5, 1, 1, 1],
                              unit="tokens", padded_total=16)
        p = tmp_path / "BENCH_local.jsonl"
        telemetry.export(str(p))
    assert check_jsonl.check_file(str(p), provenance=True) == []


def test_cli_exit_codes(tmp_path):
    (tmp_path / "BENCH_local.jsonl").write_text("not json\n")
    assert check_jsonl.main(["--repo", str(tmp_path)]) == 1
    (tmp_path / "BENCH_local.jsonl").write_text("")
    assert check_jsonl.main(["--repo", str(tmp_path)]) == 0


def test_benchmark_json_rows_satisfy_the_checker(tmp_path):
    """The stamp the checker demands is exactly what benchmark_json
    emits — the two can never drift apart."""
    from harp_tpu.utils.metrics import benchmark_json

    p = tmp_path / "BENCH_local.jsonl"
    p.write_text(benchmark_json("fresh", {"iters_per_sec": 1.0}) + "\n")
    assert check_jsonl.check_file(str(p), provenance=True) == []


def test_lint_row_invariants(tmp_path):
    """Invariant 6: lint rows must be stamped, use registered rule ids,
    and carry non-negative integer counts."""
    rows = [
        # missing provenance entirely
        {"kind": "lint", "violations": 0, "per_rule": {}},
        # unregistered rule id in per_rule
        {"kind": "lint", "backend": "cpu", "date": "2026-08-04",
         "commit": "abc", "per_rule": {"HL999": 1}},
        # negative per-file count
        {"kind": "lint", "backend": "cpu", "date": "2026-08-04",
         "commit": "abc", "per_file": {"a.py": -1}},
    ]
    p = tmp_path / "BENCH_local.jsonl"
    p.write_text("".join(json.dumps(r) + "\n" for r in rows))
    errors = check_jsonl.check_file(str(p))
    assert len(errors) == 3
    assert ":1:" in errors[0] and "provenance" in errors[0]
    assert ":2:" in errors[1] and "HL999" in errors[1]
    assert ":3:" in errors[2] and "negative" in errors[2]


def test_lint_row_accepts_thread_rules_and_rejects_forgeries(tmp_path):
    """Invariant 6, Layer-5 extension (PR 20): the HL4xx thread rules
    are registered vocabulary — a row counting them passes, a forged
    neighbor id fails."""
    stamp = {"backend": "cpu", "date": "2026-08-06", "commit": "abc1234"}
    good = {"kind": "lint", "violations": 5, **stamp,
            "per_rule": {"HL401": 1, "HL402": 1, "HL403": 1,
                         "HL404": 1, "HL405": 1}}
    bad = {"kind": "lint", "violations": 1, **stamp,
           "per_rule": {"HL499": 1}}
    p = tmp_path / "BENCH_local.jsonl"
    p.write_text(json.dumps(good) + "\n" + json.dumps(bad) + "\n")
    errors = check_jsonl.check_file(str(p))
    assert len(errors) == 1
    assert ":2:" in errors[0] and "HL499" in errors[0]


def _sheet(**over):
    """A valid kmeans.fit byte sheet (the hand-computed Layer-4 shape),
    with per-test forgeries spliced in."""
    coll = {"site": "kmeans.py:324", "primitive": "psum",
            "verb": "allreduce", "axis": "workers",
            "wire_dtype": "float32", "per_shard_bytes": 1060,
            "calls_per_trace": 3, "amplification": 2, "dynamic": False,
            "path": "/shard_map/scan"}
    coll.update({k: v for k, v in over.items() if k in coll})
    sheet = {"collectives": [coll], "bytes_per_trace": 1060,
             "amplified_bytes": 2120, "donated_args": [],
             "donated_avals": []}
    sheet.update({k: v for k, v in over.items() if k in sheet})
    return sheet


def test_lint_byte_sheet_invariants(tmp_path):
    """Invariant 6, CommGraph extension: byte sheets must name
    registered programs/primitives/verbs and non-negative bytes —
    forged rows must each trip exactly their own violation."""
    stamp = {"backend": "cpu", "date": "2026-08-04", "commit": "abc1234"}
    base = {"kind": "lint", "violations": 0, **stamp}
    rows = [
        {**base, "byte_sheets": {"kmeans.fit": _sheet()}},       # fine
        {**base, "byte_sheets": {"notaprogram": _sheet()}},
        {**base, "byte_sheets": {
            "kmeans.fit": _sheet(primitive="send_recv")}},
        {**base, "byte_sheets": {
            "kmeans.fit": _sheet(verb="gossip")}},
        {**base, "byte_sheets": {
            "kmeans.fit": _sheet(bytes_per_trace=-5)}},
        {**base, "byte_sheets": {
            "kmeans.fit": _sheet(amplification=-1)}},
    ]
    p = tmp_path / "rows.jsonl"
    p.write_text("".join(json.dumps(r) + "\n" for r in rows))
    errors = check_jsonl.check_file(str(p))
    assert len(errors) == 5, errors
    assert ":2:" in errors[0] and "notaprogram" in errors[0]
    assert ":3:" in errors[1] and "send_recv" in errors[1]
    assert ":4:" in errors[2] and "gossip" in errors[2]
    assert ":5:" in errors[3] and "bytes_per_trace" in errors[3]
    assert ":6:" in errors[4] and "amplification" in errors[4]


def test_serve_row_invariants(tmp_path):
    """Invariant 7: serve rows must be stamped, percentiles monotone,
    qps positive, and steady_compiles exactly 0 — a serving-throughput
    claim that silently recompiled per batch is not serving evidence."""
    stamp = {"backend": "cpu", "date": "2026-08-04", "commit": "abc1234"}
    rows = [
        {"kind": "serve", "app": "kmeans", "qps": 100.0, "p50_ms": 1.0,
         "p95_ms": 2.0, "p99_ms": 3.0, "steady_compiles": 0,
         **stamp},                                          # fine
        {"kind": "serve", "qps": 100.0, "p50_ms": 1.0, "p95_ms": 2.0,
         "p99_ms": 3.0, "steady_compiles": 0},              # unstamped
        {"kind": "serve", "qps": 100.0, "p50_ms": 2.5, "p95_ms": 2.0,
         "p99_ms": 3.0, "steady_compiles": 0, **stamp},     # crossed
        {"kind": "serve", "qps": 0.0, "p50_ms": 1.0, "p95_ms": 2.0,
         "p99_ms": 3.0, "steady_compiles": 0, **stamp},     # qps <= 0
        {"kind": "serve", "qps": 100.0, "p50_ms": 1.0, "p95_ms": 2.0,
         "p99_ms": 3.0, "steady_compiles": 2, **stamp},     # compiled!
        {"kind": "serve", "qps": 100.0, "p50_ms": -1.0, "p95_ms": 2.0,
         "p99_ms": 3.0, "steady_compiles": 0, **stamp},     # negative
    ]
    p = tmp_path / "rows.jsonl"
    p.write_text("".join(json.dumps(r) + "\n" for r in rows))
    errors = check_jsonl.check_file(str(p))
    assert len(errors) == 5
    assert ":2:" in errors[0] and "provenance" in errors[0]
    assert ":3:" in errors[1] and "monotone" in errors[1]
    assert ":4:" in errors[2] and "qps" in errors[2]
    assert ":5:" in errors[3] and "steady_compiles" in errors[3]
    assert ":6:" in errors[4] and "p50_ms" in errors[4]


def test_sustained_serve_row_invariants(tmp_path):
    """Invariant 7, sustained extension: continuous-batching rows need
    offered_qps >= achieved_qps > 0 and non-negative queue-depth
    percentiles — a sustained claim without queue evidence cannot grade
    the padding-vs-latency knobs."""
    stamp = {"backend": "cpu", "date": "2026-08-04", "commit": "abc1234"}
    base = {"kind": "serve", "app": "kmeans", "qps": 100.0,
            "p50_ms": 1.0, "p95_ms": 2.0, "p99_ms": 3.0,
            "steady_compiles": 0, **stamp}
    qd = {"qdepth_p50": 3.0, "qdepth_p95": 9.0, "qdepth_p99": 12.0}
    rows = [
        {**base, "mode": "sustained", "offered_qps": 200.0,
         "achieved_qps": 100.0, **qd},                       # fine
        {**base, "offered_qps": 90.0, "achieved_qps": 100.0,
         **qd},                                              # ach > off
        {**base, "mode": "sustained", "offered_qps": 200.0,
         "achieved_qps": 0.0, **qd},                         # ach <= 0
        {**base, "offered_qps": 200.0, "achieved_qps": 100.0,
         "qdepth_p50": 3.0, "qdepth_p95": 9.0},              # missing p99
        {**base, "offered_qps": 200.0, "achieved_qps": 100.0,
         **{**qd, "qdepth_p95": -1.0}},                      # negative
        {**base, "mode": "sustained", **qd},                 # no qps pair
    ]
    p = tmp_path / "rows.jsonl"
    p.write_text("".join(json.dumps(r) + "\n" for r in rows))
    errors = check_jsonl.check_file(str(p))
    assert len(errors) == 5
    assert ":2:" in errors[0] and "offered_qps >= achieved_qps" in errors[0]
    assert ":3:" in errors[1] and "achieved" in errors[1]
    assert ":4:" in errors[2] and "qdepth_p99" in errors[2]
    assert ":5:" in errors[3] and "qdepth_p95" in errors[3]
    assert ":6:" in errors[4] and "offered" in errors[4]


def test_degraded_serve_row_invariants(tmp_path):
    """Invariant 9: fault-plane serve rows (PR 10) must balance their
    books — fractions in [0, 1], fault_retries a non-negative integer,
    and served + shed + failed == offered.  A row where requests vanish
    is not degradation evidence."""
    stamp = {"backend": "cpu", "date": "2026-08-04", "commit": "abc1234"}
    base = {"kind": "serve", "app": "kmeans", "qps": 100.0,
            "p50_ms": 1.0, "p95_ms": 2.0, "p99_ms": 3.0,
            "steady_compiles": 0, **stamp}
    deg = {"offered_requests": 100, "served_requests": 95,
           "shed_requests": 4, "failed_requests": 1,
           "shed_frac": 0.04, "deadline_miss_frac": 0.02,
           "fault_retries": 3}
    rows = [
        {**base, **deg},                                     # fine
        {**base, **deg, "shed_frac": 1.5},                   # frac > 1
        {**base, **deg, "deadline_miss_frac": -0.1},         # frac < 0
        {**base, **deg, "fault_retries": -2},                # negative
        {**base, **deg, "served_requests": 90},              # unbalanced
        {**base, "shed_frac": 0.0},                          # partial row
        {**base, **deg, "fault_retries": 2.5},               # non-integer
    ]
    p = tmp_path / "rows.jsonl"
    p.write_text("".join(json.dumps(r) + "\n" for r in rows))
    errors = check_jsonl.check_file(str(p))
    assert [e.split(":")[1] for e in errors] == ["2", "3", "4", "5",
                                                 "6", "6", "6", "6",
                                                 "6", "6", "7"]
    assert "shed_frac" in errors[0] and "[0, 1]" in errors[0]
    assert "deadline_miss_frac" in errors[1]
    assert "fault_retries" in errors[2]
    assert "exactly one of the three" in errors[3]
    # a partial degraded row is missing EVERY other book-keeping field
    assert sum("must" in e for e in errors[4:10]) == 6
    assert "fault_retries=2.5" in errors[10]


def test_sustained_bench_row_satisfies_the_checker(tmp_path, mesh):
    """Round-trip: benchmark_sustained through benchmark_json must pass
    the extended invariant 7 as-is in a bench file."""
    from harp_tpu.serve.bench import benchmark_sustained
    from harp_tpu.utils.metrics import benchmark_json

    res = benchmark_sustained(app="kmeans", n_requests=24,
                              rows_per_request=1, burst_admit=4,
                              ladder=(1, 8), offered_qps=2000.0,
                              state_shape={"k": 4, "d": 8})
    assert res["offered_qps"] >= res["achieved_qps"] > 0
    p = tmp_path / "BENCH_local.jsonl"
    p.write_text(benchmark_json("serve_kmeans_sustained", res) + "\n")
    assert check_jsonl.check_file(str(p), provenance=True) == []


def test_serve_bench_row_satisfies_the_checker(tmp_path, mesh):
    """Round-trip: what serve.bench emits through benchmark_json must
    pass invariant 7 as-is — even teed into a bench file."""
    from harp_tpu.serve.bench import benchmark
    from harp_tpu.utils.metrics import benchmark_json

    res = benchmark(app="kmeans", n_requests=12, rows_per_request=1,
                    burst=4, ladder=(1, 8),
                    state_shape={"k": 4, "d": 8})
    p = tmp_path / "BENCH_local.jsonl"
    p.write_text(benchmark_json("serve_kmeans", res) + "\n")
    assert check_jsonl.check_file(str(p), provenance=True) == []


def test_lint_cli_row_satisfies_the_checker(tmp_path, capsys):
    """Round-trip: the line `python -m harp_tpu lint --json` prints must
    pass invariant 6 as-is — even teed into a bench file."""
    from harp_tpu.analysis import cli as lint_cli

    lint_cli.main(["--json", "--layer", "ast"])
    line = capsys.readouterr().out.strip().splitlines()[-1]
    p = tmp_path / "BENCH_local.jsonl"
    p.write_text(line + "\n")
    assert check_jsonl.check_file(str(p), provenance=True) == []


def test_ingest_row_invariants(tmp_path):
    """Invariant 8: ingest rows must be stamped, overlap_efficiency in
    [0, 1], and host/point rates positive — a non-positive rate means
    the instrumented epoch loop never ran."""
    stamp = {"backend": "cpu", "date": "2026-08-04", "commit": "abc1234"}
    base = {"kind": "ingest", "config": "kmeans_ingest_ab_smoke",
            "overlap_efficiency": 0.97, "host_gb_per_sec": 4.2,
            "points_per_sec": 2.5e6}
    rows = [
        {**base, **stamp},                                   # fine
        base,                                                # unstamped
        {**base, "overlap_efficiency": 1.2, **stamp},        # oe > 1
        {**base, "host_gb_per_sec": 0.0, **stamp},           # rate <= 0
        {**base, "points_per_sec": -5.0, **stamp},           # negative
        {**base, "overlap_efficiency": None, **stamp},       # missing
    ]
    p = tmp_path / "rows.jsonl"
    p.write_text("".join(json.dumps(r) + "\n" for r in rows))
    errors = check_jsonl.check_file(str(p))
    assert len(errors) == 5
    assert ":2:" in errors[0] and "provenance" in errors[0]
    assert ":3:" in errors[1] and "overlap_efficiency" in errors[1]
    assert ":4:" in errors[2] and "host_gb_per_sec" in errors[2]
    assert ":5:" in errors[3] and "points_per_sec" in errors[3]
    assert ":6:" in errors[4] and "overlap_efficiency" in errors[4]


def test_ingest_bench_row_satisfies_the_checker(tmp_path, mesh):
    """Round-trip: benchmark_ingest through benchmark_json must pass
    invariant 8 as-is — even teed into a bench file."""
    import numpy as np

    from harp_tpu.models.kmeans_stream import benchmark_ingest
    from harp_tpu.utils.metrics import benchmark_json

    rng = np.random.default_rng(8)
    pts = rng.normal(size=(2048, 8)).astype(np.float16)
    f = tmp_path / "pts.npy"
    np.save(f, pts)
    res = benchmark_ingest(np.load(f, mmap_mode="r"), k=4, iters=2,
                           chunk_points=512, mesh=mesh,
                           disk_bytes=f.stat().st_size)
    p = tmp_path / "BENCH_local.jsonl"
    p.write_text(benchmark_json("kmeans_ingest", res) + "\n")
    assert check_jsonl.check_file(str(p), provenance=True) == []


# -- invariant 10: plan rows (PR 11) ----------------------------------------

def _plan_row(**over):
    """A minimal valid plan row; forge one field per test below."""
    site = {"site": "kmeans.py:346", "primitive": "psum",
            "verb": "allreduce", "schedule": "keep",
            "sheet_bytes": 2120, "predicted_bytes": 2120,
            "cost_s": 1e-7, "alternatives": {}, "candidates": {},
            "flip_candidate": None}
    row = {"kind": "plan", "config": "plan", "program": "kmeans.fit",
           "topology": "sim_ring_8", "rates_source": "declared",
           "sites": [site], "predicted_bytes_total": 2120,
           "flip_candidates": [], "backend": "cpu",
           "date": "2026-08-04", "commit": "abc1234"}
    row.update(over)
    return row


def _plan_errs(row):
    return check_jsonl._check_plan_row("t", 1, row)


def test_plan_row_valid_round_trip(tmp_path):
    p = tmp_path / "rows.jsonl"
    p.write_text(json.dumps(_plan_row()) + "\n")
    assert check_jsonl.check_file(str(p)) == []


def test_plan_row_requires_provenance():
    row = _plan_row()
    del row["backend"]
    assert any("provenance" in e for e in _plan_errs(row))


def test_plan_row_rejects_unknown_program_and_topology():
    assert any("unregistered program" in e
               for e in _plan_errs(_plan_row(program="made.up")))
    assert any("unknown topology" in e
               for e in _plan_errs(_plan_row(topology="v9000")))


def test_plan_row_rejects_unknown_and_non_keep_schedules():
    row = _plan_row()
    row["sites"][0]["schedule"] = "teleport"
    assert any("unknown schedule" in e for e in _plan_errs(row))
    # a non-"keep" CHOICE is a bypassed flip gate, even with coherent
    # bytes — the planner fails closed by contract
    row = _plan_row()
    row["sites"][0]["schedule"] = "wire_int8"
    row["sites"][0]["predicted_bytes"] = 530
    assert any("fails closed" in e for e in _plan_errs(row))


def test_plan_row_predicted_bytes_must_equal_sheet_scaling():
    # drifted keep prediction: the plan prices a program we do not run
    row = _plan_row()
    row["sites"][0]["predicted_bytes"] = 2121
    errs = _plan_errs(row)
    assert any("must equal the frozen scaling" in e for e in errs)
    # negative / non-int bytes are refused before the equality check
    row = _plan_row()
    row["sites"][0]["sheet_bytes"] = -5
    assert any("non-negative integer" in e for e in _plan_errs(row))


def test_plan_cli_rows_satisfy_the_checker(tmp_path, capsys, mesh):
    """Round-trip: python -m harp_tpu plan --json rows pass invariant
    10 as-is — even teed into a committed file."""
    from harp_tpu.plan import cli

    rc = cli.main(["--program", "mfsgd.epoch", "--json"])
    assert rc == 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    p = tmp_path / "rows.jsonl"
    p.write_text(line + "\n")
    assert check_jsonl.check_file(str(p)) == []


# ---------------------------------------------------------------------------
# Invariant 11: trace rows (PR 12)
# ---------------------------------------------------------------------------

_TSTAMP = {"backend": "cpu", "date": "2026-08-05", "commit": "abc1234"}


def _trace_rows():
    """A minimal complete 2-request timeline (1 served, 1 shed)."""
    return [
        {"kind": "trace", "ev": "event", "req": 1, "name": "arrival",
         "ts": 0.001, **_TSTAMP},
        {"kind": "trace", "ev": "event", "req": 2, "name": "arrival",
         "ts": 0.002, **_TSTAMP},
        {"kind": "trace", "ev": "event", "req": 2, "name": "shed",
         "ts": 0.002, "reason": "queue_full", **_TSTAMP},
        {"kind": "trace", "ev": "request", "req": 2, "ts": 0.002,
         "t0": 0.002, "outcome": "shed", "n_events": 2, **_TSTAMP},
        {"kind": "trace", "ev": "batch", "ts": 0.004, "seq": 0,
         "t0": 0.003, "rung": 8, "rows": 3, "padding_frac": 0.625,
         "members": [[1, 0, 3]], "events": [{"name": "form", "ts": 0.003}],
         **_TSTAMP},
        {"kind": "trace", "ev": "request", "req": 1, "ts": 0.004,
         "t0": 0.001, "outcome": "served", "n_events": 3, **_TSTAMP},
    ]


def _trace_errs(tmp_path, rows):
    p = tmp_path / "rows.jsonl"
    p.write_text("".join(json.dumps(r) + "\n" for r in rows))
    return check_jsonl.check_file(str(p))


def test_trace_rows_valid_round_trip(tmp_path):
    assert _trace_errs(tmp_path, _trace_rows()) == []


def test_trace_row_requires_provenance_and_known_shape(tmp_path):
    rows = _trace_rows()
    rows[0] = {k: v for k, v in rows[0].items() if k != "backend"}
    errs = _trace_errs(tmp_path, rows)
    assert any("missing provenance" in e and ":1:" in e for e in errs)
    rows = _trace_rows()
    rows[0]["ev"] = "wormhole"
    assert any("ev='wormhole'" in e for e in _trace_errs(tmp_path, rows))


def test_trace_rows_must_be_monotone(tmp_path):
    rows = _trace_rows()
    rows[2]["ts"] = 0.0005  # earlier than row 1's 0.001
    errs = _trace_errs(tmp_path, rows)
    assert any("decreased" in e and "monotone" in e for e in errs)
    rows = _trace_rows()
    rows[1]["ts"] = "later"
    assert any("non-negative number" in e
               for e in _trace_errs(tmp_path, rows))


def test_trace_request_spans_must_terminate(tmp_path):
    # drop request 1's terminal row: its events now dangle
    rows = [r for r in _trace_rows()
            if not (r["ev"] == "request" and r["req"] == 1)]
    errs = _trace_errs(tmp_path, rows)
    assert any("no terminated outcome row" in e and "[1]" in e
               for e in errs)
    # an unknown outcome is refused at the row
    rows = _trace_rows()
    rows[-1]["outcome"] = "vanished"
    assert any("outcome='vanished'" in e
               for e in _trace_errs(tmp_path, rows))


def test_trace_counts_reconcile_with_degraded_ledger(tmp_path):
    serve = {"kind": "serve", "app": "kmeans", "qps": 100.0,
             "p50_ms": 1.0, "p95_ms": 2.0, "p99_ms": 3.0,
             "steady_compiles": 0, "offered_requests": 2,
             "served_requests": 1, "shed_requests": 1,
             "failed_requests": 0, "shed_frac": 0.5,
             "deadline_miss_frac": 0.0, "fault_retries": 0, **_TSTAMP}
    assert _trace_errs(tmp_path, [serve] + _trace_rows()) == []
    # a ledger claiming different outcome totals must fail the file
    bad = dict(serve, served_requests=2, shed_requests=0)
    errs = _trace_errs(tmp_path, [bad] + _trace_rows())
    assert any("do not reconcile" in e for e in errs)


def test_trace_outcome_vocabulary_in_sync():
    """check_jsonl freezes the trace vocabularies standalone; drift
    from the live reqtrace module fails here."""
    from harp_tpu.utils import reqtrace

    assert tuple(reqtrace.OUTCOMES) == check_jsonl.KNOWN_TRACE_OUTCOMES


def test_exported_trace_rows_satisfy_the_checker(tmp_path, mesh):
    """Round-trip: a real continuous-plane run through
    telemetry.export passes invariant 11 as-is."""
    import numpy as np

    from harp_tpu.serve.engines import ENGINES
    from harp_tpu.serve.server import Server
    from harp_tpu.utils import telemetry

    with telemetry.scope(True):
        rng = np.random.default_rng(3)
        srv = Server("kmeans",
                     state=ENGINES["kmeans"].synthetic_state(rng, k=4, d=8),
                     mesh=mesh, ladder=(1, 8),
                     cache_dir=str(tmp_path / "aot"))
        srv.startup()
        r = srv.make_runner(max_queue_rows=4)
        r.submit("A", {"id": "A", "x": rng.normal(size=(3, 8)).tolist()},
                 now=0.001)
        r.submit("B", {"id": "B", "x": rng.normal(size=(3, 8)).tolist()},
                 now=0.002)
        r.step(0.003)
        r.step(0.004)
        p = tmp_path / "run.jsonl"
        telemetry.export(str(p))
    assert check_jsonl.check_file(str(p)) == []
    trace = [json.loads(ln) for ln in p.read_text().splitlines()
             if json.loads(ln).get("kind") == "trace"]
    assert sum(r.get("ev") == "request" for r in trace) == 2


def test_golden_trace_fixture_is_clean_and_loads():
    """The committed 2-request golden trace (tests/data) passes the
    checker — the fixture the trace CLI smoke drives."""
    p = os.path.join(os.path.dirname(__file__), "data",
                     "golden_trace.jsonl")
    assert check_jsonl.check_file(p) == []
    from harp_tpu.utils import reqtrace, telemetry

    rows = telemetry.load_rows(p)["trace"]
    s = reqtrace.summarize_rows(rows)
    assert (s["requests"], s["served"], s["shed"], s["failed"]) == \
        (2, 1, 1, 0)
    assert s["unterminated"] == []


# -- invariant 12: model rows (PR 13) ---------------------------------------

def _model_row(**over):
    """A minimal valid model row; forge one field per test below."""
    row = {"kind": "model", "program": "kmeans.fit", "config": None,
           "configs": ["kmeans", "kmeans_int8"],
           "topology": "v4_32", "rates_source": "declared",
           "metric": "program_runs_per_sec",
           "predicted_s": 0.0400001,
           "predicted_rate": 25.0,
           "bound": "overhead",
           "terms": {"compute_s": 0.0, "memory_s": 0.0,
                     "wire_s": 1e-7, "overhead_s": 0.04},
           "backend": "cpu", "date": "2026-08-05", "commit": "abc1234"}
    row.update(over)
    return row


def _model_errs(row):
    return check_jsonl._check_model_row("t", 1, row)


def test_model_row_valid_round_trip(tmp_path):
    p = tmp_path / "rows.jsonl"
    p.write_text(json.dumps(_model_row()) + "\n")
    assert check_jsonl.check_file(str(p)) == []


def test_model_row_requires_provenance():
    row = _model_row()
    del row["commit"]
    assert any("provenance" in e for e in _model_errs(row))


def test_model_row_needs_a_subject():
    # a prediction about nothing prices nothing
    row = _model_row(program=None, config=None, configs=[])
    assert any("neither a program nor a config" in e
               for e in _model_errs(row))


def test_model_row_rejects_unknown_program_and_config():
    assert any("unregistered program" in e
               for e in _model_errs(_model_row(program="made.up")))
    assert any("not in the frozen list" in e
               for e in _model_errs(_model_row(config="warp_drive")))
    assert any("not in the frozen list" in e
               for e in _model_errs(_model_row(configs=["kmeans", "nope"])))


def test_model_row_rejects_bad_vocabularies():
    assert any("rates_source" in e
               for e in _model_errs(_model_row(rates_source="vibes")))
    assert any("bound" in e
               for e in _model_errs(_model_row(bound="luck")))


def test_model_row_predicted_seconds_must_be_positive():
    for bad in (0, -1.0, None, "fast"):
        assert any("predicted_s" in e
                   for e in _model_errs(_model_row(predicted_s=bad))), bad


def test_model_row_terms_must_sum_to_total():
    row = _model_row(predicted_s=0.9)  # terms sum to 0.0400001
    assert any("must sum to the total" in e for e in _model_errs(row))
    # a missing or negative term is equally loud
    row = _model_row()
    del row["terms"]["wire_s"]
    assert any("terms" in e for e in _model_errs(row))
    row = _model_row()
    row["terms"]["wire_s"] = -1e-9
    assert any("terms" in e for e in _model_errs(row))


def test_model_row_bound_must_name_the_largest_term():
    row = _model_row(bound="compute")  # overhead dominates
    assert any("largest term" in e for e in _model_errs(row))


def test_model_vocabularies_in_sync_with_perfmodel():
    """The frozen invariant-12 vocabularies mirror harp_tpu.perfmodel
    (this file stays standalone; drift fails here, tier-1)."""
    from harp_tpu import perfmodel

    assert tuple(perfmodel.BOUNDS) == check_jsonl.KNOWN_MODEL_BOUNDS
    assert tuple(perfmodel.RATES_SOURCES) == \
        check_jsonl.KNOWN_MODEL_RATES_SOURCES


# -- invariant 13: health rows (PR 14) --------------------------------------

_HSTAMP = {"backend": "cpu", "date": "2026-08-05", "commit": "abc1234"}


def _health_row(**over):
    """A minimal valid slo_burn health row; forge one field per test."""
    row = {"kind": "health", "detector": "slo_burn", "severity": "warn",
           "tag": "serve.kmeans", "offered": 10, "served": 8, "shed": 2,
           "failed": 0, "fast_burn": 4.0, "slow_burn": 2.0,
           "breaches": 1, **_HSTAMP}
    row.update(over)
    return row


def _health_errs(row):
    return check_jsonl._check_health_row("t", 1, row)


def _skew_trigger_row(**plan_over):
    plan = {"phase": "p", "unit": "tokens",
            "moves": [{"id": "f1", "from": 0, "to": 2, "work": 12.0}],
            "ratio_before": 1.8, "ratio_after": 1.05,
            "work_after": [10.0, 10.0, 11.0, 9.0]}
    plan.update(plan_over)
    return _health_row(detector="skew_trigger", phase="p",
                       wasted_frac=0.42, supersteps=3, consecutive=3,
                       plan=plan)


def test_health_row_valid_round_trip(tmp_path):
    rows = [_health_row(), _skew_trigger_row(),
            _health_row(detector="budget_drift", violations=2,
                        worst="h2d_calls used 2 > budget 1"),
            _health_row(detector="evidence_regression", severity="info",
                        config="kmeans", verdict="confirmed",
                        measured=380.9, incumbent=381.2)]
    p = tmp_path / "rows.jsonl"
    p.write_text("".join(json.dumps(r) + "\n" for r in rows))
    assert check_jsonl.check_file(str(p), provenance=True) == []


def test_health_row_requires_provenance_and_vocabularies():
    row = _health_row()
    del row["backend"]
    assert any("provenance" in e for e in _health_errs(row))
    assert any("detector='gut_feeling'" in e
               for e in _health_errs(_health_row(detector="gut_feeling")))
    assert any("severity='mild'" in e
               for e in _health_errs(_health_row(severity="mild")))
    assert any("verdict='vibes'" in e
               for e in _health_errs(_health_row(verdict="vibes")))


def test_health_row_counts_and_ratios_nonnegative():
    assert any("shed=-1" in e
               for e in _health_errs(_health_row(shed=-1)))
    assert any("breaches=1.5" in e
               for e in _health_errs(_health_row(breaches=1.5)))
    assert any("fast_burn" in e
               for e in _health_errs(_health_row(fast_burn=-0.1)))
    assert any("wasted_frac" in e
               for e in _health_errs(_health_row(wasted_frac="lots")))


def test_evidence_regression_row_requires_verdict():
    row = _health_row(detector="evidence_regression", config="kmeans")
    assert any("verdict=None" in e for e in _health_errs(row))
    row["verdict"] = "model_invalidated"
    assert _health_errs(row) == []


def test_skew_trigger_row_requires_replayable_plan():
    assert _health_errs(_skew_trigger_row()) == []
    # no plan at all: the elastic hook has no payload
    row = _skew_trigger_row()
    del row["plan"]
    assert any("suggest_rebalance object" in e for e in _health_errs(row))
    # forged plan internals each trip their own violation
    assert any("worker index" in e for e in _health_errs(
        _skew_trigger_row(moves=[{"id": "f1", "from": -1, "to": 2,
                                  "work": 1.0}])))
    assert any("work=None" in e for e in _health_errs(
        _skew_trigger_row(moves=[{"id": "f1", "from": 0, "to": 2,
                                  "work": None}])))
    assert any("moves='nope'" in e
               for e in _health_errs(_skew_trigger_row(moves="nope")))
    assert any("ratio_after" in e for e in _health_errs(
        _skew_trigger_row(ratio_after=-2.0)))


def test_health_vocabularies_in_sync_with_health_module():
    """check_jsonl freezes the health vocabularies standalone; drift
    from the live harp_tpu.health module fails here (tier-1)."""
    from harp_tpu import health

    assert tuple(health.DETECTORS) == check_jsonl.KNOWN_HEALTH_DETECTORS
    assert tuple(health.SEVERITIES) == check_jsonl.KNOWN_HEALTH_SEVERITIES
    assert tuple(health.VERDICTS) == check_jsonl.KNOWN_HEALTH_VERDICTS


def test_exported_health_rows_satisfy_the_checker(tmp_path):
    """Round-trip: what the monitor exports (via telemetry.export) must
    pass invariant 13 as-is — even teed into a bench file."""
    from harp_tpu import health
    from harp_tpu.utils import skew, telemetry

    with telemetry.scope(True):
        for _ in range(health.TRIGGER_SUPERSTEPS):
            skew.record_partition(
                "files", [10, 1, 0, 1], unit="bytes",
                units=[[("a", 6), ("b", 4)], [("c", 1)], [], [("d", 1)]])
        health.monitor.observe_budget("serve.kmeans",
                                      [("h2d_calls", 2, 1)])
        p = tmp_path / "BENCH_local.jsonl"
        telemetry.export(str(p))
    assert check_jsonl.check_file(str(p), provenance=True) == []


def test_golden_health_fixture_is_clean_and_summarizes():
    """The committed golden health fixture (tests/data) passes the
    checker — the fixture the health CLI smoke drives."""
    p = os.path.join(os.path.dirname(__file__), "data",
                     "golden_health.jsonl")
    assert check_jsonl.check_file(p) == []
    from harp_tpu import health
    from harp_tpu.utils import telemetry

    rows = telemetry.load_rows(p)["health"]
    s = health.summarize_rows(rows)
    assert s["findings"] == 4
    assert s["worst_severity"] == "page"
    assert s["actionable"] == 3  # page + warn + warn; confirmed is info


# ---------------------------------------------------------------------------
# Invariant 14: elastic rows (PR 15)
# ---------------------------------------------------------------------------

_ESTAMP = {"backend": "cpu", "date": "2026-08-05", "commit": "abc1234"}


def _elastic_row(event="rebalance", **over):
    base = {
        "rebalance": {"kind": "elastic", "event": "rebalance",
                      "phase": "mfsgd.epochs", "n_workers": 8, "moves": 3,
                      "loads_before": [4000.0] + [150.0] * 7,
                      "loads_after": [640.0, 630.0] + [630.0] * 6,
                      "total": 5050.0, "wasted_frac_before": 0.84,
                      "wasted_frac_after": 0.02, "trigger_supersteps": 3,
                      **_ESTAMP},
        "shrink": {"kind": "elastic", "event": "shrink",
                   "phase": "mfsgd.epochs", "lost_worker": 3,
                   "site": "dispatch", "ordinal": 2,
                   "n_workers_before": 8, "n_workers_after": 7,
                   "capacity_frac": 0.875, **_ESTAMP},
        "resume": {"kind": "elastic", "event": "resume",
                   "phase": "mfsgd.epochs", "n_workers": 7, "from_step": 0,
                   "loads": [721.0] * 7, "total": 5047.0,
                   "wasted_frac": 0.0, "replayed_plan": True, **_ESTAMP},
    }[event]
    base = dict(base)
    base.update(over)
    return base


def _elastic_errs(row):
    return check_jsonl._check_elastic_row("t", 1, row)


def test_elastic_rows_valid_round_trip(tmp_path):
    # fix the resume loads to actually sum to total
    resume = _elastic_row("resume", loads=[721.0] * 7, total=5047.0)
    rows = [_elastic_row("rebalance",
                         loads_before=[4000.0] + [150.0] * 7,
                         loads_after=[631.25] * 8, total=5050.0),
            _elastic_row("shrink"), resume]
    p = tmp_path / "rows.jsonl"
    p.write_text("".join(json.dumps(r) + "\n" for r in rows))
    assert check_jsonl.check_file(str(p), provenance=True) == []


def test_elastic_row_requires_stamp_and_event_vocab():
    row = _elastic_row("shrink")
    del row["backend"]
    assert any("provenance" in e for e in _elastic_errs(row))
    grow = _elastic_row("shrink")
    grow["event"] = "grow"
    assert any("event='grow'" in e for e in _elastic_errs(grow))


def test_elastic_rebalance_row_forgeries_fire():
    ok = _elastic_row("rebalance",
                      loads_before=[4000.0] + [150.0] * 7,
                      loads_after=[631.25] * 8, total=5050.0)
    assert _elastic_errs(ok) == []
    # loads not summing to total
    assert any("conserve work" in e for e in _elastic_errs(
        _elastic_row("rebalance", loads_after=[1.0] * 8)))
    # loads without a total
    bad = _elastic_row("rebalance")
    del bad["total"]
    assert any("total" in e for e in _elastic_errs(bad))
    # negative / non-list loads
    assert any("non-negative" in e for e in _elastic_errs(
        _elastic_row("rebalance", loads_before=[-1.0] * 8,
                     total=-8.0)))
    assert any("non-empty list" in e for e in _elastic_errs(
        _elastic_row("rebalance", loads_before="heavy")))
    # a "rebalance" that made things worse
    assert any("worse" in e for e in _elastic_errs(
        _elastic_row("rebalance", wasted_frac_before=0.1,
                     wasted_frac_after=0.5,
                     loads_before=[631.25] * 8,
                     loads_after=[631.25] * 8)))
    # missing before/after evidence entirely
    nofrac = _elastic_row("rebalance", loads_before=[631.25] * 8,
                          loads_after=[631.25] * 8)
    del nofrac["wasted_frac_before"]
    assert any("before/after" in e.lower() or "before AND after" in e
               for e in _elastic_errs(nofrac))
    # fractions outside [0, 1]
    assert any("[0, 1]" in e for e in _elastic_errs(
        _elastic_row("rebalance", wasted_frac_after=1.5)))


def test_elastic_shrink_row_needs_strictly_fewer_survivors():
    assert _elastic_errs(_elastic_row("shrink")) == []
    assert any("survivor count" in e for e in _elastic_errs(
        _elastic_row("shrink", n_workers_after=8)))
    assert any("survivor count" in e for e in _elastic_errs(
        _elastic_row("shrink", n_workers_before=None)))
    assert any("lost_worker=-1" in e for e in _elastic_errs(
        _elastic_row("shrink", lost_worker=-1)))


def test_elastic_vocab_in_sync_with_elastic_module():
    import harp_tpu.elastic as E

    assert tuple(E.EVENTS) == check_jsonl.KNOWN_ELASTIC_EVENTS


# ---------------------------------------------------------------------------
# Invariant 15: profile attribution rows (PR 16)
# ---------------------------------------------------------------------------

_PSTAMP = {"backend": "cpu", "date": "2026-08-06", "commit": "abc1234"}


def _profile_row(**over):
    base = {
        "kind": "profile", "app": "lda", "program": "lda.epoch",
        "wall_s": 0.04, "reps": 4, "n_devices": 8,
        "terms": {"mxu_s": 0.001, "elementwise_s": 0.002,
                  "gather_dus_s": 0.0, "scatter_s": 0.0,
                  "wire_s": 0.033, "overhead_s": 0.004},
        "bound": "wire", "sum_rel_err": 0.02, "wire_bytes": 2308,
        "wire_sites": 3, "wire_unmatched": 0, "dispatches": 4,
        "dispatches_per_rep": 1, "dispatch_reconciled": True,
        "compiles_in_window": 0, "reconciled": True, **_PSTAMP}
    base.update(over)
    return base


def _profile_errs(row):
    return check_jsonl._check_profile_row("t", 1, row)


def test_profile_row_valid_round_trip(tmp_path):
    assert _profile_errs(_profile_row()) == []
    p = tmp_path / "PROFILE_attrib.jsonl"
    p.write_text(json.dumps(_profile_row()) + "\n")
    assert check_jsonl.check_file(str(p)) == []


def test_profile_row_requires_provenance_and_vocabularies():
    row = _profile_row()
    del row["backend"]
    assert any("provenance" in e for e in _profile_errs(row))
    assert any("app=" in e for e in _profile_errs(
        _profile_row(app="word2vec")))
    # program must be a registered lint driver, not free text
    assert any("unregistered program" in e for e in _profile_errs(
        _profile_row(program="lda.mystery")))


def test_profile_row_buckets_must_sum_to_wall():
    assert any("sum to" in e for e in _profile_errs(
        _profile_row(terms={"mxu_s": 0.001, "elementwise_s": 0.002,
                            "gather_dus_s": 0.0, "scatter_s": 0.0,
                            "wire_s": 0.01, "overhead_s": 0.004})))


def test_profile_row_rejects_unknown_bucket_name():
    bad = _profile_row()
    bad["terms"] = dict(bad["terms"])
    bad["terms"]["dma_s"] = bad["terms"].pop("wire_s")
    assert any("frozen mechanism" in e for e in _profile_errs(bad))


def test_profile_row_bound_must_name_the_largest_bucket():
    assert any("largest bucket" in e for e in _profile_errs(
        _profile_row(bound="mxu")))
    assert any("bound=" in e for e in _profile_errs(
        _profile_row(bound="hbm")))


def test_profile_row_fails_closed_on_reconciliation():
    # cross-check counters must be literally clean, not merely present
    assert any("exactly 0" in e for e in _profile_errs(
        _profile_row(compiles_in_window=1)))
    assert any("exactly 0" in e for e in _profile_errs(
        _profile_row(wire_unmatched=2)))
    assert any("dispatches=" in e for e in _profile_errs(
        _profile_row(dispatches=7)))
    assert any("sum_rel_err" in e for e in _profile_errs(
        _profile_row(sum_rel_err=0.9)))


def test_profile_vocabularies_in_sync_with_profile_module():
    """check_jsonl freezes the attribution vocabularies standalone;
    drift from the live harp_tpu.profile module fails here (tier-1)."""
    from harp_tpu.health import sentinel
    from harp_tpu.profile import attribution

    assert tuple(attribution.BUCKETS) == check_jsonl.KNOWN_PROFILE_BUCKETS
    assert tuple(attribution.PROFILE_APPS) == check_jsonl.KNOWN_PROFILE_APPS
    assert attribution.SUM_REL_TOL == check_jsonl.PROFILE_SUM_REL_TOL
    assert "profile_drift" in sentinel.DETECTORS


def test_golden_profile_fixture_is_clean_and_grades():
    """The committed golden profile fixture (tests/data) passes the
    checker, and the health grader reads it as drift-free against
    itself — the fixture the profile CLI smoke drives."""
    p = os.path.join(os.path.dirname(__file__), "data",
                     "golden_profile.jsonl")
    assert check_jsonl.check_file(p) == []
    import json as _json

    from harp_tpu.health import grade as HG

    rows = [_json.loads(l) for l in open(p)]
    committed = {r["app"]: r for r in rows}
    assert sorted(committed) == ["kmeans", "lda"]
    for r in rows:
        fresh = dict(r, terms=dict(r["terms"]))
        assert HG.grade_profile_row(fresh, ".", committed=committed) is None


def test_committed_profile_attribution_covers_every_app():
    """PROFILE_attrib.jsonl (the committed baseline the profile_drift
    detector grades against) carries one reconciled row per app in the
    frozen vocabulary — including the four PR-16 newly priced apps."""
    p = os.path.join(os.path.dirname(__file__), "..",
                     "PROFILE_attrib.jsonl")
    assert check_jsonl.check_file(p) == []
    rows = [json.loads(l) for l in open(p)]
    apps = {r["app"] for r in rows if r.get("kind") == "profile"}
    assert apps == set(check_jsonl.KNOWN_PROFILE_APPS)
    assert all(r["reconciled"] is True for r in rows)


# ---------------------------------------------------------------------------
# Invariant 16: steptrace rows (PR 18)
# ---------------------------------------------------------------------------

_TSTAMP = {"backend": "cpu", "date": "2026-08-06", "commit": "abc1234"}


def _st_flight(**over):
    fl = {"dispatches": 0, "readbacks": 0, "h2d_calls": 0, "compiles": 0}
    fl.update(over)
    return fl


def _st_rows():
    """A minimal valid forged timeline: one run, one completed span,
    one dispatch mark, one skew lane — internally reconciled."""
    fl = _st_flight(dispatches=1, readbacks=1)
    return [
        {"kind": "steptrace", "ev": "mark", "run": 1, "ts": 0.01,
         "source": "flight", "name": "dispatch", "seq": 0,
         "site": "kmeans.fit", **_TSTAMP},
        {"kind": "steptrace", "ev": "lane", "run": 1, "ts": 0.015,
         "seq": 0, "phase": "kmeans.fit", "work": [1.0] * 8,
         "unit": "points", **_TSTAMP},
        {"kind": "steptrace", "ev": "superstep", "run": 1, "seq": 0,
         "step": 0, "phase": "kmeans.fit", "outcome": "completed",
         "t0": 0.005, "ts": 0.02, "flight": fl, **_TSTAMP},
        {"kind": "steptrace", "ev": "run", "run": 1,
         "phase": "kmeans.fit", "t0": 0.0, "ts": 0.03, "supersteps": 1,
         "marks": 1, "lanes": 1,
         "outcomes": {"completed": 1, "faulted": 0, "rebalanced": 0,
                      "resumed": 0},
         "flight": dict(fl), "span_flight": dict(fl), **_TSTAMP},
    ]


def _st_check(rows, tmp_path, extra=()):
    p = tmp_path / "steptrace.jsonl"
    p.write_text("".join(json.dumps(r) + "\n"
                         for r in list(extra) + list(rows)))
    return check_jsonl.check_file(str(p), provenance=True)


def test_steptrace_rows_valid_round_trip(tmp_path):
    assert _st_check(_st_rows(), tmp_path) == []


def test_steptrace_row_requires_provenance_and_vocabularies(tmp_path):
    rows = _st_rows()
    del rows[0]["backend"]
    assert any("provenance" in e for e in _st_check(rows, tmp_path))
    rows = _st_rows()
    rows[0]["ev"] = "epoch"
    assert any("ev='epoch'" in e for e in _st_check(rows, tmp_path))
    rows = _st_rows()
    rows[0]["source"] = "vibes"
    assert any("source='vibes'" in e for e in _st_check(rows, tmp_path))


def test_steptrace_rows_must_be_monotone(tmp_path):
    rows = _st_rows()
    rows[1]["ts"] = 0.001  # lane stamped before the preceding mark
    assert any("monotone" in e for e in _st_check(rows, tmp_path))


def test_steptrace_every_run_must_terminate(tmp_path):
    rows = _st_rows()[:-1]  # drop the terminating run row
    assert any("no terminating run row" in e
               for e in _st_check(rows, tmp_path))
    # and a run row may appear exactly once
    rows = _st_rows() + [_st_rows()[-1]]
    assert any("duplicate steptrace run row" in e
               for e in _st_check(rows, tmp_path))


def test_steptrace_span_outcome_vocabulary_enforced(tmp_path):
    rows = _st_rows()
    rows[2]["outcome"] = "exploded"
    errs = _st_check(rows, tmp_path)
    assert any("outcome='exploded'" in e for e in errs)


def test_steptrace_run_summary_must_rederive(tmp_path):
    # claimed superstep count vs actual span rows
    rows = _st_rows()
    rows[-1]["supersteps"] = 2
    assert any("claims 2 superstep(s)" in e
               for e in _st_check(rows, tmp_path))
    # claimed outcome tally vs span outcomes
    rows = _st_rows()
    rows[-1]["outcomes"] = {"completed": 0, "faulted": 1,
                            "rebalanced": 0, "resumed": 0}
    assert any("do not match the run row's" in e
               for e in _st_check(rows, tmp_path))
    # span flight sums exceeding the run's own flight delta
    rows = _st_rows()
    rows[2]["flight"] = _st_flight(dispatches=3, readbacks=1)
    rows[-1]["span_flight"] = _st_flight(dispatches=3, readbacks=1)
    assert any("cannot own more ops than the run recorded" in e
               for e in _st_check(rows, tmp_path))


def test_steptrace_dispatch_marks_must_match_flight_exactly(tmp_path):
    # drop the dispatch mark but keep the run's flight delta at 1
    rows = [r for r in _st_rows()
            if not (r["ev"] == "mark" and r["name"] == "dispatch")]
    rows[-1]["marks"] = 0
    assert any("must agree EXACTLY" in e for e in _st_check(rows, tmp_path))


def test_steptrace_cannot_outclaim_the_transfer_ledger(tmp_path):
    """A timeline attributing more dispatches than the file's own
    kind:'transfer' rows recorded is forged."""
    transfer = {"kind": "transfer", "op": "dispatch", "calls": 0,
                "bytes": 0, "site": "forged", **_TSTAMP}
    errs = _st_check(_st_rows(), tmp_path, extra=[transfer])
    assert any("cannot own more dispatches" in e for e in errs)


def test_steptrace_elastic_marks_reconcile_event_for_event(tmp_path):
    # an elastic mark with no kind:'elastic' row
    rows = _st_rows()
    rows.insert(1, {"kind": "steptrace", "ev": "mark", "run": 1,
                    "ts": 0.012, "source": "elastic",
                    "name": "rebalance", "seq": 0, "phase": "kmeans.fit",
                    **_TSTAMP})
    rows[-1]["marks"] = 2
    assert any("one story" in e for e in _st_check(rows, tmp_path))
    # and the converse: a timeline-covered elastic row with no mark
    erow = _elastic_row("rebalance",
                        loads_before=[4000.0] + [150.0] * 7,
                        loads_after=[631.25] * 8, total=5050.0,
                        on_timeline=True)
    errs = _st_check(_st_rows(), tmp_path, extra=[erow])
    assert any("one story" in e for e in errs)
    # an UNCOVERED row (manual install outside any run) is legitimate
    erow_off = dict(erow, on_timeline=False)
    assert _st_check(_st_rows(), tmp_path, extra=[erow_off]) == []


def test_steptrace_health_marks_need_sentinel_rows(tmp_path):
    # a finding mark with no kind:'health' row in the file
    rows = _st_rows()
    rows.insert(1, {"kind": "steptrace", "ev": "mark", "run": 1,
                    "ts": 0.012, "source": "health", "name": "slo_burn",
                    "seq": 0, **_TSTAMP})
    rows[-1]["marks"] = 2
    assert any("must exist in the sentinel export" in e
               for e in _st_check(rows, tmp_path))
    # with the matching health row the same file is clean
    assert _st_check(rows, tmp_path, extra=[_health_row()]) == []


def test_steptrace_consume_mark_needs_consumed_trigger_row(tmp_path):
    consume = {"kind": "steptrace", "ev": "mark", "run": 1, "ts": 0.012,
               "source": "health", "name": "consume_skew_trigger",
               "seq": 0, "phase": "p", **_TSTAMP}
    rows = _st_rows()
    rows.insert(1, consume)
    rows[-1]["marks"] = 2
    # no skew_trigger row at all
    assert any("exactly-once handshake" in e
               for e in _st_check(rows, tmp_path))
    # a trigger row that was never consumed does not cover it either
    errs = _st_check(rows, tmp_path, extra=[_skew_trigger_row()])
    assert any("exactly-once handshake" in e for e in errs)
    # the consumed row closes the loop
    consumed = dict(_skew_trigger_row(), consumed=True)
    assert _st_check(rows, tmp_path, extra=[consumed]) == []


def test_steptrace_vocab_in_sync_with_steptrace_module():
    from harp_tpu.utils import steptrace as ST

    assert ST.EVS == check_jsonl.KNOWN_STEPTRACE_EVS
    assert ST.OUTCOMES == check_jsonl.KNOWN_STEPTRACE_OUTCOMES
    assert ST.SOURCES == check_jsonl.KNOWN_STEPTRACE_SOURCES
    assert ST.FLIGHT_KEYS == check_jsonl.KNOWN_STEPTRACE_FLIGHT_KEYS


def test_golden_steptrace_fixture_is_clean_and_summarizes():
    """The committed golden timeline fixture (tests/data) passes the
    checker — the fixture the timeline CLI smoke drives."""
    p = os.path.join(os.path.dirname(__file__), "data",
                     "golden_steptrace.jsonl")
    assert check_jsonl.check_file(p) == []
    from harp_tpu.utils import steptrace, telemetry

    rows = telemetry.load_rows(p)["steptrace"]
    s = steptrace.summarize_rows(rows)
    assert s["runs"] == 1 and s["unterminated"] == []
    assert s["supersteps"] >= 2 and s["dispatch_mismatch"] == []


# ---------------------------------------------------------------------------
# invariant 17: memory-ledger rows (PR 19)
# ---------------------------------------------------------------------------

def _mem_rows():
    """A minimal valid forged ledger: stage → donate → dispatch →
    output → restore → free → executable → vmem pass → summary,
    internally reconciled (the exact shape memrec.export_jsonl
    writes)."""
    return [
        {"kind": "memory", "ev": "buffer", "event": "staged", "buf": 1,
         "bytes": 1024, "label": "mesh.shard_array", "seq": 1,
         "live_bytes": 1024, "peak_bytes": 1024, **_TSTAMP},
        {"kind": "memory", "ev": "buffer", "event": "donated", "buf": 1,
         "bytes": 1024, "label": "mesh.shard_array", "seq": 2,
         "live_bytes": 0, "peak_bytes": 1024, **_TSTAMP},
        {"kind": "memory", "ev": "dispatch", "label": "serve.kmeans.b8",
         "seq": 3, "donated": [1], "donated_bytes": 1024,
         "live_bytes": 0, "peak_bytes": 1024, **_TSTAMP},
        {"kind": "memory", "ev": "buffer", "event": "output", "buf": 2,
         "bytes": 4, "label": "serve.kmeans.b8", "seq": 4,
         "live_bytes": 4, "peak_bytes": 1024, **_TSTAMP},
        {"kind": "memory", "ev": "buffer", "event": "restored", "buf": 0,
         "bytes": 4096, "label": "ckpt:step_1", "seq": 5,
         "live_bytes": 4, "peak_bytes": 1024, **_TSTAMP},
        {"kind": "memory", "ev": "buffer", "event": "freed", "buf": 2,
         "bytes": 4, "label": "serve.kmeans.b8", "seq": 6,
         "live_bytes": 0, "peak_bytes": 1024, **_TSTAMP},
        {"kind": "memory", "ev": "executable", "name": "serve.kmeans.b8",
         "seq": 7, "source": "compile", "argument_bytes": 256,
         "output_bytes": 256, "temp_bytes": 0,
         "generated_code_bytes": 0, "exec_hbm_bytes": 512, **_TSTAMP},
        {"kind": "memory", "ev": "vmem_check",
         "kernel": "kmeans.partials_int8", "seq": 8,
         "predicted_bytes": 1048576, "budget_bytes": 14680064,
         "fits": True, "refused": False, **_TSTAMP},
        {"kind": "memory", "ev": "summary", "seq": 9, "events": 8,
         "staged_bytes": 1024, "freed_bytes": 4, "donated_bytes": 1024,
         "peak_hbm_bytes": 1024, "live_hbm_bytes": 0,
         "hbm_bytes": 17179869184, "headroom_frac": 1.0,
         "executables": 1, "exec_hbm_bytes": 512, "vmem_checks": 1,
         "vmem_refusals": 0, **_TSTAMP},
    ]


def _mem_check(rows, tmp_path):
    p = tmp_path / "memory.jsonl"
    p.write_text("".join(json.dumps(r) + "\n" for r in rows))
    return check_jsonl.check_file(str(p), provenance=True)


def test_memory_rows_valid_round_trip(tmp_path):
    assert _mem_check(_mem_rows(), tmp_path) == []


def test_memory_row_requires_provenance_and_vocabularies(tmp_path):
    rows = _mem_rows()
    del rows[0]["backend"]
    assert any("provenance" in e for e in _mem_check(rows, tmp_path))
    rows = _mem_rows()
    rows[0]["ev"] = "malloc"
    assert any("ev='malloc'" in e for e in _mem_check(rows, tmp_path))
    rows = _mem_rows()
    rows[0]["event"] = "leaked"
    assert any("event='leaked'" in e for e in _mem_check(rows, tmp_path))
    rows = _mem_rows()
    rows[6]["source"] = "vibes"
    assert any("'compile' or 'cache'" in e
               for e in _mem_check(rows, tmp_path))


def test_memory_seq_must_strictly_increase(tmp_path):
    rows = _mem_rows()
    rows[1]["seq"] = 1  # replayed seq
    assert any("did not increase" in e for e in _mem_check(rows, tmp_path))
    rows = _mem_rows()
    rows[0]["bytes"] = -5
    assert any("non-negative" in e for e in _mem_check(rows, tmp_path))


def test_memory_watermark_must_rederive_exactly(tmp_path):
    # a forged peak the events cannot reproduce
    rows = _mem_rows()
    rows[0]["peak_bytes"] = 2048
    assert any("peak_bytes=2048 != derived 1024" in e
               for e in _mem_check(rows, tmp_path))
    # a forged live count on a buffer row
    rows = _mem_rows()
    rows[3]["live_bytes"] = 999
    assert any("re-derive from the event stream EXACTLY" in e
               for e in _mem_check(rows, tmp_path))
    # a summary asserting a peak the stream never reached
    rows = _mem_rows()
    rows[-1]["peak_hbm_bytes"] = 4096
    assert any("asserted, not measured" in e
               for e in _mem_check(rows, tmp_path))


def test_memory_donated_buffer_must_leave_live_set(tmp_path):
    # drop the donated buffer event: the dispatch row's claimed buffer
    # is then still live — the runtime twin of HL303 fires
    rows = [r for r in _mem_rows()
            if not (r.get("ev") == "buffer"
                    and r.get("event") == "donated")]
    errs = _mem_check(rows, tmp_path)
    assert any("still in the live set" in e and "HL303" in e
               for e in errs)
    # freeing a buffer that was never staged is equally forged
    rows = _mem_rows()
    rows[5]["buf"] = 77
    assert any("is not in the live set" in e
               for e in _mem_check(rows, tmp_path))


def test_memory_vmem_flags_must_follow_their_own_bytes(tmp_path):
    rows = _mem_rows()
    rows[7]["fits"] = False  # contradicts predicted <= budget
    errs = _mem_check(rows, tmp_path)
    assert any("contradicts predicted" in e for e in errs)
    rows = _mem_rows()
    rows[7]["refused"] = True  # refused must be the negation of fits
    assert any("negation of fits" in e for e in _mem_check(rows, tmp_path))


def test_memory_executable_components_must_sum(tmp_path):
    rows = _mem_rows()
    rows[6]["exec_hbm_bytes"] = 9999
    assert any("component sum" in e for e in _mem_check(rows, tmp_path))


def test_memory_export_must_terminate_in_one_summary(tmp_path):
    # no summary at all
    rows = _mem_rows()[:-1]
    assert any("no terminating summary" in e
               for e in _mem_check(rows, tmp_path))
    # a second summary
    rows = _mem_rows() + [dict(_mem_rows()[-1], seq=10)]
    assert any("second memory summary" in e
               for e in _mem_check(rows, tmp_path))
    # a late buffer event after the summary
    late = dict(_mem_rows()[0], seq=10)
    rows = _mem_rows() + [late]
    assert any("after the summary row" in e
               for e in _mem_check(rows, tmp_path))


def test_memory_headroom_must_be_computed(tmp_path):
    rows = _mem_rows()
    rows[-1]["headroom_frac"] = 0.5
    assert any("headroom must be computed" in e
               for e in _mem_check(rows, tmp_path))
    rows = _mem_rows()
    rows[-1]["hbm_bytes"] = 0
    assert any("positive integer" in e for e in _mem_check(rows, tmp_path))


def test_memory_vocab_in_sync_with_memrec_module():
    from harp_tpu.utils import memrec

    assert memrec.EVS == check_jsonl.KNOWN_MEMORY_EVS
    assert memrec.BUFFER_EVENTS == check_jsonl.KNOWN_MEMORY_EVENTS


def test_golden_memory_fixture_is_clean_and_summarizes():
    """The committed golden memory fixture (tests/data) passes the
    checker AND the module's own replay — the fixture the memory CLI
    smoke drives."""
    p = os.path.join(os.path.dirname(__file__), "data",
                     "golden_memory.jsonl")
    assert check_jsonl.check_file(p) == []
    from harp_tpu.utils import memrec, telemetry

    s = memrec.summarize_rows(telemetry.load_rows(p)["memory"])
    assert s["errors"] == []
    assert s["vmem_refusals"] == 1        # the walkthrough's refusal
    assert s["donated_bytes"] > 0         # the HL303 runtime twin
    assert s["executables"] == 1


# the derived evidence kinds that ship BOTH an offline validator
# (python -m harp_tpu trace/timeline/health/memory, profile --json)
# and a committed golden fixture; a new telemetry spine must join this
# tuple with its checker + fixture or the pin fails tier-1
GOLDEN_SPINE_KINDS = ("trace", "health", "profile", "steptrace",
                      "memory")


def test_meta_every_spine_kind_has_checker_and_golden_fixture():
    """Satellite 3 (PR 19): every spine kind with an offline CLI has a
    check_jsonl invariant (a ``_check_<kind>_row`` checker) AND a clean
    committed golden fixture under tests/data/ containing rows of that
    kind — a new spine cannot land half-pinned."""
    data = os.path.join(os.path.dirname(__file__), "data")
    goldens = sorted(f for f in os.listdir(data)
                     if f.startswith("golden_") and f.endswith(".jsonl"))
    assert goldens == sorted(f"golden_{k}.jsonl"
                             for k in GOLDEN_SPINE_KINDS)
    for kind in GOLDEN_SPINE_KINDS:
        checker = getattr(check_jsonl, f"_check_{kind}_row", None)
        assert callable(checker), f"no check_jsonl invariant for {kind}"
        p = os.path.join(data, f"golden_{kind}.jsonl")
        assert check_jsonl.check_file(p) == [], kind
        kinds_in_file = {json.loads(ln).get("kind")
                         for ln in open(p) if ln.strip()}
        assert kind in kinds_in_file, f"{p} holds no {kind} rows"
