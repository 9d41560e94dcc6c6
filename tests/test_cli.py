"""L8 launcher tests — unified CLI dispatcher."""

import harp_tpu.__main__ as cli


def test_list(capsys):
    assert cli.main(["--list"]) == 0
    out = capsys.readouterr().out
    for app in ("kmeans", "mfsgd", "lda", "mlp", "subgraph", "rf", "bench"):
        assert app in out


def test_unknown_app(capsys):
    assert cli.main(["nosuchapp"]) == 2
    assert "unknown app" in capsys.readouterr().err


def test_dispatch_kmeans_smoke(capsys):
    rc = cli.main(["kmeans", "--n", "512", "--d", "8", "--k", "4",
                   "--iters", "3", "--bench"])
    assert rc == 0
    assert "iters_per_sec" in capsys.readouterr().out


def test_dispatch_stats_smoke(capsys):
    rc = cli.main(["stats", "pca", "--n", "512", "--d", "8"])
    assert rc == 0
    assert "top5_evals" in capsys.readouterr().out


def test_stats_all_algos_run(capsys):
    """Every daal_* launcher equivalent dispatches and prints a result."""
    from harp_tpu.models import stats

    for algo in ("cov", "moments", "naive", "linreg", "ridge",
                 "qr", "svd", "als"):
        stats.main([algo, "--n", "512", "--d", "8"])
        assert algo.replace("qr", "tsqr").replace(
            "naive", "naive_bayes") in capsys.readouterr().out


def test_dispatch_kmeans_stream_split_glob(capsys, tmp_path):
    """--input with a glob of split files runs the per-worker file-stream
    path (the HDFS-split input shape) and prints one JSON line."""
    import json

    import numpy as np

    rng = np.random.default_rng(0)
    for i in range(3):
        np.savetxt(tmp_path / f"part_{i}.csv",
                   rng.normal(size=(50 + 20 * i, 4)).astype(np.float32),
                   fmt="%.5f", delimiter=",")
    rc = cli.main(["kmeans-stream", "--input", str(tmp_path / "part_*.csv"),
                   "--k", "3", "--iters", "2", "--chunk", "32"])
    assert rc == 0
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["files"] == 3 and np.isfinite(rec["inertia"])
    # numeric schema even for split input (jsonl consumers do arithmetic)
    assert rec["n"] == 50 + 70 + 90 and rec["d"] == 4


def test_dispatch_svm_libsvm_file(capsys, tmp_path):
    """The reference's native input format trains end-to-end via the CLI
    (sparse ELL path, labels mapped from arbitrary binary values)."""
    import numpy as np

    rng = np.random.default_rng(0)
    lines = []
    for _ in range(64):
        x1, x2 = rng.normal(size=2)
        label = 2 if x1 + x2 > 0 else 1  # 1/2-labeled, as UCI files often are
        lines.append(f"{label} 1:{x1:.4f} 2:{x2:.4f}")
    p = tmp_path / "train.svm"
    p.write_text("\n".join(lines) + "\n")
    rc = cli.main(["svm", "--libsvm", str(p)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "train_acc" in out
    import json as _json

    acc = _json.loads(out.strip().splitlines()[-1])["train_acc"]
    assert acc > 0.85  # separable-ish data must actually train


def test_svm_sparse_matches_dense(mesh):
    """fit_sparse on an ELL view of dense data == fit on the dense data."""
    import numpy as np

    from harp_tpu.models.svm import SVM, SVMConfig

    rng = np.random.default_rng(1)
    n, d = 128, 8
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = np.sign(x @ rng.normal(size=d) + 1e-3).astype(np.float32)
    cfg = SVMConfig(inner_steps=50, outer_rounds=2, sv_per_worker=8)
    dense = SVM(cfg, mesh).fit(x, y)
    # every entry stored: ELL == dense data, so the models must agree
    ids = np.tile(np.arange(d, dtype=np.int32), (n, 1))
    ones = np.ones((n, d), np.float32)
    sparse = SVM(cfg, mesh).fit_sparse(ids, x, ones, y, d)
    np.testing.assert_allclose(sparse.w, dense.w, rtol=1e-4, atol=1e-6)
    assert abs(sparse.b - dense.b) < 1e-4


def test_svm_libsvm_rejects_bad_inputs(tmp_path):
    import pytest

    from harp_tpu.models import svm as S

    p = tmp_path / "zb.svm"
    p.write_text("1 0:1.0 2:2.0\n2 1:1.0\n")  # 0-based indices
    with pytest.raises(SystemExit, match="zero-based"):
        S.main(["--libsvm", str(p)])

    p2 = tmp_path / "multi.svm"
    p2.write_text("1 1:1.0\n2 1:2.0\n3 1:3.0\n")
    with pytest.raises(SystemExit, match="2 label values"):
        S.main(["--libsvm", str(p2)])


def test_dispatch_lda_ckpt_resume(capsys, tmp_path, monkeypatch):
    """LDA CLI trains with checkpoints; a rerun RESUMES (zero epochs run)."""
    from harp_tpu.models.lda import LDA

    calls = []
    orig = LDA.sample_epoch
    monkeypatch.setattr(LDA, "sample_epoch",
                        lambda self: (calls.append(1), orig(self))[1])

    args = ["lda", "--docs", "16", "--vocab", "16", "--topics", "2",
            "--tokens-per-doc", "4", "--epochs", "2",
            "--d-tile", "8", "--w-tile", "8", "--entry-cap", "16",
            "--ckpt-dir", str(tmp_path / "c")]
    assert cli.main(args) == 0
    first = capsys.readouterr().out
    assert "log_likelihood" in first
    assert len(calls) == 2  # both epochs trained

    calls.clear()
    assert cli.main(args) == 0
    second = capsys.readouterr().out
    assert len(calls) == 0  # resumed from the checkpoint: nothing re-ran
    assert first == second  # and the restored chain state is identical


def test_dispatch_kmeans_ckpt_resume_cli(capsys, tmp_path):
    """kmeans grows the driver --ckpt-dir/--ckpt-every/--resume wiring
    (PR 10): a run checkpoints in chunks; a rerun with --resume picks up
    the finished run (nothing re-runs) and reports the SAME inertia —
    and the continuation across a 'process restart' is bit-identical to
    an uninterrupted run in a fresh dir."""
    import json

    import numpy as np

    from harp_tpu.utils.checkpoint import CheckpointManager

    args = ["kmeans", "--n", "256", "--d", "8", "--k", "4", "--iters",
            "6", "--ckpt-every", "2"]
    a = str(tmp_path / "a")
    assert cli.main(args + ["--ckpt-dir", a]) == 0
    row1 = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert row1["resumed_from"] is None
    mgr = CheckpointManager(a)
    assert mgr.latest_step() == 2  # 3 chunks of 2 iterations

    assert cli.main(args + ["--ckpt-dir", a, "--resume"]) == 0
    row2 = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert row2["resumed_from"] == 2
    assert row2["inertia"] == row1["inertia"]
    _, s1 = mgr.restore_latest()
    assert np.asarray(s1["centroids"]).shape == (4, 8)
    assert np.isfinite(np.asarray(s1["centroids"])).all()


def test_resume_flag_contract_across_drivers(tmp_path):
    """--resume without --ckpt-dir, or against an empty dir, fails
    loudly on every driver that grew it (a mistyped dir must not
    silently retrain from epoch 0)."""
    import pytest

    for argv in (
        ["kmeans", "--resume"],
        ["mfsgd", "--resume", "--epochs", "1"],
        ["lda", "--resume", "--epochs", "1"],
    ):
        with pytest.raises(SystemExit, match="requires --ckpt-dir"):
            cli.main(argv)
    empty = str(tmp_path / "nothing-here")
    with pytest.raises(SystemExit, match="no checkpoints"):
        cli.main(["mfsgd", "--resume", "--ckpt-dir", empty,
                  "--epochs", "1"])


def test_dispatch_mfsgd_resume_cli_bit_identical(capsys, tmp_path):
    """mfsgd --resume end to end: train 2 of 4 epochs, then finish the
    run under --resume from a fresh driver; the final checkpointed
    factors are BIT-identical to one uninterrupted 4-epoch run."""
    import json

    import numpy as np

    from harp_tpu.utils.checkpoint import CheckpointManager

    base = ["mfsgd", "--users", "32", "--items", "24", "--nnz", "300",
            "--rank", "4", "--algo", "dense", "--u-tile", "8",
            "--i-tile", "8", "--entry-cap", "32", "--ckpt-every", "2"]
    a, b = str(tmp_path / "a"), str(tmp_path / "b")
    assert cli.main(base + ["--epochs", "4", "--ckpt-dir", a]) == 0
    capsys.readouterr()

    assert cli.main(base + ["--epochs", "2", "--ckpt-dir", b]) == 0
    capsys.readouterr()
    assert cli.main(base + ["--epochs", "4", "--ckpt-dir", b,
                            "--resume"]) == 0
    row = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert row["resumed_from"] == 1  # epochs 0-1 were already done
    assert row["epochs_run"] == 2    # only 2-3 ran under --resume

    _, sa = CheckpointManager(a).restore_latest()
    _, sb = CheckpointManager(b).restore_latest()
    np.testing.assert_array_equal(np.asarray(sa["W"]),
                                  np.asarray(sb["W"]))
    np.testing.assert_array_equal(np.asarray(sa["H"]),
                                  np.asarray(sb["H"]))


def test_dispatch_file_inputs(capsys, tmp_path):
    """kmeans/mfsgd/lda consume input files like the Harp apps' HDFS paths."""
    import numpy as np

    rng = np.random.default_rng(0)
    # kmeans: two CSV shards via a glob
    for j in range(2):
        np.savetxt(tmp_path / f"pts{j}.csv",
                   rng.normal(size=(64, 4)).astype(np.float32), delimiter=",")
    assert cli.main(["kmeans", "--input", str(tmp_path / "pts*.csv"),
                     "--k", "2", "--iters", "2"]) == 0
    out = capsys.readouterr().out
    assert '"n": 128' in out and "inertia" in out

    # mfsgd: rating triples, dims inferred from ids
    lines = [f"{rng.integers(0, 24)} {rng.integers(0, 16)} {rng.normal():.3f}"
             for _ in range(300)]
    (tmp_path / "r.txt").write_text("\n".join(lines) + "\n")
    assert cli.main(["mfsgd", "--input", str(tmp_path / "r.txt"),
                     "--rank", "4", "--epochs", "2",
                     "--u-tile", "8", "--i-tile", "8"]) == 0
    out = capsys.readouterr().out
    assert '"nnz": 300' in out and "rmse_final" in out

    # lda: doc-word tokens with a count column (expanded)
    tok = ["0 1 2", "0 3 1", "1 2 3", "2 0 1"]
    (tmp_path / "tok.txt").write_text("\n".join(tok) + "\n")
    assert cli.main(["lda", "--input", str(tmp_path / "tok.txt"),
                     "--topics", "2", "--d-tile", "8", "--w-tile", "8",
                     "--epochs", "2",
                     "--ckpt-dir", str(tmp_path / "lc")]) == 0
    out = capsys.readouterr().out
    assert "log_likelihood" in out

    # zero matches → clear SystemExit, not a concatenate traceback
    import pytest

    with pytest.raises(SystemExit, match="no input files"):
        cli.main(["kmeans", "--input", str(tmp_path / "nope*.csv")])
    with pytest.raises(SystemExit, match="no input files"):
        cli.main(["mfsgd", "--input", str(tmp_path / "nope*.txt")])

    # an empty shard among real ones is skipped, not a concat crash
    (tmp_path / "pts_empty.csv").write_text("")
    assert cli.main(["kmeans", "--input", str(tmp_path / "pts*.csv"),
                     "--k", "2", "--iters", "1"]) == 0
    assert '"n": 128' in capsys.readouterr().out

    # rating files without a rating column are refused (a silent all-zero
    # fit would look like success)
    (tmp_path / "pairs.txt").write_text("0 1\n2 3\n")
    with pytest.raises(SystemExit, match="no rating column"):
        cli.main(["mfsgd", "--input", str(tmp_path / "pairs.txt")])

    # negative ids are refused
    (tmp_path / "neg.txt").write_text("-1 2 3.0\n0 1 1.0\n")
    with pytest.raises(SystemExit, match="negative"):
        cli.main(["mfsgd", "--input", str(tmp_path / "neg.txt")])

    # ragged rows are refused (a short row would read as a fabricated 0.0)
    (tmp_path / "ragged.txt").write_text("0 1 4.5\n2 3\n")
    with pytest.raises(SystemExit, match="disagree on column count"):
        cli.main(["mfsgd", "--input", str(tmp_path / "ragged.txt")])


def test_lda_explicit_zero_counts_dropped(capsys, tmp_path):
    """'doc word 0' means absent (dropped); bare pairs mean one token."""
    import pytest

    (tmp_path / "z.txt").write_text("0 1 2\n0 2 0\n1 0 1\n")
    assert cli.main(["lda", "--input", str(tmp_path / "z.txt"),
                     "--topics", "2", "--algo", "scatter", "--chunk", "8",
                     "--epochs", "1"]) == 0
    capsys.readouterr()

    (tmp_path / "allz.txt").write_text("0 1 0\n1 2 0\n")
    with pytest.raises(SystemExit, match="all token counts are zero"):
        cli.main(["lda", "--input", str(tmp_path / "allz.txt"),
                  "--topics", "2", "--algo", "scatter", "--chunk", "8",
                  "--epochs", "1"])


def test_triples_two_column_fallback_matches_native(tmp_path, monkeypatch):
    """Bare 'doc word' rows (no count) load identically on both paths."""
    import numpy as np

    import harp_tpu.native.datasource as ds

    p = tmp_path / "two.txt"
    p.write_text("0 1\n2 3\n")
    native = ds.load_triples(str(p))
    monkeypatch.setattr(ds, "load_native", lambda: None)
    fallback = ds.load_triples(str(p))
    for a, b in zip(native, fallback):
        np.testing.assert_allclose(a, b)
    np.testing.assert_array_equal(native[2], [0.0, 0.0])


def test_dispatch_bench_smoke(capsys):
    rc = cli.main(["bench", "--verbs", "allreduce", "rotate",
                   "--min-kb", "1024", "--max-mb", "1", "--reps", "2"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "allreduce" in out


def test_stats_file_inputs(capsys, tmp_path):
    """The daal_* stats launchers consume CSV/triple files like HDFS paths."""
    import numpy as np

    from harp_tpu.models import stats

    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 5)).astype(np.float32)
    np.savetxt(tmp_path / "m.csv", x, delimiter=",")
    stats.main(["pca", "--input", str(tmp_path / "m.csv")])
    assert "top5_evals" in capsys.readouterr().out

    # supervised: last column is the target
    w = rng.normal(size=4).astype(np.float32)
    xy = np.concatenate([x[:, :4], (x[:, :4] @ w)[:, None]], 1)
    np.savetxt(tmp_path / "xy.csv", xy, delimiter=",")
    stats.main(["linreg", "--input", str(tmp_path / "xy.csv")])
    out = capsys.readouterr().out
    assert "fit_rmse" in out
    import json as _json

    assert _json.loads(out.strip().splitlines()[-1])["fit_rmse"] < 1e-2

    # naive bayes with integer labels in the last column
    labels = rng.integers(0, 3, 64).astype(np.float32)
    nb = np.concatenate([np.abs(x[:, :4]), labels[:, None]], 1)
    np.savetxt(tmp_path / "nb.csv", nb, delimiter=",")
    stats.main(["naive", "--input", str(tmp_path / "nb.csv")])
    assert "train_acc" in capsys.readouterr().out

    # als reads rating triples
    (tmp_path / "r.txt").write_text(
        "\n".join(f"{rng.integers(0, 12)} {rng.integers(0, 8)} "
                  f"{rng.normal():.3f}" for _ in range(200)) + "\n")
    stats.main(["als", "--input", str(tmp_path / "r.txt")])
    assert "rmse_history" in capsys.readouterr().out

    # single-column file for a supervised algo is refused
    np.savetxt(tmp_path / "one.csv", x[:, :1], delimiter=",")
    import pytest

    with pytest.raises(SystemExit, match=">= 2 columns"):
        stats.main(["ridge", "--input", str(tmp_path / "one.csv")])


def test_stats_file_inputs_validation(tmp_path):
    import numpy as np
    import pytest

    from harp_tpu.models import stats

    (tmp_path / "neg.txt").write_text("-1 2 3.0\n0 1 1.0\n")
    with pytest.raises(SystemExit, match="negative user/item ids"):
        stats.main(["als", "--input", str(tmp_path / "neg.txt")])

    rng = np.random.default_rng(0)
    x = np.abs(rng.normal(size=(32, 3))).astype(np.float32)
    frac = np.concatenate([x, rng.normal(size=(32, 1)).astype(np.float32)], 1)
    np.savetxt(tmp_path / "frac.csv", frac, delimiter=",")
    with pytest.raises(SystemExit, match="must be integers"):
        stats.main(["naive", "--input", str(tmp_path / "frac.csv")])

    big = np.concatenate([x, np.full((32, 1), 1e6, np.float32)], 1)
    np.savetxt(tmp_path / "big.csv", big, delimiter=",")
    with pytest.raises(SystemExit, match="regression target"):
        stats.main(["naive", "--input", str(tmp_path / "big.csv")])


def test_dispatch_trace_cli_smoke(capsys, tmp_path):
    """python -m harp_tpu trace (PR 12): the committed golden 2-request
    fixture summarizes clean (exit 0) in human and JSON modes, exports
    a loadable Perfetto trace.json, and the failure exits are honest —
    1 for an incomplete trace, 2 for an unreadable file."""
    import json
    import os

    golden = os.path.join(os.path.dirname(__file__), "data",
                          "golden_trace.jsonl")
    assert cli.main(["trace", golden]) == 0
    out = capsys.readouterr().out
    assert "1 served / 1 shed / 0 failed" in out
    assert "[shed]" in out and "queue_full" in out  # the shed walkthrough

    pf = tmp_path / "trace.json"
    assert cli.main(["trace", golden, "--json",
                     "--perfetto", str(pf)]) == 0
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert (row["requests"], row["served"], row["shed"]) == (2, 1, 1)
    assert row["unterminated"] == []
    assert all(k in row for k in ("backend", "date", "commit"))
    perf = json.loads(pf.read_text())
    assert perf["traceEvents"] and all(
        "ph" in e and "name" in e for e in perf["traceEvents"])
    assert any(e["ph"] == "X" for e in perf["traceEvents"])

    # incomplete trace (events with no terminal row) exits 1
    lines = [ln for ln in open(golden)
             if '"ev": "request"' not in ln or '"req": 1' not in ln]
    bad = tmp_path / "bad.jsonl"
    bad.write_text("".join(lines))
    assert cli.main(["trace", str(bad)]) == 1
    assert "unterminated" in capsys.readouterr().err

    # unreadable input exits 2
    assert cli.main(["trace", str(tmp_path / "nope.jsonl")]) == 2


def test_dispatch_timeline_cli_smoke(capsys, tmp_path):
    """python -m harp_tpu timeline (PR 18): the committed golden
    2-superstep fixture summarizes clean (exit 0) in human and JSON
    modes, exports a loadable Perfetto trace.json, and the failure
    exits are honest — 1 for an unterminated timeline, 2 for an
    unreadable file."""
    import json
    import os

    golden = os.path.join(os.path.dirname(__file__), "data",
                          "golden_steptrace.jsonl")
    assert cli.main(["timeline", golden]) == 0
    out = capsys.readouterr().out
    assert "1 run(s), 2 superstep(s)" in out
    assert "2 completed / 0 faulted / 0 rebalanced / 0 resumed" in out
    assert "[mfsgd.epochs]" in out          # the run header
    assert "flight:dispatch" in out         # a threaded spine mark

    pf = tmp_path / "trace.json"
    assert cli.main(["timeline", golden, "--json",
                     "--perfetto", str(pf)]) == 0
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert (row["runs"], row["supersteps"], row["completed"]) == (1, 2, 2)
    assert row["unterminated"] == [] and row["dispatch_mismatch"] == []
    assert all(k in row for k in ("backend", "date", "commit"))
    perf = json.loads(pf.read_text())
    assert perf["traceEvents"] and all(
        "ph" in e and "name" in e for e in perf["traceEvents"])
    assert any(e["ph"] == "X" for e in perf["traceEvents"])

    # a timeline whose run row was lost (killed mid-export) exits 1
    lines = [ln for ln in open(golden) if '"ev": "run"' not in ln]
    bad = tmp_path / "bad.jsonl"
    bad.write_text("".join(lines))
    assert cli.main(["timeline", str(bad)]) == 1
    assert "unterminated" in capsys.readouterr().err

    # unreadable input exits 2
    assert cli.main(["timeline", str(tmp_path / "nope.jsonl")]) == 2


def test_dispatch_health_cli_smoke(capsys, tmp_path):
    """python -m harp_tpu health (PR 14): the committed golden fixture
    summarizes with exit 1 (actionable findings), a healthy file exits
    0, an unreadable one exits 2, and --json emits one stamped line."""
    import json
    import os

    golden = os.path.join(os.path.dirname(__file__), "data",
                          "golden_health.jsonl")
    assert cli.main(["health", golden]) == 1  # page + warns: actionable
    out = capsys.readouterr().out
    assert "4 finding(s), 3 actionable" in out
    assert "slo_burn" in out and "skew_trigger" in out
    assert "budget_drift" in out and "evidence_regression" in out
    assert "ratio 1.72 -> 1.05" in out  # the inline rebalance plan

    assert cli.main(["health", golden, "--json"]) == 1
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert row["findings"] == 4 and row["worst_severity"] == "page"
    assert all(k in row for k in ("backend", "date", "commit"))

    # a healthy file (info-only findings, no config rows) exits 0
    ok = tmp_path / "ok.jsonl"
    ok.write_text(json.dumps(
        {"kind": "health", "detector": "evidence_regression",
         "severity": "info", "config": "kmeans", "verdict": "confirmed",
         "backend": "cpu", "date": "2026-08-05",
         "commit": "x"}) + "\n")
    assert cli.main(["health", str(ok)]) == 0
    assert "no findings" not in capsys.readouterr().out  # 1 info row

    # unreadable input exits 2
    assert cli.main(["health", str(tmp_path / "nope.jsonl")]) == 2
    assert "cannot read" in capsys.readouterr().err


def test_health_cli_grades_fresh_bench_rows(capsys, tmp_path,
                                            monkeypatch):
    """The grader half: a sprint output file with a regressed fresh row
    (vs a committed incumbent in --repo) exits 1 and names the verdict;
    --no-grade-bench turns the same file healthy."""
    import json

    from harp_tpu import health

    health.monitor.reset()
    repo = tmp_path / "repo"
    repo.mkdir()
    (repo / "BENCH_local.jsonl").write_text(json.dumps(
        {"config": "rf", "trees_per_sec": 10.0, "backend": "tpu",
         "date": "2026-08-01", "commit": "abc1234"}) + "\n")
    fresh = tmp_path / "sprint.jsonl"
    fresh.write_text(json.dumps(
        {"config": "rf", "trees_per_sec": 5.0, "backend": "tpu",
         "date": "2026-08-05", "commit": "def5678"}) + "\n")
    assert cli.main(["health", str(fresh), "--repo", str(repo),
                     "--json"]) == 1
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert row["graded_configs"] == 1 and row["actionable"] == 1
    health.monitor.reset()
    assert cli.main(["health", str(fresh), "--repo", str(repo),
                     "--no-grade-bench"]) == 0
    capsys.readouterr()
    health.monitor.reset()


def test_health_cli_grade_model_emits_checker_clean_row(capsys):
    """--grade-model on the real repo: the committed evidence grades
    clean (tier-1 pins perfmodel.grade ok), the CLI exits 0, and the
    one emitted kind:'health' row passes invariant 13 — the line that
    gets appended to the evidence file after a measurement run."""
    import json
    import os
    import sys

    from harp_tpu import health

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "scripts"))
    import check_jsonl

    health.monitor.reset()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert cli.main(["health", "--grade-model", "--repo", root]) == 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    row = json.loads(line)
    assert row["kind"] == "health"
    assert row["detector"] == "evidence_regression"
    assert row["verdict"] == "confirmed"
    assert check_jsonl._check_health_row("t", 1, row) == []
    health.monitor.reset()


def test_dispatch_profile_cli_smoke(capsys, monkeypatch):
    """python -m harp_tpu profile (PR 16): a real single-app capture
    emits one invariant-15-clean kind:'profile' row under --json (the
    PROFILE_attrib.jsonl regeneration path), --all iterates the frozen
    app vocabulary, an unknown app exits 2, and any unreconciled row
    exits 1."""
    import json
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "scripts"))
    import check_jsonl

    assert cli.main(["profile", "kmeans", "--json"]) == 0
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert row["kind"] == "profile" and row["app"] == "kmeans"
    assert check_jsonl._check_profile_row("t", 1, row) == []

    # human rendering names the bound and the reconciliation verdict
    assert cli.main(["profile", "kmeans"]) == 0
    out = capsys.readouterr().out
    assert "bound=" in out and "[ok]" in out

    # unknown app exits 2 and lists the vocabulary; no app exits 2
    assert cli.main(["profile", "word2vec"]) == 2
    assert "unknown app" in capsys.readouterr().err
    assert cli.main(["profile"]) == 2
    capsys.readouterr()

    # --all iterates every registered app (capture stubbed so the smoke
    # stays in seconds); an unreconciled row turns exit 0 into 1
    from harp_tpu.profile import attribution

    golden = os.path.join(os.path.dirname(__file__), "data",
                          "golden_profile.jsonl")
    template = json.loads(open(golden).readline())
    calls = []

    def fake_capture(app, reps=4):
        calls.append(app)
        return dict(template, app=app)

    monkeypatch.setattr(attribution, "capture", fake_capture)
    assert cli.main(["profile", "--all", "--json"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert calls == list(attribution.PROFILE_APPS)
    assert len(lines) == len(attribution.PROFILE_APPS)

    monkeypatch.setattr(
        attribution, "capture",
        lambda app, reps=4: dict(template, app=app, reconciled=False))
    assert cli.main(["profile", "kmeans"]) == 1
    assert "FAILED" in capsys.readouterr().out


def test_health_cli_grades_profile_rows(capsys, tmp_path):
    """PR-16 satellite: a fresh kind:'profile' row whose bound flipped
    vs the committed PROFILE_attrib.jsonl baseline is a warn-severity
    profile_drift finding (exit 1); the committed baseline grades
    drift-free against itself (exit 0)."""
    import json
    import os

    from harp_tpu import health

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    committed = os.path.join(root, "PROFILE_attrib.jsonl")
    health.monitor.reset()
    assert cli.main(["health", committed, "--repo", root]) == 0
    capsys.readouterr()

    rows = [json.loads(l) for l in open(committed)]
    r = next(x for x in rows if x["app"] == "lda")
    t = dict(r["terms"])
    t["mxu_s"], t["wire_s"] = t["mxu_s"] + t["wire_s"], 0.0
    drifted = tmp_path / "drifted.jsonl"
    drifted.write_text(json.dumps(dict(r, terms=t, bound="mxu")) + "\n")
    health.monitor.reset()
    assert cli.main(["health", str(drifted), "--repo", root]) == 1
    out = capsys.readouterr().out
    assert "profile_drift" in out and "FLIPPED" in out
    health.monitor.reset()


def test_elastic_cli_knobs_bind_without_executing(capsys, monkeypatch):
    """PR-15 satellite: --elastic / --max-worker-loss on the mfsgd /
    lda / kmeans-stream apps forward into the elastic fit entries.
    Each entry is stubbed with a signature-binding stub, so a typo'd or
    removed kwarg in the
    CLI wiring fails HERE — without training anything."""
    import inspect

    import harp_tpu.elastic.apps as EA

    calls = []

    def stubbed(attr):
        real = getattr(EA, attr)
        sig = inspect.signature(real)

        class _Ad:
            losses = 0

            class mesh:
                num_workers = 8

            def metric(self):
                return 1.0

        def stub(*a, **kw):
            sig.bind(*a, **kw)  # TypeError on any rejected kwarg
            calls.append(attr)
            return _Ad()

        monkeypatch.setattr(EA, attr, stub)

    for attr in ("mfsgd_elastic_fit", "lda_elastic_fit",
                 "kmeans_stream_elastic_fit"):
        stubbed(attr)

    assert cli.main(["mfsgd", "--elastic", "--users", "32", "--items",
                     "16", "--nnz", "64", "--epochs", "1",
                     "--max-worker-loss", "1"]) == 0
    assert "mfsgd_elastic_cli" in capsys.readouterr().out
    assert cli.main(["lda", "--elastic", "--docs", "16", "--vocab",
                     "16", "--topics", "2", "--tokens-per-doc", "4",
                     "--epochs", "1"]) == 0
    assert "lda_elastic_cli" in capsys.readouterr().out
    assert cli.main(["kmeans-stream", "--elastic", "--n", "64", "--d",
                     "4", "--k", "2", "--iters", "1"]) == 0
    assert "kmeans_stream_elastic_cli" in capsys.readouterr().out
    assert calls == ["mfsgd_elastic_fit", "lda_elastic_fit",
                     "kmeans_stream_elastic_fit"]

    # --elastic refuses file inputs loudly (no silent non-elastic fit)
    import pytest

    with pytest.raises(SystemExit, match="synthetic"):
        cli.main(["mfsgd", "--elastic", "--input", "nope.txt"])


def test_elastic_cli_kmeans_stream_smoke(capsys, tmp_path):
    """One real end-to-end elastic CLI run (the cheapest app): prints a
    JSON row with the elastic fields."""
    import json

    rc = cli.main(["kmeans-stream", "--elastic", "--n", "256", "--d",
                   "4", "--k", "3", "--iters", "2",
                   "--ckpt-dir", str(tmp_path / "ck")])
    assert rc == 0
    import numpy as np

    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert row["config"] == "kmeans_stream_elastic_cli"
    assert row["n_workers"] == 8 and row["worker_losses"] == 0
    assert np.isfinite(row["inertia"])


def test_dispatch_memory_cli_smoke(capsys, tmp_path):
    """python -m harp_tpu memory (PR 19): the committed golden ledger
    fixture summarizes clean (exit 0) in human and JSON modes, an
    unterminated export exits 1, an unreadable file exits 2."""
    import json
    import os

    golden = os.path.join(os.path.dirname(__file__), "data",
                          "golden_memory.jsonl")
    assert cli.main(["memory", golden]) == 0
    out = capsys.readouterr().out
    assert "9 buffer event(s)" in out and "2 dispatch(es)" in out
    assert "peak HBM" in out and "headroom" in out
    assert "vmem checks 1 (1 refused)" in out    # the refusal evidence

    assert cli.main(["memory", golden, "--json"]) == 0
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert row["errors"] == []
    assert row["peak_hbm_bytes"] == 1056772
    assert row["vmem_refusals"] == 1 and row["donated_bytes"] == 16384

    # an export whose summary row was lost (killed mid-write) exits 1
    lines = [ln for ln in open(golden) if '"ev": "summary"' not in ln]
    bad = tmp_path / "bad.jsonl"
    bad.write_text("".join(lines))
    assert cli.main(["memory", str(bad)]) == 1
    assert "unterminated" in capsys.readouterr().err

    # unreadable input exits 2
    assert cli.main(["memory", str(tmp_path / "nope.jsonl")]) == 2
    assert "unreadable" in capsys.readouterr().err
