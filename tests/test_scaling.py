"""Scaling-evidence tooling: the simulated-worker sweep.

The sweep's absolute CPU rates are explicitly non-predictive (1-core
host serializes the simulated devices); what these tests pin is the
MACHINERY — cells run and emit well-formed rows with a collective-op
share, and a hung or failed cell costs only itself.
"""

import importlib.util
import json
import os

import pytest

_HERE = os.path.dirname(__file__)


def _load(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_HERE, "..", "scripts", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_sweep_shapes_cover_every_app_and_divide():
    ss = _load("scaling_sweep")
    for app in ss.APPS:
        assert app in ss.RATE_KEYS
        for mode in ("strong", "weak"):
            for n in (1, 2, 4, 8):
                kw = ss.shapes(app, mode, n)
                first = next(iter(kw.values()))
                assert first % n == 0, (app, mode, n, kw)
    # strong mode: total work must not depend on n
    assert ss.shapes("kmeans", "strong", 1) == ss.shapes("kmeans", "strong", 8)
    assert ss.shapes("lda", "weak", 8)["n_docs"] == \
        8 * ss.shapes("lda", "weak", 1)["n_docs"]


def test_sweep_child_emits_row_with_comm_share(mesh):
    # subgraph is the fastest cell (~0.1 s); conftest pins 8 devices, so
    # the in-process child must be asked for exactly 8 workers
    ss = _load("scaling_sweep")
    lines = []
    ss.child("subgraph", "strong", 8,
             emit=lambda line, **kw: lines.append(line))
    row = json.loads(lines[-1])
    assert row["app"] == "subgraph" and row["n_workers"] == 8
    assert row["rate"] > 0 and row["traced_sec"] > 0
    assert 0.0 <= row["comm_fraction"] <= 1.0
    assert row["cpu_sim"] is True  # the non-predictive marker


def test_sweep_parent_survives_hung_and_failed_cells(tmp_path, monkeypatch):
    """A hung cell (TimeoutExpired) or a crashed child must cost only
    itself: the parent records an error row and keeps going (review
    finding, round 5)."""
    import subprocess
    import types

    ss = _load("scaling_sweep")

    def fake_run(cmd, **kw):
        app = cmd[cmd.index("--child") + 1]
        if app == "kmeans":
            raise subprocess.TimeoutExpired(cmd, 1800)
        if app == "mfsgd":
            return types.SimpleNamespace(returncode=1, stdout="",
                                         stderr="boom\ndied")
        return types.SimpleNamespace(
            returncode=0, stdout='{"app": "%s", "ok": 1}\n' % app,
            stderr="")

    monkeypatch.setattr(ss.subprocess, "run", fake_run)
    out = tmp_path / "scaling.jsonl"
    rc = ss.main(["--out", str(out), "--workers", "2",
                  "--apps", "kmeans", "mfsgd", "lda",
                  "--modes", "strong"])
    assert rc == 1  # failures are reported in the exit status
    rows = [json.loads(ln) for ln in out.read_text().splitlines()]
    assert len(rows) == 3  # every cell produced a row, good or bad
    by_app = {r["app"]: r for r in rows}
    assert "timeout" in by_app["kmeans"]["error"]
    assert by_app["mfsgd"]["error"] == "died"
    assert by_app["lda"]["ok"] == 1
