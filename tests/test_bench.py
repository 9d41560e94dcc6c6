"""Driver-contract tests for bench.py — ONE JSON line, north-star pair.

The driver parses bench.py's stdout as a single JSON record; it must
carry kmeans AND mfsgd values, name the device it ran on, never print a
number it did not measure, and exit non-zero when any cell failed or —
in full mode — when the backend is not a TPU.  Runs bench.main()
in-process on conftest's 8-device CPU simulation.
"""

import io
import json
import os
import sys
from contextlib import redirect_stdout

import pytest

BENCH = os.path.join(os.path.dirname(__file__), "..", "bench.py")


def _run_bench(argv):
    """(stdout, exit code) of ``python bench.py argv`` run in-process."""
    import runpy

    buf = io.StringIO()
    old = sys.argv
    sys.argv = ["bench.py"] + argv
    try:
        with redirect_stdout(buf), pytest.raises(SystemExit) as ei:
            runpy.run_path(BENCH, run_name="__main__")
    finally:
        sys.argv = old
    return buf.getvalue(), ei.value.code


def _record(out):
    lines = [ln for ln in out.strip().splitlines() if ln.startswith("{")]
    assert len(lines) == 1, out
    return json.loads(lines[0])


def _load_bench_ingest():
    """Fresh scripts/bench_ingest module (shared by the chunk-sizing and
    int8-wire preset tests)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_ingest", os.path.join(os.path.dirname(__file__), "..",
                                     "scripts", "bench_ingest.py"))
    bi = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bi)
    return bi


def _load_bench(tmp_path=None):
    """Fresh bench module; optionally point its __file__ at tmp_path so
    the _flip_state file lookup reads fixtures there."""
    import importlib.util

    name = f"bench_mod_{_load_bench.n}"
    _load_bench.n += 1
    spec = importlib.util.spec_from_file_location(name, BENCH)
    b = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(b)
    if tmp_path is not None:
        b.__dict__["__file__"] = str(tmp_path / "bench.py")
    return b


_load_bench.n = 0


def test_bench_tables_stay_consistent():
    # BASELINES, _CONFIG_KEYS and UNITS are parallel tables — a config
    # added to one but not the others would KeyError mid-sweep
    b = _load_bench()
    assert set(b.BASELINES) == {name for name, _ in b._CONFIG_KEYS}
    assert {key for _, key in b._CONFIG_KEYS} <= set(b.UNITS)


def test_bench_smoke_emits_one_line_with_north_star_pair(mesh):
    out, code = _run_bench(["--smoke", "kmeans", "mfsgd"])
    assert code == 0
    rec = _record(out)
    # headline contract fields
    assert {"metric", "value", "unit", "vs_baseline"} <= rec.keys()
    assert rec["unit"] == "iter/s"
    assert rec["value"] > 0, rec
    # the north-star pair: kmeans (headline) AND mfsgd (submetric)
    assert rec["submetrics"]["mfsgd"]["value"] > 0, rec
    assert rec["submetrics"]["mfsgd"]["unit"] == "updates/s/chip"
    assert "error" not in rec
    # the record names the device it ran on, as JAX reports it
    import jax

    assert rec["platform"] == "cpu"
    assert rec["device_kind"] == jax.devices()[0].device_kind
    assert rec["n_devices"] == 8
    # a CPU run carries no roofline fields
    assert "pct_peak_flops" not in rec
    assert "pct_peak_flops" not in rec["submetrics"]["mfsgd"]


def test_bench_rejects_unknown_config_names(mesh):
    out, code = _run_bench(["--smoke", "kmaens"])
    assert code == 2 and out == ""


def test_bench_full_mode_refuses_without_a_tpu(mesh, capsys):
    # full mode measures: on any backend but a TPU (JAX's own silent drop
    # to the CPU included) it exits non-zero before running anything
    out, code = _run_bench(["kmeans"])
    assert code == 1
    assert out == ""  # no record, no number
    assert "needs a TPU" in capsys.readouterr().err


def test_bench_failing_cell_exits_nonzero_and_keeps_the_others(
        mesh, monkeypatch):
    # a cell that raises still lets the others run; the process then
    # exits 1, and the failed cell reads null + error, never 0.0
    from harp_tpu.models import kmeans

    def boom(**kw):
        raise RuntimeError("synthetic kmeans failure")

    monkeypatch.setattr(kmeans, "benchmark", boom)
    out, code = _run_bench(["--smoke", "kmeans", "mfsgd", "kmeans_int8_fused"])
    assert code == 1
    rec = _record(out)
    assert rec["value"] is None and rec["vs_baseline"] is None
    assert "synthetic kmeans failure" in rec["error"]
    fused = rec["submetrics"]["kmeans_int8_fused"]
    assert fused["value"] is None and "synthetic" in fused["error"]
    assert rec["submetrics"]["mfsgd"]["value"] > 0  # the sweep went on
    # nothing it did not measure: no remembered numbers from a file
    assert "last_measured" not in rec


def test_bench_record_carries_flip_state(mesh):
    # the driver record must MIRROR FLIP_DECISIONS.jsonl: summarized
    # when the file has verdicts, absent when it doesn't.  The gate
    # rewrites that artifact (tee truncation on a crashed gate can even
    # leave it empty), so the test checks record/file consistency, not a
    # hardcoded table size
    out, _ = _run_bench(["--smoke", "kmeans"])
    fs = _record(out).get("flip_state")
    rows = []
    try:
        with open(os.path.join(os.path.dirname(BENCH),
                               "FLIP_DECISIONS.jsonl")) as f:
            for ln in f:
                try:
                    row = json.loads(ln)
                except ValueError:
                    continue
                if "flip_decision" in row:
                    rows.append(row)
    except OSError:
        pass
    if not rows:
        assert fs is None
        return
    assert fs["candidates"] == len(rows)
    assert 0 <= fs["decided"] <= fs["candidates"]
    assert 0 <= fs["flips_authorized"] <= fs["decided"]


def test_bench_per_config_watchdog_parses_and_bounds(mesh):
    """Satellite (PR 10): --max-seconds-per-config=S parses strictly and
    the subprocess-free timer skips a hung thunk after ~S seconds (the
    thread is abandoned; the sweep moves on) while fast thunks and their
    exceptions pass through untouched."""
    import threading
    import time

    b = _load_bench()
    assert b._parse_max_seconds(["--smoke"]) is None
    assert b._parse_max_seconds(["--max-seconds-per-config=2.5"]) == 2.5
    for bad in (["--max-seconds-per-config"],       # no '=' form
                ["--max-seconds-per-config=nope"],  # non-numeric
                ["--max-seconds-per-config=0"]):    # non-positive
        with pytest.raises(SystemExit):
            b._parse_max_seconds(bad)

    # fast thunk: result passes through, no error
    res, err = b._run_with_timeout(lambda: {"v": 7}, 30.0)
    assert res == {"v": 7} and err is None
    # no timer requested: straight call
    assert b._run_with_timeout(lambda: 3, None) == (3, None)
    # thunk exceptions re-raise for the existing per-config handling
    with pytest.raises(ValueError, match="boom"):
        b._run_with_timeout(lambda: (_ for _ in ()).throw(
            ValueError("boom")), 30.0)

    # hung thunk: warn-and-skip within the bound, not forever
    release = threading.Event()

    def hang():
        release.wait(60)
        return "too late"

    t0 = time.monotonic()
    res, err = b._run_with_timeout(hang, 0.2)
    took = time.monotonic() - t0
    release.set()  # let the abandoned worker die promptly
    assert res is None
    assert "timeout" in err and "0.2" in err
    assert took < 5  # bounded: nowhere near the 60 s hang


def test_bench_timed_out_config_is_recorded_and_skipped(mesh):
    """End to end: a config that overruns --max-seconds-per-config shows
    up in the record as an error submetric (the timeout string), the
    sweep still measures the configs after it, and the exit code is 1."""
    import threading

    b = _load_bench()
    release = threading.Event()
    real = b._configs

    def patched(smoke):
        cfgs = real(smoke)
        out = []
        for name, unit, key, thunk in cfgs:
            if name == "kmeans":
                out.append((name, unit, key,
                            lambda: release.wait(60) or {"iters_per_sec":
                                                         1.0}))
            elif name == "subgraph":  # fast fake: the sweep-continues pin
                out.append((name, unit, key,
                            lambda: {"vertices_per_sec": 123.0}))
            else:
                out.append((name, unit, key, thunk))
        return out

    b._configs = patched
    old = sys.argv
    sys.argv = ["bench.py", "--smoke", "kmeans", "subgraph",
                "--max-seconds-per-config=0.5"]
    buf = io.StringIO()
    try:
        with redirect_stdout(buf):
            assert b.main() == 1  # a timed-out cell fails the run
    finally:
        sys.argv = old
        release.set()
    rec = json.loads(buf.getvalue())
    assert "timeout" in rec["error"]  # surfaced on the headline (kmeans)
    # the timed-out config reads null; the config AFTER it still measured
    assert rec["value"] is None
    assert rec["submetrics"]["subgraph"]["value"] > 0


def test_flip_state_tolerates_truncated_tee_lines(tmp_path):
    # a sprint killed mid-write leaves a truncated last line; the summary
    # must count the valid rows, not vanish (review finding, round 5)
    b = _load_bench(tmp_path)
    (tmp_path / "FLIP_DECISIONS.jsonl").write_text(
        json.dumps({"flip_decision": "a", "flip": True, "speedup": 1.2,
                    "quality_ok": True}) + "\n"
        + json.dumps({"flip_decision": "b", "flip": False,
                      "speedup": None, "quality_ok": None}) + "\n"
        + '{"flip_decision": "c", "flip": fal')  # truncated mid-write
    fs = b._flip_state()
    assert fs == {"candidates": 2, "decided": 1, "flips_authorized": 1}
    # no file at all -> None (no flip_state key in the record)
    b.__dict__["__file__"] = str(tmp_path / "nowhere" / "bench.py")
    assert b._flip_state() is None


def test_ingest_smoke_preset_runs_int8_wire(tmp_path, monkeypatch, mesh):
    """run_smoke(quantize='int8') executes the int8-WIRE ingest end to
    end (round 5: the kmeans_ingest_int8 sweep twin).  The full-mode
    binding test stubs
    _bench_ingest, so without this nothing exercises the preset's
    quantize threading."""
    bi = _load_bench_ingest()
    # REAL isolation: the module's DATA_DIR is an absolute repo path
    # (cwd-independent), so redirect it — a chdir would silently share
    # .bench_data with concurrent bench/measure runs (review finding)
    monkeypatch.setattr(bi, "DATA_DIR", str(tmp_path))

    res = bi.run_smoke(quantize="int8")
    assert res["wire_dtype"] == "int8"
    assert res["points_per_sec"] > 0 and res["inertia"] > 0
    # and the exact-wire default is unchanged
    res_f = bi.run_smoke()
    assert res_f["wire_dtype"] != "int8"
    # same data, same seed: int8 quantization moves inertia by well
    # under the contract's 1% (measured 1.6e-4 rel on the 12 GB run)
    assert abs(res["inertia"] - res_f["inertia"]) / res_f["inertia"] < 0.01
