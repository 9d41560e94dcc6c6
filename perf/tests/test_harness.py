"""CPU rehearsal of the harness: every cell at a tiny shape, discovery
by name, the shape of the last line, the verdict of ``correct``, and
that a new cell, configuration, driver and metric are new files only.
No number printed here is a speed."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from perf import harness, spec

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = spec.load_json(os.path.join(ROOT, "BENCHMARK.json"))
# cells that ran on the chip and were not admitted are rehearsed too
PARKED = [spec.load_json(os.path.join(ROOT, "perf", "parked", f))
          for f in sorted(os.listdir(os.path.join(ROOT, "perf", "parked")))]
CELLS = [w["name"] for w in BENCH["workloads"]] + [
    p["workload"]["name"] for p in PARKED]


def _with_parked(bench):
    bench = json.loads(json.dumps(bench))
    for p in PARKED:
        bench["workloads"].append(p["workload"])
        bench["end_to_end"] += p["end_to_end"]
        bench["per_layer"] += p["per_layer"]
    return bench

# the in-test override, by the configuration's driver: the shape only
# (rows, widths and data scale), and a block short enough for the CPU
TINY = {
    "kmeans": {"data": {"n_per_chip": 2048, "d": 16, "k": 8},
               "traffic": {"steps": 3, "trace_seconds": 0.2}},
    "mfsgd": {"data": {"n_users": 600, "n_items": 300, "nnz": 30_000,
                       "user_max": 400, "user_median": 30,
                       "item_max": 900, "item_median": 40},
              "traffic": {"steps": 2, "trace_seconds": 0.2},
              # 30,000 ratings are 15 minibatches of the reference: the
              # order of visits matters far more than at 80M, so the toy
              # size gets a toy band (0.8% measured here)
              "reference": {"first_epoch_rtol": 0.03}},
    # 20,000 tokens over 2 x 8 word tiles and 2 document tiles; the bands
    # at this size are read in test_lda_check.py
    "lda": {"data": {"n_docs": 200, "n_tokens": 20_000, "vocab_size": 2000,
                     "n_topics": 16},
            "knobs": {"d_tile": 128, "w_tile": 128, "entry_cap": 256},
            "traffic": {"steps": 1, "trace_seconds": 0.05}},
}
_CELLS = {w["name"]: w for w in _with_parked(BENCH)["workloads"]}
_CONFIGS = {c["name"]: c for c in BENCH["configs"]}


def _tiny(cell_name):
    config = _CONFIGS[_CELLS[cell_name]["config"]]
    return TINY[spec.load_json(os.path.join(ROOT, config["file"]))["driver"]]


@pytest.fixture()
def checkout(tmp_path):
    """A root that holds BENCHMARK.json and perf/ (without the tests), so
    that caches and traces land outside the repository."""
    with open(tmp_path / "BENCHMARK.json", "w") as fh:
        json.dump(_with_parked(BENCH), fh)
    shutil.copytree(os.path.join(ROOT, "perf"), tmp_path / "perf",
                    ignore=shutil.ignore_patterns(
                        "tests", "testdata", "__pycache__"))
    return str(tmp_path)


def _run(root, cell, trace, override, lines=None):
    return harness.run_cell(
        root, cell, seed=5, seconds=0.3, trace=trace,
        require_platform=None, override=override,
        say=(lines.append if lines is not None else lambda s: None))


def _check_last_line(out, root, cell, trace):
    assert set(out) == {"correct", "attempted", "failed", "metrics",
                        "device", "compared"} | (
                            {"breakdown"} if trace else set())
    json.dumps(out)  # one JSON object, nothing numpy left in it
    # every number the check held beside its limit, last on the line
    assert list(out)[-1] == "compared" and out["compared"]
    for c in out["compared"].values():
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    dev = out["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    if trace:
        assert {"busy_s", "window_s"} <= set(dev)
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    group = "per_layer" if trace else "end_to_end"
    wanted = {m["name"]: m for m in spec.Cell(root, cell).metrics(group)}
    assert set(out["metrics"]) <= set(wanted)
    for name, m in out["metrics"].items():
        assert m["unit"] == wanted[name]["unit"]
        if wanted[name]["source"] == "program_counter":
            assert m["value"] is not None
        else:  # no rate, time or share from a CPU
            assert m["value"] is None and "not measured" in m["note"]
    if not trace:
        assert "setup_s" in out["metrics"] and len(out["metrics"]) >= 2


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses_untraced(cell, checkout):
    lines = []
    out = _run(checkout, cell, False, _tiny(cell), lines)
    assert out["correct"] is True
    _check_last_line(out, checkout, cell, trace=False)
    info = json.loads(lines[0][len("info "):])
    assert info["cell"] == cell and info["blocks"] == out["attempted"]
    # zero cold compiles inside the window, in every cell
    w = info["in_window"]
    assert w["compile_events"] - w["cache_hits"] == 0
    assert os.path.isdir(os.path.join(checkout, ".jax_cache")) or \
        os.environ.get("JAX_COMPILATION_CACHE_DIR")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses_traced(cell, checkout):
    out = _run(checkout, cell, True, _tiny(cell))
    assert out["correct"] is True
    _check_last_line(out, checkout, cell, trace=True)
    # the counts a CPU can make are there
    counted = {m["name"] for m in
               spec.Cell(checkout, cell).metrics("per_layer")
               if m["source"] == "program_counter"}
    assert counted and counted <= set(out["metrics"])
    assert os.path.isdir(os.path.join(checkout, harness.TRACE_DIR, cell))


def test_perturbed_kmeans_reference_fails_the_check(checkout, monkeypatch):
    from perf.reference import kmeans as reference

    true = reference.step

    def bent(bands, centroids):
        c, inertia = true(bands, centroids)
        return c, inertia * 1.001

    monkeypatch.setattr(reference, "step", bent)
    for cell in ("kmeans-resident", "kmeans-job-host"):
        out = _run(checkout, cell, False, _tiny(cell))
        assert out["correct"] is False and out["failed"] == 0


def test_kmeans_reference_is_lloyd():
    """The reference against numpy written out longhand."""
    import numpy as np

    from perf.reference import kmeans as reference

    rng = np.random.default_rng(0)
    x = rng.standard_normal((600, 5)).astype(np.float32)
    c = x[:4].copy()
    bands = reference.stage_host(x)
    got, inertia = reference.lloyd(bands, c, 3)
    for _ in range(3):
        d2 = ((x[:, None, :] - c[None]) ** 2).sum(-1)
        a = d2.argmin(1)
        last = d2.min(1).sum()
        c = np.stack([x[a == j].mean(0) if (a == j).any() else c[j]
                      for j in range(4)])
    np.testing.assert_allclose(got, c, atol=1e-5)
    assert inertia == pytest.approx(last, rel=1e-5)
    assert reference.cost(bands, c) <= last + 1e-3


def test_perturbed_mfsgd_reference_fails_the_check(checkout, monkeypatch):
    from perf.reference import mfsgd as reference

    true = reference.rmse
    monkeypatch.setattr(reference, "rmse",
                        lambda *a, **k: 1.5 * true(*a, **k))
    out = _run(checkout, "mfsgd-epochs", False, _tiny("mfsgd-epochs"))
    assert out["correct"] is False


def test_new_cell_config_driver_and_metric_are_new_files_only(checkout):
    """Adds a configuration, a traffic mix, a driver, two per-layer
    metrics and a cell: entries in BENCHMARK.json and files under perf/,
    no edit to any file that was there."""
    before = {}
    for dirpath, _, names in os.walk(os.path.join(checkout, "perf")):
        for n in names:
            p = os.path.join(dirpath, n)
            before[p] = open(p, "rb").read()
    shutil.copytree(os.path.join(HERE, "dummy"),
                    os.path.join(checkout, "perf"), dirs_exist_ok=True)
    bench = spec.load_json(os.path.join(checkout, "BENCHMARK.json"))
    bench["configs"].append({
        "name": "dummy", "source": "perf/tests", "reduced": [],
        "file": "perf/configs/dummy.json", "why": "a stand-in"})
    bench["workloads"].append({
        "name": "dummy-cell", "config": "dummy", "traffic": "dummy-mix",
        "chips": 1, "why": "a stand-in"})
    for m in bench["end_to_end"]:
        if "workloads" in m and m["name"] == "items_per_s_chip":
            m["workloads"].append("dummy-cell")
    for name in ("dummy_metric", "dummy_absent"):
        bench["per_layer"].append({
            "name": name, "unit": "count", "better": "higher",
            "source": "program_counter", "layer": "app drivers",
            "moves": "items_per_s_chip", "workloads": ["dummy-cell"]})
    with open(os.path.join(checkout, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)
    out = _run(checkout, "dummy-cell", True, None)
    assert out["correct"] is True
    assert out["metrics"] == {"dummy_metric": {"value": 42,
                                               "unit": "count"}}
    out = _run(checkout, "dummy-cell", False, None)
    assert set(out["metrics"]) == {"items_per_s_chip", "setup_s"}
    for p, content in before.items():
        assert open(p, "rb").read() == content, f"{p} was edited"


def test_unknown_workload_is_refused(checkout):
    with pytest.raises(SystemExit):
        _run(checkout, "no-such-cell", False, None)


def test_run_py_refuses_anything_but_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perf", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
    assert "needs a TPU" in p.stderr


def test_unknown_device_kind_has_no_peaks():
    from perf import workmodels

    with pytest.raises(KeyError):
        workmodels.peaks_for("cpu")
    least = workmodels.least_seconds(
        {"model": "kmeans_point_iteration", "d": 300, "k": 100,
         "point_bytes": 4}, 4e6, "TPU v5 lite")
    # 4.8 GB at 819 GB/s, against 4.8e11 FLOP at 197 TFLOP/s
    assert least["wall"] == "hbm"
    assert least["seconds"] == pytest.approx(4e6 * 1204 / 819e9)
    assert least["mxu_s"] == pytest.approx(4e6 * 120_000 / 197e12)


# ---- BENCHMARK.json against the contract's static rules ------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.mark.parametrize("parked", [False, True])
def test_benchmark_json_keeps_the_contract(parked, checkout):
    """BENCHMARK.json as it is, and as it would be with the parked cells'
    entries added."""
    root = checkout if parked else ROOT
    b = spec.load_json(os.path.join(root, "BENCHMARK.json"))
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 << 10
    assert 1 <= b["run_seconds"] <= 51
    cfgs = {c["name"]: c for c in b["configs"]}
    cells = {w["name"]: w for w in b["workloads"]}
    assert len(cfgs) == len(b["configs"]) and len(cells) == len(b["workloads"])
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("perf/")
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert all(len(c[k]) <= 200 for k in ("source", "why"))
        assert c["reduced"] == spec.load_json(
            os.path.join(ROOT, c["file"]))["reduced"]
        assert any(w["config"] == c["name"] for w in b["workloads"])
    pairs = set()
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in cfgs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert os.path.isfile(os.path.join(
            ROOT, "perf", "traffic", w["traffic"] + ".json"))
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(
        1, len(cells) // 4)
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)
    for name in cells:
        cell = spec.Cell(root, name)
        mine = {m["name"] for m in cell.metrics("end_to_end")}
        assert "setup_s" in mine and len(mine) >= 2
        layer = cell.metrics("per_layer")
        assert layer
        for m in layer:  # reported only where the metric it moves is
            assert m["moves"] in mine, (name, m["name"])
            assert callable(cell.reader("per_layer", m["name"]))
        for m in cell.metrics("end_to_end"):
            assert callable(cell.reader("end_to_end", m["name"]))
        assert callable(cell.driver_module().Driver)
