"""The cell ``mlp-epochs`` (configuration ``mlp-mnist8m-b2k``) on the
CPU: what its files state, what its work model counts, its rehearsal at a
toy shape through ``harness.run_cell``, its reader on runs with and
without what it reads, and the five controls ``correct`` is held to,
planted at the toy shape.  No number printed here is a speed."""

import dataclasses
import json
import os
import shutil

import pytest

import mlp_faults
from harp_tpu.models import mlp as M
from perf import harness, spec, workmodels

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = spec.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CONFIG = spec.load_json(
    os.path.join(ROOT, "perf", "configs", "mlp-mnist8m-b2k.json"))
CELL = "mlp-epochs"
COMPARED = {"logits_rel", "logits_as_stated_rel", "step_rel",
            "step_as_stated_rel", "block_rel", "block_loss_rel",
            "blocks_not_finite",
            "loss_window_above_first"}
SHARED = ["items_per_s_chip", "compiles_in_window", "dispatches_per_block",
          "collective_share", "collective_bytes_per_item", "xla_share",
          "step_roofline", "kernel_share"]


@pytest.fixture()
def checkout(tmp_path):
    """BENCHMARK.json and perf/ (without its tests) outside the
    repository, so that caches and traces land there."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "perf"), tmp_path / "perf",
                    ignore=shutil.ignore_patterns(
                        "tests", "testdata", "__pycache__"))
    return str(tmp_path)


def _run(root, trace, lines=None, override=mlp_faults.TINY):
    return harness.run_cell(
        root, CELL, seed=2147489005, seconds=0.3, trace=trace,
        require_platform=None, override=override,
        say=(lines.append if lines is not None else lambda s: None))


# ---- what the files state -------------------------------------------------

def _knobs_config():
    """The ``MLPConfig`` the configuration's knobs state."""
    knobs = {k: v for k, v in CONFIG["knobs"].items()
             if k != "batch_per_worker"}
    return M.MLPConfig(**{**knobs, "sizes": tuple(knobs["sizes"])})


def test_knobs_are_the_programs_defaults():
    """Every ``MLPConfig`` field at its default, and the per-worker
    batch the default global batch over the deployment's four workers."""
    assert CONFIG["knobs"]["batch_per_worker"] * 4 == 8192
    assert dataclasses.asdict(_knobs_config()) == dataclasses.asdict(
        M.MLPConfig())
    assert set(CONFIG["knobs"]) - {"batch_per_worker"} == {
        f.name for f in dataclasses.fields(M.MLPConfig)}
    # one worker's rows after load_resident's own trim of the 8.1M
    assert (8_100_000 // 8192) * 8192 // 4 == CONFIG["data"]["n_per_chip"]
    assert CONFIG["data"]["n_per_chip"] % 2048 == 0


def test_entries_are_appended_and_reduced_agrees():
    """The cell's entries stand where PR 36 appended them: fifth cell,
    fourth configuration, last of the shared metrics' cells, its one
    metric; later PRs append theirs after them."""
    entry = BENCH["configs"][3]
    assert entry["name"] == CONFIG["name"] == "mlp-mnist8m-b2k"
    assert entry["reduced"] == CONFIG["reduced"] == ["n_per_chip"]
    assert entry["source"] == CONFIG["source"]
    assert "8.1M x 784" in entry["source"] and len(entry["source"]) <= 200
    cell = BENCH["workloads"][4]
    assert (cell["name"], cell["config"], cell["chips"]) == (
        CELL, "mlp-mnist8m-b2k", 1)
    assert os.path.isfile(os.path.join(
        ROOT, "perf", "traffic", cell["traffic"] + ".json"))
    metrics = {m["name"]: m for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    for name in SHARED:
        assert metrics[name]["workloads"][4] == CELL
    mine = [m for m in BENCH["per_layer"] if m["workloads"] == [CELL]]
    assert [(m["name"], m["layer"], m["moves"], m["source"])
            for m in mine] == [
        ("mlp_step_us", "step programs", "items_per_s_chip", "device_trace"),
        # PR 40, after it: the share of the step under the scope mlp.layer1
        ("mlp_layer1_share", "step programs", "items_per_s_chip",
         "device_trace")]
    # still one four-chip cell
    assert [w["name"] for w in BENCH["workloads"] if w["chips"] == 4] == [
        "kmeans-resident-4chip"]
    # every limit of the comparison stands in the file, with its reason
    assert set(CONFIG["reference"]) == {
        "probe_rows", "step_rel_limit", "step_as_stated_rel_limit",
        "logits_rel_limit", "logits_as_stated_rel_limit", "block_rel_limit",
        "block_loss_rel_limit", "loss_margin", "why"}


def test_work_model_counts_the_sample_whatever_implements_it():
    per = spec.load_module(os.path.join(
        ROOT, "perf", "work_models", "mlp_sample_step.py")).per_item(
            CONFIG["work"])
    assert per == {"flops": 2_407_424.0, "bytes": 3140.0,
                   "peak": "bf16_flops"}
    least = workmodels.least_seconds(CONFIG["work"], 2048, "TPU v5 lite")
    assert least["wall"] == "mxu"
    assert least["seconds"] == pytest.approx(2048 * 2_407_424 / 197e12)
    assert least["hbm_s"] == pytest.approx(2048 * 3140 / 819e9)
    assert CONFIG["work"]["sizes"] == CONFIG["knobs"]["sizes"]


# ---- the rehearsal ---------------------------------------------------------

def test_cell_rehearses_and_counts(checkout):
    lines = []
    out = _run(checkout, False, lines)
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"items_per_s_chip", "setup_s"}
    info = json.loads(lines[0][len("info "):])
    assert info["item"] == "sample-step"
    # 2 epochs of 32 batches of 64 rows a block, every row used
    assert info["items"] == info["blocks"] * 2 * 2048
    # the table is made and taken on the device: nothing crosses in set-up
    assert info["setup"]["h2d_bytes"] == 0
    w = info["in_window"]
    assert w["compile_events"] - w["cache_hits"] == 0
    assert (w["dispatches"], w["readbacks"]) == (info["blocks"],) * 2
    assert set(out["compared"]) == COMPARED

    out = _run(checkout, True)
    assert out["correct"] is True
    got = out["metrics"]
    assert got["compiles_in_window"]["value"] == 0
    assert got["dispatches_per_block"]["value"] == 2.0
    # the gradient allreduce as the CommLedger prices it: every
    # parameter and the two metrics, float32, a step of 64 samples
    sizes = mlp_faults.TINY["knobs"]["sizes"]
    n_params = sum(fi * fo + fo for fi, fo in zip(sizes[:-1], sizes[1:]))
    assert got["collective_bytes_per_item"]["value"] == pytest.approx(
        4 * (n_params + 2) / 64)
    # a CPU trace has no device plane: no busy time to divide
    assert "mlp_step_us" not in got


def test_reader_on_runs_with_and_without_what_it_reads():
    cell = spec.Cell(ROOT, CELL)
    step_us = cell.reader("per_layer", "mlp_step_us")
    run = harness.RunData(cell, harness.Recorder())
    # a program from before the counter, or an untraced run
    run.extra = {"optimizer_steps_per_block": None}
    run.trace, run.trace_blocks = {"busy_s": 3.0}, 3
    assert step_us(run) is None
    run.extra = {"optimizer_steps_per_block": 1000}
    run.trace = None
    assert step_us(run) is None
    # with them: 3 s busy over 3 blocks of 1000 steps
    run.trace = {"busy_s": 3.0}
    assert step_us(run) == pytest.approx(1000.0)


def test_a_program_without_the_batch_order_is_refused_at_once(
        checkout, monkeypatch):
    """The parent under this PR's benchmark files: an exit code before
    any table is made."""
    monkeypatch.delattr(M.MLPTrainer, "resident_batch_order")
    made = []
    monkeypatch.setattr("perf.mnist_like.table_device",
                        lambda *a, **k: made.append(a))
    with pytest.raises(SystemExit, match="resident_batch_order"):
        _run(checkout, False)
    assert made == []


# ---- the controls ----------------------------------------------------------

def test_float32_activations_are_held_token_for_token():
    """Beside ``correct``'s two ``*_as_stated_rel`` readings, which tell
    bf16 activations by their results on the chip (PERF.md section 6):
    the program the cell's knobs build holds no bfloat16 value anywhere,
    and the planted control's does."""
    import jax
    import jax.numpy as jnp

    from harp_tpu.parallel.mesh import WorkerMesh

    assert CONFIG["knobs"]["half_precision"] is False
    assert "pinned_not_held_by_correct" not in CONFIG["reference"]
    cfg = _knobs_config()
    mesh = WorkerMesh(jax.devices()[:1])

    def program_text():
        fn, tx = M.make_epoch_fn(mesh, cfg, 8, 4, epochs=1)
        params = M.init_params(cfg, jax.random.key(0))
        return str(jax.make_jaxpr(fn)(
            params, tx.init(params), jnp.zeros((32, cfg.sizes[0])),
            jnp.zeros((32,), jnp.int32), jnp.zeros((2,), jnp.uint32)))

    assert "bf16" not in program_text()
    with mlp_faults.half_precision():
        assert "bf16" in program_text()


STEP = {"step_rel", "step_as_stated_rel"}
BLOCK = {"block_rel", "block_loss_rel"}
FAILS = {"half_precision": STEP | {"logits_rel", "logits_as_stated_rel",
                                   "block_rel"},
         "bf16_parameters": STEP | BLOCK,
         "lr_halved": STEP | BLOCK,
         "bias_gradient_left_out": STEP | BLOCK,
         # a fault in the timed program alone: (a) and (b) run other
         # programs and cannot see it; (c) is taken from the timed one
         "epochs_program_lr_halved": BLOCK}


@pytest.mark.parametrize("control", sorted(mlp_faults.CONTROLS))
def test_planted_control_reads_not_correct(control, checkout):
    with mlp_faults.CONTROLS[control]():
        out = _run(checkout, False)
    assert out["correct"] is False and out["failed"] == 0
    over = {name for name, c in out["compared"].items()
            if not c["value"] <= c["limit"]}
    assert over == FAILS[control]
    if control == "lr_halved":  # half the step, to the digit
        assert out["compared"]["step_rel"]["value"] == pytest.approx(
            0.5, abs=1e-3)
    if control == "bias_gradient_left_out":  # that leaf did not move
        assert out["compared"]["step_rel"]["value"] == pytest.approx(1.0)
