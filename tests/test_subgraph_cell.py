"""The cell ``subgraph-colorings`` (configuration ``subgraph-orkut-u5``)
on the CPU: what its files state, what its work model counts, its
rehearsal at a toy shape through ``harness.run_cell``, its readers on
runs with and without what they read, and the seven controls ``correct``
is held to, planted at the toy shape.  No number printed here is a
speed."""

import dataclasses
import json
import os
import shutil

import pytest

import subgraph_faults
from harp_tpu.models import subgraph as SG
from perf import harness, spec, workmodels

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = spec.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CONFIG = spec.load_json(
    os.path.join(ROOT, "perf", "configs", "subgraph-orkut-u5.json"))
CELL = "subgraph-colorings"
COMPARED = {"entries_missing", "vertices_split_wrong", "draw_z",
            "counts_rel", "counts_not_positive", "blocks_repeating",
            "window_mean_z"}
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


def _run(root, trace, lines=None, override=subgraph_faults.TINY):
    return harness.run_cell(
        root, CELL, seed=2147489005, seconds=0.3, trace=trace,
        require_platform=None, override=override,
        say=(lines.append if lines is not None else lambda s: None))


# ---- what the files state -------------------------------------------------

def test_knobs_are_the_programs_defaults_but_the_width():
    """Every ``SubgraphConfig`` field the run does not set is pinned, at
    its default but for ``max_degree`` (the smallest power of two above
    the graph's mean degree 76.28, where the default 64 was sized for a
    mean of 16) and the colours, stated where the default says "the
    template's size"."""
    knobs = CONFIG["knobs"]
    assert set(knobs) | {"n_trials", "seed"} == {
        f.name for f in dataclasses.fields(SG.SubgraphConfig)}
    default = dataclasses.asdict(SG.SubgraphConfig())
    changed = {k: v for k, v in knobs.items() if default[k] != v}
    assert set(changed) <= {"max_degree", "n_colors", "trial_chunk"}
    assert knobs["max_degree"] == 128 and knobs["n_colors"] == 5
    data = CONFIG["data"]
    mean = 2 * data["n_edges"] / data["n_vertices"]
    assert knobs["max_degree"] // 2 < mean < knobs["max_degree"]
    assert SG.TEMPLATES[knobs["template"]] == CONFIG["work"]["template"] \
        == [-1, 0, 0, 1, 1]
    traffic = spec.load_json(os.path.join(ROOT, "perf", "traffic",
                                          "colorings.json"))
    assert traffic["steps"] == knobs["trial_chunk"] in (8, 4, 2, 1)
    assert traffic["mode"] == "steady"
    # the source's own size and the program's default chunk: no rung of
    # the rule's buys a shorter block (PERF.md section 6), so none is taken
    assert CONFIG["reduced"] == []
    assert (data["n_vertices"], data["n_edges"]) == (3_072_441, 117_185_083)
    assert knobs["trial_chunk"] == 8 == SG.SubgraphConfig().trial_chunk
    assert "3,072,441" in CONFIG["assumed"]["scale"]
    assert (CONFIG["work"]["entries"], CONFIG["work"]["vertices"]) == (
        2 * data["n_edges"], data["n_vertices"])


def test_entries_are_appended_and_reduced_agrees():
    entry = BENCH["configs"][4]
    assert entry["name"] == CONFIG["name"] == "subgraph-orkut-u5"
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"]
    assert "3,072,441 vertices, 117,185,083 edges" in entry["source"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    cell = BENCH["workloads"][5]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == (CELL, "subgraph-orkut-u5", "colorings", 1)
    assert len(cell["why"]) <= 200
    metrics = {m["name"]: m for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    for name in SHARED:
        assert metrics[name]["workloads"][5] == CELL
    mine = [m for m in BENCH["per_layer"] if m["workloads"] == [CELL]]
    assert [(m["name"], m["layer"], m["moves"], m["source"], m["better"])
            for m in mine] == [
        ("subgraph_install_s", "host data path", "setup_s",
         "program_span", "lower"),
        ("subgraph_executed_pad_share", "step programs", "items_per_s_chip",
         "program_counter", "lower"),
        ("subgraph_ns_per_entry", "step programs", "items_per_s_chip",
         "device_trace", "lower"),
        # PR 40, after them: shares of the block by the program's scopes
        ("subgraph_tail_share", "step programs", "items_per_s_chip",
         "device_trace", "lower"),
        ("subgraph_padded_share", "step programs", "items_per_s_chip",
         "device_trace", "lower"),
        ("subgraph_order_share", "step programs", "items_per_s_chip",
         "device_trace", "lower")]
    at = BENCH["per_layer"].index(mine[0])
    assert BENCH["per_layer"][at:at + 3] == mine[:3]
    # still one four-chip cell
    assert [w["name"] for w in BENCH["workloads"] if w["chips"] == 4] == [
        "kmeans-resident-4chip"]
    # every limit of the comparison stands in the file, with its reason
    assert set(CONFIG["reference"]) == {
        "counts_rel_limit", "draw_z_limit", "window_mean_z_limit", "why"}
    assert {"degree_law", "edges", "template", "max_degree", "deployment",
            "scale"} <= set(CONFIG["assumed"])
    assert len(CONFIG["guarantees"]) >= 5


def test_work_model_counts_distinct_sub_templates_and_no_padding():
    per = spec.load_module(os.path.join(
        ROOT, "perf", "work_models", "subgraph_vertex_coloring.py")).per_item
    work = {"template": [-1, 0, 0, 1, 1], "n_colors": 5,
            "entries": 234_370_166, "vertices": 3_072_441}
    assert {**CONFIG["work"], "entries": 0, "vertices": 0} == {
        **work, "model": "subgraph_vertex_coloring", "entries": 0,
        "vertices": 0}
    mean = 234_370_166 / 3_072_441
    # the leaf (5 columns) and the star (10): 15 columns gathered over
    # mean-degree rows, two sums' ids, 15 columns written / written and
    # read, one colour; 75 terms of the subset convolutions
    assert per(work) == {
        "flops": pytest.approx(mean * 15 + 150),
        "bytes": pytest.approx(4 * mean * 15 + 4 * mean * 2 + 12 * 15 + 4),
        "peak": "f32_flops"}
    assert per(work)["bytes"] == pytest.approx(5371.1, abs=0.1)
    # u5-star: four equal leaves are one sum; u5-path: four distinct
    assert per({**work, "template": [-1, 0, 0, 0, 0]})["bytes"] == \
        pytest.approx(4 * mean * 5 + 4 * mean + 12 * 5 + 4)
    assert per({**work, "template": [-1, 0, 1, 2, 3]})["bytes"] == \
        pytest.approx(4 * mean * 30 + 4 * mean * 4 + 12 * 30 + 4)
    least = workmodels.least_seconds(CONFIG["work"], 1e6, "TPU v5 lite")
    assert least["wall"] == "hbm"
    assert least["seconds"] == pytest.approx(
        1e6 * per(CONFIG["work"])["bytes"] / 819e9)


# ---- the rehearsal ---------------------------------------------------------

def test_cell_rehearses_and_counts(checkout):
    lines = []
    out = _run(checkout, False, lines)
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"items_per_s_chip", "setup_s"}
    info = json.loads(lines[0][len("info "):])
    assert info["item"] == "vertex-coloring"
    assert info["items"] == info["blocks"] * 4 * 300
    # the graph crosses once, in set-up: five arrays
    assert info["setup"]["h2d_calls"] == 5
    w = info["in_window"]
    assert w["compile_events"] - w["cache_hits"] == 0
    assert (w["dispatches"], w["readbacks"]) == (info["blocks"],) * 2
    assert w["h2d_bytes"] == 0  # no colouring is uploaded
    assert set(out["compared"]) == COMPARED
    check = info["check"]
    assert (check["entries_padded_part"], check["entries_tail"]) == (
        1664, 1336)
    # counts of this size are whole numbers in float32: the same bits
    assert check["rooted_first"] == check["rooted_reference"]

    SG._FN_CACHE.clear()  # the ledger prices a program when it is traced
    out = _run(checkout, True)
    assert out["correct"] is True
    got = out["metrics"]
    assert got["compiles_in_window"]["value"] == 0
    assert got["dispatches_per_block"]["value"] == 2.0
    # one worker's allgathers of the leaf's packed colours (one word a
    # vertex) and of the star's table, and the allreduce of the counts,
    # over a block's 300 x 4 items
    assert got["collective_bytes_per_item"]["value"] == pytest.approx(
        4 * (300 * (1 + 10 * 4) + 4) / (300 * 4))
    assert got["subgraph_executed_pad_share"]["value"] == pytest.approx(
        100 * (1 - 3000 / (300 * 8 + 1336)))
    assert got["subgraph_install_s"]["note"] == "not measured: no chip"
    # a CPU trace has no device plane: no busy time to divide
    assert "subgraph_ns_per_entry" not in got


def test_readers_on_runs_with_and_without_what_they_read(monkeypatch):
    from harp_tpu.utils import skew, telemetry

    cell = spec.Cell(ROOT, CELL)
    run = harness.RunData(cell, harness.Recorder())
    ns = cell.reader("per_layer", "subgraph_ns_per_entry")
    run.trace, run.trace_blocks = {"busy_s": 6.0}, 3
    assert ns(run) is None  # a driver from before the counts
    run.extra = {"colorings_per_block": 8, "adjacency_entries": 1000}
    assert ns(run) == pytest.approx(1e9 * 6.0 / (3 * 8 * 1000))
    run.trace = None
    assert ns(run) is None  # an untraced run
    # a program without the span, the record or the query
    monkeypatch.setattr(skew, "ledger", skew.SkewLedger())
    assert cell.reader("per_layer", "subgraph_executed_pad_share")(run) is None
    telemetry.tracer.reset()
    run.window = (10.0, 20.0)
    assert cell.reader("per_layer", "subgraph_install_s")(run) is None
    monkeypatch.delattr(telemetry.SpanTracer, "durations")
    assert cell.reader("per_layer", "subgraph_install_s")(run) is None


def test_a_program_without_the_pair_is_refused_at_once(checkout, monkeypatch):
    """The parent under this PR's benchmark files: an exit code before
    any graph is made."""
    monkeypatch.delattr(SG, "SubgraphCounter")
    made = []
    monkeypatch.setattr("perf.graph_like.edges",
                        lambda *a, **k: made.append(a))
    with pytest.raises(SystemExit, match="SubgraphCounter"):
        _run(checkout, False)
    assert made == []


# ---- the controls ----------------------------------------------------------

INSTALLED = {"entries_missing", "vertices_split_wrong"}
FAILS = {"tail_left_out": INSTALLED | {"counts_rel"},
         "hub_truncated": INSTALLED | {"counts_rel"},
         # at the toy size a count is a whole number under 2^24, and
         # bfloat16 keeps eight of its bits
         "bf16_tables": {"counts_rel"},
         "wrong_position_map": {"counts_rel"},
         # five distinct colours are never there: every count is nought,
         # block after block
         "four_colours_of_five": {"counts_rel", "counts_not_positive",
                                  "blocks_repeating"},
         # the query answers with the first block's colours for every
         # block, and every block returns the first block's counts
         "first_block_redrawn": {"draw_z", "blocks_repeating"},
         # the second block's colours are not uniform, and its counts a
         # fifth lower
         "skewed_after_first": {"draw_z", "window_mean_z"}}


@pytest.mark.parametrize("control", sorted(subgraph_faults.CONTROLS))
def test_planted_control_reads_not_correct(control, checkout):
    with subgraph_faults.CONTROLS[control]():
        out = _run(checkout, False)
    assert out["correct"] is False
    over = {name for name, c in out["compared"].items()
            if not c["value"] <= c["limit"]}
    assert over == FAILS[control]
    if control == "hub_truncated":  # one vertex: the hub
        assert out["compared"]["vertices_split_wrong"]["value"] == 1
        assert out["compared"]["entries_missing"]["value"] == 60 - 8
    if control == "tail_left_out":
        assert out["compared"]["entries_missing"]["value"] == 1336
    if control == "four_colours_of_five":
        # five distinct colours are never there: every count is nought
        assert out["failed"] == out["attempted"]
    if control == "first_block_redrawn":
        assert out["compared"]["blocks_repeating"]["value"] \
            == out["attempted"]
