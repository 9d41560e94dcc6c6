"""The benchmark cell ``lda-sweeps`` rehearsed end to end on the CPU at
a cut-down data block, untraced and traced, through the default path of
the program: the fused sampler over its chunk list
(``ops/lda_kernel.stage_chunk_list``), in interpret mode.  The override
is ``perf/tests/test_harness.py``'s own ``TINY["lda"]`` (since PR 31 that
file holds one toy override a driver and rehearses this cell too; here
the cell's checks are read more closely).  ``correct`` is decided as the
configuration's ``reference`` says: (a) and (b) on the state the window
left, (c) on the chain as it stood after ``chain_sweeps`` = 4 sweeps, a
window that ended sooner at its last, inside 0.6 of the plain sampler's
step there; ``perf/tests/test_lda_check.py`` plants the faults.  Beside
the rehearsals: the cell's six per-layer readers on runs that lack what
they read, its entries in ``BENCHMARK.json``, its knobs against the
program's defaults, its work model.  No number printed here is a
speed."""

import json
import os
import time

import pytest

from harp_tpu.utils import skew, telemetry
from perf import harness, spec, workmodels

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


_H = spec.load_module(os.path.join(ROOT, "perf", "tests", "test_harness.py"))
BENCH, checkout, _check_last_line = _H.BENCH, _H.checkout, _H._check_last_line

CELL = "lda-sweeps"
CONFIG = "lda-enwiki-v1m-k1k"
MINE = ["lda_kernel_roofline", "lda_executed_pad_share",
        "lda_kernel_ns_per_slot", "lda_pack_s", "lda_partition_sort_s",
        "lda_partition_pack_s"]
SHARED = ["compiles_in_window", "dispatches_per_block", "collective_share",
          "collective_bytes_per_item", "xla_share", "step_roofline",
          "kernel_share"]
# the shape only: 20,000 tokens over 2 x 8 word tiles and 2 document
# tiles; the bands at this size are read in perf/tests/test_lda_check.py
TINY = _H.TINY["lda"]


def _run(root, trace, lines=None):
    return harness.run_cell(
        # a window of a block or two: 20,000 tokens flatten in a dozen
        # sweeps and their likelihood is noisy there (test_lda_reference)
        root, CELL, seed=2147484001, seconds=0.05, trace=trace,
        require_platform=None, override=TINY,
        say=(lines.append if lines is not None else lambda s: None))


def test_entries_are_appended_and_nothing_else_changed():
    """The cell's entries stand where PR 29 appended them: fourth cell,
    third configuration, its six metrics together; later PRs append
    theirs after them."""
    assert [w["name"] for w in BENCH["workloads"]][3] == CELL
    cell = BENCH["workloads"][3]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "sweeps", 1)
    entry = BENCH["configs"][2]
    assert entry["name"] == CONFIG and len(entry["source"]) <= 200
    for part in ("BASELINE.json configs[2]", "edu.iu.lda", "rotation",
                 "enwiki", "1M-word vocabulary"):
        assert part in entry["source"]
    names = [m["name"] for m in BENCH["per_layer"]]
    at = names.index(MINE[0])
    assert names[at:at + len(MINE)] == MINE
    by_name = {m["name"]: m for m in BENCH["per_layer"] + BENCH["end_to_end"]}
    for name in MINE:
        assert by_name[name]["workloads"] == [CELL]
    for name in SHARED + ["items_per_s_chip"]:
        assert by_name[name]["workloads"][3] == CELL
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    config = spec.Cell(ROOT, CELL).config
    assert config["reduced"] == ["n_docs", "n_tokens"] == entry["reduced"]
    assert config["source"] == entry["source"]
    assert config["item"] == "token-sample"
    assert config["data"]["vocab_size"] == 1_000_000
    assert config["data"]["n_topics"] == config["work"]["n_topics"] == 1000


def test_knobs_are_the_programs_defaults():
    from harp_tpu.models.lda import LDAConfig

    default, config = LDAConfig(), spec.Cell(ROOT, CELL).config
    for knob in ("algo", "d_tile", "w_tile", "entry_cap", "carry_db",
                 "pallas_exact_gathers", "ndk_dtype", "sampler", "rng_impl",
                 "rotate_chunks", "rotate_wire"):
        assert config["knobs"][knob] == getattr(default, knob), knob
    assert len(config["knobs"]) == 11
    assert (default.alpha, default.beta) == (config["data"]["alpha"],
                                             config["data"]["beta"])


def test_cell_rehearses_untraced(checkout):  # noqa: F811
    lines = []
    out = _run(checkout, False, lines)
    assert out["correct"] is True
    _check_last_line(out, checkout, CELL, trace=False)
    assert set(out["metrics"]) == {"items_per_s_chip", "setup_s"}
    info = json.loads(lines[0][len("info "):])
    assert info["item"] == "token-sample"
    assert info["items"] == TINY["data"]["n_tokens"] * out["attempted"]
    w = info["in_window"]
    assert w["compile_events"] - w["cache_hits"] == 0
    # one dispatch and one readback a block
    assert w["dispatches"] == w["readbacks"] == out["attempted"]
    check = info["check"]
    assert check["sweeps"] == out["attempted"] + 1  # and the warm-up
    assert check["count_mismatches"] == check["nk_total_off"] == 0
    assert check["ll_tables_rel"] <= check["ll_tables_rel_limit"] == 1e-5
    assert check["ll_chain_abs"] <= check["ll_chain_abs_limit"]


def test_what_correct_cannot_hold_is_not_listed_as_held():
    """Exact count gathers leave no trace a chain's statistics can show
    (the configuration's ``reference.why``), so they are a pinned knob
    under a tier-1 test, and the file says so."""
    config = spec.Cell(ROOT, CELL).config
    assert not any("gather" in g or "wire" in g
                   for g in config["guarantees"])
    pinned = " ".join(config["pinned_not_held_by_correct"])
    assert "pallas_exact_gathers" in pinned and "rotate_wire" in pinned
    assert config["knobs"]["pallas_exact_gathers"] is True
    assert config["knobs"]["rotate_wire"] == "exact"
    guard = "test_kernel_draws_the_exact_posterior_above_256"
    assert guard in pinned
    with open(os.path.join(ROOT, "tests", "test_lda_kernel.py")) as fh:
        assert "def " + guard + "(" in fh.read()


def test_cell_rehearses_traced(checkout):  # noqa: F811
    out = _run(checkout, True)
    assert out["correct"] is True
    _check_last_line(out, checkout, CELL, trace=True)
    got = out["metrics"]
    assert got["compiles_in_window"]["value"] == 0
    assert got["dispatches_per_block"]["value"] == 2.0
    # a count, of the chunk list as staged: 20,000 tokens in 2 rows of
    # 2 runs of 128-slot chunks, the no-ops that end the shorter run
    # counted (a toy tile holds 600 tokens: mostly whole chunks)
    share = got["lda_executed_pad_share"]["value"]
    chunks = 20_000 / (1 - share / 100) / 128
    assert 0.0 < share < 50.0 and chunks == pytest.approx(round(chunks))
    assert round(chunks) % 4 == 0
    # the program's spans were read; a CPU's seconds are not printed
    for name in ("lda_pack_s", "lda_partition_sort_s",
                 "lda_partition_pack_s"):
        assert got[name] == {"value": None, "unit": "s",
                             "note": "not measured: no chip"}
    # interpret mode leaves no Mosaic call in a CPU trace
    assert "lda_kernel_roofline" not in got
    assert "lda_kernel_ns_per_slot" not in got


def test_readers_read_nothing_where_nothing_is(monkeypatch):
    """The driver lays these files over the parent's checkout too: no
    ``lda.pack_tokens`` span and no ``lda.kernel_slots`` record there; an
    untraced run has no trace."""
    cell = spec.Cell(ROOT, CELL)
    run = harness.RunData(cell, harness.Recorder())
    run.window = (10.0, 20.0)
    telemetry.tracer.reset()
    skew.ledger.reset()
    for name in MINE:
        assert cell.reader("per_layer", name)(run) is None
    run.trace = {"class_s": {"xla": 1.0}, "busy_s": 1.0}  # no Mosaic call
    run.least, run.trace_items = {"seconds": 0.5}, 1000
    for name in MINE:
        assert cell.reader("per_layer", name)(run) is None
    # a Mosaic call, and still no record of the slots
    run.trace = {"class_s": {"kernel": 1.0}, "busy_s": 1.0}
    assert cell.reader("per_layer", "lda_kernel_ns_per_slot")(run) is None
    # one of the two spans alone is not the sum
    with telemetry.scope():
        with telemetry.span("lda.install"):
            pass
        run.window = (time.perf_counter(), time.perf_counter() + 1)
        assert cell.reader("per_layer", "lda_pack_s")(run) is None
    monkeypatch.delattr(telemetry.SpanTracer, "durations")
    assert cell.reader("per_layer", "lda_pack_s")(run) is None


def test_readers_read_what_is_there():
    cell = spec.Cell(ROOT, CELL)
    run = harness.RunData(cell, harness.Recorder())

    def set_tokens():  # the program's nesting
        with telemetry.span("lda.pack_tokens"):
            with telemetry.span("lda.pack.partition"):
                with telemetry.span("mfsgd.partition.sort"):
                    time.sleep(0.001)
            with telemetry.span("lda.pack.counts"):
                time.sleep(0.002)
        with telemetry.span("lda.install"):
            time.sleep(0.001)

    with telemetry.scope():
        with telemetry.span("mfsgd.set_ratings"):
            with telemetry.span("mfsgd.partition.sort"):
                time.sleep(0.003)
        set_tokens()
        in_setup = {r["span"]: r["dur"] for r in telemetry.tracer.records}
        start = time.perf_counter()
        set_tokens()  # after the window opened: not set-up
        run.window = (start, time.perf_counter())
        assert cell.reader("per_layer", "lda_pack_s")(run) == pytest.approx(
            in_setup["lda.pack_tokens"] + in_setup["lda.install"])
        # the partitioner's span counts where it ran below pack_tokens;
        # MF-SGD's own run of it (under mfsgd.set_ratings) is not LDA's
        assert cell.reader("per_layer", "lda_partition_sort_s")(run) == \
            pytest.approx(in_setup["mfsgd.partition.sort"])
        assert cell.reader("per_layer", "lda_partition_pack_s")(run) is None
        skew.ledger.record_partition("lda.kernel_slots", [250], unit="tokens",
                                     padded_total=1000)
        assert cell.reader("per_layer", "lda_executed_pad_share")(run) == \
            pytest.approx(75.0)
        run.trace = {"class_s": {"kernel": 2.0}, "busy_s": 2.5}
        run.least, run.trace_items = {"seconds": 0.03}, 500
        # 500 tokens are a quarter of the slots: 2 s over 2,000 slots
        assert cell.reader("per_layer", "lda_kernel_ns_per_slot")(run) == \
            pytest.approx(1e6)
    assert cell.reader("per_layer", "lda_kernel_roofline")(run) == \
        pytest.approx(1.5)


def test_work_model_counts_the_tokens_rows_and_its_posterior_only():
    least = workmodels.least_seconds(
        {"model": "lda_token_sample", "n_topics": 1000}, 1_000_000,
        "TPU v5 lite")
    assert least["wall"] == "hbm"
    # 8,016 bytes a token at 819 GB/s: 9.8 ns
    assert least["seconds"] == pytest.approx(1e6 * 8016 / 819e9)
    assert least["mxu_s"] == pytest.approx(1e6 * 6000 / 49.25e12)


def test_traffic_holds_two_whole_blocks_in_the_traced_part():
    traffic = spec.Cell(ROOT, CELL).traffic
    assert (traffic["mode"], traffic["steps"]) == ("steady", 1)
    assert "block" in traffic["why_steps"]
