"""The six per-layer metrics that read the program's own spans and its
``mfsgd.kernel_slots`` record, rehearsed on the CPU: which cell prints
which, and that a program without the spans, the record or the query
reads as nothing.  No number printed here is a speed."""

import os

import pytest

from perf import spec
from test_harness import BENCH, ROOT, _run, _tiny, checkout  # noqa: F401

SPANS = ["partition_sort_s", "partition_pack_s", "coverage_s",
         "stage_blocks_s"]
SIX = SPANS + ["executed_pad_share", "kernel_ns_per_slot"]


def test_the_six_are_read_in_the_mfsgd_cell_only():
    mine = {m["name"]: m for m in BENCH["per_layer"] if m["name"] in SIX}
    assert sorted(mine) == sorted(SIX)
    assert all(m["workloads"] == ["mfsgd-epochs"] for m in mine.values())
    # appended in this order among themselves (later PRs append theirs)
    assert [m["name"] for m in BENCH["per_layer"] if m["name"] in SIX] == SIX


def test_traced_mfsgd_rehearsal_prints_spans_and_executed_padding(checkout):
    out = _run(checkout, "mfsgd-epochs", True, _tiny("mfsgd-epochs"))
    got = out["metrics"]
    for name in SPANS:  # read, and a CPU's seconds are not printed
        assert got[name] == {"value": None, "unit": "s",
                             "note": "not measured: no chip"}
    # a count: the toy tiles are 16..1024 wide and staged in 128s or 512s
    pad = got["executed_pad_share"]
    assert pad["unit"] == "%" and 0.0 < pad["value"] < 100.0
    # retired in PR 28: it counted the rectangular entries, which no
    # longer run
    assert "pad_share" not in got
    # interpret mode leaves no Mosaic call in a CPU trace
    assert got.get("kernel_ns_per_slot", {"value": None})["value"] is None


def test_untraced_mfsgd_rehearsal_records_no_span(checkout):
    from harp_tpu.utils import skew, telemetry

    telemetry.tracer.reset()
    skew.reset()
    out = _run(checkout, "mfsgd-epochs", False, _tiny("mfsgd-epochs"))
    assert not set(out["metrics"]) & set(SIX)
    # telemetry is off but for the check's own block, after the window
    assert {r["span"] for r in telemetry.tracer.records} <= {"mfsgd.epochs"}
    assert "mfsgd.kernel_slots" not in skew.ledger.summary()


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]
                                  if w["name"] != "mfsgd-epochs"])
def test_other_cells_print_none_of_the_six(cell, checkout):
    out = _run(checkout, cell, True, _tiny(cell))
    assert not set(out["metrics"]) & set(SIX)


def test_a_program_without_them_reads_as_nothing(monkeypatch):
    """The driver lays these files over the parent's checkout too: no
    ``durations`` query, no span, no ``mfsgd.kernel_slots`` there."""
    from harp_tpu.utils import skew, telemetry
    from perf import harness

    monkeypatch.delattr(telemetry.SpanTracer, "durations")
    monkeypatch.setattr(skew, "ledger", skew.SkewLedger())
    cell = spec.Cell(ROOT, "mfsgd-epochs")
    run = harness.RunData(cell, harness.Recorder())
    run.window = (10.0, 20.0)
    run.trace = {"class_s": {"kernel": 1.0}, "busy_s": 1.0}
    run.trace_items = 1000
    for name in SIX:
        assert cell.reader("per_layer", name)(run) is None
    assert os.path.isfile(os.path.join(ROOT, "perf", "program_telemetry.py"))


def test_readers_cut_at_the_window_and_count_slots():
    """Longhand: spans under ``mfsgd.set_ratings`` that end before the
    window are summed, the others are not; ns per slot divides by
    valid / (1 - padding)."""
    import time

    from harp_tpu.utils import skew, telemetry
    from perf import harness

    def stage():
        with telemetry.span("mesh.shard_array"):
            time.sleep(0.002)

    cell = spec.Cell(ROOT, "mfsgd-epochs")
    run = harness.RunData(cell, harness.Recorder())
    with telemetry.scope():
        with telemetry.span("mfsgd.set_ratings"):
            stage()
            stage()
        stage()  # the factors' placement: under no set_ratings
        start = time.perf_counter()
        with telemetry.span("mfsgd.set_ratings"):
            stage()  # after the window opened
        run.window = (start, time.perf_counter())
        in_setup = [r["dur"] for r in telemetry.tracer.records
                    if r["span"] == "mesh.shard_array"][:2]
        skew.ledger.record_partition("mfsgd.kernel_slots", [250.0],
                                     unit="ratings", padded_total=1000)
        run.trace = {"class_s": {"kernel": 2e-6}, "busy_s": 1.0}
        run.trace_items = 500  # two epochs of 250 ratings
        read = {n: cell.reader("per_layer", n)(run) for n in SIX}
    assert read["stage_blocks_s"] == pytest.approx(sum(in_setup))
    assert read["coverage_s"] is None
    assert read["executed_pad_share"] == pytest.approx(75.0)
    # 2 us over 2 x 1000 slots
    assert read["kernel_ns_per_slot"] == pytest.approx(1.0)
