"""The reduction from a profiler trace to numbers: on a hand-made trace
whose answers can be worked out on paper, and on a small trace recorded
on the chip (``perf/testdata``)."""

import os
from types import SimpleNamespace as NS

import pytest

from perf import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "..", "testdata",
                        "kmeans_tiny_v5e.xplane.pb")


def _ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def _fake():
    """Window 0..1000 ns.  Device 0: a while loop 100..700 holding a
    fusion 100..300, a Mosaic call 300..500 and an all-reduce 550..650;
    then a lone fusion 800..900.  Idle: 0..100 (host in `dispatch`),
    700..800 and 900..1000 (host in `readback`); inside the loop, 500..550
    and 650..700 are the loop's own time, not idle."""
    ops = NS(name="XLA Ops", events=[
        _ev("while.3", 100, 600), _ev("fusion.1", 100, 200),
        _ev("tpu_custom_call.7", 300, 200), _ev("all-reduce.2", 550, 100),
        _ev("fusion.9", 800, 100)])
    other = NS(name="XLA Modules", events=[_ev("jit_step", 100, 800)])
    host = NS(name="python", events=[
        _ev("perf:window", 0, 1000), _ev("perf:dispatch", 0, 120),
        _ev("perf:readback", 120, 880), _ev("unrelated", 0, 1000)])
    return NS(planes=[
        NS(name="/device:TPU:0", lines=[ops, other]),
        NS(name="/host:CPU", lines=[host])])


def test_union_and_self_times():
    assert tr._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    selfs = dict(tr.self_times([("outer", 0, 10), ("a", 1, 4),
                                ("b", 4, 6), ("a2", 2, 3)]))
    assert selfs == {"outer": 5, "a": 2, "b": 2, "a2": 1}


@pytest.mark.parametrize("name,cls", [
    ("fusion.96", "xla"), ("copy.3", "xla"), ("closed_call.14", "xla"),
    ("all-reduce.1", "collective"), ("all-reduce-start.2", "collective"),
    ("collective-permute-done.5", "collective"),
    ("all-gather.7", "collective"), ("all-to-all", "collective"),
    ("tpu_custom_call.3", "kernel"), ("custom-call.12", "kernel"),
])
def test_op_classes(name, cls):
    assert tr.op_class(name) == cls


def test_reduce_on_a_trace_worked_out_by_hand():
    r = tr.reduce(_fake())
    ns = 1e-9
    assert r["n_devices"] == 1
    assert r["window_s"] == pytest.approx(1000 * ns)
    assert r["busy_s"] == pytest.approx(700 * ns)       # 100..700, 800..900
    assert r["class_s"]["xla"] == pytest.approx(300 * ns)
    assert r["class_s"]["kernel"] == pytest.approx(200 * ns)
    assert r["class_s"]["collective"] == pytest.approx(100 * ns)
    assert r["device_ops"][0][0] in ("fusion.1", "tpu_custom_call.7")
    assert "while.3" not in dict(r["device_ops"])
    gaps = dict(r["idle_gaps"])
    assert gaps["dispatch"] == pytest.approx(100 * ns)
    assert gaps["readback"] == pytest.approx(200 * ns)
    assert sum(gaps.values()) == pytest.approx(
        r["window_s"] - r["busy_s"])
    assert [s[0] for s in r["spans"]][:2] == ["perf:window",
                                              "perf:dispatch"]


def test_reduce_cuts_to_the_window_and_averages_devices():
    fake = _fake()
    fake.planes[1].lines[0].events[0] = _ev("perf:window", 200, 700)
    second = NS(name="/device:TPU:1", lines=[NS(name="XLA Ops", events=[
        _ev("fusion.1", 200, 700)])])
    fake.planes.insert(1, second)
    r = tr.reduce(fake)
    ns = 1e-9
    assert r["n_devices"] == 2 and r["window_s"] == pytest.approx(700 * ns)
    # device 0 inside 200..900: 200..700 and 800..900; device 1: all of it
    assert r["busy_s"] == pytest.approx((600 + 700) / 2 * ns)


def test_reduce_without_a_device_plane_reports_nothing_busy():
    fake = _fake()
    del fake.planes[0]
    r = tr.reduce(fake)
    assert r["busy_s"] == 0.0 and r["n_devices"] == 0
    assert r["device_ops"] == [] and r["idle_gaps"] == []


def test_busy_intervals_are_from_the_window_start():
    """Ops a microsecond apart are one busy stretch (a program's ops
    follow each other a nanosecond apart); a longer gap splits it."""
    ops = NS(name="XLA Ops", events=[
        _ev("fusion.1", 10_000, 5_000), _ev("fusion.2", 15_001, 4_999),
        _ev("fusion.3", 50_000, 10_000)])
    host = NS(name="python", events=[_ev("perf:window", 5_000, 95_000)])
    r = tr.reduce(NS(planes=[NS(name="/device:TPU:0", lines=[ops]),
                             NS(name="/host:CPU", lines=[host])]))
    assert r["busy"] == [pytest.approx([5e-6, 15e-6]),
                         pytest.approx([45e-6, 55e-6])]
    assert r["busy_s"] == pytest.approx(19_999e-9)


@pytest.mark.parametrize("text,name,opcode", [
    ("%fusion.14 = (f32[4000000]{0:T(1024)S(1)}, s32[4000000]{0:T(1024)"
     "S(1)}) fusion(bf16[4000000,300]{0,1:T(8,128)(2,1)} %gte.141), "
     "kind=kOutput, calls=%fused_computation", "fusion.14", "fusion"),
    ("%closed_call.14 = (f32[64,138496]{1,0:T(8,128)S(1)}, f32[1,1]{1,0:"
     "T(1,128)}) custom-call(s32[28673]{0:T(1024)S(1)} %a), "
     "custom_call_target=\"tpu_custom_call\"", "closed_call.14",
     "custom-call"),
    ("%while.2 = (s32[]{:T(128)}, f32[100,300]{0,1:T(8,128)}) while((s32[]"
     "{:T(128)}, f32[100,300]{0,1:T(8,128)}) %tuple), condition=%c, body=%b",
     "while.2", "while"),
    ("%all-reduce.3 = f32[100,301]{1,0:T(8,128)} all-reduce(f32[100,301]"
     "{1,0:T(8,128)} %x), replica_groups={{0,1,2,3}}", "all-reduce.3",
     "all-reduce"),
    ("%copy-done.2 = f32[100,300]{0,1:T(8,128)} copy-done((f32[100,300]{0,1:"
     "T(8,128)}, u32[]{:S(2)}) %copy-start.2)", "copy-done.2", "copy-done"),
])
def test_hlo_text_names_are_parsed(text, name, opcode):
    assert tr.parse_op(text) == (name, opcode)


def test_hlo_text_classes_and_short_names():
    kernel = ("%closed_call.14 = (f32[64,8]{1,0}) custom-call(s32[8]{0} "
              "%a), custom_call_target=\"tpu_custom_call\"")
    assert tr.op_class(kernel) == "kernel"
    assert tr.short_name(kernel) == "closed_call.14 [tpu_custom_call]"
    assert tr.short_name("%fusion.14 = f32[8]{0} fusion(f32[8]{0} %x), "
                         "kind=kLoop") == "fusion.14"
    assert tr.short_name("%multiply_reduce_fusion.4 = f32[8]{0} fusion("
                         "f32[8]{0} %x)") == "multiply_reduce_fusion.4 [fusion]"
    assert tr._is_container("%while.2 = (s32[]) while((s32[]) %t)")


# ---- traces recorded on a TPU v5 lite (my chip run, PR 22) ---------------

def test_recorded_kmeans_trace():
    """29 blocks of 4 Lloyd iterations on 65,536 x 300 points, k=100:
    every op is an XLA fusion, and the device waits on the host's
    readbacks for four fifths of the window."""
    r = tr.reduce(tr.load(RECORDED))
    assert r["n_devices"] == 1
    assert r["window_s"] == pytest.approx(0.051337699, rel=1e-6)
    assert r["busy_s"] == pytest.approx(0.011074814, rel=1e-6)
    assert set(r["class_s"]) == {"xla"}
    assert r["class_s"]["xla"] == pytest.approx(0.01098885, rel=1e-6)
    assert [op[0] for op in r["device_ops"][:2]] == [
        "fusion.14", "multiply_reduce_fusion.4 [fusion]"]
    assert r["device_ops"][0][1] == pytest.approx(0.004166962, rel=1e-6)
    gaps = dict(r["idle_gaps"])
    assert gaps["readback"] == pytest.approx(0.04005078, rel=1e-6)
    assert gaps["dispatch"] == pytest.approx(0.000212105, rel=1e-6)
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert sum(1 for s in r["spans"] if s[0] == "perf:dispatch") == 29
    assert len(r["busy"]) == 29 and r["busy"][0][0] > 0


def test_recorded_mfsgd_trace():
    """5 blocks of 8 epochs over 20.0M ratings: one Mosaic call holds
    96% of the busy time, and the device is idle 0.4% of the window."""
    r = tr.reduce(tr.load(os.path.join(
        HERE, "..", "testdata", "mfsgd_epochs_v5e.xplane.pb")))
    assert r["window_s"] == pytest.approx(3.364054921, rel=1e-6)
    assert r["busy_s"] == pytest.approx(3.351228126, rel=1e-6)
    assert r["class_s"]["kernel"] == pytest.approx(3.219664865, rel=1e-6)
    assert r["class_s"]["xla"] == pytest.approx(0.131415119, rel=1e-6)
    assert "collective" not in r["class_s"]
    assert r["device_ops"][0][0] == "closed_call.14 [tpu_custom_call]"
    assert dict(r["idle_gaps"])["readback"] == pytest.approx(
        0.012098479, rel=1e-6)
