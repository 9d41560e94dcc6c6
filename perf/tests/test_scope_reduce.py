"""Device time by the program's scopes (``perf/scope_reduce.py``) on a
hand-made trace whose answers can be worked out on paper, on the trace
recorded on the chip, and through the five readers."""

import json
import os
from types import SimpleNamespace as NS

import pytest

from harp_tpu.utils import telemetry
from perf import harness, scope_reduce, spec, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
RECORDED = os.path.join(HERE, "..", "testdata", "kmeans_tiny_v5e.xplane.pb")
NS_ = 1e-9
READERS = ("scoped_share", "subgraph_tail_share", "subgraph_padded_share",
           "subgraph_order_share", "mlp_layer1_share")

# two programs that both own a ``fusion.1``, under different scopes
MODULES = {
    "jit_a": {
        "fusion.1": "jit(a)/subgraph.sum.leaf/subgraph.tail/while/body/"
                    "closed_call/scatter-add",
        "fusion.2": "jit(a)/subgraph.sum.leaf/subgraph.padded/gather",
        "while.3": "jit(a)/subgraph.sum.leaf/subgraph.tail/while",
        "fusion.4": "jit(a)/subgraph.sum.t3/subgraph.order.put/scatter"},
    "jit_b": {
        "fusion.1": "jit(b)/transpose(jvp(mlp.layer1))/dot_general",
        "fusion.7": "jit(b)/jvp(mlp.layer1)/dot_general",
        "fusion.8": "jit(b)/mlp.loss/reduce_sum",
        "broadcast.5": "jit(b)/broadcast.25"},
}


def _ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def _fake():
    """Window 0..1000 ns.  Program ``jit_a`` runs 100..500: a ``while``
    100..500 holding ``fusion.1`` 100..300 (the tail of the leaf's sum)
    and ``fusion.2`` 300..450 (its padded part); the loop's last 50 ns are
    its own.  Program ``jit_b`` runs 600..900: ``fusion.1`` 600..700
    (layer 1, backward), ``copy.9`` 700..760 (no map entry),
    ``broadcast.5`` 760..770 (an ``op_name`` with no scope), ``fusion.7``
    770..900 (layer 1, forward).  ``fusion.8`` 950..1100 lies under no
    module event and only ``jit_b`` has one: the loss; the window cuts it
    to 50 ns."""
    ops = NS(name="XLA Ops", events=[
        _ev("%while.3 = (s32[]) while((s32[]) %t), condition=%c, body=%b",
            100, 400),
        _ev("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %x), kind=kCustom",
            100, 200),
        _ev("fusion.2", 300, 150),
        _ev("%fusion.1 = f32[4]{0} fusion(f32[4]{0} %y), kind=kOutput",
            600, 100),
        _ev("copy.9", 700, 60), _ev("broadcast.5", 760, 10),
        _ev("fusion.7", 770, 130), _ev("fusion.8", 950, 150)])
    modules = NS(name="XLA Modules", events=[
        _ev("jit_b(222)", 600, 300), _ev("jit_a(111)", 100, 400)])
    host = NS(name="python", events=[_ev("perf:window", 0, 1000)])
    return NS(planes=[
        NS(name="/device:TPU:0", lines=[ops, modules]),
        NS(name="/host:CPU", lines=[host])])


def test_by_scope_on_a_trace_worked_out_by_hand():
    r = scope_reduce.reduce(_fake(), MODULES)
    assert r["n_devices"] == 1
    # 200 + 150 + 100 + 60 + 10 + 130 + 50; the loop's own 50 are skipped
    assert r["busy_s"] == pytest.approx(700 * NS_)
    assert r["by_path"] == pytest.approx({
        "mlp.layer1": 230 * NS_,
        "subgraph.sum.leaf/subgraph.tail": 200 * NS_,
        "subgraph.sum.leaf/subgraph.padded": 150 * NS_,
        "mlp.loss": 50 * NS_})
    assert list(r["by_path"])[0] == "mlp.layer1"   # largest first
    # a nested op counts under each scope of its path
    assert r["by_scope"] == pytest.approx({
        "subgraph.sum.leaf": 350 * NS_, "mlp.layer1": 230 * NS_,
        "subgraph.tail": 200 * NS_, "subgraph.padded": 150 * NS_,
        "mlp.loss": 50 * NS_})
    assert r["backward_s"] == pytest.approx({"mlp.layer1": 100 * NS_})
    assert r["unscoped_s"] == pytest.approx(70 * NS_)
    assert r["unscoped_ops"] == [["copy.9", pytest.approx(60 * NS_)],
                                 ["broadcast.5", pytest.approx(10 * NS_)]]
    # both programs' fusion.1 stand under the scope their program gave
    assert r["top_ops"]["subgraph.tail"] == [
        ["fusion.1", pytest.approx(200 * NS_)]]
    assert r["top_ops"]["mlp.layer1"] == [
        ["fusion.7", pytest.approx(130 * NS_)],
        ["fusion.1", pytest.approx(100 * NS_)]]
    assert sum(r["by_path"].values()) + r["unscoped_s"] == pytest.approx(
        r["busy_s"])


def test_by_scope_cuts_to_the_window_and_averages_devices():
    fake = _fake()
    fake.planes[1].lines[0].events[0] = _ev("perf:window", 200, 700)
    fake.planes.insert(1, NS(name="/device:TPU:1", lines=[
        NS(name="XLA Ops", events=[_ev("fusion.2", 200, 700)]),
        NS(name="XLA Modules", events=[_ev("jit_a(111)", 200, 700)])]))
    r = scope_reduce.reduce(fake, MODULES)
    assert r["n_devices"] == 2
    # device 0 inside 200..900: 100 + 150 + 100 + 60 + 10 + 130; device 1
    # all of it
    assert r["busy_s"] == pytest.approx((550 + 700) / 2 * NS_)
    assert r["by_scope"]["subgraph.padded"] == pytest.approx(
        (150 + 700) / 2 * NS_)
    assert r["by_scope"]["subgraph.tail"] == pytest.approx(100 / 2 * NS_)
    assert "mlp.loss" not in r["by_scope"]
    assert r["unscoped_s"] == pytest.approx(70 / 2 * NS_)


def test_an_op_is_its_programs_by_the_module_that_holds_it_in_time():
    """Without the module line ``fusion.1`` has two owners and stays
    unscoped; the instructions one program alone has are still found."""
    fake = _fake()
    del fake.planes[0].lines[1]
    r = scope_reduce.reduce(fake, MODULES)
    assert r["by_scope"]["subgraph.padded"] == pytest.approx(150 * NS_)
    assert r["by_scope"]["mlp.layer1"] == pytest.approx(130 * NS_)
    assert "subgraph.tail" not in r["by_scope"]
    assert r["unscoped_s"] == pytest.approx((200 + 100 + 70) * NS_)


def test_a_trace_without_a_device_plane_gives_nothing():
    fake = _fake()
    del fake.planes[0]
    assert scope_reduce.reduce(fake, MODULES) is None


def test_recorded_kmeans_trace_by_a_map_written_by_hand():
    """The v5e trace of ``test_trace_reduce.py`` (29 blocks of 4 Lloyd
    iterations), its four fusions named as the program names them now:
    what ``trace_reduce`` reads per op is what the scope reads."""
    pd = trace_reduce.load(RECORDED)
    modules = {"jit_run": {
        "fusion.14": "jit(run)/while/body/closed_call/kmeans.assign/argmin",
        "multiply_reduce_fusion.4":
            "jit(run)/while/body/closed_call/kmeans.sums/dot_general",
        "convert_reduce_fusion.2":
            "jit(run)/while/body/closed_call/kmeans.sums/reduce_sum",
        "multiply_reduce_fusion.1":
            "jit(run)/while/body/closed_call/kmeans.cast/reduce_sum"}}
    r = scope_reduce.reduce(pd, modules)
    per_op = dict(trace_reduce.reduce(pd, top=100)["device_ops"])
    assert r["by_scope"]["kmeans.assign"] == pytest.approx(
        per_op["fusion.14"]) == pytest.approx(0.004166962, rel=1e-6)
    assert r["by_scope"]["kmeans.sums"] == pytest.approx(
        per_op["multiply_reduce_fusion.4 [fusion]"]
        + per_op["convert_reduce_fusion.2 [fusion]"])
    assert r["busy_s"] == pytest.approx(sum(per_op.values()))
    assert r["busy_s"] == pytest.approx(0.01098885, rel=1e-6)  # class xla
    assert r["unscoped_s"] == pytest.approx(
        r["busy_s"] - sum(r["by_path"].values()))
    assert 0 < r["unscoped_s"] < 0.02 * r["busy_s"]
    assert {op for op, _ in r["unscoped_ops"]} <= set(per_op)


# ---- the readers ----------------------------------------------------------

@pytest.fixture()
def traced_run(tmp_path, monkeypatch):
    """A run whose trace file is there (its content is the hand-made
    trace) and whose program kept the map."""
    at = tmp_path / harness.TRACE_DIR / "a-cell" / "plugins" / "profile" / "t"
    at.mkdir(parents=True)
    (at / "host.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(trace_reduce, "load", lambda path: _fake())
    monkeypatch.setattr(telemetry.scopes, "modules", MODULES)
    return NS(cell=NS(root=str(tmp_path), name="a-cell"),
              trace={"busy_s": 750 * NS_})


def _read(name, run):
    return spec.load_module(os.path.join(
        ROOT, "perf", "layer_metrics", name + ".py")).read(run)


def test_readers_on_the_hand_made_trace(traced_run, capsys):
    got = {name: _read(name, traced_run) for name in READERS}
    assert got == pytest.approx({
        "scoped_share": 100 * 630 / 700,
        "subgraph_tail_share": 100 * 200 / 700,
        "subgraph_padded_share": 100 * 150 / 700,
        "subgraph_order_share": 0.0,   # this program ran no degree order
        "mlp_layer1_share": 100 * 230 / 700})
    # one pass and one table a run, whatever number of readers
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln]
    assert len(lines) == 1 and lines[0].startswith('info {"scopes": ')
    table = json.loads(lines[0][len("info "):])["scopes"]
    assert table["by_path"]["subgraph.sum.leaf/subgraph.tail"] \
        == pytest.approx(200 * NS_)
    assert table["unscoped_ops"][0][0] == "copy.9"


def test_order_share_adds_the_take_and_the_put(traced_run, monkeypatch):
    fake = _fake()
    fake.planes[0].lines[0].events.append(_ev("fusion.4", 510, 70))
    fake.planes[0].lines[1].events.append(_ev("jit_a(111)", 505, 90))
    monkeypatch.setattr(trace_reduce, "load", lambda path: fake)
    assert _read("subgraph_order_share", traced_run) == pytest.approx(
        100 * 70 / 770)


@pytest.mark.parametrize("why", ["untraced", "no_trace_file", "no_map",
                                 "program_without_the_map", "cpu_trace"])
def test_readers_read_nothing_where_there_is_nothing(why, traced_run,
                                                     monkeypatch, capsys):
    if why == "untraced":
        traced_run.trace = None
    elif why == "no_trace_file":
        traced_run.cell.name = "another-cell"
    elif why == "no_map":
        monkeypatch.setattr(telemetry.scopes, "modules", {})
    elif why == "program_without_the_map":   # one from before PR 40
        monkeypatch.delattr(telemetry, "scopes")
    else:
        cpu = _fake()
        del cpu.planes[0]
        monkeypatch.setattr(trace_reduce, "load", lambda path: cpu)
    assert [_read(name, traced_run) for name in READERS] == [None] * 5
    assert capsys.readouterr().out == ""
