"""MF-SGD's data path as the program itself records it: the spans inside
``MFSGD.set_ratings`` (sort / pack / coverage / stage), the query the
readers use (``SpanTracer.durations``), and ``"mfsgd.kernel_slots"``, the
skew record counted on the arrays as staged.  With telemetry off none of
it exists and the staged blocks are the same bytes.
"""

import glob
import os
import time

import numpy as np
import pytest

from harp_tpu import health
from harp_tpu.models import mfsgd as MF
from harp_tpu.utils import skew, telemetry

PARENT = "mfsgd.set_ratings"
#: children of ``mfsgd.set_ratings`` and how often one call records each
CHILDREN = {
    "pallas": {"mfsgd.partition.sort": 1, "mfsgd.partition.pack": 1,
               "mfsgd.coverage": 1, "mesh.shard_array": 4},
    "dense": {"mfsgd.partition.sort": 1, "mfsgd.partition.pack": 1,
              "mesh.shard_array": 5},
    "scatter": {"mfsgd.partition.sort": 1, "mfsgd.partition.pack": 1,
                "mesh.shard_array": 4},
}
ALGOS = sorted(CHILDREN)


def _cfg(algo):
    import jax.numpy as jnp

    # entry_cap 16: the partitioner's entries are 16 wide, which is no
    # multiple of the kernel's 128 lanes, so the coverage pass stages each
    # as one 128-wide chunk
    return MF.MFSGDConfig(algo=algo, rank=4, u_tile=8, i_tile=8,
                          entry_cap=16, chunk=64,
                          compute_dtype=jnp.float32, lr=0.02, reg=0.01)


def _ratings(seed=5, n_users=64, n_items=48, nnz=600):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, n_users, nnz).astype(np.int32),
            rng.integers(0, n_items, nnz).astype(np.int32),
            rng.normal(size=nnz).astype(np.float32))


def _model(mesh, algo):
    return MF.MFSGD(64, 48, _cfg(algo), mesh, seed=3)


@pytest.mark.parametrize("algo", ALGOS)
def test_set_ratings_records_each_span_inside_its_parent(mesh, algo):
    model = _model(mesh, algo)  # its W and H are staged outside any span
    with telemetry.scope():
        model.set_ratings(*_ratings())
        records = list(telemetry.tracer.records)
    by_name: dict[str, list[dict]] = {}
    for r in records:
        by_name.setdefault(r["span"], []).append(r)
    assert {k: len(v) for k, v in by_name.items()} == {
        PARENT: 1, **CHILDREN[algo]}
    parent = by_name[PARENT][0]
    assert parent["path"] == PARENT and parent["depth"] == 0
    children = [r for r in records if r is not parent]
    for r in children:
        assert r["path"] == f"{PARENT}/{r['span']}" and r["depth"] == 1
        assert r["t0"] >= parent["t0"]
        # t0 and dur are rounded to the microsecond
        assert r["t0"] + r["dur"] <= parent["t0"] + parent["dur"] + 2e-6
    assert sum(r["dur"] for r in children) <= parent["dur"] + 1e-5
    # each placement names the bytes it was handed
    assert [r["bytes"] for r in by_name["mesh.shard_array"]] == [
        int(b.nbytes) for b in model._blocks]
    # in the order of the data path
    order = [r["span"] for r in sorted(children, key=lambda r: r["t0"])]
    assert order[:2] == ["mfsgd.partition.sort", "mfsgd.partition.pack"]
    assert set(order[-len(model._blocks):]) == {"mesh.shard_array"}


@pytest.mark.parametrize("algo", ALGOS)
def test_telemetry_off_records_nothing_and_stages_the_same_bytes(mesh, algo):
    traced, plain = _model(mesh, algo), _model(mesh, algo)
    with telemetry.scope():
        traced.set_ratings(*_ratings())
    with telemetry.scope(False):
        plain.set_ratings(*_ratings())
        assert telemetry.tracer.records == []
        assert skew.ledger.summary() == {}
    assert len(traced._blocks) == len(plain._blocks)
    for a, b in zip(traced._blocks, plain._blocks):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.tobytes() == b.tobytes()
    assert traced.nnz == plain.nnz == 600


def test_durations_selects_by_name_ancestor_and_time():
    with telemetry.scope():
        with telemetry.span("outer"):
            with telemetry.span("leaf"):
                time.sleep(0.002)
            with telemetry.span("middle"):
                with telemetry.span("leaf"):
                    time.sleep(0.002)
        cut = time.perf_counter()
        with telemetry.span("leaf"):
            time.sleep(0.002)
        tr = telemetry.tracer
        every = tr.durations("leaf")
        assert len(every) == 3 and all(d >= 0.002 for d in every)
        assert every == [r["dur"] for r in tr.records if r["span"] == "leaf"]
        assert len(tr.durations("leaf", under="outer")) == 2
        assert len(tr.durations("leaf", under="middle")) == 1
        # an ancestor, not the span itself
        assert tr.durations("leaf", under="leaf") == []
        assert tr.durations("outer", under="outer") == []
        # absolute perf_counter seconds: before the cut, and after it
        assert tr.durations("leaf", t1=cut) == every[:2]
        assert tr.durations("leaf", t0=cut) == every[2:]
        assert tr.durations("leaf", under="outer", t0=cut) == []
        assert tr.durations("no-such-span") == []


@pytest.mark.parametrize("algo", ALGOS)
def test_kernel_slots_counts_the_staged_arrays(mesh, algo):
    model = _model(mesh, algo)
    u, i, v = _ratings()
    with telemetry.scope():
        model.set_ratings(u, i, v)
        rows = skew.ledger.summary()
    part, slots = rows["mfsgd.partition"], rows["mfsgd.kernel_slots"]
    staged = np.asarray(model._blocks[0])
    assert slots["padding_frac"] == pytest.approx(
        1.0 - len(v) / staged.size, abs=1e-6)
    # the same ratings on the same workers, over other slots
    assert slots["work"] == part["work"] and slots["total"] == len(v)
    assert slots["unit"] == "ratings" and slots["source"] == "ingest"
    if algo == "pallas":
        # the kernel's lanes: entries 16 wide are staged 128 wide, and the
        # record made before the coverage pass cannot see it
        assert staged.shape[-1] == 128
        assert slots["padding_frac"] > part["padding_frac"] + 0.1
    else:
        assert slots["padding_frac"] == part["padding_frac"]


def test_kernel_slots_is_no_second_skew_finding(mesh):
    """Seven ratings in ten on worker 0: the monitor hears of it under
    ``mfsgd.partition`` and under no other name."""
    rng = np.random.default_rng(1)
    u = np.concatenate([rng.integers(0, 8, 700),
                        rng.integers(8, 64, 300)]).astype(np.int32)
    i = rng.integers(0, 48, 1000).astype(np.int32)
    v = rng.normal(size=1000).astype(np.float32)
    model = _model(mesh, "pallas")
    with telemetry.scope():
        for _ in range(health.sentinel.TRIGGER_SUPERSTEPS):
            model.set_ratings(u, i, v)
        assert skew.ledger.summary()["mfsgd.kernel_slots"]["runs"] == \
            health.sentinel.TRIGGER_SUPERSTEPS
        fired = [f["phase"] for f in health.monitor.findings()
                 if f["detector"] == "skew_trigger"]
    assert fired == ["mfsgd.partition"]


def test_spans_are_host_events_of_a_profiler_trace(mesh, tmp_path):
    """``telemetry.span`` enters ``TraceAnnotation``: the same spans lie
    on the profiler's clock, where a device trace can be read against
    them."""
    import jax
    from jax.profiler import ProfileData

    model = _model(mesh, "pallas")
    with telemetry.scope():
        model.set_ratings(*_ratings())
        model.compile_epochs(2)
        telemetry.tracer.reset()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            model.set_ratings(*_ratings())
            model.train_epochs(2)
        finally:
            jax.profiler.stop_trace()
        recorded = {r["span"] for r in telemetry.tracer.records}
    assert recorded == {PARENT, *CHILDREN["pallas"], "mfsgd.epochs"}
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    events, every = {}, []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                every.append((ev.start_ns, ev.start_ns + ev.duration_ns))
                if plane.name.startswith("/host:") and ev.name in recorded:
                    events.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    assert set(events) == recorded
    assert len(events["mesh.shard_array"]) == 4
    first, last = min(s for s, _ in every), max(e for _, e in every)
    (p0, p1), = events[PARENT]
    for name, spans in events.items():
        for s, e in spans:
            assert first <= s <= e <= last
            if name not in (PARENT, "mfsgd.epochs"):
                assert p0 <= s and e <= p1
    # the epochs ran after the ratings were staged
    assert events["mfsgd.epochs"][0][0] >= p1
