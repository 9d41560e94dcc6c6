"""harp serve — micro-batcher, AOT executable cache, engines, server.

The acceptance gates of the serving subsystem, all on the 8-sim-worker
CPU mesh (no chip):

- shape-ladder bucketing is minimal (padding bounded), ragged tails pad
  to their rung, oversized requests span batches and reassemble;
- the steady-state loop holds ``compiles=0, dispatches=1, readbacks=1``
  per batch for kmeans-assign AND mfsgd-topk (the budget pin);
- a warm restart against a populated executable cache performs ZERO XLA
  compiles before serving its first request (CompileWatch-proven);
- engine outputs match numpy references;
- the stdio JSONL protocol round-trips end-to-end, checkpoint included.
"""

import io
import json

import numpy as np
import pytest

from harp_tpu.serve.batcher import MicroBatcher, ShapeLadder
from harp_tpu.serve.engines import ENGINES
from harp_tpu.serve.server import Server
from harp_tpu.utils import flightrec, telemetry


# ---------------------------------------------------------------------------
# ShapeLadder / MicroBatcher (pure host, no jax)
# ---------------------------------------------------------------------------

def test_ladder_bucket_is_minimal_rung():
    lad = ShapeLadder((1, 8, 64, 512))
    assert lad.bucket(1) == 1
    assert lad.bucket(2) == 8
    assert lad.bucket(8) == 8
    assert lad.bucket(9) == 64
    assert lad.bucket(512) == 512
    with pytest.raises(ValueError):
        lad.bucket(513)
    with pytest.raises(ValueError):
        lad.bucket(0)


def test_ladder_padding_fraction_bounded():
    # minimality bound: (rung - n)/rung < 1 - prev_rung/rung for every n
    lad = ShapeLadder((1, 8, 64, 512))
    rungs = (0,) + lad.rungs
    for n in range(1, 513):
        s = lad.bucket(n)
        prev = max(r for r in rungs if r < s)
        assert (s - n) / s < 1 - prev / s + 1e-12


def test_batcher_coalesces_and_pads_ragged_tail():
    mb = MicroBatcher((1, 8, 32))
    for i in range(5):
        mb.put(i, 9)  # 45 rows queued
    batches = list(mb.batches())
    assert [b.rung for b in batches] == [32, 32]
    assert [b.rows for b in batches] == [32, 13]
    assert batches[1].padding_frac == pytest.approx((32 - 13) / 32)
    # every row of every request landed exactly once, in order
    seen = {i: 0 for i in range(5)}
    for b in batches:
        for req, lo, hi in b.requests:
            assert hi > lo
            assert lo == seen[req]  # contiguous, in-order slices
            seen[req] = hi
    assert all(v == 9 for v in seen.values())
    assert mb.padding_frac() == pytest.approx((64 - 45) / 64)


def test_batcher_single_request_takes_smallest_rung():
    mb = MicroBatcher((1, 8, 64))
    mb.put("a", 1)
    (b,) = list(mb.batches())
    assert b.rung == 1 and b.rows == 1 and b.padding_frac == 0.0


def test_batcher_request_larger_than_max_rung_spans_batches():
    mb = MicroBatcher((1, 8, 32))
    mb.put("big", 70)
    batches = list(mb.batches())
    assert [b.rung for b in batches] == [32, 32, 8]
    assert [b.rows for b in batches] == [32, 32, 6]
    slices = [(lo, hi) for b in batches for _, lo, hi in b.requests]
    assert slices == [(0, 32), (32, 64), (64, 70)]


# ---------------------------------------------------------------------------
# flightrec.SteadyState (the serving-loop guard)
# ---------------------------------------------------------------------------

def test_steady_state_raises_on_violation(mesh):
    with telemetry.scope(True):
        steady = flightrec.SteadyState(compiles=0, dispatches=0,
                                       readbacks=1, tag="t")
        with pytest.raises(flightrec.BudgetExceeded, match="dispatches"):
            with steady.batch():
                flightrec.transfers.record_dispatch("site")
        assert steady.violations == 1


def test_steady_state_warn_mode_counts_and_continues(mesh):
    with telemetry.scope(True):
        steady = flightrec.SteadyState(dispatches=0, action="warn",
                                       tag="t")
        with pytest.warns(RuntimeWarning, match="steady-state budget"):
            with steady.batch():
                flightrec.transfers.record_dispatch("site")
        with steady.batch():
            pass
        s = steady.summary()
        assert s["batches"] == 2 and s["violations"] == 1


def test_steady_state_noop_when_disabled(mesh):
    steady = flightrec.SteadyState(dispatches=0)
    with telemetry.scope(False):
        with steady.batch():
            pass
    assert steady.batches == 0


# ---------------------------------------------------------------------------
# Engines vs numpy references
# ---------------------------------------------------------------------------

def _server(app, state, mesh, tmp_path, ladder=(1, 8, 64), **opts):
    srv = Server(app, state=state, mesh=mesh, ladder=ladder,
                 cache_dir=str(tmp_path / f"aot_{app}"),
                 engine_opts=opts or None)
    srv.startup()
    return srv


def test_kmeans_assign_matches_numpy(mesh, tmp_path):
    rng = np.random.default_rng(0)
    state = ENGINES["kmeans"].synthetic_state(rng, k=16, d=32)
    srv = _server("kmeans", state, mesh, tmp_path)
    x = rng.normal(size=(11, 32)).astype(np.float32)
    (resp,) = srv.process([{"id": 7, "x": x.tolist()}])
    ref = np.argmin(((x[:, None, :] - state["centroids"][None]) ** 2
                     ).sum(-1), axis=1)
    assert resp["id"] == 7 and resp["result"] == ref.tolist()


def test_mfsgd_topk_matches_numpy(mesh, tmp_path):
    rng = np.random.default_rng(1)
    # n_items deliberately NOT divisible by 8 workers: the padded shard
    # must never leak a phantom item into the top-k
    state = ENGINES["mfsgd"].synthetic_state(rng, n_users=64, n_items=50,
                                             rank=8)
    srv = _server("mfsgd", state, mesh, tmp_path, topk=5)
    users = [0, 13, 49, 63]
    (resp,) = srv.process([{"id": 1, "users": users}])
    W, H = state["W"], state["H"]
    for row, u in zip(resp["result"], users):
        scores = W[u] @ H.T
        ref = np.argsort(-scores)[:5]
        assert row["items"] == ref.tolist()
        np.testing.assert_allclose(row["scores"], scores[ref], rtol=1e-4)


def test_lda_infer_recovers_dominant_topic(mesh, tmp_path):
    # peaked synthetic phi: topic t owns vocab band t — a doc drawn from
    # one band must fold in to that topic
    V, K = 64, 4
    Nwk = np.full((V, K), 0.1, np.float32)
    band = V // K
    for t in range(K):
        Nwk[t * band:(t + 1) * band, t] = 100.0
    srv = _server("lda", {"Nwk": Nwk}, mesh, tmp_path)
    x = np.zeros((2, V), np.float32)
    x[0, 2 * band:3 * band] = 5.0   # topic 2 words
    x[1, 0:band] = 3.0              # topic 0 words
    (resp,) = srv.process([{"id": 0, "x": x.tolist()}])
    thetas = np.asarray([r["theta"] for r in resp["result"]])
    np.testing.assert_allclose(thetas.sum(1), 1.0, atol=1e-3)
    assert thetas[0].argmax() == 2 and thetas[1].argmax() == 0


def test_mlp_rf_svm_predict_roundtrip(mesh, tmp_path):
    rng = np.random.default_rng(2)
    for app in ("mlp", "rf", "svm"):
        state = ENGINES[app].synthetic_state(rng)
        srv = _server(app, state, mesh, tmp_path, ladder=(1, 8))
        req = srv.engine.synthetic_request(rng, 5)
        (resp,) = srv.process([{"id": app, **req}])
        assert resp["id"] == app and len(resp["result"]) == 5
    # svm label is the sign of the score
    assert all(r["label"] == (1 if r["score"] >= 0 else -1)
               for r in resp["result"])


def test_engine_rejects_bad_state_and_bad_rows(mesh, tmp_path):
    rng = np.random.default_rng(3)
    with pytest.raises(KeyError, match="centroids"):
        ENGINES["kmeans"]({"wrong": 1}, mesh)
    state = ENGINES["kmeans"].synthetic_state(rng, k=4, d=8)
    srv = _server("kmeans", state, mesh, tmp_path, ladder=(1, 8))
    resp = srv.process([
        {"id": 0, "x": [[0.0] * 8]},          # fine
        {"id": 1, "x": [[0.0] * 5]},          # wrong width
        {"id": 2},                            # missing key
    ])
    assert "result" in resp[0]
    assert "error" in resp[1] and "error" in resp[2]


def test_oversized_request_reassembles_across_batches(mesh, tmp_path):
    rng = np.random.default_rng(4)
    state = ENGINES["kmeans"].synthetic_state(rng, k=8, d=16)
    srv = _server("kmeans", state, mesh, tmp_path, ladder=(1, 8, 32))
    x = rng.normal(size=(70, 16)).astype(np.float32)
    (resp,) = srv.process([{"id": 0, "x": x.tolist()}])
    ref = np.argmin((((x[:, None, :] - state["centroids"][None]) ** 2)
                     ).sum(-1), axis=1)
    assert resp["result"] == ref.tolist()
    assert [r for r, _, _ in srv.last_batch_times] == [32, 32, 8]


# ---------------------------------------------------------------------------
# THE budget pin: steady state at compiles=0, dispatches=1, readbacks=1
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("app", ["kmeans", "mfsgd"])
def test_steady_state_budget_pin(app, mesh, tmp_path):
    rng = np.random.default_rng(5)
    state = ENGINES[app].synthetic_state(rng)
    with telemetry.scope(True):
        srv = _server(app, state, mesh, tmp_path, ladder=(1, 8, 64))
        # warm every rung once (first dispatch may e.g. transfer consts)
        srv.process([srv.engine.synthetic_request(rng, n)
                     for n in (1, 8, 64)])
        srv.steady.reset()
        base = flightrec.snapshot()
        reqs = [srv.engine.synthetic_request(rng, 3) for _ in range(12)]
        srv.process(reqs)  # 36 rows → batches of 8-rung/64-rung shapes
        spent = flightrec.delta_since(base)
        n_batches = srv.steady.batches
        assert n_batches >= 1
        # EXACT accounting, not just under-budget: one dispatch and one
        # stacked readback per batch, zero compiles in steady state
        assert spent["compiles"] == 0
        assert spent["dispatches"] == n_batches
        assert spent["readbacks"] == n_batches
        assert srv.steady.violations == 0


def test_budget_violation_is_loud_in_raise_mode(mesh, tmp_path):
    rng = np.random.default_rng(6)
    state = ENGINES["kmeans"].synthetic_state(rng, k=4, d=8)
    with telemetry.scope(True):
        srv = _server("kmeans", state, mesh, tmp_path, ladder=(1, 8))
        # sabotage: an extra tracked dispatch inside the batch scope must
        # trip the dispatches=1 budget (the per-epoch-dispatch trap)
        real_exec = srv._exec[1]

        def noisy(*args):
            flightrec.transfers.record_dispatch("extra")
            return real_exec(*args)

        srv._exec[1] = noisy
        with pytest.raises(flightrec.BudgetExceeded, match="dispatches"):
            srv.process([srv.engine.synthetic_request(rng, 1)])


# ---------------------------------------------------------------------------
# Continuous plane: scheduler policy, in-flight admission, exact budgets
# ---------------------------------------------------------------------------

def test_continuous_scheduler_policy_on_injected_clock():
    """The two knobs on a deterministic timeline: never hold work while
    idle, accumulate while in flight, flush at the deadline, fill-aware
    rung choice (full smaller rungs from a deep backlog, pad up only at
    >= half fill — the rule that turned the first sustained sweep's
    0.81x regression into the 1.78x win)."""
    from harp_tpu.serve.batcher import ContinuousScheduler

    s = ContinuousScheduler((1, 8, 64), max_queue_delay_s=0.010)
    assert not s.ready(0.0, idle=True)          # nothing queued
    s.put("a", 1, 0.0)
    assert s.ready(0.0, idle=True)              # idle never holds work
    assert not s.ready(0.0, idle=False)         # in flight: accumulate
    assert not s.ready(0.009, idle=False)       # deadline not reached
    assert s.ready(0.010, idle=False)           # max-queue-delay flush
    assert s.next_deadline() == pytest.approx(0.010)
    s.put("b", 63, 0.001)
    assert s.ready(0.001, idle=False)           # 64 rows = max rung
    b = s.next_batch(0.001)
    assert b.rung == 64 and b.rows == 64        # full max-rung batch
    assert len(s) == 0

    # fill-aware rung choice: 100-row backlog on a (1, 8, 64, 512)
    # ladder must NOT cover at 512 (80% padding) — it takes a full 64
    s2 = ContinuousScheduler((1, 8, 64, 512))
    s2.put("big", 100, 0.0)
    b1 = s2.next_batch(0.0)
    assert (b1.rung, b1.rows) == (64, 64)
    b2 = s2.next_batch(0.0)                     # 36 left: 64-rung >= half
    assert (b2.rung, b2.rows) == (64, 36)
    assert s2.padding_frac() == pytest.approx(28 / 128)
    # 5 queued rows: >= half of rung 8, pad up rather than 5x rung-1
    s2.put("c", 5, 0.0)
    b3 = s2.next_batch(0.0)
    assert (b3.rung, b3.rows) == (8, 5)
    # greedy policy covers everything at the minimal rung (PR 6 rule)
    g = ContinuousScheduler((1, 8, 64, 512), rung_policy="greedy")
    g.put("big", 100, 0.0)
    assert g.ready(0.0, idle=False)             # greedy never waits
    bg = g.next_batch(0.0)
    assert (bg.rung, bg.rows) == (512, 100)


def test_continuous_admission_while_in_flight_and_order(mesh, tmp_path):
    """Seeded arrival trace through the runner on a fake clock: requests
    from two interleaved connections are admitted WHILE batches are in
    flight, every response matches numpy, and each connection's
    responses come back in its admission order."""
    rng = np.random.default_rng(30)
    state = ENGINES["kmeans"].synthetic_state(rng, k=8, d=16)
    srv = _server("kmeans", state, mesh, tmp_path, ladder=(1, 8, 32))
    runner = srv.make_runner(max_queue_delay_s=0.005,
                             clock=lambda: 0.0)
    ref_x = {}
    arrivals = rng.exponential(0.001, size=20).cumsum()
    order = []
    out = []
    for i, t in enumerate(arrivals):
        conn = "A" if i % 3 else "B"
        key = (conn, i)
        x = rng.normal(size=(1 + i % 4, 16)).astype(np.float32)
        ref_x[key] = x
        order.append(key)
        assert runner.submit(key, {"id": i, "x": x.tolist()},
                             now=float(t)) == []
        out.extend(runner.step(float(t)))  # admission mid-pipeline
    out.extend(runner.drain(float(arrivals[-1])))
    assert runner.pending() == 0
    got = {k: r for k, r in out}
    assert len(got) == 20
    cent = state["centroids"]
    for key, x in ref_x.items():
        ref = np.argmin(((x[:, None, :] - cent[None]) ** 2).sum(-1), 1)
        assert got[key]["result"] == ref.tolist()
    for conn in ("A", "B"):
        keys = [k for k, _ in out if k[0] == conn]
        assert keys == [k for k in order if k[0] == conn]  # FIFO per conn


def test_continuous_oversized_request_spans_in_flight(mesh, tmp_path):
    """An oversized request spans several batches while OTHER requests
    are admitted mid-flight; reassembly is exact and ordered."""
    rng = np.random.default_rng(31)
    state = ENGINES["kmeans"].synthetic_state(rng, k=8, d=16)
    srv = _server("kmeans", state, mesh, tmp_path, ladder=(1, 8, 32))
    runner = srv.make_runner(clock=lambda: 0.0)
    big = rng.normal(size=(70, 16)).astype(np.float32)
    runner.submit("big", {"id": "big", "x": big.tolist()}, now=0.0)
    out = list(runner.step(0.0))        # dispatch rows 0..31
    small = rng.normal(size=(2, 16)).astype(np.float32)
    runner.submit("small", {"id": "small", "x": small.tolist()},
                  now=0.0)              # admitted while big is in flight
    out += runner.drain(0.0)
    keys = [k for k, _ in out]
    assert keys == ["big", "small"]     # big's tail still beats small
    got = {k: r for k, r in out}
    cent = state["centroids"]
    for key, x in (("big", big), ("small", small)):
        ref = np.argmin(((x[:, None, :] - cent[None]) ** 2).sum(-1), 1)
        assert got[key]["result"] == ref.tolist()
    assert runner.dispatched >= 3       # 32 + 32 + ragged tail


@pytest.mark.parametrize("app", ["kmeans", "mfsgd"])
def test_continuous_steady_state_budget_pin(app, mesh, tmp_path):
    """THE continuous budget pin: windows stay under (compiles=0,
    dispatches<=1, readbacks<=1) and the run totals are EXACT — one
    dispatch and one readback per dispatched batch, zero compiles."""
    rng = np.random.default_rng(32)
    state = ENGINES[app].synthetic_state(rng)
    with telemetry.scope(True):
        srv = _server(app, state, mesh, tmp_path, ladder=(1, 8, 64))
        srv.process([srv.engine.synthetic_request(rng, n)
                     for n in (1, 8, 64)])      # warm every rung
        srv.steady.reset()
        base = flightrec.snapshot()
        runner = srv.make_runner(clock=lambda: 0.0)
        for i in range(12):
            runner.submit(i, srv.engine.synthetic_request(rng, 3),
                          now=0.0)
            runner.step(0.0)
        runner.drain(0.0)
        spent = flightrec.delta_since(base)
        n_batches = runner.dispatched
        assert n_batches >= 2
        assert spent["compiles"] == 0
        assert spent["dispatches"] == n_batches
        assert spent["readbacks"] == n_batches
        assert srv.steady.violations == 0
        assert runner.verify_exact() == spent


def test_continuous_sabotaged_overlap_raises(mesh, tmp_path):
    """A window that dispatches twice (broken overlap bookkeeping) must
    trip the per-window budget loudly, and verify_exact must catch a
    readback that bypassed the tracked path."""
    rng = np.random.default_rng(33)
    state = ENGINES["kmeans"].synthetic_state(rng, k=4, d=8)
    with telemetry.scope(True):
        srv = _server("kmeans", state, mesh, tmp_path, ladder=(1, 8))
        runner = srv.make_runner(clock=lambda: 0.0)
        real_exec = srv._exec[1]

        def noisy(*args):
            flightrec.transfers.record_dispatch("extra")
            return real_exec(*args)

        srv._exec[1] = noisy
        runner.submit(0, srv.engine.synthetic_request(rng, 1), now=0.0)
        with pytest.raises(flightrec.BudgetExceeded, match="dispatches"):
            runner.step(0.0)
        srv._exec[1] = real_exec

        # under-spending is as wrong as over-spending: a batch whose
        # readback bypassed flightrec.readback leaves totals short
        srv.steady.reset()
        runner2 = srv.make_runner(clock=lambda: 0.0)
        runner2.submit(1, srv.engine.synthetic_request(rng, 1), now=0.0)
        runner2.step(0.0)                     # dispatch
        batch, out_dev = runner2._in_flight.popleft()
        np.asarray(out_dev)                   # untracked readback
        runner2._complete(batch, np.asarray(out_dev), 0.0)
        with pytest.raises(flightrec.BudgetExceeded, match="readbacks"):
            runner2.verify_exact()


def test_sustained_ab_row_is_coherent(mesh):
    """The in-process sustained A/B at smoke shape: same seeded trace
    through both planes, offered >= achieved > 0, exact steady totals,
    queue evidence present.  (The >= 1.3x acceptance ratio is graded on
    the committed full-shape row, not asserted at smoke shapes.)"""
    from harp_tpu.serve.bench import benchmark_sustained

    res = benchmark_sustained(app="kmeans", n_requests=96,
                              rows_per_request=1, burst_admit=8,
                              ladder=(1, 8, 32),
                              state_shape={"k": 8, "d": 16})
    assert res["mode"] == "sustained"
    assert res["offered_qps"] >= res["achieved_qps"] > 0
    assert res["burst_qps"] > 0
    assert res["qps_ratio_vs_burst"] == pytest.approx(
        res["achieved_qps"] / res["burst_qps"], rel=1e-3)
    assert res["steady_compiles"] == 0
    assert res["steady_dispatches"] == res["batches"]
    assert res["steady_readbacks"] == res["batches"]
    assert res["budget_violations"] == 0
    assert res["p50_ms"] <= res["p95_ms"] <= res["p99_ms"]
    for k in ("qdepth_p50", "qdepth_p95", "qdepth_p99"):
        assert res[k] >= 0


# ---------------------------------------------------------------------------
# Fault plane: shedding, deadlines, retry-with-restage, isolation (PR 10)
# ---------------------------------------------------------------------------

def _kmeans_server(mesh, tmp_path, seed=40, k=4, d=8, ladder=(1, 8),
                   budget_action="raise"):
    rng = np.random.default_rng(seed)
    state = ENGINES["kmeans"].synthetic_state(rng, k=k, d=d)
    srv = Server("kmeans", state=state, mesh=mesh, ladder=ladder,
                 cache_dir=str(tmp_path / "aot"),
                 budget_action=budget_action)
    srv.startup()
    return srv, state, rng


def _assign_ref(state, x):
    return np.argmin(((x[:, None, :] - state["centroids"][None]) ** 2
                      ).sum(-1), 1).tolist()


def test_runner_sheds_on_admission_queue_full(mesh, tmp_path):
    """Bounded admission: a request that would overflow the queue gets a
    STRUCTURED shed response at submit — and admission reopens once the
    queue drains."""
    srv, state, rng = _kmeans_server(mesh, tmp_path)
    runner = srv.make_runner(max_queue_rows=4, rung_policy="greedy")
    xa = rng.normal(size=(3, 8)).astype(np.float32)
    assert runner.submit("a", {"id": "a", "x": xa.tolist()}, now=0.0) == []
    ((key, resp),) = runner.submit(
        "b", {"id": "b", "x": rng.normal(size=(3, 8)).tolist()}, now=0.0)
    assert key == "b" and resp["shed"] is True
    assert resp["reason"] == "queue_full"
    assert "shed" in resp["error"] and resp["id"] == "b"
    assert runner.shed == 1
    got = dict(runner.drain(now=0.0))
    assert got["a"]["result"] == _assign_ref(state, xa)
    # queue drained: the next request is admitted, not shed
    assert runner.submit(
        "c", {"id": "c", "x": xa.tolist()}, now=1.0) == []
    assert dict(runner.drain(now=1.0))["c"]["result"] == \
        _assign_ref(state, xa)


def test_runner_deadline_sheds_queued_and_counts_late(mesh, tmp_path):
    """Per-request deadlines: a request still queued past its deadline
    is shed with a structured error (never dispatched, never unbounded
    latency); one that completes late is served but counted."""
    srv, state, rng = _kmeans_server(mesh, tmp_path)
    runner = srv.make_runner(deadline_s=0.05, rung_policy="greedy")
    xa = rng.normal(size=(2, 8)).astype(np.float32)
    runner.submit("a", {"id": "a", "x": xa.tolist()}, now=0.0)
    ((key, resp),) = runner.step(now=0.2)  # expired before any dispatch
    assert key == "a" and resp["shed"] is True
    assert resp["reason"] == "deadline"
    assert runner.shed == 1 and runner.pending() == 0

    # late COMPLETION: dispatched in time, read back after the deadline
    runner.submit("b", {"id": "b", "x": xa.tolist()}, now=1.0)
    assert runner.step(now=1.0) == []  # dispatch window
    got = dict(runner.step(now=2.0))   # readback, 1 s late
    assert got["b"]["result"] == _assign_ref(state, xa)
    assert runner.deadline_misses == 1
    assert runner.shed == 1  # the late serve was NOT shed


def test_runner_retries_transient_fault_with_fresh_stage(mesh, tmp_path):
    """Retry-with-restage: an injected transient dispatch fault retries
    the batch through a FRESHLY staged buffer (the donated one is never
    re-dispatched — the serve.retry_restage protocol drive proves that
    under the HL303 audit at lint time); every response still comes back
    correct and the steady-state totals stay EXACT (failed attempts are
    never counted as dispatches)."""
    from harp_tpu.utils.fault import FaultInjector

    with telemetry.scope(True):
        srv, state, rng = _kmeans_server(mesh, tmp_path)
        runner = srv.make_runner(max_retries=2, rung_policy="greedy")
        inj = FaultInjector(seed=0, fail={"dispatch": (2,)})
        xs = {f"r{i}": rng.normal(size=(1, 8)).astype(np.float32)
              for i in range(4)}
        got = {}
        with inj.arm():
            for key, x in xs.items():
                runner.submit(key, {"id": key, "x": x.tolist()})
                got.update(runner.step())
            got.update(runner.drain())
        assert inj.injected["dispatch"] == 1
        assert runner.fault_retries == 1
        assert runner.engine_failures == 0
        for key, x in xs.items():
            assert got[key]["result"] == _assign_ref(state, x)
        spent = runner.verify_exact()  # exact despite the fault
        assert spent["dispatches"] == runner.dispatched
        assert srv.steady.violations == 0


def test_runner_hard_failure_isolates_batch(mesh, tmp_path):
    """Retries exhausted: the batch's requests get structured errors and
    the runner KEEPS SERVING — one crashing batch is not a dead server."""
    from harp_tpu.utils.fault import FaultInjector

    srv, state, rng = _kmeans_server(mesh, tmp_path)
    runner = srv.make_runner(max_retries=1, rung_policy="greedy")
    inj = FaultInjector(fail={"dispatch": (2, 3)})  # batch 2, both tries
    xa = rng.normal(size=(1, 8)).astype(np.float32)
    xc = rng.normal(size=(1, 8)).astype(np.float32)
    got = {}
    with inj.arm():
        runner.submit("a", {"id": "a", "x": xa.tolist()})
        got.update(runner.step())
        runner.submit("b", {"id": "b", "x": xa.tolist()})
        got.update(runner.step())  # fails, retries, hard-fails
        runner.submit("c", {"id": "c", "x": xc.tolist()})
        got.update(runner.step())
        got.update(runner.drain())
    assert "engine failure after 1 retries" in got["b"]["error"]
    assert "shed" not in got["b"]  # a hard failure is not a shed
    assert runner.engine_failures == 1 and runner.failed == 1
    assert runner.fault_retries == 1
    assert got["a"]["result"] == _assign_ref(state, xa)
    assert got["c"]["result"] == _assign_ref(state, xc)
    assert runner.pending() == 0  # nothing leaked


def test_runner_hard_failure_discards_spanning_tail(mesh, tmp_path):
    """An oversized request whose middle batch hard-fails must not leave
    tail segments queued (they would dispatch into an already-errored
    request); later requests still serve."""
    from harp_tpu.utils.fault import FaultInjector

    srv, state, rng = _kmeans_server(mesh, tmp_path, ladder=(1, 4))
    runner = srv.make_runner(max_retries=0, rung_policy="greedy")
    big = rng.normal(size=(10, 8)).astype(np.float32)  # spans 3 batches
    xc = rng.normal(size=(1, 8)).astype(np.float32)
    got = {}
    with FaultInjector(fail={"dispatch": (2,)}).arm():
        runner.submit("big", {"id": "big", "x": big.tolist()})
        got.update(runner.step())  # batch 1 of the span: ok
        got.update(runner.step())  # batch 2: hard fail (max_retries=0)
        runner.submit("c", {"id": "c", "x": xc.tolist()})
        got.update(runner.drain())
    assert "engine failure" in got["big"]["error"]
    assert got["c"]["result"] == _assign_ref(state, xc)
    assert runner.pending() == 0 and len(runner.sched) == 0


def test_sustained_degraded_row_under_faults(mesh):
    """The acceptance bench: sustained CPU-sim load with seeded ~1%
    transient dispatch faults + a deadline + a bounded queue.  The
    server stays up, every request comes back as served / structured
    shed / hard-fail (the invariant-9 ledger), clean batches still
    compile nothing, and the row passes the extended checker."""
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "scripts"))
    import check_jsonl

    from harp_tpu.serve.bench import benchmark_sustained

    res = benchmark_sustained(
        app="kmeans", n_requests=96, rows_per_request=1, burst_admit=8,
        ladder=(1, 8, 32), state_shape={"k": 8, "d": 16},
        fault_rate=0.01, fault_seed=34,  # seed 34: first draw (0.004)
        deadline_ms=10_000.0, max_queue_rows=4096, max_retries=3)  # fires
    assert res["offered_requests"] == 96
    assert (res["served_requests"] + res["shed_requests"]
            + res["failed_requests"]) == 96
    assert res["faults_injected"] >= 1  # chaos actually ran
    assert res["fault_retries"] >= 1    # and the retry path absorbed it
    assert 0.0 <= res["shed_frac"] <= 1.0
    assert 0.0 <= res["deadline_miss_frac"] <= 1.0
    assert res["steady_compiles"] == 0  # clean batches never recompile
    # PR 14: a retry-with-restage stages twice in its batch window, and
    # the sustained bench's "one staging per window" warn budget counts
    # exactly those windows — the drift IS the committed restage
    # evidence (it also lands in the budget-drift health row), so under
    # injected faults violations > 0 is the CORRECT reading
    assert 1 <= res["budget_violations"] <= res["fault_retries"]
    assert res["health_budget_drift"] == res["budget_violations"]
    assert res["health_findings"] >= 1
    # the committed-row contract: a stamped copy passes invariants 7 + 9
    row = {**res, "backend": "cpu", "date": "2026-08-04", "commit": "x"}
    assert check_jsonl._check_serve_row("t", 1, row) == []
    # and a forged unbalanced ledger fails invariant 9
    bad = dict(row, served_requests=row["served_requests"] - 1)
    assert any("must come back as exactly one" in e
               for e in check_jsonl._check_serve_row("t", 1, bad))


# ---------------------------------------------------------------------------
# TCP transport: real socket, concurrent connections, ordered responses
# ---------------------------------------------------------------------------

def _tcp_client(port, lines, n_responses):
    import socket

    s = socket.create_connection(("127.0.0.1", port), timeout=60)
    f = s.makefile("rw")
    for line in lines:
        f.write(line + "\n")
    f.flush()
    got = [json.loads(f.readline()) for _ in range(n_responses)]
    f.write(json.dumps({"cmd": "quit"}) + "\n")
    f.flush()
    s.close()
    return got


def test_tcp_front_end_routes_and_orders_per_connection(mesh, tmp_path):
    """Two concurrent clients over a real socket: each gets exactly its
    own responses, in its own send order, with correct numerics."""
    import threading

    from harp_tpu.serve.transport import TCPFrontEnd

    rng = np.random.default_rng(34)
    state = ENGINES["kmeans"].synthetic_state(rng, k=8, d=16)
    srv = Server("kmeans", state=state, mesh=mesh, ladder=(1, 8, 32),
                 cache_dir=str(tmp_path / "aot"), budget_action="warn")
    srv.startup()
    fe = TCPFrontEnd(srv, port=0,
                     max_queue_delay_s=0.002).start_in_thread()
    try:
        xs = {nm: [rng.normal(size=(1 + i % 3, 16)).astype(np.float32)
                   for i in range(12)] for nm in ("A", "B")}
        results = {}

        def run(nm):
            lines = [json.dumps({"id": f"{nm}-{i}", "x": x.tolist()})
                     for i, x in enumerate(xs[nm])]
            results[nm] = _tcp_client(fe.port, lines, len(lines))

        ts = [threading.Thread(target=run, args=(nm,)) for nm in xs]
        for t in ts:
            t.start()
        for t in ts:
            t.join(120)
        cent = state["centroids"]
        for nm, batches in xs.items():
            assert [r["id"] for r in results[nm]] == \
                [f"{nm}-{i}" for i in range(12)]
            for r, x in zip(results[nm], batches):
                ref = np.argmin(((x[:, None, :] - cent[None]) ** 2
                                 ).sum(-1), 1)
                assert r["result"] == ref.tolist()
    finally:
        fe.shutdown()
        fe.join(60)


def test_tcp_front_end_stats_errors_and_shutdown(mesh, tmp_path):
    """Control plane over TCP: stats carries the continuous counters,
    bad JSON answers an error without killing the connection, and
    shutdown drains in-flight work before the socket closes."""
    import socket

    from harp_tpu.serve.transport import TCPFrontEnd

    rng = np.random.default_rng(35)
    state = ENGINES["kmeans"].synthetic_state(rng, k=4, d=8)
    srv = Server("kmeans", state=state, mesh=mesh, ladder=(1, 8),
                 cache_dir=str(tmp_path / "aot"), budget_action="warn")
    srv.startup()
    fe = TCPFrontEnd(srv, port=0).start_in_thread()
    s = socket.create_connection(("127.0.0.1", fe.port), timeout=60)
    f = s.makefile("rw")
    f.write("this is not json\n")
    f.write(json.dumps({"cmd": "stats"}) + "\n")
    f.flush()
    first = json.loads(f.readline())
    second = json.loads(f.readline())
    assert first["error"] == "unparseable JSON"
    assert second["kind"] == "serve_stats"
    assert second["continuous"]["mode"] == "continuous"
    x = rng.normal(size=(3, 8)).astype(np.float32)
    f.write(json.dumps({"id": "last", "x": x.tolist()}) + "\n")
    f.write(json.dumps({"cmd": "shutdown"}) + "\n")
    f.flush()
    resp = json.loads(f.readline())  # drained before close
    ref = np.argmin(((x[:, None, :] - state["centroids"][None]) ** 2
                     ).sum(-1), 1)
    assert resp["id"] == "last" and resp["result"] == ref.tolist()
    fe.join(60)
    s.close()


def test_tcp_client_disconnect_mid_flight_cleanup(mesh, tmp_path):
    """A client that slams its socket shut with responses outstanding
    costs exactly its own work: the dispatcher finishes the in-flight
    batches, the orphaned responses are dropped, the admitted work
    drains fully (nothing leaks in the assembler), and a concurrent
    connection is untouched."""
    import socket
    import threading
    import time as _time

    from harp_tpu.serve.transport import TCPFrontEnd

    rng = np.random.default_rng(36)
    state = ENGINES["kmeans"].synthetic_state(rng, k=4, d=8)
    srv = Server("kmeans", state=state, mesh=mesh, ladder=(1, 8),
                 cache_dir=str(tmp_path / "aot"), budget_action="warn")
    srv.startup()
    fe = TCPFrontEnd(srv, port=0,
                     max_queue_delay_s=0.002).start_in_thread()
    try:
        # rude client: 6 requests, then the socket slams shut unread
        rude = socket.create_connection(("127.0.0.1", fe.port),
                                        timeout=60)
        payload = b"".join(
            json.dumps({"id": f"rude-{i}",
                        "x": rng.normal(size=(2, 8)).tolist()}
                       ).encode() + b"\n" for i in range(6))
        rude.sendall(payload)
        rude.close()  # mid-flight: nothing was read back

        # polite client on its own connection: full round trip
        xs = [rng.normal(size=(1 + i % 3, 8)).astype(np.float32)
              for i in range(8)]
        lines = [json.dumps({"id": f"ok-{i}", "x": x.tolist()})
                 for i, x in enumerate(xs)]
        got = _tcp_client(fe.port, lines, len(lines))
        assert [r["id"] for r in got] == [f"ok-{i}" for i in range(8)]
        cent = state["centroids"]
        for r, x in zip(got, xs):
            ref = np.argmin(((x[:, None, :] - cent[None]) ** 2).sum(-1),
                            1)
            assert r["result"] == ref.tolist()

        # every admitted request (rude ones included) fully drained —
        # the orphans were SERVED then dropped at delivery, not leaked
        deadline = _time.monotonic() + 30
        while _time.monotonic() < deadline and (
                fe.runner.completed < 14 or fe.runner.pending()):
            _time.sleep(0.01)
        assert fe.runner.completed == 14
        assert fe.runner.pending() == 0

        # and the server still answers its control plane
        s = socket.create_connection(("127.0.0.1", fe.port), timeout=60)
        f = s.makefile("rw")
        f.write(json.dumps({"cmd": "stats"}) + "\n")
        f.flush()
        stats = json.loads(f.readline())
        assert stats["kind"] == "serve_stats"
        assert stats["continuous"]["completed"] == 14
        f.write(json.dumps({"cmd": "quit"}) + "\n")
        f.flush()
        s.close()
    finally:
        fe.shutdown()
        fe.join(60)
    assert threading.active_count() < 50  # no runaway leaked threads


# ---------------------------------------------------------------------------
# AOT executable cache: warm restart compiles NOTHING
# ---------------------------------------------------------------------------

def test_warm_restart_performs_zero_compiles(mesh, tmp_path):
    import jax

    rng = np.random.default_rng(7)
    state = ENGINES["kmeans"].synthetic_state(rng, k=8, d=16)
    cache_dir = str(tmp_path / "aot")
    ladder = (1, 8)
    req = {"id": 0, "x": rng.normal(size=(3, 16)).astype(
        np.float32).tolist()}
    with telemetry.scope(True):
        srv = Server("kmeans", state=state, mesh=mesh, ladder=ladder,
                     cache_dir=cache_dir)
        cold = srv.startup()
        assert cold["cache_misses"] == len(ladder)
        assert cold["compiles"] >= len(ladder)
        (ref,) = srv.process([req])

    # fresh process stand-in: drop jax's in-memory caches so any compile
    # on the second startup would be OBSERVED by CompileWatch, then
    # prove there isn't one
    jax.clear_caches()
    with telemetry.scope(True):
        srv2 = Server("kmeans", state=state, mesh=mesh, ladder=ladder,
                      cache_dir=cache_dir)
        warm = srv2.startup()
        assert warm["cache_hits"] == len(ladder)
        assert warm["cache_misses"] == 0
        assert warm["compiles"] == 0  # THE acceptance criterion
        (resp,) = srv2.process([req])
        assert resp["result"] == ref["result"]
        # and the first responses stayed compile-free too
        assert flightrec.compile_watch.count == 0


def test_corrupt_cache_entry_falls_back_to_compile(mesh, tmp_path):
    import os

    rng = np.random.default_rng(8)
    state = ENGINES["kmeans"].synthetic_state(rng, k=4, d=8)
    cache_dir = str(tmp_path / "aot")
    srv = Server("kmeans", state=state, mesh=mesh, ladder=(1,),
                 cache_dir=cache_dir)
    srv.startup()
    (entry,) = [f for f in os.listdir(cache_dir) if f.endswith(".pkl")]
    with open(os.path.join(cache_dir, entry), "wb") as fh:
        fh.write(b"not a pickle")
    srv2 = Server("kmeans", state=state, mesh=mesh, ladder=(1,),
                  cache_dir=cache_dir)
    with pytest.warns(RuntimeWarning, match="unreadable"):
        info = srv2.startup()
    assert info["cache_misses"] == 1  # recompiled, didn't crash
    (resp,) = srv2.process([{"id": 0, "x": [[0.0] * 8]}])
    assert "result" in resp


def test_cache_key_changes_with_fingerprint(mesh, tmp_path):
    from harp_tpu.serve.cache import ExecutableCache

    rng = np.random.default_rng(9)
    eng = ENGINES["kmeans"](
        ENGINES["kmeans"].synthetic_state(rng, k=4, d=8), mesh)
    a = ExecutableCache(str(tmp_path / "c"), fingerprint="aaaa")
    b = ExecutableCache(str(tmp_path / "c"), fingerprint="bbbb")
    args = eng.trace_args(1)
    assert a._key("kmeans", args) != b._key("kmeans", args)
    # and with the rung: shapes participate
    assert a._key("kmeans", args) != a._key("kmeans", eng.trace_args(8))


def test_cache_misses_when_engine_options_change(mesh, tmp_path):
    """Options baked into the program as constants (mfsgd topk, lda
    em_iters/alpha) shape NO input aval — a restart with different flags
    must miss, never serve the other option's executable."""
    rng = np.random.default_rng(21)
    state = ENGINES["mfsgd"].synthetic_state(rng, n_users=64, n_items=48,
                                             rank=8)
    cache_dir = str(tmp_path / "aot")
    req = {"id": 0, "users": [1, 2, 3]}
    srv5 = Server("mfsgd", state=state, mesh=mesh, ladder=(4,),
                  cache_dir=cache_dir, engine_opts={"topk": 5})
    srv5.startup()
    (r5,) = srv5.process([req])
    assert all(len(row["items"]) == 5 for row in r5["result"])

    srv7 = Server("mfsgd", state=state, mesh=mesh, ladder=(4,),
                  cache_dir=cache_dir, engine_opts={"topk": 7})
    info = srv7.startup()
    assert info["cache_hits"] == 0 and info["cache_misses"] == 1
    (r7,) = srv7.process([req])
    assert all(len(row["items"]) == 7 for row in r7["result"])

    # same options again: hit (the tag keys, it doesn't disable caching)
    srv5b = Server("mfsgd", state=state, mesh=mesh, ladder=(4,),
                   cache_dir=cache_dir, engine_opts={"topk": 5})
    assert srv5b.startup()["cache_hits"] == 1

    # lda's constants tag too (em_iters is the fori_loop trip count)
    lda_state = ENGINES["lda"].synthetic_state(rng, vocab_size=32,
                                               n_topics=4)
    tags = {ENGINES["lda"](lda_state, mesh, em_iters=k).cache_tag()
            for k in (4, 8)}
    assert len(tags) == 2


def test_cache_load_survives_arbitrary_deserialize_errors(
        mesh, tmp_path, monkeypatch):
    """'The cache can lose, never lie' covers exception types the key
    didn't anticipate (e.g. jaxlib XlaRuntimeError) — any bad entry must
    degrade to a fresh compile, not crash startup."""
    from jax.experimental import serialize_executable

    rng = np.random.default_rng(22)
    state = ENGINES["kmeans"].synthetic_state(rng, k=4, d=8)
    cache_dir = str(tmp_path / "aot")
    Server("kmeans", state=state, mesh=mesh, ladder=(1,),
           cache_dir=cache_dir).startup()

    def boom(*a, **k):
        raise RuntimeError("xla runtime rejected the payload")

    monkeypatch.setattr(serialize_executable, "deserialize_and_load",
                        boom)
    srv2 = Server("kmeans", state=state, mesh=mesh, ladder=(1,),
                  cache_dir=cache_dir)
    with pytest.warns(RuntimeWarning, match="unreadable"):
        info = srv2.startup()
    assert info["cache_misses"] == 1
    monkeypatch.undo()
    (resp,) = srv2.process([{"id": 0, "x": [[0.0] * 8]}])
    assert "result" in resp


# ---------------------------------------------------------------------------
# stdio protocol + CLI end-to-end
# ---------------------------------------------------------------------------

def test_stdio_roundtrip_with_stats_and_quit(mesh, tmp_path):
    rng = np.random.default_rng(10)
    state = ENGINES["kmeans"].synthetic_state(rng, k=8, d=16)
    srv = _server("kmeans", state, mesh, tmp_path, ladder=(1, 8))
    x = rng.normal(size=(2, 16)).astype(np.float32)
    stdin = io.StringIO("\n".join([
        json.dumps({"id": "a", "x": x.tolist()}),
        "this is not json",
        json.dumps({"cmd": "stats"}),
        json.dumps({"id": "b", "x": x[:1].tolist()}),
        json.dumps({"cmd": "quit"}),
    ]) + "\n")
    out = io.StringIO()
    served = srv.serve_stdio(stdin, out)
    lines = [json.loads(ln) for ln in out.getvalue().splitlines()]
    assert served == 2
    assert lines[0]["id"] == "a" and len(lines[0]["result"]) == 2
    assert lines[1]["error"] == "unparseable JSON"
    assert lines[2]["kind"] == "serve_stats"
    assert lines[3]["id"] == "b" and len(lines[3]["result"]) == 1


def test_burst_reader_sees_past_text_layer_buffering():
    """Queued lines a TextIOWrapper would have buffered internally (where
    select on the fd can't see them) must land in the CURRENT burst, and
    a partial trailing line must carry over to the next one."""
    import os

    from harp_tpu.serve.server import _BurstReader

    r, w = os.pipe()
    stdin = os.fdopen(r, "r")  # the buffered text wrapper main() gets
    try:
        os.write(w, b'{"id": 1}\n{"id": 2}\n{"id": 3}\n{"id": 4')
        reader = _BurstReader(stdin)
        burst = reader.read_burst()
        assert [json.loads(ln)["id"] for ln in burst] == [1, 2, 3]
        os.write(w, b'}\n')  # the partial line completes
        assert [json.loads(ln)["id"]
                for ln in reader.read_burst()] == [4]
        os.close(w)
        assert reader.read_burst() == []  # EOF
    finally:
        stdin.close()


def test_cli_serves_from_checkpoint_end_to_end(mesh, tmp_path,
                                               monkeypatch, capsys):
    """THE acceptance walkthrough: train-ish state → CheckpointManager →
    ``python -m harp_tpu serve kmeans --ckpt ...`` → JSONL in, JSONL out
    (restore_latest picks the newest step)."""
    import sys

    import harp_tpu.__main__ as cli
    from harp_tpu.utils.checkpoint import CheckpointManager

    rng = np.random.default_rng(11)
    stale = {"centroids": rng.normal(size=(4, 8)).astype(np.float32)}
    fresh = {"centroids": rng.normal(size=(4, 8)).astype(np.float32)}
    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    mgr.save(1, stale)
    mgr.save(5, fresh)  # the newest step must win

    x = rng.normal(size=(3, 8)).astype(np.float32)
    monkeypatch.setattr(sys, "stdin", io.StringIO(
        json.dumps({"id": 0, "x": x.tolist()}) + "\n"
        + json.dumps({"cmd": "quit"}) + "\n"))
    rc = cli.main(["serve", "kmeans", "--ckpt", str(tmp_path / "ckpt"),
                   "--ladder", "1,8"])
    assert rc == 0
    out = capsys.readouterr().out
    (resp,) = [json.loads(ln) for ln in out.splitlines()]
    ref = np.argmin(((x[:, None, :] - fresh["centroids"][None]) ** 2
                     ).sum(-1), axis=1)
    assert resp["result"] == ref.tolist()


def test_cli_bench_emits_valid_serve_row(mesh, capsys):
    import os
    import sys

    import harp_tpu.__main__ as cli

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "scripts"))
    import check_jsonl

    rc = cli.main(["serve", "kmeans", "--bench", "--requests", "24",
                   "--rows-per-request", "2", "--ladder", "1,8"])
    assert rc == 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    row = json.loads(line)
    assert row["config"] == "serve_kmeans" and row["kind"] == "serve"
    assert row["qps"] > 0 and row["steady_compiles"] == 0
    assert row["p50_ms"] <= row["p95_ms"] <= row["p99_ms"]


def test_serve_bench_mfsgd_row(mesh, tmp_path):
    from harp_tpu.serve.bench import benchmark

    # a fresh cache_dir: the default is a fixed directory (a second run
    # starts warm), and this row asserts the cold start
    res = benchmark(app="mfsgd", n_requests=24, rows_per_request=2,
                    burst=8, ladder=(1, 8),
                    state_shape={"n_users": 64, "n_items": 48,
                                 "rank": 8}, topk=4,
                    cache_dir=str(tmp_path))
    assert res["kind"] == "serve" and res["app"] == "mfsgd"
    assert res["steady_compiles"] == 0 and res["budget_violations"] == 0
    assert res["p50_ms"] <= res["p95_ms"] <= res["p99_ms"]
    assert res["cache_misses"] == 2 and res["cache_hits"] == 0


def test_server_requires_state_or_ckpt(mesh):
    with pytest.raises(ValueError, match="state= or ckpt="):
        Server("kmeans")
