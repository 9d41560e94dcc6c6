"""Flight recorder — compile/transfer telemetry + budget guards.

Three layers of evidence, all on the CPU backend with zero hardware:

1. the collectors see what actually happened (CompileWatch counts XLA
   backend compiles with span attribution; TransferLedger counts
   shard_array H2D bytes, device_sync/readback round trips, tracked
   dispatches);
2. the budget guard catches the documented CLAUDE.md driver-loop traps — a
   per-step ``PRNGKey(int)`` re-seed trips ``compiles=1``, a per-epoch
   readback loop trips ``readbacks=1``;
3. the shipped kmeans/lda/mfsgd epoch loops PASS their pinned budgets
   (one compile per config, zero recompiles across reruns, one readback
   per run) — the dispatch-discipline contract every future perf PR
   must keep.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harp_tpu.utils import flightrec, prng, telemetry

# ---------------------------------------------------------------------------
# collectors
# ---------------------------------------------------------------------------

def test_compile_watch_counts_and_attributes_spans(mesh):
    with telemetry.scope():
        with telemetry.span("phase"):
            jax.jit(lambda x: x * 3.0 + 1.0)(jnp.ones(7))
        n = flightrec.compile_watch.count
        assert n >= 1
        summ = flightrec.compile_watch.summary()
        assert summ["count"] == n
        assert summ["total_s"] > 0
        assert "phase" in summ["by_span"]
        # a cached re-invocation compiles nothing
        jax.jit(lambda x: x * 3.0 + 1.0)  # new wrapper but not called
        assert flightrec.compile_watch.count == n


def test_shard_array_records_h2d_bytes(mesh):
    x = np.ones((64, 16), np.float32)
    with telemetry.scope():
        mesh.shard_array(x, 0)
        assert flightrec.transfers.h2d_bytes == x.nbytes
        assert flightrec.transfers.h2d_calls == 1
        sites = flightrec.transfers.summary()["sites"]
        assert sites[0]["op"] == "h2d"
        # the site is THIS test file, not the mesh wrapper
        assert "test_flightrec.py" in sites[0]["site"]


def test_device_sync_and_readback_count_round_trips(mesh):
    from harp_tpu.utils.timing import device_sync

    y = jnp.arange(8.0)
    with telemetry.scope():
        device_sync(y)
        out = flightrec.readback(y)
        assert flightrec.transfers.readbacks == 2
        # device_sync reads one scalar; readback() reads the whole array
        assert flightrec.transfers.d2h_bytes == 4 + y.size * 4
        assert np.array_equal(out, np.arange(8.0))


def test_track_counts_dispatches(mesh):
    f = flightrec.track(jax.jit(lambda x: x + 1), "unit.f")
    x = jnp.ones(4)
    with telemetry.scope():
        f(x)
        f(x)
        assert flightrec.transfers.dispatches == 2
        sites = flightrec.transfers.summary()["sites"]
        assert {"unit.f"} == {s["site"] for s in sites
                              if s["op"] == "dispatch"}


def test_bucket_by_destination_records_staged_bytes(mesh):
    from harp_tpu.parallel.dispatch import bucket_by_destination

    dest = jnp.array([0, 1, 0, 1], jnp.int32)
    pay = jnp.ones((4, 3), jnp.float32)
    with telemetry.scope():
        bucket_by_destination(dest, (pay,), capacity=2, n_dest=2)
        # 2 dests x 2 slots x 3 f32 = 48 B staged exchange buffer
        assert flightrec.transfers.bucket_bytes == 48


# ---------------------------------------------------------------------------
# budget guard
# ---------------------------------------------------------------------------

def test_budget_passes_within_limits(mesh):
    with telemetry.scope():
        with flightrec.budget(readbacks=2, dispatches=1) as b:
            flightrec.record_readback(4)
        assert b.spent()["readbacks"] == 1


def test_budget_raises_and_names_every_violated_counter(mesh):
    with telemetry.scope():
        with pytest.raises(flightrec.BudgetExceeded) as ei:
            with flightrec.budget(readbacks=1, h2d_bytes=10, tag="unit"):
                flightrec.record_readback(4)
                flightrec.record_readback(4)
                flightrec.record_h2d(100)
        msg = str(ei.value)
        assert "readbacks used 2 > budget 1" in msg
        assert "h2d_bytes used 100 > budget 10" in msg
        assert "[unit]" in msg


def test_budget_warn_mode_warns_instead_of_raising(mesh):
    with telemetry.scope():
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            with flightrec.budget(readbacks=0, action="warn"):
                flightrec.record_readback(4)
        assert any("readbacks used 1 > budget 0" in str(x.message)
                   for x in w)


def test_budget_is_noop_when_telemetry_disabled(mesh):
    with telemetry.scope(False):
        with flightrec.budget(readbacks=0) as b:
            from harp_tpu.utils.timing import device_sync

            device_sync(jnp.ones(2))  # would trip if armed
        assert b is None


def test_budget_propagates_body_exception_unchecked(mesh):
    with telemetry.scope():
        with pytest.raises(ValueError, match="inner"):
            with flightrec.budget(readbacks=0):
                flightrec.record_readback(4)  # would also violate
                raise ValueError("inner")


def test_mapper_budget_warns_on_violation(mesh):
    """CollectiveApp(budget=...) enforces warn-mode over map_collective."""
    from harp_tpu.mapper import CollectiveApp
    from harp_tpu.utils.timing import device_sync

    class App(CollectiveApp):
        def map_collective(self):
            y = jnp.ones(2)
            device_sync(y)
            device_sync(y)  # second round trip busts readbacks=1
            return 0

    with telemetry.scope():
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            App(mesh=mesh, budget={"readbacks": 1}).run()
        assert any("readbacks used 2 > budget 1" in str(x.message)
                   for x in w)


# ---------------------------------------------------------------------------
# the documented driver-loop traps, machine-checked (acceptance criteria)
# ---------------------------------------------------------------------------

def test_reseeding_prngkey_per_step_trips_compile_budget(mesh):
    """CLAUDE.md trap: a step function that bakes a fresh
    ``PRNGKey(python_int)`` into its traced program compiles once PER
    SEED — the compiles budget turns that from a wall-clock anomaly into
    a test failure.  The raw-key-bits fix (utils.prng) passes the same
    budget with zero compiles once warm."""
    x = jnp.ones(16)

    def trapped_step(seed):
        # fresh jit wrapper per step, seed baked in as a constant — the
        # shape the trap takes in real driver code
        f = jax.jit(lambda v, s=seed: v * jax.random.normal(
            jax.random.PRNGKey(s), v.shape).sum())
        return f(x)

    with telemetry.scope():
        trapped_step(0)  # warm the shared sub-ops
        with pytest.raises(flightrec.BudgetExceeded, match="compiles"):
            with flightrec.budget(compiles=1):
                for seed in (1, 2, 3):
                    trapped_step(seed)

        # the fix: ONE program, key bits as an argument
        g = jax.jit(lambda v, k: v * jax.random.normal(k, v.shape).sum())
        g(x, jnp.asarray(prng.key_bits(0)))  # warm: the only compile
        with flightrec.budget(compiles=0):
            for seed in (1, 2, 3):
                g(x, jnp.asarray(prng.key_bits(seed)))


def test_per_epoch_readback_trips_readback_budget(mesh):
    """CLAUDE.md trap: reading a metric back every epoch pays the
    20-150 ms dispatch/readback round trip per epoch; one stacked
    readback per run is the contract the budget pins."""
    from harp_tpu.utils.timing import device_sync

    f = jax.jit(lambda x: x * 1.01)
    x = jnp.ones(8)
    x = f(x)  # warm

    with telemetry.scope():
        with pytest.raises(flightrec.BudgetExceeded, match="readbacks"):
            with flightrec.budget(readbacks=1):
                y = x
                for _ in range(4):
                    y = f(y)
                    device_sync(y)  # the per-epoch readback loop
        # the fix: sync once per run
        with flightrec.budget(readbacks=1):
            y = x
            for _ in range(4):
                y = f(y)
            device_sync(y)


# ---------------------------------------------------------------------------
# pinned budgets for the shipped epoch loops (acceptance criteria)
# ---------------------------------------------------------------------------

def test_mfsgd_epoch_loop_passes_pinned_budget(mesh):
    """One AOT compile per epoch count, then one dispatch + ONE stacked
    readback per train_epochs run, and ZERO recompiles on rerun."""
    import harp_tpu.models.mfsgd as MF

    cfg = MF.MFSGDConfig(rank=4, algo="dense", u_tile=8, i_tile=8,
                         entry_cap=32)
    with telemetry.scope():
        m = MF.MFSGD(64, 48, cfg, mesh, seed=3)
        u, i, v = MF.synthetic_ratings(64, 48, 600, rank=4, seed=3)
        m.set_ratings(u, i, v)
        m.train_epoch()  # warmup: the single-epoch compile
        with flightrec.budget(compiles=1, dispatches=0, readbacks=0,
                              tag="mfsgd.compile_epochs"):
            m.compile_epochs(3)
        # first run: +2 small-op compiles (the stacked-stats readback
        # program), one dispatch, one readback — then steady state
        with flightrec.budget(compiles=2, dispatches=1, readbacks=1,
                              tag="mfsgd.train_epochs#1"):
            m.train_epochs(3)
        with flightrec.budget(compiles=0, dispatches=1, readbacks=1,
                              h2d_bytes=0, tag="mfsgd.train_epochs#2") as b:
            m.train_epochs(3)
        assert b.spent()["dispatches"] == 1
        assert b.spent()["readbacks"] == 1


def test_lda_epoch_loop_passes_pinned_budget(mesh):
    """One AOT compile per epoch count; each sample_epochs run is one
    dispatch + one readback + only the per-worker keys' H2D (64 B at 8
    workers), with zero recompiles — including across _advance_keys
    re-seeds (the raw-key-bits fix)."""
    import harp_tpu.models.lda as L

    cfg = L.LDAConfig(n_topics=8, algo="dense", d_tile=16, w_tile=16,
                      entry_cap=64)
    with telemetry.scope():
        lda = L.LDA(64, 48, cfg, mesh, seed=0)
        d_ids, w_ids = L.benchmark_corpus(64, 48, 4, 0)
        lda.set_tokens(d_ids, w_ids)
        lda.sample_epoch()  # warmup: the single-epoch compile
        with flightrec.budget(compiles=1, dispatches=0, readbacks=0,
                              tag="lda.compile_epochs"):
            lda.compile_epochs(2)
        keys_bytes = mesh.num_workers * 2 * 4
        for rerun in range(2):  # steady from the FIRST run
            with flightrec.budget(compiles=0, dispatches=1, readbacks=1,
                                  h2d_bytes=keys_bytes,
                                  tag=f"lda.sample_epochs#{rerun}") as b:
                lda.sample_epochs(2)
            assert b.spent()["dispatches"] == 1
            assert b.spent()["readbacks"] == 1


def test_kmeans_fit_passes_pinned_budget(mesh):
    """Steady-state fit: one compile (the per-call jit), one dispatch
    for ALL iterations, two readbacks (inertia + centroids), and H2D of
    exactly the points once."""
    import harp_tpu.models.kmeans as KM

    pts = np.random.default_rng(0).normal(size=(256, 8)).astype(np.float32)
    with telemetry.scope():
        KM.fit(pts, k=4, iters=3, mesh=mesh, seed=0)  # warm shared ops
        with flightrec.budget(compiles=1, dispatches=1, readbacks=2,
                              h2d_bytes=pts.nbytes, tag="kmeans.fit") as b:
            KM.fit(pts, k=4, iters=3, mesh=mesh, seed=0)
        assert b.spent()["h2d_bytes"] == pts.nbytes
        assert b.spent()["dispatches"] == 1


# ---------------------------------------------------------------------------
# zero-cost when disabled (satellite)
# ---------------------------------------------------------------------------

def test_zero_cost_when_disabled(mesh):
    """With telemetry off the flight-recorder entry points must not touch
    arrays or add dispatches: the traced epoch program is bit-identical
    (jaxpr equality — no instrumentation ops), the numeric result is
    identical, and every counter stays at zero.  With telemetry on, the
    same single tracked dispatch is simply *counted* — so the recorded
    dispatch count is also the disabled run's dispatch count."""
    import harp_tpu.models.mfsgd as MF

    def build_and_run():
        cfg = MF.MFSGDConfig(rank=4, algo="dense", u_tile=8, i_tile=8,
                             entry_cap=32)
        m = MF.MFSGD(64, 48, cfg, mesh, seed=3)
        u, i, v = MF.synthetic_ratings(64, 48, 600, rank=4, seed=3)
        m.set_ratings(u, i, v)
        rmse = m.train_epoch()
        jaxpr = str(jax.make_jaxpr(m._epoch_fn.__wrapped__)(
            m.W, m.H, *m._blocks))
        return rmse, jaxpr

    with telemetry.scope(False):
        rmse_off, jaxpr_off = build_and_run()
        assert flightrec.compile_watch.count == 0
        assert flightrec.transfers.h2d_bytes == 0
        assert flightrec.transfers.dispatches == 0
        assert flightrec.transfers.readbacks == 0
    with telemetry.scope(True):
        rmse_on, jaxpr_on = build_and_run()
        assert flightrec.transfers.dispatches == 1  # the train_epoch call
    assert rmse_on == rmse_off
    assert jaxpr_on == jaxpr_off


# ---------------------------------------------------------------------------
# export / report / checker round trips
# ---------------------------------------------------------------------------

def test_export_rows_carry_provenance_and_pass_check_jsonl(mesh, tmp_path):
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "scripts"))
    import check_jsonl

    with telemetry.scope():
        with telemetry.span("unit"):
            flightrec.track(jax.jit(lambda x: x - 2.0), "unit")(jnp.ones(5))
        mesh.shard_array(np.ones((8, 4), np.float32), 0)
        p = tmp_path / "flight.jsonl"
        telemetry.export(str(p))
    rows = telemetry.load_rows(str(p))
    assert rows["compile"] and rows["transfer"]
    for r in rows["compile"] + rows["transfer"]:
        for f in ("backend", "date", "commit"):
            assert f in r, (f, r)
    assert check_jsonl.check_file(str(p)) == []


def test_live_report_surfaces_compile_and_transfer_sections(mesh):
    from harp_tpu import report

    with telemetry.scope():
        with telemetry.span("unit"):
            flightrec.track(jax.jit(lambda x: x / 2.0), "unit")(jnp.ones(5))
        mesh.shard_array(np.ones((8, 4), np.float32), 0)
        row, spans = report.live_report()
    assert row["compile"]["count"] >= 1
    assert row["transfer"]["h2d_bytes"] == 8 * 4 * 4
    assert row["transfer"]["dispatches"] == 1
    text = report.render(row, spans)
    assert "compiles (XLA backend):" in text
    assert "transfers (host<->device):" in text
