"""Prefetch-pipelined ingest (harp_tpu/ingest.py, PR 8).

Contract under test: every depth of the shared host pipeline is
BIT-EXACT (stages are deterministic per chunk, consumption is in
order) — only the overlap changes; the flight budgets wrapping the
pipeline loops are exact (chunk bytes on the wire, zero post-warmup
compiles); and the stall detector turns a secretly-serialized pipeline
into a loud RuntimeWarning instead of a silently wrong measurement.
"""

import threading
import time
import warnings

import jax
import numpy as np
import pytest

from harp_tpu import ingest
from harp_tpu.models import kmeans as K
from harp_tpu.models import kmeans_stream as KS
from harp_tpu.models import mlp as M
from harp_tpu.utils import flightrec, telemetry

def _blobs(n=4096, d=24, c=4, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.normal(size=(n, d)).astype(np.float32)
            + (rng.integers(0, c, size=(n, 1)) * 6).astype(np.float32))


# ---------------------------------------------------------------------------
# the pipeline itself (no jax involved)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("depth,rt,pt", [(1, 1, 1), (2, 1, 1), (4, 2, 2)])
def test_pipeline_preserves_order_and_values(depth, rt, pt):
    with ingest.IngestPipeline(lambda j: j, lambda r: r * 10,
                               lambda r: r + 1, depth=depth,
                               read_threads=rt, prep_threads=pt) as pipe:
        assert list(pipe.stream(13)) == [j * 10 + 1 for j in range(13)]
        assert pipe.stats.chunks == 13
        # a second stream through the SAME pipeline (epoch reuse)
        assert list(pipe.stream(3)) == [1, 11, 21]


def test_pipeline_single_reader_runs_in_order():
    """Stateful sequential sources (FileSplits) depend on read(j)
    executing in submission order on one thread."""
    seen = []

    def read(j):
        seen.append(j)
        time.sleep(0.001 * (3 - j % 3))  # adversarial per-call jitter
        return j

    with ingest.IngestPipeline(read, depth=4) as pipe:
        assert list(pipe.stream(9)) == list(range(9))
    assert seen == list(range(9))


def test_pipeline_propagates_stage_errors():
    def read(j):
        if j == 3:
            raise RuntimeError("disk on fire")
        return j

    with ingest.IngestPipeline(read, depth=2) as pipe:
        with pytest.raises(RuntimeError, match="disk on fire"):
            list(pipe.stream(8))


def test_pipeline_rejects_bad_knobs():
    with pytest.raises(ValueError, match="depth"):
        ingest.IngestPipeline(lambda j: j, depth=0)
    with pytest.raises(ValueError, match="threads"):
        ingest.IngestPipeline(lambda j: j, read_threads=0)


# ---------------------------------------------------------------------------
# the stall detector (satellite: sabotaged overlap must be LOUD)
# ---------------------------------------------------------------------------

def test_stall_detector_fires_on_sabotaged_overlap():
    """The canonical dead pipeline: each read is gated on the PREVIOUS
    chunk's consumption (a shared buffer of size one), so depth-2
    prefetch cannot actually work ahead — the consumer waits a full
    read per chunk despite computing in between, and the detector must
    say so."""
    sem = threading.Semaphore(1)

    def read(j):
        sem.acquire()           # can never run ahead of consumption
        time.sleep(0.02)
        return j

    pipe = ingest.IngestPipeline(read, depth=2, tag="unit.sabotage",
                                 stall_warn=0.5)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        for _ in pipe.stream(8):
            time.sleep(0.01)    # compute the reads SHOULD hide under
            sem.release()
    assert pipe.stats.overlap_efficiency < 0.5, pipe.stats
    assert pipe.stats.stalls == 1
    assert any("stalled" in str(x.message) for x in w), \
        [str(x.message) for x in w]


def test_no_stall_warning_when_overlap_works():
    """Same costs WITHOUT the shared lock: reads hide behind the
    consumer sleep and the detector stays silent."""

    def read(j):
        time.sleep(0.01)
        return j

    pipe = ingest.IngestPipeline(read, depth=2, tag="unit.healthy",
                                 stall_warn=0.5)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        for _ in pipe.stream(8):
            time.sleep(0.01)
    assert not any("stalled" in str(x.message) for x in w), \
        [str(x.message) for x in w]
    assert pipe.stats.overlap_efficiency >= 0.5, pipe.stats


# ---------------------------------------------------------------------------
# kmeans_stream on the pipeline: depth is invisible to the math
# ---------------------------------------------------------------------------

def test_kmeans_stream_depths_bit_exact(mesh):
    """prefetch 0 (legacy chain) / 1 / 2 / 4 produce the IDENTICAL
    clustering — and all match the committed-golden contract vs the
    resident fit."""
    pts = _blobs()
    ref_c, ref_i = K.fit(pts, k=8, iters=5, mesh=mesh, seed=3)
    outs = [KS.fit_streaming(pts, k=8, iters=5, chunk_points=1000,
                             mesh=mesh, seed=3, prefetch=p)
            for p in (0, 1, 2, 4)]
    for c, i in outs[1:]:
        np.testing.assert_array_equal(c, outs[0][0])
        assert i == outs[0][1]
    assert np.allclose(outs[0][0], ref_c, rtol=1e-4, atol=1e-4)
    assert abs(outs[0][1] - ref_i) < 1e-3 * abs(ref_i)


def test_kmeans_stream_int8_gate_rides_pipeline(mesh):
    """quantize='int8' through the pipeline: bit-exact across depths
    (the quantize stage moved threads, not math) and within the
    existing inertia tolerance of f32."""
    pts = _blobs()
    _, i_f32 = KS.fit_streaming(pts, k=8, iters=4, chunk_points=1000,
                                mesh=mesh, seed=3)
    outs = [KS.fit_streaming(pts, k=8, iters=4, chunk_points=1000,
                             mesh=mesh, seed=3, quantize="int8",
                             prefetch=p) for p in (0, 1, 4)]
    for c, i in outs[1:]:
        np.testing.assert_array_equal(c, outs[0][0])
        assert i == outs[0][1]
    assert abs(outs[0][1] - i_f32) < 0.05 * abs(i_f32)


def test_kmeans_stream_files_depths_bit_exact(mesh, tmp_path):
    """The stateful file-split source (sequential cursors + epoch reset)
    is depth-invariant too."""
    pts = _blobs(n=1300, d=10)
    paths = []
    bounds = np.linspace(0, len(pts), 4).astype(int)
    for i in range(3):
        p = tmp_path / f"s{i}.npy"
        np.save(p, pts[bounds[i]:bounds[i + 1]])
        paths.append(str(p))
    init = pts[:5].copy()
    outs = [KS.fit_streaming_files(paths, k=5, iters=3, chunk_points=256,
                                   mesh=mesh, init=init, prefetch=p)
            for p in (1, 3)]
    np.testing.assert_array_equal(outs[0][0], outs[1][0])
    assert outs[0][1] == outs[1][1]


def test_benchmark_ingest_reports_pipeline_fields(mesh, tmp_path):
    pts = _blobs(n=2048, d=16).astype(np.float16)
    f = tmp_path / "pts.npy"
    np.save(f, pts)
    mm = np.load(f, mmap_mode="r")
    import os

    r = KS.benchmark_ingest(mm, k=4, iters=2, chunk_points=512,
                            mesh=mesh, disk_bytes=os.path.getsize(f))
    assert r["kind"] == "ingest" and r["prefetch_depth"] == 2
    assert 0.0 <= r["overlap_efficiency"] <= 1.0
    assert 0.0 < r["device_hidden_fraction"] <= 1.0
    assert r["pipeline"]["chunks"] == 4
    assert r["pipeline"]["blocked_s"] > 0


# ---------------------------------------------------------------------------
# budget pins: exact chunk bytes, zero post-warmup compiles
# ---------------------------------------------------------------------------

def test_kmeans_stream_h2d_budget_exact(mesh):
    """The whole fit ships EXACTLY iters × chunk-data bytes plus the two
    one-time masks — nothing re-uploads, nothing sneaks past the
    counted shard_array path."""
    pts = _blobs(n=2048, d=16)
    chunk = 512                     # divides n: full mask only
    iters = 3
    exact = chunk * 4 + iters * (2048 // chunk) * chunk * 16 * 4
    with telemetry.scope():
        with flightrec.budget(h2d_bytes=exact, tag="unit.ks.h2d") as b:
            KS.fit_streaming(pts, k=4, iters=iters, chunk_points=chunk,
                             mesh=mesh, seed=0, prefetch=2)
        assert b.spent()["h2d_bytes"] == exact


def test_kmeans_stream_zero_postwarmup_compiles(mesh):
    """Epochs after the first compile NOTHING: a 4-epoch fit spends no
    more backend compiles than a 1-epoch fit does for its per-epoch
    machinery (the only delta is the final history stack's shape)."""
    pts = _blobs(n=2048, d=16)
    kw = dict(k=4, chunk_points=512, mesh=mesh, seed=0, prefetch=2)
    with telemetry.scope():
        KS.fit_streaming(pts, iters=4, **kw)   # warms every shape incl.
        base = flightrec.compile_watch.count   # the 4-long stack
        KS.fit_streaming(pts, iters=1, **kw)
        c1 = flightrec.compile_watch.count - base
        with flightrec.budget(compiles=c1, tag="unit.ks.compiles") as b:
            KS.fit_streaming(pts, iters=4, **kw)
        # epochs 2-4 added zero compiles beyond the 1-epoch run's set
        assert b.spent()["compiles"] <= c1


def test_interior_epoch_budget_fires_on_recompiling_chunk_loop(mesh,
                                                               monkeypatch):
    """Liveness of the warn-mode guard inside _stream_train: a chunk fn
    that recompiles per call (the classic driver-loop trap) must trip the
    epoch budget's compiles=0 arm on every post-warmup epoch."""
    orig = KS._make_accum_fn

    def recompiling(mesh_, cfg_):
        fn = orig(mesh_, cfg_)

        def wrapped(*args):
            return jax.jit(lambda *a: fn(*a))(*args)  # fresh jit per call

        return wrapped

    monkeypatch.setattr(KS, "_make_accum_fn", recompiling)
    pts = _blobs(n=1024, d=8)
    with telemetry.scope():
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            KS.fit_streaming(pts, k=4, iters=2, chunk_points=512,
                             mesh=mesh, seed=0, prefetch=2)
    assert any("kmeans_stream.ingest" in str(x.message)
               and "compiles" in str(x.message) for x in w), \
        [str(x.message) for x in w]


def test_clean_runs_emit_no_budget_warnings(mesh):
    """The shipped loops PASS their own interior budgets: a telemetry-on
    multi-epoch kmeans fit and mlp fit emit zero budget warnings."""
    pts = _blobs(n=2048, d=16)
    x, y = M.synthetic_mnist(n=256, d=16, classes=4, seed=1)
    with telemetry.scope():
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            KS.fit_streaming(pts, k=4, iters=3, chunk_points=500,
                             mesh=mesh, seed=0)  # padded tail chunk too
            tr = M.MLPTrainer(M.MLPConfig(sizes=(16, 32, 4), lr=0.1),
                              mesh, seed=0)
            tr.fit(x, y, batch_size=64, epochs=2)
    budget_warnings = [x for x in w
                       if "budget exceeded" in str(x.message)]
    assert not budget_warnings, [str(x.message) for x in budget_warnings]


# ---------------------------------------------------------------------------
# mlp on the pipeline (satellite: no more per-epoch full-copy reshuffle)
# ---------------------------------------------------------------------------

def test_mlp_fit_depths_bit_exact(mesh):
    cfg = M.MLPConfig(sizes=(16, 32, 4), lr=0.1)
    x, y = M.synthetic_mnist(n=256, d=16, classes=4, seed=1)
    runs = {}
    for p in (1, 2, 4):
        tr = M.MLPTrainer(cfg, mesh, seed=0)
        hist = tr.fit(x, y, batch_size=64, epochs=2, prefetch=p)
        runs[p] = (hist, [np.asarray(l) for l in
                          jax.tree.leaves(tr.params)])
    for p in (2, 4):
        assert runs[p][0] == runs[1][0]
        for a, b in zip(runs[p][1], runs[1][1]):
            np.testing.assert_array_equal(a, b)


def test_mlp_batch_reader_yields_views():
    """THE saved-host-copies pin: the reader hands VIEWS of the caller's
    arrays — the pre-PR ``x[perm]`` gather copied every row, every
    epoch."""
    x = np.arange(64 * 8, dtype=np.float32).reshape(64, 8)
    y = np.zeros(64, np.int32)
    read = M._batch_reader(x, y, 16, np.array([2, 0, 1, 3]))
    xb, yb = read(0)
    assert np.shares_memory(xb, x) and np.shares_memory(yb, y)
    np.testing.assert_array_equal(xb, x[32:48])  # batch index 2
    xb3, _ = read(3)
    np.testing.assert_array_equal(xb3, x[48:64])


def test_mlp_fit_h2d_budget_exact_and_zero_recompiles(mesh):
    """Per epoch the wire carries exactly the batch bytes (f32 rows +
    i32 labels) and a warmed trainer's fit compiles nothing."""
    cfg = M.MLPConfig(sizes=(16, 32, 4), lr=0.1)
    x, y = M.synthetic_mnist(n=256, d=16, classes=4, seed=1)
    tr = M.MLPTrainer(cfg, mesh, seed=0)
    tr.fit(x, y, batch_size=64, epochs=1)  # warm: the step compile
    epochs = 2
    exact = epochs * 256 * (16 * 4 + 4)
    with telemetry.scope():
        with flightrec.budget(compiles=0, h2d_bytes=exact,
                              tag="unit.mlp.fit") as b:
            tr.fit(x, y, batch_size=64, epochs=epochs)
        assert b.spent()["h2d_bytes"] == exact
        assert b.spent()["compiles"] == 0


def test_mlp_load_resident_skips_host_copy_when_aligned(mesh):
    """load_resident with divisible-by-batch f32 input stages WITHOUT
    the pre-PR full-row gather; trimming still drops a uniform random
    subset and keeps row order."""
    cfg = M.MLPConfig(sizes=(16, 32, 4), lr=0.1)
    x, y = M.synthetic_mnist(n=192, d=16, classes=4, seed=2)
    tr = M.MLPTrainer(cfg, mesh, seed=0)
    assert tr.load_resident(x, y, batch_size=64) == 192
    xs, ys, _, _ = tr._resident
    np.testing.assert_array_equal(np.asarray(xs), x)  # input order kept
    np.testing.assert_array_equal(np.asarray(ys), y)
    # trim path: usable < n drops rows but preserves relative order
    assert tr.load_resident(x[:150], y[:150], batch_size=64, seed=7) == 128
    xs2 = np.asarray(tr._resident[0])
    idx = [int(np.flatnonzero((x[:150] == row).all(1))[0]) for row in xs2]
    assert idx == sorted(idx) and len(set(idx)) == 128


# ---------------------------------------------------------------------------
# rf + fileformat on the pipeline
# ---------------------------------------------------------------------------

def test_rf_binize_chunked_bit_exact():
    from harp_tpu.models import rf as R

    rng = np.random.default_rng(0)
    x = rng.normal(size=(5000, 9)).astype(np.float32)
    edges = R.quantile_bins(x, 8)
    ref = R.binize(x, edges)
    for prefetch in (1, 2):
        np.testing.assert_array_equal(
            R.binize_chunked(x, edges, chunk_rows=1024,
                             prefetch=prefetch), ref)


def test_load_sharded_csv_matches_serial_loader_order(mesh, tmp_path):
    """The threaded per-file loads reassemble in submission order: the
    stacked output is bit-identical to loading each split serially."""
    from harp_tpu import fileformat as FF

    rng = np.random.default_rng(3)
    paths = []
    for i in range(5):
        p = tmp_path / f"f{i}.csv"
        np.savetxt(p, rng.normal(size=(20 + 11 * i, 4)), fmt="%.5f",
                   delimiter=",")
        paths.append(str(p))
    stacked, counts = FF.load_sharded_csv(paths, 3)
    splits = FF.multi_file_splits(paths, 3)
    from harp_tpu.native import datasource as DS

    rows_pad = stacked.shape[0] // 3
    for w, files in enumerate(splits):
        parts = [DS.load_csv(p) for p in files]
        ref = (np.concatenate(parts, 0) if parts
               else np.zeros((0, 4), np.float32))
        got = stacked[w * rows_pad: w * rows_pad + counts[w]]
        np.testing.assert_array_equal(got, ref)


def _load_bench_ingest():
    """Fresh scripts/bench_ingest module."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "bench_ingest", os.path.join(os.path.dirname(__file__), "..",
                                     "scripts", "bench_ingest.py"))
    bi = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bi)
    return bi


def test_ingest_smoke_preset_runs_int8_wire(tmp_path, monkeypatch, mesh):
    """run_smoke(quantize='int8') executes the int8-WIRE ingest end to
    end; nothing else exercises the preset's quantize threading."""
    bi = _load_bench_ingest()
    # REAL isolation: the module's DATA_DIR is an absolute repo path
    # (cwd-independent), so redirect it — a chdir would silently share
    # .bench_data with concurrent runs
    monkeypatch.setattr(bi, "DATA_DIR", str(tmp_path))

    res = bi.run_smoke(quantize="int8")
    assert res["wire_dtype"] == "int8"
    assert res["points_per_sec"] > 0 and res["inertia"] > 0
    # and the exact-wire default is unchanged
    res_f = bi.run_smoke()
    assert res_f["wire_dtype"] != "int8"
    # same data, same seed: int8 quantization moves inertia by well
    # under the contract's 1% (measured 1.6e-4 rel on the 12 GB run)
    assert abs(res["inertia"] - res_f["inertia"]) / res_f["inertia"] < 0.01
