"""Streaming / blocked-epoch KMeans — the 1B-point north-star path.

Reference parity (SURVEY.md §1, §7): the north-star metric is "KMeans
iter/sec (1B pts, k=1k)".  1B×300 f32 is 1.2 TB (int8: 300 GB) — it
cannot be device-resident on one chip (v5e: 16 GB HBM), and Harp never
needed it resident either: each mapper streamed its HDFS file split
through memory.  The TPU-native equivalent keeps ONLY the centroids
[k, d] and the partial accumulators [k, d]+[k] device-resident and
streams the points through HBM in fixed-shape chunks:

- **Real data** (:func:`fit_streaming`): host chunks (numpy / np.memmap,
  so the source may be a disk file far larger than RAM) are padded to one
  static shape, double-buffered onto the mesh with ``jax.device_put``
  (async dispatch overlaps the transfer of chunk j+1 with the compute of
  chunk j), and accumulated per-worker on device.  One ``allreduce`` per
  epoch — not per chunk — merges the partials, exactly Harp's
  regroup+allgather phase at epoch granularity.  ``quantize="int8"``
  streams int8 chunks (¼ the host→HBM bytes; scales from one chunked
  host pre-pass).
- **Synthetic at full scale** (:func:`benchmark_streaming`): the whole
  multi-epoch run is ONE jitted program; chunk j is regenerated on device
  from a PRNG keyed by j alone (every epoch revisits the same points —
  regeneration is the stand-in for re-reading a file split, it never
  touches the host link), so the 1B×300 k=1000 config is *formulable* on a
  single chip in bounded HBM and trivially shards over a pod mesh.

Peak HBM per worker ≈ chunk_rows × (d + k) × 4 bytes for the points
block + score matrix (the [chunk, k] scores dominate at k=1000), plus
the [k, d] state — the ``chunk_points`` knob bounds it explicitly.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from harp_tpu.ingest import IngestPipeline
from harp_tpu.parallel import collective as C
from harp_tpu.parallel.mesh import WorkerMesh, current_mesh
from harp_tpu.utils import prng, telemetry
from harp_tpu.utils.timing import device_sync

from harp_tpu.models.kmeans import (  # shared MXU partials formulation
    _INT8_SUM_ROW_LIMIT,
    _check_int8_chunk_rows,
    _clip_round_int8,
    _normalize_centroids,
    _partials_block,
    _partials_block_int8,
    kmeanspp_init,
)


@dataclasses.dataclass
class StreamConfig:
    # epoch counts are runtime arguments (fit_streaming(iters=...) /
    # run_fn(..., n_iters)), never config state: the synthetic program
    # traces n_iters as a scalar so changing it can't recompile
    k: int = 1000
    # rows per streamed chunk (across the whole mesh; rounded up to a
    # multiple of num_workers).  Bounds peak HBM: the dominant buffers are
    # the [chunk/nw, d] points block and [chunk/nw, k] score matrix —
    # 262144×(300+1000)×4 ≈ 1.4 GB at the north-star shapes.
    chunk_points: int = 262_144
    dtype: Any = jnp.float32
    quantize: str | None = None  # None | "int8" (host-quantized chunks)

    def __post_init__(self):
        if self.k < 1:
            raise ValueError(f"k must be >= 1, got {self.k}")
        if self.chunk_points < 1:
            raise ValueError(f"chunk_points must be >= 1, got {self.chunk_points}")
        if self.quantize not in (None, "int8"):
            raise ValueError(f"quantize must be None or 'int8', got {self.quantize!r}")


def _make_accum_fn(mesh: WorkerMesh, cfg: StreamConfig):
    """Per-chunk accumulate: NO collective inside — partials land in a
    per-worker accumulator ([nw, k, d] sharded on dim 0); the epoch-end
    :func:`_make_finish_fn` does the one allreduce."""

    def accum(pts, mask, centroids, sums, counts, inertia):
        # per-worker views: pts [chunk/nw, d], sums [1, k, d], counts
        # [1, k], inertia [1]; centroids replicated
        c2 = (centroids.astype(jnp.float32) ** 2).sum(-1)
        if cfg.quantize == "int8":
            pts_q, col_scale = pts
            s, c, i = _partials_block_int8(pts_q, col_scale, centroids, c2,
                                           mask=mask)
        else:
            # chunks may arrive in a narrow wire dtype (f16 disk data
            # ships as f16 — half the H2D bytes); the widening cast is
            # exact, so this is bit-identical to casting on the host
            s, c, i = _partials_block(pts.astype(cfg.dtype), centroids,
                                      c2, mask=mask)
        return sums + s[None], counts + c[None], inertia + i[None]

    pts_spec = ((mesh.spec(0), P()) if cfg.quantize == "int8"
                else mesh.spec(0))
    sh = mesh.spec(0)
    return jax.jit(mesh.shard_map(
        accum,
        in_specs=(pts_spec, mesh.spec(0), P(), sh, sh, sh),
        out_specs=(sh, sh, sh),
    ))


def _make_finish_fn(mesh: WorkerMesh):
    """Epoch tail: allreduce the per-worker partials, normalize, keep old
    centroid on empty clusters (same rule as kmeans.fit)."""

    def finish(sums, counts, inertia, centroids):
        s, c, i = C.allreduce((sums[0], counts[0], inertia[0]))
        return _normalize_centroids(s, c, centroids), i

    sh = mesh.spec(0)
    return jax.jit(mesh.shard_map(
        finish, in_specs=(sh, sh, sh, P()), out_specs=(P(), P())))


def _validate_explicit_init(init, k, d):
    """The ONE explicit-``[k, d]``-init check, shared by every fit
    variant — k AND the feature dim, so a mismatch fails here with a
    plain message, not inside a jitted matmul."""
    arr = np.asarray(init, np.float32)
    if arr.ndim != 2 or arr.shape[0] != k or arr.shape[1] != d:
        raise ValueError(f"explicit init must be [k={k}, d={d}], "
                         f"got shape {arr.shape}")
    return arr


def _topup_rows(rows, count, rng):
    """Pad ``rows`` to exactly ``count`` by UNIFORM resampling (equal
    allgather shapes across processes; no positional bias)."""
    if rows.shape[0] >= count:
        return rows[:count]
    extra = rng.choice(rows.shape[0], size=count - rows.shape[0])
    return np.concatenate([rows, rows[np.sort(extra)]], 0)


def _init_centroids(points, n, k, seed, init):
    """Same seeding contract as kmeans.fit, but memmap-safe: only the
    selected rows are ever materialized.  ``init`` may also be an
    explicit ``[k, d]`` array (warm start / cross-variant comparisons)."""
    if not isinstance(init, str):  # explicit centroids
        return _validate_explicit_init(init, k, points.shape[1])
    if init == "kmeans++":
        rng = np.random.default_rng(0 if seed is None else seed)
        idx = np.sort(rng.choice(n, size=min(n, 50_000), replace=False))
        return kmeanspp_init(np.asarray(points[idx], np.float32), k,
                             seed=0 if seed is None else seed)
    if init != "random":
        raise ValueError(f"init must be 'random' or 'kmeans++', got {init!r}")
    if seed is None:
        idx = np.arange(k)
    else:
        idx = np.sort(np.random.default_rng(seed).choice(n, size=k,
                                                         replace=False))
    return np.asarray(points[idx], np.float32)


def _int8_amax(points, n, chunk):
    """Per-feature |max| over a source in one chunked host pass (a
    memmap never loads more than one chunk)."""
    amax = np.zeros(points.shape[1], np.float32)
    for lo in range(0, n, chunk):
        blk = np.asarray(points[lo:lo + chunk], np.float32)
        np.maximum(amax, np.abs(blk).max(0), out=amax)
    return amax


def _amax_to_scales(amax):
    """THE int8 scale rule — one place, so the single-source and
    sharded-ingest paths can never disagree on it."""
    return np.maximum(amax, 1e-30) / 127.0


def _int8_scales(points, n, chunk):
    return _amax_to_scales(_int8_amax(points, n, chunk))


# wire-dtype codes for the cross-process agreement allgather (0 = "ship
# the compute dtype"); only narrow FLOAT formats are worth a code — int
# sources upcast host-side as before
_WIRE_CODES = {"float16": 1, "bfloat16": 2}
_WIRE_FROM_CODE = {1: "float16", 2: "bfloat16"}


def _resolve_wire_dtype(wire, np_dtype, src_dtype):
    """H2D payload dtype for the float chunk-streaming paths.

    ``wire="auto"`` (the default) ships the SOURCE dtype when it is a
    narrower float than the compute dtype — f16 disk data crosses
    host→device as f16 and widens on device, which is bit-identical to
    the host-side cast (widening is exact) at half the transfer bytes;
    the host link is the streaming bottleneck, not HBM
    (BASELINE.md real-ingest rows).  Anything else — f32 sources, int
    sources, mixed-file sets (``src_dtype=None``) — ships the compute
    dtype unchanged.  An explicit dtype forces the wire format;
    narrower than the source is a LOSSY opt-in compression (e.g.
    ``wire_dtype=jnp.bfloat16`` on f32 data).  ``wire=None`` restores
    the legacy ship-compute-dtype behavior.

    Multi-host: every process must resolve the SAME wire dtype or the
    per-host chunk programs compile differently and the job deadlocks —
    "auto" allgathers a dtype code and falls back to the compute dtype
    unless all processes agree.
    """
    if wire is None:
        return np_dtype
    if isinstance(wire, str) and wire == "auto":
        name = np.dtype(src_dtype).name if src_dtype is not None else None
        code = _WIRE_CODES.get(name, 0)
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils as mh

            codes = np.atleast_1d(np.asarray(
                mh.process_allgather(np.int64(code))))
            code = int(codes[0]) if (codes == codes[0]).all() else 0
        wire_np = (np.dtype(_WIRE_FROM_CODE[code]) if code else np_dtype)
        return wire_np if wire_np.itemsize < np_dtype.itemsize else np_dtype
    w = np.dtype(jnp.dtype(wire).name)
    if w.name not in ("float16", "bfloat16", "float32", "float64"):
        raise ValueError(f"wire_dtype must be a float dtype, got {w.name}")
    return w


def fit_streaming(points, k=1000, iters=10, chunk_points=262_144,
                  mesh: WorkerMesh | None = None, seed=0,
                  dtype=jnp.float32, quantize=None, init="random",
                  return_history=False, ckpt_dir=None, ckpt_every=5,
                  max_restarts=3, fault=None, instrument=None,
                  wire_dtype="auto", prefetch=2):
    """Blocked-epoch Lloyd over a source too large for HBM.

    ``wire_dtype``: H2D payload format (:func:`_resolve_wire_dtype`) —
    "auto" ships narrow-float sources (f16 disk) in their own dtype and
    widens on device: bit-identical results, half the transfer bytes.

    ``prefetch``: host-pipeline work-ahead depth
    (:class:`harp_tpu.ingest.IngestPipeline`, PR 8).  ``>= 2`` (default
    2) runs read/parse and pad/quantize on background threads so chunk
    j+1's host stages overlap chunk j's transfer AND compute; masks ship
    once and memmap sources ride a single-copy chain (device_put reads
    the mapped pages directly).  ``1`` runs the same staged chain inline
    (serial); ``0`` selects the pre-pipeline serial loop verbatim — the
    measured A/B incumbent in scripts/bench_ingest.py: the staged chain
    sustains 1.7-2.2× the legacy loop's host byte rate at the smoke A/B
    shape (1-core CPU host, 2026-08-04; BENCH_local
    kmeans_ingest_ab_smoke).  Every depth is bit-exact: the stages are
    deterministic per chunk and chunks are consumed in order.

    ``points``: [n, d] numpy array, ``np.memmap``, or any sequential
    source honoring the slice contract (``harp_tpu.native.CSVPoints``).
    Semantics are identical to ``kmeans.fit`` — one epoch assigns EVERY
    point against the epoch-start centroids, so the result is full-batch
    Lloyd, not minibatch — only the execution is chunked.  One deliberate
    seeding divergence: ``init="kmeans++"`` runs the D² seeding on a
    uniform subsample of at most 50 000 rows (``_init_centroids``), not
    the full source — exact kmeans++ needs k full passes over the data
    (k=1000 → 1000 sweeps of a 1.2 TB file); the subsample keeps seeding
    O(1) while Lloyd itself remains exact full-batch.  Returns
    ``(centroids [k, d], inertia)`` (+ per-epoch inertia history with
    ``return_history=True``; the history is read back in one stacked
    transfer at the end — never per epoch, per the per-epoch readback
    trap).

    ``ckpt_dir`` enables checkpoint/resume with the same recovery
    contract as the other model ``fit``\\ s (utils.fault.fit_epochs):
    a 1B-point run is exactly the multi-hour job that needs to survive a
    preemption.  Epochs are deterministic given the centroids (the data
    is re-read each sweep), so centroids + completed history are the
    whole state.

    ``instrument``: pass an empty dict to collect per-epoch pipeline
    timing under key ``"epochs"``: ``host_s`` (time blocked in
    ``put_chunk`` — disk read/parse + pad + H2D dispatch; the part device
    compute is supposed to hide behind), ``sync_s`` (device tail NOT
    hidden: blocking wait on the epoch result after the last chunk), and
    ``epoch_s`` (wall).  Instrumented runs deliberately pay ONE extra
    device sync per epoch (one round trip — negligible
    against multi-second epochs, but don't instrument micro-runs you
    intend to time).  Consumed by :func:`benchmark_ingest`.
    """
    mesh = mesh or current_mesh()
    n, d = points.shape
    nw = mesh.num_workers
    cfg = StreamConfig(k=k, chunk_points=chunk_points,
                       dtype=dtype, quantize=quantize)
    chunk = -(-min(cfg.chunk_points, n) // nw) * nw  # static chunk shape

    init_c = _init_centroids(points, n, k, seed, init)
    centroids = jax.device_put(jnp.asarray(init_c, dtype=dtype),
                               mesh.replicated())
    np_dtype = np.dtype(jnp.dtype(dtype).name)
    wire_np = _resolve_wire_dtype(wire_dtype, np_dtype,
                                  getattr(points, "dtype", None))
    scale_dev = None
    if quantize == "int8":
        # same exact-int32 accumulation bound as kmeans.fit — here it
        # applies PER CHUNK (cross-chunk accumulation is f32); the limit
        # resolves at call time so tests can shrink it
        _check_int8_chunk_rows(chunk // nw, _INT8_SUM_ROW_LIMIT)
        scales = _int8_scales(points, n, chunk)
        scale_dev = jax.device_put(jnp.asarray(scales), mesh.replicated())

    if iters == 0:  # same contract as kmeans.fit(iters=0)
        return (np.asarray(init_c, np.float32), 0.0, np.zeros(0, np.float32)
                ) if return_history else (np.asarray(init_c, np.float32), 0.0)
    offsets = list(range(0, n, chunk))
    pipe, h2d_epoch = _make_source_pipeline(
        mesh, points, offsets, chunk, n, d, quantize,
        scales if quantize == "int8" else None, scale_dev, wire_np,
        prefetch)
    return _stream_train(mesh, cfg, pipe, len(offsets), centroids, iters,
                         dtype, return_history, ckpt_dir, ckpt_every,
                         max_restarts, fault, instrument,
                         epoch_h2d_bytes=h2d_epoch)


def _legacy_put_chunk(mesh, points, chunk, n, d, quantize, scales,
                      scale_dev, wire_np):
    """The pre-PR-8 serial host chain, verbatim: materialize the slice,
    build + upload a fresh mask per chunk, pad, cast, ship.  Kept as the
    runnable INCUMBENT arm of the bench_ingest A/B (``prefetch=0``) —
    the committed pipeline-speedup row needs the loop it beat to stay
    measurable; numerics are identical to the staged chain."""

    def put_chunk(lo):
        hi = min(lo + chunk, n)
        blk = np.asarray(points[lo:hi])
        m = np.zeros(chunk, np.float32)
        m[:hi - lo] = 1.0
        if hi - lo < chunk:  # pad the tail to the one static shape
            pad = np.zeros((chunk - (hi - lo), d), blk.dtype)
            blk = np.concatenate([blk, pad], 0)
        if quantize == "int8":
            q = _clip_round_int8(blk.astype(np.float32), scales)
            return ((mesh.shard_array(q, 0), scale_dev),
                    mesh.shard_array(m, 0))
        return (mesh.shard_array(blk.astype(wire_np, copy=False), 0),
                mesh.shard_array(m, 0))

    return put_chunk


def _make_source_pipeline(mesh, points, offsets, chunk, n, d, quantize,
                          scales, scale_dev, wire_np, prefetch):
    """(:class:`IngestPipeline`, exact per-epoch H2D bytes) for a
    sliceable source (ndarray / np.memmap / CSVPoints).

    The staged chain does strictly less host work than the legacy loop:
    masks are j-independent (all-ones for full chunks, ONE tail shape)
    and epoch-independent, so they ship once here and the device arrays
    are reused every chunk — and ``read`` hands the raw slice through
    (np.memmap slices stay lazy views; the single data copy happens
    inside ``shard_array``'s device_put, which reads the mapped pages
    directly, instead of materialize-then-ship).  ``prep`` pads the
    tail, quantizes, or casts to the wire dtype — the CPU-bound stage
    the background threads overlap with transfer + compute when
    ``prefetch >= 2``.  ``prefetch=0`` returns the legacy chain."""
    n_chunks = len(offsets)
    if prefetch == 0:
        legacy = _legacy_put_chunk(mesh, points, chunk, n, d, quantize,
                                   scales, scale_dev, wire_np)
        itemsize = 1 if quantize == "int8" else wire_np.itemsize
        pipe = IngestPipeline(lambda j: legacy(offsets[j]), depth=1,
                              tag="kmeans_stream.legacy", stall_warn=None)
        return pipe, n_chunks * chunk * (d * itemsize + 4)

    tail = n - offsets[-1]
    mask_full = mask_tail = None
    if n_chunks > 1 or tail == chunk:
        mask_full = mesh.shard_array(np.ones(chunk, np.float32), 0)
    if tail < chunk:
        m = np.zeros(chunk, np.float32)
        m[:tail] = 1.0
        mask_tail = mesh.shard_array(m, 0)

    def read(j):
        lo = offsets[j]
        return points[lo:min(lo + chunk, n)]

    def prep(blk):
        rows = blk.shape[0]
        if rows < chunk:
            pad = np.zeros((chunk - rows, d), blk.dtype)
            blk = np.concatenate([np.asarray(blk), pad], 0)
        if quantize == "int8":
            return _clip_round_int8(np.asarray(blk, np.float32),
                                    scales), rows
        # no copy when the source already holds the wire dtype — the
        # widening/narrowing cast (when any) is the only transform
        return np.asarray(blk, wire_np), rows

    def ship(prepped):
        blk, rows = prepped
        m = mask_full if rows == chunk else mask_tail
        data = mesh.shard_array(blk, 0)
        if quantize == "int8":
            return (data, scale_dev), m
        return data, m

    pipe = IngestPipeline(read, prep, ship, depth=max(1, prefetch),
                          tag="kmeans_stream.ingest")
    itemsize = 1 if quantize == "int8" else wire_np.itemsize
    return pipe, n_chunks * chunk * d * itemsize


def _stream_train(mesh, cfg, pipe, n_chunks, centroids, iters, dtype,
                  return_history, ckpt_dir, ckpt_every, max_restarts,
                  fault, instrument, epoch_h2d_bytes=None,
                  epoch_reset=None):
    """The shared blocked-epoch driver behind every ``fit_streaming*``
    variant: prefetch-pipelined chunk loop (:class:`IngestPipeline`,
    PR 8), one allreduce per epoch, checkpoint/resume, optional pipeline
    timing.  ``pipe.stream(n_chunks)`` yields the epoch's device chunk
    inputs in order; ``epoch_reset`` (file-split sources) rewinds the
    readers before each sweep.  Each epoch's chunk loop runs under a
    warn-mode flight budget — exactly ``epoch_h2d_bytes`` on the wire
    and zero recompiles once the first epoch owns the accum compile —
    so a re-upload or a recompile in the loop fails loudly on CPU, not
    on silicon."""
    nw = mesh.num_workers
    k = cfg.k
    d = int(centroids.shape[-1])
    accum_fn = _make_accum_fn(mesh, cfg)
    finish_fn = _make_finish_fn(mesh)
    zeros = lambda: (
        jax.device_put(jnp.zeros((nw, k, d), jnp.float32), mesh.sharding(mesh.spec(0))),
        jax.device_put(jnp.zeros((nw, k), jnp.float32), mesh.sharding(mesh.spec(0))),
        jax.device_put(jnp.zeros((nw,), jnp.float32), mesh.sharding(mesh.spec(0))),
    )
    history: list = []
    epoch_idx = 0

    def train_one():
        nonlocal centroids, epoch_idx
        ep0 = time.perf_counter()
        sums, counts, inertia = zeros()
        if epoch_reset is not None:
            epoch_reset()
        with telemetry.budget(h2d_bytes=epoch_h2d_bytes,
                              compiles=None if epoch_idx == 0 else 0,
                              action="warn", tag="kmeans_stream.ingest"):
            for cur in pipe.stream(n_chunks):
                sums, counts, inertia = accum_fn(cur[0], cur[1], centroids,
                                                 sums, counts, inertia)
        epoch_idx += 1
        new_c, ep_inertia = finish_fn(sums, counts, inertia, centroids)
        centroids = new_c
        history.append(ep_inertia)
        if instrument is not None:  # one deliberate sync/epoch (docstring)
            t = time.perf_counter()
            device_sync(ep_inertia)
            instrument.setdefault("epochs", []).append({
                # blocked_s is the comparable of the old "time in
                # put_chunk": caller time spent inside the ingest path
                "host_s": pipe.stats.blocked_s,
                "sync_s": time.perf_counter() - t,
                "epoch_s": time.perf_counter() - ep0,
                "pipeline": pipe.stats.as_dict(),
            })

    def get_state():
        # LIVE objects, zero syncs: fit_epochs calls this every epoch (not
        # just at checkpoints) and CheckpointManager.save materializes at
        # save time itself; a per-epoch jnp.stack+readback here would cost
        # two device round trips per sweep and break the double buffer
        return {"centroids": centroids, "hist": list(history)}

    def set_state(state):
        nonlocal centroids, history
        check_restored_shapes([("centroids", state["centroids"], centroids)])
        c = state["centroids"]
        if isinstance(c, jax.Array):      # normal step-to-step flow
            centroids = c
            history = list(state["hist"])
        else:                             # numpy from a fresh restore
            centroids = jax.device_put(
                jnp.asarray(np.asarray(c), dtype=dtype), mesh.replicated())
            history = [np.float32(v) for v in state["hist"]]

    from harp_tpu.utils.fault import check_restored_shapes, fit_epochs

    try:
        fit_epochs(train_one, get_state, set_state, iters, ckpt_dir,
                   ckpt_every=ckpt_every, max_restarts=max_restarts,
                   fault=fault, phase="kmeans_stream.iters")
    finally:
        pipe.close()  # reap the stage threads on every exit path
    final = np.asarray(jnp.stack(history))  # ONE readback for all epochs
    c_host = np.asarray(centroids)
    if return_history:
        return c_host, float(final[-1]), final
    return c_host, float(final[-1])


def fit_streaming_local(points_local, k=1000, iters=10,
                        chunk_points=262_144, mesh: WorkerMesh | None = None,
                        seed=0, dtype=jnp.float32, quantize=None,
                        init="random", return_history=False, ckpt_dir=None,
                        ckpt_every=5, max_restarts=3, fault=None,
                        instrument=None, wire_dtype="auto", prefetch=2):
    """Multi-host blocked-epoch Lloyd where EACH PROCESS streams only its
    own split — Harp's HDFS-split ingest (SURVEY.md §4.2 "load points
    shard"): no host ever reads or materializes the whole dataset, so
    the measured ~14 GB/s single-host ingest floor (BASELINE.md) divides
    by the process count.

    ``points_local``: this process's ``[n_local, d]`` slice (ndarray or
    ``np.memmap``; a random-slicing source — the per-epoch access walks
    each local worker's sub-slice, not one ascending scan, so
    ``CSVPoints`` is not supported here).  The global row order is
    process-major (process p's rows precede p+1's), each process's rows
    block-partitioned over its local devices.  Semantics match
    :func:`fit_streaming`: full-batch Lloyd, every point visited once
    per epoch against epoch-start centroids — with an explicit ``init``
    array the two produce the same clustering up to partial-sum rounding
    (tested in tests/multiproc_worker.py).  Single-process it is simply
    ``fit_streaming`` with a different chunk layout.

    ``init``: "random" (each process contributes ⌈k/nproc⌉ seed rows,
    allgathered, first k kept), "kmeans++" (D² seeding on an allgathered
    ≤50k-row subsample, ⌈50k/nproc⌉ per process), or an explicit
    ``[k, d]`` array.  ``quantize="int8"`` works across hosts: each
    process takes the per-feature |max| over ITS split (one chunked
    pass) and the scales are the allgathered elementwise max — identical
    to the single-source scales on the same global data.  Other knobs —
    checkpoint/resume, ``instrument`` — behave as in
    :func:`fit_streaming`.
    """
    mesh = mesh or current_mesh()
    nw = mesh.num_workers
    nproc = jax.process_count()
    if nw % nproc:
        raise ValueError(f"{nw} workers do not divide over {nproc} processes")
    ldev = nw // nproc               # workers (devices) on this process
    n_local, d = points_local.shape
    if n_local == 0:
        raise ValueError("every process must hold at least one row "
                         "(this one has an empty split)")
    cfg = StreamConfig(k=k, chunk_points=chunk_points, dtype=dtype,
                       quantize=quantize)
    np_dtype = np.dtype(jnp.dtype(dtype).name)
    # resolved BEFORE any other collective: "auto" allgathers a dtype
    # code, and collective order must match across processes
    wire_np = _resolve_wire_dtype(wire_dtype, np_dtype,
                                  getattr(points_local, "dtype", None))

    from jax.experimental import multihost_utils as mh

    n_all = np.atleast_1d(np.asarray(
        mh.process_allgather(np.int64(n_local))))          # [nproc]
    npw = -(-n_local // ldev)        # rows per LOCAL worker (this process)
    npw_all = -(-n_all // ldev)      # the same, per process
    # chunk rows per worker: derived from GLOBAL info so every process
    # builds the same static [nw*cl] chunk shape; per-process shortfall
    # is padding (mask 0)
    cl = max(1, min(-(-cfg.chunk_points // nw), int(npw_all.max())))
    # every process loops the global max chunk count (late ones all-pad)
    n_chunks = int((-(-npw_all // cl)).max())
    scale_dev = scales = None
    if quantize == "int8":
        _check_int8_chunk_rows(cl, _INT8_SUM_ROW_LIMIT)
        # global per-feature scales = allgathered max of LOCAL |max|es:
        # same amax pass + scale rule as the single-source _int8_scales
        amax = np.asarray(mh.process_allgather(
            _int8_amax(points_local, n_local, ldev * cl))
        ).reshape(-1, d).max(0)
        scales = _amax_to_scales(amax)
        scale_dev = jax.device_put(jnp.asarray(scales), mesh.replicated())

    def local_seed_rows(count, rng_seed):
        """``count`` rows of this split (equal shape on every process for
        the allgather).  A split shorter than ``count`` is topped up by
        UNIFORM resampling — no positional bias, unlike a cyclic pad."""
        rng = np.random.default_rng(0 if rng_seed is None else rng_seed)
        if n_local >= count:
            idx = (np.arange(count) if rng_seed is None
                   else rng.choice(n_local, size=count, replace=False))
        else:
            idx = np.concatenate([np.arange(n_local),
                                  rng.choice(n_local, count - n_local)])
        return np.asarray(points_local[np.sort(idx)], np.float32)

    if not isinstance(init, str):
        init_c = _init_centroids(points_local, n_local, k, seed, init)
    elif init == "random":
        per = -(-k // nproc)
        if n_local < per:
            # resampled rows would be exact DUPLICATE centroids —
            # permanently-empty clusters that silently degrade the fit
            # (fit_streaming's n < k case raises too); seed explicitly
            raise ValueError(
                f"init='random' needs >= ceil(k/nproc) = {per} rows per "
                f"process split, this one has {n_local}; pass an explicit "
                "[k, d] init array instead")
        mine = local_seed_rows(per, None if seed is None else seed)
        init_c = np.asarray(mh.process_allgather(mine)).reshape(-1, d)[:k]
    elif init == "kmeans++":
        # subsample sized by the GLOBAL row count (matching fit_streaming's
        # min(n, 50k) contract), split evenly across processes
        per = -(-min(50_000, int(n_all.sum())) // nproc)
        sub = np.asarray(mh.process_allgather(
            local_seed_rows(per, 0 if seed is None else seed))).reshape(-1, d)
        init_c = kmeanspp_init(sub, k, seed=0 if seed is None else seed)
    else:
        raise ValueError(f"init must be 'random', 'kmeans++' or a [k, d] "
                         f"array, got {init!r}")
    centroids = jax.device_put(jnp.asarray(init_c, dtype=dtype),
                               mesh.replicated())

    def read(j):
        # stage 1: assemble this process's per-worker raw rows into the
        # one static local chunk shape (the disk/page-cache reads)
        asm_dtype = np.float32 if quantize == "int8" else wire_np
        blk = np.zeros((ldev * cl, d), asm_dtype)
        msk = np.zeros(ldev * cl, np.float32)
        for w in range(ldev):
            w_end = min((w + 1) * npw, n_local)
            lo = w * npw + j * cl
            hi = min(lo + cl, w_end)
            if hi > lo:
                blk[w * cl: w * cl + hi - lo] = np.asarray(
                    points_local[lo:hi]).astype(asm_dtype, copy=False)
                msk[w * cl: w * cl + hi - lo] = 1.0
        return blk, msk

    def prep(t):
        blk, msk = t
        if quantize == "int8":
            return _clip_round_int8(blk, scales), msk
        return blk, msk

    def ship(t):
        blk, msk = t
        data = mesh.shard_array_local(blk, nw * cl)
        if quantize == "int8":
            return (data, scale_dev), mesh.shard_array_local(msk, nw * cl)
        return data, mesh.shard_array_local(msk, nw * cl)

    if iters == 0:
        return (np.asarray(init_c, np.float32), 0.0, np.zeros(0, np.float32)
                ) if return_history else (np.asarray(init_c, np.float32), 0.0)
    pipe = IngestPipeline(read, prep, ship, depth=max(1, prefetch),
                          tag="kmeans_stream.local")
    item = 1 if quantize == "int8" else wire_np.itemsize
    h2d_epoch = n_chunks * ldev * cl * (d * item + 4)  # this process
    return _stream_train(mesh, cfg, pipe, n_chunks, centroids, iters,
                         dtype, return_history, ckpt_dir, ckpt_every,
                         max_restarts, fault, instrument,
                         epoch_h2d_bytes=h2d_epoch)


def fit_streaming_files(paths, k=1000, iters=10, chunk_points=262_144,
                        mesh: WorkerMesh | None = None, seed=0,
                        dtype=jnp.float32, quantize=None, init="random",
                        return_history=False, ckpt_dir=None, ckpt_every=5,
                        max_restarts=3, fault=None, instrument=None,
                        reader_chunk_rows=65_536, info=None,
                        wire_dtype="auto", prefetch=2):
    """Blocked-epoch Lloyd over a DIRECTORY of file splits — Harp's real
    input shape (SURVEY.md §4.2): files are dealt to workers by the
    size-balanced ``multi_file_splits`` rule and each worker streams
    ONLY its own files (npy memmap or text via the native
    double-buffered parser), so in a multi-host job every file is read
    by exactly one process and the host ingest floor divides by the
    host count, file-granular like HDFS splits.

    ``paths``: resolved file list (use ``harp_tpu.fileformat.list_files``
    for a glob/dir; the list is sorted here for a deterministic
    assignment).  ``info``: pass a dict to receive ``n_total`` / ``d``
    (the CLI reports them; no other way to learn the global row count
    without a second counting pass).  ``quantize="int8"`` streams int8
    chunks with the shared scale rule — each process's
    ``FileSplits.amax`` pass (one extra streaming sweep of its files)
    feeds the allgathered global max.  Semantics are full-batch Lloyd, identical to
    :func:`fit_streaming` on the same rows (the row ORDER differs —
    worker-major over file assignments — which Lloyd does not see:
    epochs are order-independent given the same init; tested).  Workers
    may own zero files (more workers than files: their chunks are all
    padding); a whole PROCESS with zero rows works with an explicit
    ``init`` array (string seeding has nothing to sample there and
    raises).  ``init`` as in :func:`fit_streaming_local`, seeded by
    ``FileSplits.sample`` — random rows across this process's files.
    """
    from harp_tpu.native.datasource import FileSplits

    mesh = mesh or current_mesh()
    nw = mesh.num_workers
    nproc = jax.process_count()
    if nw % nproc:
        raise ValueError(f"{nw} workers do not divide over {nproc} processes")
    ldev = nw // nproc
    pid = jax.process_index()
    local_workers = range(pid * ldev, (pid + 1) * ldev)
    fs = FileSplits(sorted(paths), nw, local_workers,
                    chunk_rows=reader_chunk_rows)
    try:
        return _fit_streaming_files(fs, paths, k, iters, chunk_points,
                                    mesh, nproc, ldev, pid, local_workers,
                                    seed, dtype, quantize, init,
                                    return_history, ckpt_dir, ckpt_every,
                                    max_restarts, fault, instrument, info,
                                    wire_dtype, prefetch)
    finally:
        fs.close()  # also on iters==0 and validation raises: no fd leaks


def _fit_streaming_files(fs, paths, k, iters, chunk_points, mesh, nproc,
                         ldev, pid, local_workers, seed, dtype, quantize,
                         init, return_history, ckpt_dir, ckpt_every,
                         max_restarts, fault, instrument, info=None,
                         wire_dtype="auto", prefetch=2):
    nw = mesh.num_workers
    cfg = StreamConfig(k=k, chunk_points=chunk_points, dtype=dtype,
                       quantize=quantize)
    np_dtype = np.dtype(jnp.dtype(dtype).name)
    # before the other allgathers: collective order must match per-process
    wire_np = _resolve_wire_dtype(wire_dtype, np_dtype, fs.dtype)

    from jax.experimental import multihost_utils as mh

    n_per_worker = np.zeros(nw, np.int64)
    for w in local_workers:
        n_per_worker[w] = fs.rows(w)
    n_per_worker = np.asarray(
        mh.process_allgather(n_per_worker)).reshape(-1, nw).max(0)
    n_total = int(n_per_worker.sum())
    if n_total == 0:
        raise ValueError(f"{len(paths)} input files contain no rows")
    # feature dim must agree ACROSS processes too (each FileSplits only
    # sees its own files); a process with no files adopts the global d
    d_all = np.atleast_1d(np.asarray(
        mh.process_allgather(np.int64(fs.cols))))
    d = int(d_all.max())
    if np.any((d_all != 0) & (d_all != d)):
        raise ValueError(
            f"input files disagree on column count across processes "
            f"({sorted(set(int(v) for v in d_all if v))}) — a ragged mix "
            "would silently misalign features")
    rows_per_proc = n_per_worker.reshape(nproc, ldev).sum(1)
    cl = max(1, min(-(-cfg.chunk_points // nw), int(n_per_worker.max())))
    n_chunks = int((-(-n_per_worker // cl)).max())
    if info is not None:
        info.update({"n_total": n_total, "d": d})
    scale_dev = scales = None
    if quantize == "int8":
        _check_int8_chunk_rows(cl, _INT8_SUM_ROW_LIMIT)
        local_amax = fs.amax()
        if local_amax.shape[0] != d:   # a no-file process: contribute 0s
            local_amax = np.zeros(d, np.float32)
        amax = np.asarray(mh.process_allgather(local_amax)
                          ).reshape(-1, d).max(0)
        scales = _amax_to_scales(amax)
        scale_dev = jax.device_put(jnp.asarray(scales), mesh.replicated())

    if not isinstance(init, str):
        init_c = _validate_explicit_init(init, k, d)
    elif init in ("random", "kmeans++"):
        if (rows_per_proc == 0).any():
            raise ValueError(
                f"process(es) {np.flatnonzero(rows_per_proc == 0).tolist()}"
                " own no rows under the file assignment — string seeding "
                "has nothing to sample there; pass an explicit [k, d] "
                "init array (or use fewer workers)")
        per = -(-(k if init == "random" else min(50_000, n_total)) // nproc)
        if init == "random" and (rows_per_proc < per).any():
            # SYMMETRIC check (rows_per_proc is globally replicated): a
            # one-sided raise would leave the other processes hanging in
            # the allgather below
            short = np.flatnonzero(rows_per_proc < per).tolist()
            raise ValueError(
                f"init='random' needs >= ceil(k/nproc) = {per} rows per "
                f"process; process(es) {short} hold fewer — pass an "
                "explicit [k, d] init array instead")
        rng = np.random.default_rng((0 if seed is None else seed, pid))
        mine = _topup_rows(fs.sample(per, rng=rng), per, rng)
        gathered = np.asarray(mh.process_allgather(mine)).reshape(-1, d)
        init_c = (gathered[:k] if init == "random" else
                  kmeanspp_init(gathered, k, seed=0 if seed is None else seed))
    else:
        raise ValueError(f"init must be 'random', 'kmeans++' or a [k, d] "
                         f"array, got {init!r}")
    centroids = jax.device_put(jnp.asarray(init_c, dtype=dtype),
                               mesh.replicated())

    def read(j):
        # stateful sequential source: the pipeline's read stage runs on
        # ONE thread in submission order (IngestPipeline default), so
        # the per-worker file cursors advance exactly as the serial
        # loop's did; fs.reset() runs as _stream_train's epoch_reset
        # before each sweep's stream starts
        asm_dtype = np.float32 if quantize == "int8" else wire_np
        blk = np.zeros((ldev * cl, d), asm_dtype)
        msk = np.zeros(ldev * cl, np.float32)
        for li, w in enumerate(local_workers):
            rows = fs.next_block(w, cl)
            t = rows.shape[0]
            if t:
                blk[li * cl: li * cl + t] = rows.astype(asm_dtype,
                                                        copy=False)
                msk[li * cl: li * cl + t] = 1.0
        return blk, msk

    def prep(t):
        blk, msk = t
        if quantize == "int8":
            return _clip_round_int8(blk, scales), msk
        return blk, msk

    def ship(t):
        blk, msk = t
        data = mesh.shard_array_local(blk, nw * cl)
        if quantize == "int8":
            return (data, scale_dev), mesh.shard_array_local(msk, nw * cl)
        return data, mesh.shard_array_local(msk, nw * cl)

    if iters == 0:
        return (np.asarray(init_c, np.float32), 0.0, np.zeros(0, np.float32)
                ) if return_history else (np.asarray(init_c, np.float32), 0.0)
    pipe = IngestPipeline(read, prep, ship, depth=max(1, prefetch),
                          tag="kmeans_stream.files")
    item = 1 if quantize == "int8" else wire_np.itemsize
    h2d_epoch = n_chunks * ldev * cl * (d * item + 4)  # this process
    return _stream_train(mesh, cfg, pipe, n_chunks, centroids, iters,
                         dtype, return_history, ckpt_dir, ckpt_every,
                         max_restarts, fault, instrument,
                         epoch_h2d_bytes=h2d_epoch, epoch_reset=fs.reset)


def _make_chunk_gen(key, rows: int, d: int, dtype):
    """THE chunk generator — shared by the real synthetic program and its
    gen-only calibration twin so the two can never time different RNG
    schemes.  ``key`` is the worker's (pre-split) key; chunk j is a
    deterministic function of (worker, j), identical across epochs."""

    def gen(j):
        return jax.random.normal(jax.random.fold_in(key[0], j), (rows, d),
                                 dtype)

    return gen


def make_synthetic_run_fn(mesh: WorkerMesh, cfg: StreamConfig, d: int,
                          n_chunks: int):
    """The fully-fused formulation: fori_loop(epochs) × scan(chunks), all
    on device.  The ``key`` argument is pre-split per worker (sharded over
    the mesh); chunk j's points come from ``fold_in(worker_key, j)`` — a
    deterministic function of (worker, j) alone, so every epoch sees the
    same dataset (regeneration ≡ re-reading a file split).
    This is what makes the 1B-point config runnable on ONE chip: live HBM
    is one [chunk/nw, d] block + [chunk/nw, k] scores + the [k, d] state,
    never the dataset."""
    rows = cfg.chunk_points // mesh.num_workers

    # device-side int8 twin: the synthetic stream is N(0,1) per feature,
    # so a STATIC 5σ amax covers all but ~3e-7 of draws (clipped) — no
    # calibration pass, same _amax_to_scales rule as the ingest path
    col_scale = (jax.device_put(_amax_to_scales(np.full(d, 5.0, np.float32)))
                 if cfg.quantize == "int8" else None)
    if cfg.quantize == "int8":
        # same exact-int32 accumulation guard as every host int8 path
        _check_int8_chunk_rows(rows, _INT8_SUM_ROW_LIMIT)

    def run(key, centroids, n_iters):
        gen = _make_chunk_gen(key, rows, d, cfg.dtype)

        def epoch(i, st):
            c, _ = st
            c2 = (c.astype(jnp.float32) ** 2).sum(-1)

            def chunk_body(acc, j):
                if cfg.quantize == "int8":
                    q = _clip_round_int8(gen(j), col_scale[None, :], xp=jnp)
                    s, cnt, it = _partials_block_int8(q, col_scale, c, c2)
                else:
                    s, cnt, it = _partials_block(gen(j), c, c2)
                return (acc[0] + s, acc[1] + cnt, acc[2] + it), None

            acc0 = (jnp.zeros((cfg.k, d), jnp.float32),
                    jnp.zeros((cfg.k,), jnp.float32), jnp.float32(0.0))
            (sums, counts, inertia), _ = lax.scan(
                chunk_body, acc0, jnp.arange(n_chunks))
            sums, counts, inertia = C.allreduce((sums, counts, inertia))
            return _normalize_centroids(sums, counts, c), inertia

        return lax.fori_loop(0, n_iters, epoch, (centroids, jnp.float32(0.0)))

    return jax.jit(mesh.shard_map(
        run, in_specs=(mesh.spec(0), P(), P()), out_specs=(P(), P())))


def make_gen_only_fn(mesh: WorkerMesh, cfg: StreamConfig, d: int,
                     n_chunks: int):
    """Calibration twin of :func:`make_synthetic_run_fn`: the same
    fori_loop × scan × PRNG generation, but the per-chunk work is a
    trivial running sum instead of the Lloyd partials — timing it
    isolates the data-regeneration overhead that a real ingest pipeline
    would not pay (its data arrives from disk/HBM, not a PRNG)."""
    rows = cfg.chunk_points // mesh.num_workers

    def run(key, n_iters):
        gen = _make_chunk_gen(key, rows, d, cfg.dtype)

        def epoch(i, acc):
            def chunk_body(a, j):
                # touch every generated value so XLA can't elide the RNG
                return a + gen(j).astype(jnp.float32).sum(), None

            acc, _ = lax.scan(chunk_body, acc, jnp.arange(n_chunks))
            return acc

        return C.allreduce(lax.fori_loop(0, n_iters, epoch,
                                         jnp.float32(0.0)))

    return jax.jit(mesh.shard_map(
        run, in_specs=(mesh.spec(0), P()), out_specs=P()))


def benchmark_streaming(n=100_000_000, d=300, k=1000, iters=3,
                        chunk_points=262_144, mesh=None, seed=0,
                        dtype=jnp.float32, warmup=1, calibrate_gen=False,
                        quantize=None):
    """iter/s of the blocked-epoch formulation at north-star scale.

    The dataset is device-regenerated (see :func:`make_synthetic_run_fn`)
    so ``n`` is bounded by FLOPs, not HBM or host RAM: n=1_000_000_000
    with k=1000 runs in ~1.4 GB of live HBM per chip.  Warmup reuses the
    SAME compiled program (n_iters is a traced scalar) per the
    recompile-in-the-timed-region trap.

    ``calibrate_gen`` (opt-in: a second full-scale compile + timed run):
    also time a generation-only twin of the program and report
    ``gen_sec_per_iter`` + ``iters_per_sec_ex_gen`` — the RNG
    regeneration is measurement scaffolding a real ingest pipeline would
    not pay.  The raw rate stays the headline; the ex-gen rate is an
    UPPER estimate of the compute rate (in the fused real program the
    RNG partially overlaps the Lloyd matmuls, so standalone gen time can
    over-subtract), and when the calibration is not credible (gen time
    ≥ 90% of the total — overlap/timing noise) ``iters_per_sec_ex_gen``
    is reported as None rather than an inflated number.
    """
    mesh = mesh or current_mesh()
    nw = mesh.num_workers
    # chunk never exceeds n: a small-n request must not silently measure a
    # 262144-point epoch (the dict reports the points actually processed)
    cfg = StreamConfig(k=k,
                       chunk_points=-(-min(chunk_points, n) // nw) * nw,
                       dtype=dtype, quantize=quantize)
    n_chunks = max(1, n // cfg.chunk_points)
    n_eff = n_chunks * cfg.chunk_points  # actual points per epoch
    run_fn = make_synthetic_run_fn(mesh, cfg, d, n_chunks)

    keys = jax.device_put(
        jax.random.split(jnp.asarray(prng.key_bits(seed)), nw),
        mesh.sharding(mesh.spec(0)))
    centroids = jax.device_put(
        jax.random.normal(jnp.asarray(prng.key_bits(seed + 1)), (k, d),
                          dtype=dtype),
        mesh.replicated())
    _, w_in = run_fn(keys, centroids, jnp.int32(max(warmup, 1)))
    device_sync(w_in)
    t0 = time.perf_counter()
    c_new, inertia = run_fn(keys, centroids, jnp.int32(iters))
    inertia_val = device_sync(inertia)
    dt = time.perf_counter() - t0
    out = {
        "iters_per_sec": iters / dt,
        "points_per_sec": n_eff * iters / dt,
        "sec_per_iter": dt / iters,
        "inertia": inertia_val,
        "n": n_eff, "d": d, "k": k, "chunk_points": cfg.chunk_points,
        "n_chunks": n_chunks, "num_workers": nw,
        "dtype": str(jnp.dtype(dtype).name), "quantize": quantize,
    }
    if calibrate_gen:
        gen_fn = make_gen_only_fn(mesh, cfg, d, n_chunks)
        device_sync(gen_fn(keys, jnp.int32(max(warmup, 1))))
        t0 = time.perf_counter()
        device_sync(gen_fn(keys, jnp.int32(iters)))
        gen_dt = time.perf_counter() - t0
        out.update(_ex_gen_fields(dt, gen_dt, iters))
    return out


def _ex_gen_fields(dt: float, gen_dt: float, iters: int) -> dict:
    """Calibration post-processing, factored for direct testing: a gen
    time that eats (nearly) the whole run means the subtraction is noise
    or overlap, and an "ex-gen" rate computed from it would be absurd —
    report None instead of a number that could land in BASELINE.md."""
    fields = {"gen_sec_per_iter": gen_dt / iters}
    if gen_dt >= 0.9 * dt:
        fields["iters_per_sec_ex_gen"] = None
        fields["gen_calibration"] = ("invalid: gen time >= 90% of total "
                                     "(RNG overlaps compute, or timing noise)")
    else:
        fields["iters_per_sec_ex_gen"] = iters / (dt - gen_dt)
    return fields


def benchmark_ingest(points, k=1000, iters=2, chunk_points=262_144,
                     mesh=None, dtype=jnp.float32, quantize=None, seed=0,
                     disk_bytes=None, compare_synthetic=False,
                     wire_dtype="auto", prefetch=2):
    """End-to-end rate of :func:`fit_streaming` on a REAL disk source —
    the honest half of the 1B-point story (SURVEY.md §1 north-star, §4.2
    "load points shard" phase).  :func:`benchmark_streaming` measures the
    compute *formulation* with device-regenerated data; this measures the
    ingest-bound *reality*: disk read + host parse/pad + H2D transfer,
    with device compute double-buffered behind it.

    ``points`` is any ``fit_streaming`` source (``np.memmap``,
    ``CSVPoints``, ndarray).  ``disk_bytes``: actual on-disk bytes per
    epoch (file size) — defaults to ``n*d*itemsize`` when the source
    exposes a dtype, else the f32 logical size; float16/int8 sources and
    text files should pass the real file size so GB/s is honest.

    Reported fields:

    - ``points_per_sec`` — end-to-end, total points × epochs / wall
      (includes centroid init and compile; the per-epoch fields exclude
      them).
    - ``host_sec_per_epoch`` / ``host_gb_per_sec`` — time blocked in the
      host half (read+parse+pad+dispatch) and the disk-byte rate over it.
      This is the pipeline's hard floor: device speed cannot fix it.
    - ``sync_sec_per_epoch`` — device tail NOT hidden behind host work
      (blocking wait after the last chunk).
    - ``overlap_efficiency`` — the HOST PIPELINE's stage-overlap score
      (:class:`harp_tpu.ingest.IngestStats`, PR 8) ∈ [0, 1]:
      consumer_s / (consumer_s + wait_s) — of the dispatch loop's time,
      the fraction spent computing rather than waiting on the pipeline;
      1.0 also when nothing needed hiding (an idle consumer or a serial
      run — no stalls is a clean score).
    - ``device_hidden_fraction`` — the pre-PR-8 "overlap_efficiency":
      host_s / (host_s + sync_s) ∈ (0, 1] — 1.0 means device compute is
      fully hidden behind ingest (purely ingest-bound); lower means the
      device is the straggler.  Renamed because the pipeline makes the
      host side fast, which legitimately LOWERS this ratio.
    - ``ingest_bound_fraction`` — host_s / epoch_s: the share of epoch
      wall spent in the host half (the remainder is dispatch overhead +
      the unhidden device tail).
    - with ``compare_synthetic=True``: ``synthetic_sec_per_epoch`` — the
      device-regenerated formulation at the SAME shapes/chunking (a
      second compile + timed run); ``epoch_s`` ≈ max(host, synthetic)
      when the double buffer overlaps perfectly.
    """
    mesh = mesh or current_mesh()
    n, d = points.shape
    np_dtype = np.dtype(jnp.dtype(dtype).name)
    wire_np = _resolve_wire_dtype(wire_dtype, np_dtype,
                                  getattr(points, "dtype", None))
    inst: dict = {}
    t0 = time.perf_counter()
    _, inertia = fit_streaming(points, k=k, iters=iters,
                               chunk_points=chunk_points, mesh=mesh,
                               seed=seed, dtype=dtype, quantize=quantize,
                               instrument=inst, wire_dtype=wire_dtype,
                               prefetch=prefetch)
    wall = time.perf_counter() - t0
    eps = inst["epochs"]
    host = sum(e["host_s"] for e in eps) / len(eps)
    sync = sum(e["sync_s"] for e in eps) / len(eps)
    epoch = sum(e["epoch_s"] for e in eps) / len(eps)
    if disk_bytes is None:
        itemsize = getattr(getattr(points, "dtype", None), "itemsize", 4)
        disk_bytes = n * d * itemsize
    out = {
        "points_per_sec": n * iters / wall,
        "epoch_sec": epoch,
        "host_sec_per_epoch": host,
        "host_gb_per_sec": disk_bytes / 1e9 / host if host else None,
        "sync_sec_per_epoch": sync,
        "overlap_efficiency": (eps[-1]["pipeline"]["overlap_efficiency"]
                               if eps[-1].get("pipeline") else None),
        "device_hidden_fraction": (host / (host + sync)
                                   if host + sync else None),
        "ingest_bound_fraction": host / epoch if epoch else None,
        "disk_gb_per_epoch": disk_bytes / 1e9,
        "inertia": float(inertia),
        "n": n, "d": d, "k": k, "iters": iters,
        "chunk_points": chunk_points, "quantize": quantize,
        # the H2D payload format + bytes actually crossing the link per
        # epoch ("int8" when quantized): the wire, not the disk, is the
        # link-bound half of the pipeline
        "wire_dtype": "int8" if quantize == "int8" else wire_np.name,
        "wire_gb_per_epoch": n * d * (1 if quantize == "int8"
                                      else wire_np.itemsize) / 1e9,
        "num_workers": mesh.num_workers,
        "source": type(points).__name__,
        # PR 8: rows are typed ingest evidence (check_jsonl invariant 8)
        # and carry the host-pipeline account (harp_tpu.ingest): depth 0
        # is the pre-pipeline serial chain, >=2 the prefetch pipeline
        "kind": "ingest",
        "prefetch_depth": prefetch,
        "pipeline": eps[-1].get("pipeline"),
    }
    if compare_synthetic:
        syn = benchmark_streaming(n=n, d=d, k=k, iters=iters,
                                  chunk_points=chunk_points, mesh=mesh,
                                  dtype=dtype, seed=seed)
        out["synthetic_sec_per_epoch"] = syn["sec_per_iter"]
        out["synthetic_points_per_sec"] = syn["points_per_sec"]
    return out


def main(argv=None):
    import argparse

    p = argparse.ArgumentParser(
        description="harp-tpu streaming KMeans (north-star 1B-point path)")
    p.add_argument("--n", type=int, default=100_000_000)
    p.add_argument("--d", type=int, default=300)
    p.add_argument("--k", type=int, default=1000)
    p.add_argument("--iters", type=int, default=3)
    p.add_argument("--chunk", type=int, default=262_144)
    p.add_argument("--dtype", default="float32", choices=["float32", "bfloat16"])
    p.add_argument("--input", default=None, metavar="NPY_PARQUET_CSV_OR_GLOB",
                   help="stream a .npy file (np.memmap), a CSV/text file "
                        "(native prefetch-threaded reader, bounded "
                        "memory), or a glob/directory of split files — "
                        "dealt to workers size-balanced, each streaming "
                        "only its own (the HDFS-split input shape) — "
                        "instead of the device-synthetic benchmark")
    p.add_argument("--quantize", choices=["int8"], default=None)
    p.add_argument("--wire-dtype", default="auto",
                   choices=["auto", "none", "float16", "bfloat16",
                            "float32"],
                   help="H2D payload format for --input streaming: auto "
                        "ships narrow-float sources as-is (f16 disk → "
                        "half the transfer bytes, bit-identical); "
                        "none = legacy ship-compute-dtype; an explicit "
                        "dtype forces the wire (narrower than the "
                        "source is lossy, opt-in)")
    p.add_argument("--init", choices=["random", "kmeans++"], default="random")
    p.add_argument("--prefetch", type=int, default=2,
                   help="ingest pipeline work-ahead depth for --input "
                        "streaming (harp_tpu.ingest): >=2 overlaps "
                        "read/quantize/ship, 1 = staged serial, 0 = the "
                        "pre-pipeline legacy loop (A/B incumbent)")
    p.add_argument("--ckpt-dir", default=None,
                   help="checkpoint/resume for long runs (rerunning with "
                        "the same dir resumes from the latest epoch)")
    p.add_argument("--ckpt-every", type=int, default=5)
    p.add_argument("--elastic", action="store_true",
                   help="elastic Lloyd (PR 15): consume mid-run "
                        "skew_trigger findings between sweeps (rebalance "
                        "point packs; masked pads keep the math exact) "
                        "and checkpoint mesh-independent centroids")
    p.add_argument("--max-worker-loss", type=int, default=0,
                   help="elastic: survive up to N permanent worker "
                        "losses by shrinking to the survivors and "
                        "replaying the repartition plan from the last "
                        "checkpoint (implies --elastic; needs --ckpt-dir "
                        "to actually resume)")
    args = p.parse_args(argv)
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    wire = {"auto": "auto", "none": None}.get(args.wire_dtype,
                                              args.wire_dtype)

    if args.elastic or args.max_worker_loss:
        # elastic mode materializes the corpus (the repartition relabels
        # rows), so it pairs with host-sized --n, not the 1B-point path
        from harp_tpu.elastic.apps import kmeans_stream_elastic_fit
        from harp_tpu.utils.metrics import benchmark_json

        if args.input:
            raise SystemExit(
                "--elastic currently pairs with the synthetic corpus; "
                "use --n/--d (file inputs ride the non-elastic "
                "streaming fit)")
        rng = np.random.default_rng(0)
        pts = rng.normal(size=(args.n, args.d)).astype(np.float32)
        ad = kmeans_stream_elastic_fit(
            pts, k=args.k, iters=args.iters, ckpt_dir=args.ckpt_dir,
            ckpt_every=args.ckpt_every,
            max_worker_loss=max(args.max_worker_loss, 0))
        print(benchmark_json("kmeans_stream_elastic_cli", {
            "k": args.k, "iters": args.iters, "n": args.n, "d": args.d,
            "inertia": ad.metric(), "n_workers": ad.mesh.num_workers,
            "worker_losses": ad.losses, "ckpt_dir": args.ckpt_dir}))
        return

    if args.input:
        from harp_tpu.fileformat import list_files

        # a literal path wins over glob expansion: 'data[v2].npy' is a
        # real file, not a character class
        paths = ([args.input] if os.path.isfile(args.input)
                 else list_files(args.input))
        if not paths:
            raise SystemExit(f"{args.input}: no input files matched")
        if len(paths) > 1:  # split directory: per-worker file streams
            split_info: dict = {}
            c, inertia = fit_streaming_files(
                paths, args.k, args.iters, args.chunk, dtype=dtype,
                quantize=args.quantize, init=args.init,
                ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                info=split_info, wire_dtype=wire, prefetch=args.prefetch)
            n_rows, d_cols = split_info["n_total"], split_info["d"]
        else:
            if paths[0].endswith(".npy"):
                pts = np.load(paths[0], mmap_mode="r")
            elif paths[0].endswith((".parquet", ".pq")):
                from harp_tpu.native.datasource import ParquetPoints

                pts = ParquetPoints(paths[0], chunk_rows=args.chunk)
            else:  # text: native streaming reader, never materialized
                from harp_tpu.native.datasource import CSVPoints

                pts = CSVPoints(paths[0], chunk_rows=args.chunk)
            c, inertia = fit_streaming(pts, args.k, args.iters, args.chunk,
                                       dtype=dtype, quantize=args.quantize,
                                       init=args.init,
                                       ckpt_dir=args.ckpt_dir,
                                       ckpt_every=args.ckpt_every,
                                       wire_dtype=wire,
                                       prefetch=args.prefetch)
            n_rows, d_cols = int(pts.shape[0]), int(pts.shape[1])
        # JSON, not dict repr: the line is teed into a .jsonl
        from harp_tpu.utils.metrics import benchmark_json

        print(benchmark_json("kmeans_stream_fit_cli",
                             {"k": args.k, "iters": args.iters,
                              "n": n_rows, "d": d_cols,
                              "files": len(paths),
                              "inertia": float(inertia)}))
    else:
        from harp_tpu.utils.metrics import benchmark_json

        print(benchmark_json("kmeans_stream_cli", benchmark_streaming(
            args.n, args.d, args.k, args.iters, args.chunk, dtype=dtype,
            quantize=args.quantize)))


if __name__ == "__main__":
    main()
