"""KMeans — graded config #1: k=100 on 1M×300 dense (allreduce pattern).

Reference parity (SURVEY.md §3.4, §4.2): Harp's ``edu.iu.kmeans.*`` (variants
``regroupallgather``, ``allreduce``) and ``edu.iu.daal_kmeans``.  Each Harp
iteration: workers assign their point shard to nearest centroids (DAAL/MKL
compute), produce partial centroid sums+counts, then ``regroup`` + ``allgather``
(or ``allreduce``) merges partials so every worker starts the next iteration
with the new centroids.

TPU-native design: the whole iteration is ONE jitted SPMD program —
``argmin(dists) → unsorted_segment_sum → psum`` — with centroids replicated
in HBM and all T iterations inside a ``fori_loop``; zero host round-trips in
the hot loop (the reference crosses JNI + sockets every iteration).  The
distance matrix is computed as ``x@cᵀ`` so the FLOPs land on the MXU; only
the cross-term depends on both x and c (||x||² is assignment-invariant and
dropped from the argmin).
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from harp_tpu.ops.pallas_compat import interpret_default
from harp_tpu.parallel import collective as C
from harp_tpu.parallel.mesh import WorkerMesh, current_mesh
from harp_tpu.utils import flightrec, prng, skew, steptrace, telemetry
from harp_tpu.utils.timing import device_sync


@dataclasses.dataclass
class KMeansConfig:
    """Harp knob parity: numMapTasks→mesh size, pointsPerFile→shard size."""

    k: int = 100
    iters: int = 10
    dtype: Any = jnp.float32  # bf16 points keep f32 accumulation (MXU-friendly)
    block_points: int = 0  # >0: process points in blocks to bound the [n,k] dist matrix
    # Harp's two app variants (edu.iu.kmeans.allreduce / .regroupallgather):
    # "allreduce" = one psum; "regroupallgather" = reduce-scatter the
    # partials so each worker owns and normalizes a centroid block, then
    # allgather the new centroids — Harp's headline variant, kept for
    # parity/explicitness.  Identical results AND identical wire traffic:
    # XLA's ring psum already lowers to reduce-scatter+allgather, so this
    # is not a performance knob.
    variant: str = "allreduce"
    # Single-pass Pallas kernel.  None = auto per path, exactly the
    # measured verdicts (FLIP_DECISIONS.jsonl): ON for quantize="int8"
    # — FLIPPED 2026-08-01, 555.1 iter/s vs 486.9 XLA int8 = 1.14× at
    # equal inertia on the graded 1M×300 k=100 shape (the VMEM-budget
    # tile chooser unlocked it: 8000-row tiles vs the old 2000 cap,
    # see ops/kmeans_kernel._tile_rows_int8) — and OFF for f32, where
    # the XLA path measured equal-or-faster (kernel 2.83 ms vs XLA
    # ~2.5 ms, ops/kmeans_kernel.py).  Resolved at READ time
    # (:func:`_use_pallas`) so dataclasses.replace keeps auto tracking.
    use_pallas: bool | None = None
    # opt-in int8 point quantization: per-feature symmetric scales, distances
    # and partial sums as int8 MXU matmuls with exact int32 accumulation —
    # quarter the per-iteration HBM traffic of f32 points.  Accuracy
    # contract (measured on CPU sim, 2026-07-30): near-equidistant
    # assignments may flip within the ~1/127 relative distance resolution;
    # from a non-degenerate init the result matches f32 to 5 digits of
    # inertia, but a degenerate random init (duplicate-cluster seeds) can
    # select a different Lloyd basin — the same sensitivity any metric
    # perturbation has.  TPU wall-clock: BASELINE.md (kmeans_int8 rows).
    quantize: str | None = None
    # PR 11 (collective planner): the per-iteration partials allreduce's
    # schedule.  "one_shot" (default — today's single fused psum, bit-
    # identical to every committed row) or "hier" (the planner's
    # hierarchical two-stage psum, collective.allreduce_hier: the
    # payload crosses the inter-host link class once per host group
    # instead of once per worker — a win only on multi-host meshes, and
    # ~2x the bytes on a flat ring, which is why it FAILS CLOSED as flip
    # candidate `kmeans_hier_psum` until chip-measured; float partials
    # reassociate across the two stages, gated on inertia like the int8
    # candidates).  Ignored by variant="regroupallgather" (that schedule
    # already two-phases through push+pull).
    psum_schedule: str = "one_shot"

    def __post_init__(self):
        if self.k < 1:
            raise ValueError(f"k must be >= 1, got {self.k}")
        if self.quantize not in (None, "int8"):
            raise ValueError(f"quantize must be None or 'int8', got {self.quantize!r}")
        if self.quantize and self.block_points:
            raise ValueError("quantize='int8' is incompatible with "
                             "block_points (the int8 paths are single-"
                             "block; use_pallas selects the fused kernel)")
        if self.variant not in ("allreduce", "regroupallgather"):
            raise ValueError(
                f"variant must be 'allreduce' or 'regroupallgather', "
                f"got {self.variant!r}")
        if self.psum_schedule not in ("one_shot", "hier"):
            raise ValueError(
                f"psum_schedule must be 'one_shot' or 'hier', "
                f"got {self.psum_schedule!r}")


def _partials_block(points, centroids, c2, mask=None):
    """Per-block partials: (sums [k,d], counts [k], inertia scalar).

    Everything routes through the MXU: the score matrix comes from
    ``x @ cᵀ`` and the per-cluster sums from ``one_hotᵀ @ x`` — no scatter,
    no gather (both are pathological on TPU; measured 180 ms/iter vs
    5.7 ms/iter fused on the 1M×300 k=100 config, 2026-07-29, 1× v5e).
    ||x||² is dropped from
    the argmin (assignment-invariant) and re-added only to the inertia.

    ``mask`` (optional [b], 0/1): rows with mask 0 contribute nothing —
    the streaming path pads its tail chunk to a fixed shape with these.
    """
    with jax.named_scope("kmeans.assign"):
        dots = jax.lax.dot_general(
            points, centroids.T, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [b, k]
        scores = c2[None, :] - 2.0 * dots
        assign = jnp.argmin(scores, axis=1)
    with jax.named_scope("kmeans.sums"):
        onehot = jax.nn.one_hot(assign, c2.shape[0], dtype=points.dtype)
    if mask is None:
        # Σx²: XLA hoists it out of the Lloyd loop, fused with the bf16
        # copy of the points that the two dots read
        with jax.named_scope("kmeans.cast"):
            x2 = (points.astype(jnp.float32) ** 2).sum()
        with jax.named_scope("kmeans.assign"):
            inertia = x2 + scores.min(axis=1).sum()
    else:
        w = mask.astype(jnp.float32)
        with jax.named_scope("kmeans.cast"):
            x2 = ((points.astype(jnp.float32) ** 2).sum(1) * w).sum()
        with jax.named_scope("kmeans.assign"):
            inertia = x2 + (scores.min(axis=1) * w).sum()
        with jax.named_scope("kmeans.sums"):
            onehot = onehot * mask.astype(onehot.dtype)[:, None]
    with jax.named_scope("kmeans.sums"):
        sums = jax.lax.dot_general(
            onehot, points, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [k, d]
        counts = onehot.sum(0).astype(jnp.float32)
    return sums, counts, inertia


# one worker-local cluster may sum at most 2^31/127 int8 contributions
# before the exact int32 accumulator could wrap
_INT8_SUM_ROW_LIMIT = (1 << 31) // 127


def _clip_round_int8(values, scale, xp=np):
    """THE int8 rounding rule — every quantized-points path (device
    resident, streaming, sharded-ingest, file-split, and the traced
    synthetic twin via ``xp=jnp``) shares this one expression so the
    variants can never disagree on it."""
    return xp.clip(xp.round(values / scale), -127, 127).astype(xp.int8)


def _check_int8_chunk_rows(rows_per_worker, limit):
    """The shared exact-int32 accumulation guard for streamed chunks.
    ``limit`` is REQUIRED: callers resolve their module's
    _INT8_SUM_ROW_LIMIT at call time (tests shrink it to exercise the
    guard) — a default here would silently bypass that."""
    if rows_per_worker > limit:
        raise ValueError(
            f"quantize='int8': {rows_per_worker} chunk rows/worker "
            f"exceeds the {limit} exact-int32 accumulation "
            "bound — use a smaller chunk_points")


def quantize_points_int8(points):
    """Per-feature symmetric int8 quantization: (q int8 [n, d], scale [d]).

    ``points ≈ q * scale[None, :]`` with per-entry error ≤ scale/2.
    Pure numpy (same formula as :func:`collective.quantize_to_int8`): the
    graded-scale matrix must not detour through one device — sharding
    happens after, in ``fit``."""
    points = np.asarray(points, np.float32)
    scale = np.maximum(np.abs(points).max(0), 1e-30) / 127.0
    return _clip_round_int8(points, scale), scale.astype(np.float32)


def _quantize_centroids(centroids, col_scale):
    """Per-iteration centroid requantization shared by the XLA int8 path
    and the fused Pallas kernel (ops/kmeans_kernel.kmeans_partials_int8):
    centroids enter the quantized-feature coordinate system
    (``cs = c · col_scale``), each ROW gets its own symmetric scale, and
    ``c2`` stays in the original space for the score decomposition.
    Returns (c_q [k, d] int8, c_scale [k] f32, c2 [k] f32)."""
    cs = centroids.astype(jnp.float32) * col_scale[None, :]      # [k, d]
    c_q, c_scale_col = C.quantize_to_int8(cs, jnp.abs(cs).max(1, keepdims=True))
    c2 = (centroids.astype(jnp.float32) ** 2).sum(-1)            # [k]
    return c_q, c_scale_col[:, 0], c2


def _partials_block_int8(pts_q, col_scale, centroids, c2, mask=None,
                         x2=None):
    """Quantized twin of :func:`_partials_block`: both matmuls run int8 on
    the MXU (v5e: 2× the bf16 rate, ¼ the f32 bytes); accumulation is
    exact int32, dequantized once per [k, d]/[k] output.  The centroid
    operand requantizes per iteration with a per-centroid scale, so the
    only approximation is the two int8 roundings inside the argmin.
    ``mask`` as in :func:`_partials_block` (int8 0/1 keeps the sums
    matmul int8; a padded row contributes exact zeros).  ``x2``: the
    iteration-invariant ``Σ‖x‖²`` — pass the hoisted value to skip this
    block's full re-read of the point stream (maskless callers only;
    the masked/streaming path sees different rows per chunk)."""
    k = centroids.shape[0]
    c_q, c_scale, _ = _quantize_centroids(centroids, col_scale)
    dots_i = jax.lax.dot_general(
        pts_q, c_q, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)                        # [n, k]
    dots = dots_i.astype(jnp.float32) * c_scale[None, :]
    scores = c2[None, :] - 2.0 * dots
    assign = jnp.argmin(scores, axis=1)
    onehot = jax.nn.one_hot(assign, k, dtype=jnp.int8)
    if mask is None:
        if x2 is None:
            x2 = ((pts_q.astype(jnp.float32) * col_scale[None, :]) ** 2
                  ).sum()
        inertia = x2 + scores.min(axis=1).sum()
    else:
        assert x2 is None, "x2 hoisting is a maskless-path optimization"
        w = mask.astype(jnp.float32)
        x2 = (((pts_q.astype(jnp.float32) * col_scale[None, :]) ** 2).sum(1)
              * w).sum()
        inertia = x2 + (scores.min(axis=1) * w).sum()
        onehot = onehot * mask.astype(jnp.int8)[:, None]
    sums_i = jax.lax.dot_general(
        onehot, pts_q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)                        # [k, d]
    sums = sums_i.astype(jnp.float32) * col_scale[None, :]
    counts = jnp.sum(onehot, axis=0, dtype=jnp.int32).astype(jnp.float32)
    return sums, counts, inertia


def kmeans_kernel_supported(n: int) -> bool:
    """use_pallas falls back to the XLA path when no tile divides the shard."""
    from harp_tpu.ops import kmeans_kernel

    return kmeans_kernel.supported(n)


def _use_pallas(cfg: KMeansConfig) -> bool:
    """Resolved use_pallas — None means auto per path (the 2026-08-01
    verdicts: fused kernel ON for int8 — 1.14× at equal inertia — OFF
    for f32 where XLA measured equal-or-faster)."""
    if cfg.use_pallas is None:
        return cfg.quantize == "int8"
    return cfg.use_pallas


def partials_arm(cfg: KMeansConfig, n: int, d: int) -> str:
    """The partials formulation :func:`kmeans_step` runs on an ``[n, d]``
    local shard: ``pallas_int8`` / ``xla_int8`` / ``pallas_f32`` /
    ``xla_f32``.  The kernels hand shapes they cannot tile to the XLA
    arm (the auto default must not make previously-working shapes
    raise); that hand-off warns here (once per distinct message) and
    the benchmark result carries the name, so it is never silent."""
    from harp_tpu.ops import kmeans_kernel

    kind = "int8" if cfg.quantize == "int8" else "f32"
    if not _use_pallas(cfg):
        return f"xla_{kind}"
    # the int8 kernel's OWN supportability: a tile within the VMEM
    # budget AND d inside the exact-accumulation bound
    ok = (kmeans_kernel.int8_supported(n, d, cfg.k) if kind == "int8"
          else kmeans_kernel_supported(n))
    if not ok:
        import warnings

        warnings.warn(f"kmeans: the fused {kind} kernel cannot tile a "
                      f"[{n}, {d}] shard (k={cfg.k}) — running the XLA arm",
                      RuntimeWarning, stacklevel=2)
    return f"pallas_{kind}" if ok else f"xla_{kind}"


def kmeans_step(points, centroids, cfg: KMeansConfig, x2=None):
    """One Lloyd iteration (device view, per-worker shard).

    Returns (new_centroids, inertia).  The partial-sums → allreduce is
    exactly Harp's regroup+allgather phase, fused to one psum.  ``x2``:
    optional hoisted ``Σ‖x‖²`` (int8 paths; iteration-invariant, see
    make_fit_fn).
    """
    if cfg.quantize == "int8":
        from harp_tpu.ops import kmeans_kernel

        pts_q, col_scale = points  # (int8 [n, d], f32 [d]) — see fit()
        if partials_arm(cfg, *pts_q.shape) == "pallas_int8":
            # fused single-pass kernel: the XLA int8 path materializes
            # ~2 GB/iter of [n, k] intermediates at the graded shape and
            # clocks the same 2.5 ms/iter as f32 (1M×300 k=100, 1× v5e,
            # 2026-07-31); the kernel reads only the int8 stream.  x2 is
            # required: the fused path never re-reads points for it.
            assert x2 is not None, "fused int8 path needs the hoisted x2"
            c_q, c_scale, c2 = _quantize_centroids(centroids, col_scale)
            sums, counts, best_sum = kmeans_kernel.kmeans_partials_int8(
                pts_q, c_q, c_scale, c2, col_scale,
                interpret=interpret_default())
            partial_inertia = best_sum + x2
        else:
            c2 = (centroids.astype(jnp.float32) ** 2).sum(-1)
            sums, counts, partial_inertia = _partials_block_int8(
                pts_q, col_scale, centroids, c2, x2=x2)
        nw = lax.axis_size(C.WORKER_AXIS)
        return _combine_partials(sums, counts, partial_inertia, centroids,
                                 cfg, nw)
    n = points.shape[0]
    block = cfg.block_points
    if partials_arm(cfg, *points.shape) == "pallas_f32":
        from harp_tpu.ops import kmeans_kernel

        if block:
            raise ValueError("block_points has no effect with use_pallas "
                             "(the kernel picks its own tile size)")
        sums, counts, partial_inertia = kmeans_kernel.kmeans_partials(
            points, centroids, interpret=interpret_default())
    elif block <= 0 or block >= n:
        with jax.named_scope("kmeans.assign"):
            c2 = (centroids.astype(jnp.float32) ** 2).sum(-1)  # [k]
        sums, counts, partial_inertia = _partials_block(points, centroids, c2)
    else:
        assert n % block == 0, "block_points must divide the local shard size"
        c2 = (centroids.astype(jnp.float32) ** 2).sum(-1)  # [k]
        blocks = points.reshape(n // block, block, points.shape[1])
        sums, counts, partial_inertia = lax.map(
            lambda b: _partials_block(b, centroids, c2), blocks
        )
        sums, counts = sums.sum(0), counts.sum(0)
        partial_inertia = partial_inertia.sum()

    nw = lax.axis_size(C.WORKER_AXIS)
    return _combine_partials(sums, counts, partial_inertia, centroids, cfg, nw)


def _normalize_centroids(sums, counts, old):
    """Empty cluster keeps its old centroid — the ONE empty-cluster policy,
    shared by every path (both fit variants AND the streaming module); a
    change here, e.g. reseeding, must apply to all of them identically."""
    return jnp.where(
        counts[:, None] > 0, sums / jnp.maximum(counts[:, None], 1.0), old
    ).astype(old.dtype)


def _combine_partials(sums, counts, partial_inertia, centroids, cfg, nw):
    """The collective+normalize tail every partials formulation shares."""
    normalize = _normalize_centroids

    if cfg.variant == "regroupallgather" and sums.shape[0] % nw == 0:
        # Harp's regroup+allgather: reduce-scatter the partials so worker w
        # owns centroid block w (the regroup/push phase), normalize locally,
        # allgather the normalized blocks.  Falls back to allreduce when
        # k isn't divisible (Harp's partitioner would round-robin uneven
        # blocks; one fused psum is the degenerate equivalent).
        my_sums, my_counts = C.push((sums, counts))
        kb = sums.shape[0] // nw
        me = lax.axis_index(C.WORKER_AXIS)
        cent_blk = lax.dynamic_slice_in_dim(centroids, me * kb, kb, 0)
        new_centroids = C.pull(normalize(my_sums, my_counts, cent_blk))
        inertia = C.allreduce(partial_inertia)
        return new_centroids, inertia

    with jax.named_scope("kmeans.combine"):
        if cfg.psum_schedule == "hier":
            # the planner's hierarchical two-stage psum (fail-closed flip
            # candidate kmeans_hier_psum; see KMeansConfig.psum_schedule)
            sums, counts, inertia = C.allreduce_hier(
                (sums, counts, partial_inertia))
        else:
            sums, counts, inertia = C.allreduce(
                (sums, counts, partial_inertia))
    with jax.named_scope("kmeans.update"):
        return normalize(sums, counts, centroids), inertia


def _effective_variant(variant: str, k: int, num_workers: int) -> str:
    """The variant that will actually run — the two-phase form needs
    ``k % num_workers == 0`` and falls back to allreduce (loudly)."""
    if variant == "regroupallgather" and k % num_workers != 0:
        import logging

        logging.getLogger("harp_tpu").warning(
            "kmeans: k=%d not divisible by %d workers — regroupallgather "
            "falls back to the (equivalent) allreduce path", k, num_workers)
        return "allreduce"
    return variant


def make_fit_fn(mesh: WorkerMesh, cfg: KMeansConfig):
    """Compile the full T-iteration KMeans run as one SPMD program."""

    def run(points, centroids):
        x2 = None
        if cfg.quantize == "int8":
            # Σ‖x‖² is iteration-invariant: one pass here instead of one
            # per Lloyd iteration (the fori_loop body would re-read the
            # whole point stream for it every iteration otherwise)
            pts_q, col_scale = points
            x2 = ((pts_q.astype(jnp.float32) * col_scale[None, :]) ** 2
                  ).sum()

        def body(i, state):
            c, _ = state
            return kmeans_step(points, c, cfg, x2=x2)

        centroids, inertia = lax.fori_loop(
            0, cfg.iters, body, (centroids, jnp.float32(0.0)))
        # per-worker active-row count folded NEXT TO the inertia — the
        # skew spine's execution counter (utils/skew.py) rides the same
        # [nw, 2] stats readback; no collective is added (the
        # out-sharding concatenates), so the hand-computed comm byte
        # sheet (tests/test_telemetry.py) and the pinned flight budgets
        # (compiles=1, dispatches=1, readbacks=2) are untouched
        rows = (points[0] if cfg.quantize == "int8" else points).shape[0]
        stats = jnp.stack([jnp.float32(rows), inertia])[None]  # [1, 2]
        return centroids, stats

    pts_spec = ((mesh.spec(0), P()) if cfg.quantize == "int8"
                else mesh.spec(0))  # (q shards, replicated col scales)
    return jax.jit(
        mesh.shard_map(run, in_specs=(pts_spec, P()),
                       out_specs=(P(), mesh.spec(0)))
    )


def kmeanspp_init(points, k, seed=0, sample=50_000):
    """k-means++ seeding (Arthur & Vassilvitskii) on a host subsample.

    Beyond-reference robustness: Harp seeds with random rows, which can
    pick duplicate-cluster seeds and strand Lloyd in a bad basin (measured:
    2× worse true inertia on separated clusters, see tests).  Runs on a
    ``sample``-row subsample so graded-scale inputs stay O(sample·k·d)."""
    pts = np.asarray(points, np.float32)
    rng = np.random.default_rng(seed)
    if len(pts) > sample:
        pts = pts[rng.choice(len(pts), size=sample, replace=False)]
    centers = [pts[rng.integers(len(pts))]]
    d2 = ((pts - centers[0]) ** 2).sum(1)
    for _ in range(k - 1):
        # float64 so the probabilities pass numpy's sum-to-one check even
        # when one entry dominates (f32 rounding can exceed the tolerance)
        d2_64 = d2.astype(np.float64)
        total = float(d2_64.sum())
        if total <= 0.0:
            # fewer than k distinct rows: every point already coincides
            # with a center — fall back to uniform picks (Lloyd's
            # keep-old-centroid rule handles the resulting empty clusters)
            nxt = pts[rng.integers(len(pts))]
        else:
            nxt = pts[rng.choice(len(pts), p=d2_64 / total)]
        centers.append(nxt)
        d2 = np.minimum(d2, ((pts - nxt) ** 2).sum(1))
    return np.stack(centers)


def fit(points, k=100, iters=10, mesh: WorkerMesh | None = None, seed=0,
        dtype=jnp.float32, block_points=0, use_pallas=None,
        variant="allreduce", quantize=None, init="random",
        psum_schedule="one_shot",
        ckpt_dir: str | None = None, ckpt_every: int = 5,
        max_restarts: int = 3, fault=None):
    """Host driver — the ``mapCollective`` residue (SURVEY.md §4.2).

    ``points``: [n, d] host or device array; sharded over workers on dim 0.
    Initialization (``init``): "random" (Harp's scheme) picks k distinct
    random rows with the integer ``seed``, or the first k points when
    ``seed=None`` — deterministic, so results match a numpy Lloyd
    reference exactly (the golden tests use this mode); "kmeans++" uses
    :func:`kmeanspp_init` (beyond-reference, far less init-sensitive).

    Checkpoint/resume (PR 10, the SURVEY.md §6 driver contract the other
    graded apps already carry): with ``ckpt_dir`` set, the T iterations
    run as ``ckpt_every``-iteration device programs with the centroids
    checkpointed between chunks through
    :class:`~harp_tpu.utils.checkpoint.CheckpointManager`; a crashed run
    (or a rerun pointing at the same dir — the CLI ``--resume``) resumes
    from the latest saved chunk instead of iteration 0.  The chunked
    schedule replays bit-identically on resume: each chunk is the same
    compiled program over the same operands, and restored centroids
    round-trip host-side exactly (f32 in, f32 out).
    """
    mesh = mesh or current_mesh()
    variant = _effective_variant(variant, k, mesh.num_workers)
    cfg = KMeansConfig(k=k, iters=iters, dtype=dtype, block_points=block_points,
                       use_pallas=use_pallas, variant=variant, quantize=quantize,
                       psum_schedule=psum_schedule)
    n = points.shape[0]
    if init == "kmeans++":
        init_c = kmeanspp_init(points, k, seed=0 if seed is None else seed)
    elif init == "random":
        if seed is None:
            init_idx = np.arange(k)
        else:
            init_idx = np.random.default_rng(seed).choice(n, size=k,
                                                          replace=False)
        init_c = np.asarray(points[np.sort(init_idx)])
    else:
        raise ValueError(f"init must be 'random' or 'kmeans++', got {init!r}")
    centroids = jnp.asarray(init_c, dtype=dtype)
    if quantize == "int8":
        if -(-n // mesh.num_workers) > _INT8_SUM_ROW_LIMIT:
            raise ValueError(
                f"quantize='int8': {n} points over {mesh.num_workers} workers "
                f"exceeds the {_INT8_SUM_ROW_LIMIT} rows/worker exact-int32 "
                "accumulation bound — use more workers or the f32 path")
        q, scale = quantize_points_int8(points)
        pts = (mesh.shard_array(q, 0),
               jax.device_put(jnp.asarray(scale), mesh.replicated()))
    else:
        pts = mesh.shard_array(
            np.asarray(points, dtype=np.dtype(jnp.dtype(dtype).name)), 0)
    centroids = jax.device_put(centroids, mesh.replicated())
    if ckpt_dir is not None:
        return _fit_ckpt(mesh, cfg, pts, centroids, iters,
                         ckpt_dir, ckpt_every=ckpt_every,
                         max_restarts=max_restarts, fault=fault)
    if fault is not None:
        raise ValueError(
            "fault injection requires ckpt_dir (recovery restarts from "
            "checkpoints; without one the injector would be silently "
            "ignored)")
    fit_fn = flightrec.track(make_fit_fn(mesh, cfg), "kmeans.fit")
    # telemetry: the T iterations run inside ONE dispatch, so the traced
    # per-iteration comm sites execute cfg.iters times per invocation;
    # the flight recorder sees that one dispatch plus exactly two
    # readbacks (inertia scalar + final centroids)
    # steptrace (PR 18): the whole-run dispatch is ONE superstep — the
    # timeline shows the single-dispatch discipline literally (one span,
    # flight {dispatches: 1})
    with telemetry.span("kmeans.fit", iters=cfg.iters, k=k), \
            telemetry.ledger.run("kmeans.fit", steps=cfg.iters), \
            steptrace.run("kmeans.fit"), \
            steptrace.superstep("kmeans.fit", 0):
        t0 = time.perf_counter()
        new_c, stats = fit_fn(pts, centroids)
        st = flightrec.readback(stats)  # [nw, 2]: per-worker rows, inertia
        inertia = float(st[0, 1])
        skew.record_execution("kmeans.fit", st[:, 0], unit="points",
                              wall_s=time.perf_counter() - t0)
        return flightrec.readback(new_c), inertia


def _fit_ckpt(mesh, cfg, pts, centroids, iters, ckpt_dir, *,
              ckpt_every=5, max_restarts=3, fault=None):
    """The recovery-looped fit: ``ckpt_every``-iteration device chunks
    under :func:`harp_tpu.utils.fault.run_with_recovery`, centroids (+
    the last chunk's stats, so a no-work resume still reports inertia)
    checkpointed between chunks.  One compiled program per distinct
    chunk length (at most two: the full chunk and a ragged tail)."""
    from harp_tpu.utils.checkpoint import CheckpointManager
    from harp_tpu.utils.fault import run_with_recovery

    mgr = CheckpointManager(ckpt_dir)
    lens = [min(ckpt_every, iters - s) for s in range(0, iters, ckpt_every)]
    fns: dict[int, Any] = {}

    def chunk_fn(n_it):
        fn = fns.get(n_it)
        if fn is None:
            fn = fns[n_it] = flightrec.track(
                make_fit_fn(mesh, dataclasses.replace(cfg, iters=n_it)),
                "kmeans.fit_ckpt")
        return fn

    nw = mesh.num_workers

    def place(c):
        return jax.device_put(jnp.asarray(np.asarray(c), dtype=cfg.dtype),
                              mesh.replicated())

    def make_state():
        return {"centroids": centroids,
                "stats": jnp.zeros((nw, 2), jnp.float32)}

    def step(ci, state):
        with steptrace.superstep("kmeans.fit_ckpt", ci):
            c = state["centroids"]
            if not isinstance(c, jax.Array):  # numpy from a fresh restore
                c = place(c)
            new_c, stats = chunk_fn(lens[ci])(pts, c)
            return {"centroids": new_c, "stats": stats}

    with telemetry.span("kmeans.fit_ckpt", iters=iters, k=cfg.k), \
            steptrace.run("kmeans.fit_ckpt"):
        final = run_with_recovery(make_state, step, len(lens), mgr,
                                  ckpt_every=1, max_restarts=max_restarts,
                                  fault=fault)
    st = np.asarray(final["stats"])
    return np.asarray(final["centroids"]), float(st[0, 1])


def benchmark(n=1_000_000, d=300, k=100, iters=10, mesh=None, dtype=jnp.float32,
              warmup=2, seed=0, use_pallas=None, variant="allreduce",
              quantize=None, psum_schedule="one_shot"):
    """Measure iter/sec on the graded 1M×300 k=100 config (north-star metric)."""
    mesh = mesh or current_mesh()
    variant = _effective_variant(variant, k, mesh.num_workers)
    cfg = KMeansConfig(k=k, iters=1, dtype=dtype, use_pallas=use_pallas,
                       variant=variant, quantize=quantize,
                       psum_schedule=psum_schedule)
    nw = mesh.num_workers
    n = (n // nw) * nw  # actual points generated/processed (and reported)

    # Generate the shard on-device (no host→HBM transfer of 1.2 GB).
    def gen(key):
        return jax.random.normal(key, (n // nw, d), dtype=dtype)

    # raw key bits (utils.prng): a fresh seed must not cost a fresh
    # (remote) compile — CLAUDE.md PRNGKey-specialization trap
    keys = jax.random.split(jnp.asarray(prng.key_bits(seed)), nw)
    points = flightrec.track(jax.jit(
        mesh.shard_map(lambda ks: gen(ks[0]), in_specs=(mesh.spec(0),),
                       out_specs=mesh.spec(0))
    ), "kmeans.datagen")(keys)
    if quantize == "int8":
        if n // nw > _INT8_SUM_ROW_LIMIT:
            raise ValueError(
                f"quantize='int8': {n // nw} rows/worker exceeds the "
                f"{_INT8_SUM_ROW_LIMIT} exact-int32 accumulation bound")
        # on-device quantization: per-feature |max| needs a cross-shard pmax
        def quant(x):
            amax = C.allreduce(jnp.abs(x).max(0), C.Combiner.MAX)
            return C.quantize_to_int8(x, amax)  # scale [d] broadcasts

        points = flightrec.track(jax.jit(mesh.shard_map(
            quant, in_specs=(mesh.spec(0),),
            out_specs=(mesh.spec(0), P()))), "kmeans.quantize")(points)
    centroids = jax.device_put(
        jax.random.normal(jnp.asarray(prng.key_bits(seed + 1)), (k, d),
                          dtype=dtype),
        mesh.replicated(),
    )

    # All iterations inside ONE jitted program: per-dispatch overhead
    # disappears from the timed loop; sync is a scalar readback (see
    # utils.timing), which cannot complete early.
    # n_iters is a traced scalar so warmup and the timed run share one
    # compilation (recompiling inside the timed region once cost 4x).
    def run(points, centroids, n_iters):
        x2 = None
        if quantize == "int8":  # hoisted Σ‖x‖², as in make_fit_fn
            pts_q, col_scale = points
            x2 = ((pts_q.astype(jnp.float32) * col_scale[None, :]) ** 2
                  ).sum()

        def body(i, st):
            c, _ = st
            return kmeans_step(points, c, cfg, x2=x2)

        return lax.fori_loop(0, n_iters, body, (centroids, jnp.float32(0.0)))

    pts_spec = ((mesh.spec(0), P()) if quantize == "int8" else mesh.spec(0))
    run_fn = flightrec.track(jax.jit(
        mesh.shard_map(
            run, in_specs=(pts_spec, P(), P()), out_specs=(P(), P()),
        )
    ), "kmeans.benchmark")
    # telemetry: n_iters is a traced scalar, so the loop body's comm sites
    # trace once — the host knows the real per-invocation trip count
    with telemetry.ledger.run("kmeans.benchmark", steps=max(warmup, 1)):
        c_w, inertia = run_fn(points, centroids, jnp.int32(max(warmup, 1)))
        device_sync(inertia)

    t0 = time.perf_counter()
    with telemetry.span("kmeans.benchmark", iters=iters), \
            telemetry.ledger.run("kmeans.benchmark", steps=iters):
        centroids, inertia = run_fn(points, centroids, jnp.int32(iters))
        inertia_val = device_sync(inertia)
    dt = time.perf_counter() - t0
    return {
        "iters_per_sec": iters / dt,
        "points_per_sec": n * iters / dt,
        "sec_per_iter": dt / iters,
        "inertia": inertia_val,
        "n": n, "d": d, "k": k, "num_workers": nw,
        "dtype": str(jnp.dtype(dtype).name),
        "variant": variant,  # the variant that actually ran (post-fallback)
        "arm": partials_arm(cfg, n // nw, d),  # ditto for the partials
        "quantize": quantize,
        "psum_schedule": psum_schedule,
    }


def main(argv=None):
    import argparse

    from harp_tpu.utils.metrics import benchmark_json

    p = argparse.ArgumentParser(description="harp-tpu KMeans (edu.iu.kmeans parity)")
    p.add_argument("--n", type=int, default=1_000_000)
    p.add_argument("--d", type=int, default=300)
    p.add_argument("--k", type=int, default=100)
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--dtype", default="float32", choices=["float32", "bfloat16"])
    p.add_argument("--variant", default="allreduce",
                   choices=["allreduce", "regroupallgather"],
                   help="Harp app variant: one fused psum, or the explicit "
                        "regroup(reduce-scatter)+allgather two-phase form")
    p.add_argument("--input", default=None, metavar="FILE_OR_GLOB",
                   help="CSV/whitespace point files (one point per row) — "
                        "the Harp app's HDFS input; default: synthetic")
    p.add_argument("--init", choices=["random", "kmeans++"], default="random",
                   help="centroid seeding: Harp's random rows, or kmeans++ "
                        "(beyond-reference; far less init-sensitive)")
    p.add_argument("--quantize", choices=["int8"], default=None,
                   help="opt-in int8 point quantization (¼ the HBM traffic; "
                        "see KMeansConfig.quantize for the accuracy contract)")
    p.add_argument("--psum-schedule", choices=["one_shot", "hier"],
                   default="one_shot",
                   help="partials-allreduce schedule: one fused psum "
                        "(default) or the planner's hierarchical two-stage "
                        "psum (flip candidate kmeans_hier_psum — see "
                        "KMeansConfig.psum_schedule)")
    p.add_argument("--bench", action="store_true", help="synthetic benchmark mode")
    p.add_argument("--ckpt-dir", default=None,
                   help="fit with checkpoint/resume: iterations run in "
                        "--ckpt-every chunks with centroids checkpointed "
                        "between them; rerunning with the same dir resumes "
                        "from the latest saved chunk")
    p.add_argument("--ckpt-every", type=int, default=5,
                   help="iterations per checkpointed chunk")
    p.add_argument("--resume", action="store_true",
                   help="assert the run RESUMES: --ckpt-dir must already "
                        "hold a checkpoint (a mistyped dir fails loudly "
                        "instead of silently restarting from iteration 0)")
    args = p.parse_args(argv)
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32

    from harp_tpu.utils.fault import resolve_resume

    resumed_from = resolve_resume(args.ckpt_dir, args.resume)

    from harp_tpu.report import maybe_emit

    if args.bench:
        out = benchmark(args.n, args.d, args.k, args.iters, dtype=dtype,
                        variant=args.variant, quantize=args.quantize,
                        psum_schedule=args.psum_schedule)
        print(out)
        maybe_emit("kmeans_bench")
    else:
        if args.input:
            from harp_tpu.native.datasource import load_csv_glob

            try:
                pts = load_csv_glob(args.input)
            except ValueError as e:
                raise SystemExit(str(e))
        else:
            rng = np.random.default_rng(0)
            pts = rng.normal(size=(args.n, args.d)).astype(np.float32)
        c, inertia = fit(pts, args.k, args.iters, dtype=dtype,
                         variant=args.variant, quantize=args.quantize,
                         init=args.init, psum_schedule=args.psum_schedule,
                         ckpt_dir=args.ckpt_dir,
                         ckpt_every=args.ckpt_every)
        print(benchmark_json("kmeans_cli", {"k": args.k, "iters": args.iters, "n": pts.shape[0],
               "d": pts.shape[1], "inertia": inertia,
               "ckpt_dir": args.ckpt_dir, "resumed_from": resumed_from}))
        maybe_emit("kmeans")


if __name__ == "__main__":
    main()
