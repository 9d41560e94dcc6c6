"""Fused KMeans assignment+accumulation — Pallas TPU kernel.

One Lloyd iteration as a single pass: each tile of points streams HBM→VMEM
once, and the scores, assignment one-hot, and [k, d]/[k] accumulators all
stay on-chip.

**Measured outcome (1M×300 k=100, 1× v5e, 2026-07-29): the XLA path wins.**
XLA fuses the `dots → argmin → one_hot → matmul` chain into its own blocked
single-pass program: 2.45 ms/iter (bf16 points) / 2.67 ms (f32) vs this
kernel's best 2.83 ms (bf16, tile=2000).  Both sit near the chip's measured
effective HBM read bandwidth (~250–310 GB/s as measured that day), so
the iteration is bandwidth-floor-bound and hand-fusion has no headroom left
— the kernel is kept as an opt-in (`KMeansConfig(use_pallas=True)`) and as
the in-tree template for single-pass streaming-accumulation kernels.

Reference parity: this corresponds to the distance/assignment inner loop
that Harp-DAAL executed in Intel DAAL's C++ KMeans kernel (SURVEY.md §3.2).

Layout notes (hard-won, keep in mind for future kernels):
- Never contract a matmul over a *sublane* dimension: Mosaic lowers the
  point-major one-hot reduction (contracting dim 0 of [tn, k]ᵀ×[tn, d]) via
  a scoped-VMEM re-layout that scales with tile rows (62 MB at tn=1000 — an
  instant VMEM OOM).  Everything here is therefore centroid-major
  ([k, tile] scores), where both matmuls contract over lanes.
- Full-tile reductions to scalars (e.g. a per-tile ||x||² sum) cost more
  than the matmuls at these shapes; inertia is instead reassembled from the
  accumulated sums/counts where possible.
- Centroids are padded to a full 128-row MXU tile; padded rows are excluded
  from the argmin by +inf scores.  Ties pick the lowest centroid index,
  matching numpy argmin semantics.
- The grid is sequential on a TensorCore, so the output refs double as
  accumulators across tiles (init at program 0).

Numerics: distances are scored in bf16 (MXU-native), so (a) boundary points
between overlapping clusters may assign differently than an f32 reference,
and (b) the returned inertia — built from the ``||x||² − 2x·c + ||c||²``
decomposition — carries an absolute error of order ``4e-3 · Σ||x||²`` from
cancellation when cluster spread ≫ within-cluster distance.  Sums/counts are
f32-accumulated and exact for unambiguous assignments.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANE = 128


def _kernel(pts_ref, c_ref, sums_ref, counts_ref, inertia_ref, *, k: int):
    kp = c_ref.shape[0]
    # bf16 operands, f32 accumulation: the MXU's native mode (~4× the f32
    # matmul rate).  XLA's default matmul precision makes the same trade for
    # f32 inputs; Pallas dots run at the literal input dtype, so the cast
    # must be explicit here.  Exactness of the one-hot is unaffected (0/1).
    pts = pts_ref[:].astype(jnp.bfloat16)              # [tn, d]
    c = c_ref[:].astype(jnp.bfloat16)                  # [kp, d]

    dots = jax.lax.dot_general(
        c, pts, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)            # [kp, tn]
    c2 = (c.astype(jnp.float32) ** 2).sum(axis=1, keepdims=True)  # [kp, 1]
    row = jax.lax.broadcasted_iota(jnp.int32, dots.shape, 0)
    scores = jnp.where(row >= k, jnp.inf, c2 - 2.0 * dots)

    best = scores.min(axis=0, keepdims=True)           # [1, tn]
    # lowest index among ties (argmin semantics) without a 1-D argmin; the
    # min runs in f32 (exact for indices ≤ kp < 2^24) because Mosaic lacks
    # integer reduce_min on older toolchains
    assign = jnp.where(scores == best, row, kp).astype(jnp.float32) \
        .min(axis=0, keepdims=True).astype(jnp.int32)
    onehot = (row == assign).astype(pts.dtype)         # [kp, tn]

    tile_sums = jax.lax.dot_general(
        onehot, pts, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)            # [kp, d]
    tile_counts = onehot.astype(jnp.float32).sum(axis=1, keepdims=True)
    x2 = (pts_ref[:].astype(jnp.float32) ** 2).sum()  # full-precision ||x||²
    tile_inertia = (x2 + best.sum()).reshape(1, 1)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        sums_ref[:] = jnp.zeros_like(sums_ref)
        counts_ref[:] = jnp.zeros_like(counts_ref)
        inertia_ref[:] = jnp.zeros_like(inertia_ref)

    sums_ref[:] += tile_sums
    counts_ref[:] += tile_counts
    inertia_ref[:] += tile_inertia


def _tile_rows(n: int) -> int | None:
    """Largest point-tile size (multiple of 8 sublanes) dividing n."""
    for tn in (2048, 2000, 1024, 1000, 512, 500, 256, 250, 200, 128, 120,
               64, 40, 16, 8):
        if n % tn == 0 and tn % 8 == 0:
            return tn
    return None


def supported(n: int) -> bool:
    """Whether the fused kernel can handle a local shard of n points."""
    return _tile_rows(n) is not None


def kmeans_partials(points, centroids, *, interpret: bool = False):
    """Fused per-shard partials: (sums [k, d] f32, counts [k] f32, inertia).

    Drop-in for the XLA `_partials_block` path: identical math (||x||² kept
    out of the argmin, re-added to inertia), single HBM pass over ``points``.
    """
    n, d = points.shape
    k = centroids.shape[0]
    tn = _tile_rows(n)
    if tn is None:
        raise ValueError(f"no supported tile size divides n={n}")
    kp = -(-k // _LANE) * _LANE
    cpad = jnp.pad(centroids, ((0, kp - k), (0, 0)))

    sums, counts, inertia = pl.pallas_call(
        functools.partial(_kernel, k=k),
        grid=(n // tn,),
        in_specs=[
            pl.BlockSpec((tn, d), lambda i: (i, 0)),
            pl.BlockSpec((kp, d), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((kp, d), lambda i: (0, 0)),
            pl.BlockSpec((kp, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((kp, d), jnp.float32),
            jax.ShapeDtypeStruct((kp, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(points, cpad)
    return sums[:k], counts[:k, 0], inertia[0, 0]


def _kernel_int8(pts_ref, cq_ref, cscale_ref, c2_ref, sums_ref, counts_ref,
                 best_ref, *, k: int):
    """int8-points twin of :func:`_kernel` (round 3).

    Same centroid-major single-pass layout; the point stream is int8 in
    HBM (¼ the f32 bytes — the measured wall of the XLA int8 path is the
    [n, k] intermediates it materializes, ~2 GB/iter at 1M×300 k=100,
    which this kernel never writes).  Operands are cast int8→bf16 in
    VMEM: |q| ≤ 127 is EXACT in bf16, products ≤ 127² and row sums
    ≤ 127²·d < 2²⁴ are exact in the f32 MXU accumulator, so the dots and
    one-hot sums equal the XLA path's int32 matmuls bit-for-bit; sums
    accumulate across tiles as int32 (per-tile values ≤ 127·tn < 2²⁴
    round-trip f32→int32 exactly).

    Score/assignment math matches ``kmeans._partials_block_int8``:
    ``scores = ||c||² − 2·(q·c_q)·c_scale`` with the same per-row
    centroid requantization — assignments are identical by construction.
    ``Σ‖x‖²`` is NOT computed here: it is iteration-invariant, so the
    caller hoists it out of the Lloyd loop (the XLA path re-reads the
    whole point stream for it every iteration).
    """
    kp = cq_ref.shape[0]
    qb = pts_ref[:].astype(jnp.bfloat16)               # [tn, d], exact
    cb = cq_ref[:].astype(jnp.bfloat16)                # [kp, d], exact
    dots_q = jax.lax.dot_general(
        cb, qb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)            # [kp, tn], exact ints
    dots = dots_q * cscale_ref[:]                      # [kp, 1] broadcast
    row = jax.lax.broadcasted_iota(jnp.int32, dots.shape, 0)
    scores = jnp.where(row >= k, jnp.inf, c2_ref[:] - 2.0 * dots)

    best = scores.min(axis=0, keepdims=True)           # [1, tn]
    # f32 tie-break min: see _kmeans_kernel (no integer reduce_min in Mosaic)
    assign = jnp.where(scores == best, row, kp).astype(jnp.float32) \
        .min(axis=0, keepdims=True).astype(jnp.int32)
    onehot = (row == assign).astype(jnp.bfloat16)      # [kp, tn] 0/1

    tile_sums = jax.lax.dot_general(
        onehot, qb, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)            # [kp, d], exact ints
    tile_counts = onehot.astype(jnp.float32).sum(axis=1, keepdims=True)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        sums_ref[:] = jnp.zeros_like(sums_ref)
        counts_ref[:] = jnp.zeros_like(counts_ref)
        best_ref[:] = jnp.zeros_like(best_ref)

    sums_ref[:] += tile_sums.astype(jnp.int32)
    counts_ref[:] += tile_counts.astype(jnp.int32)
    best_ref[:] += best.sum().reshape(1, 1)


#: scoped-VMEM budget for the int8 tile search (the OOM-calibrated
#: headroom under the 16 MB/core ceiling — see vmem_bytes_int8)
_VMEM_BUDGET_INT8 = 14 << 20


def vmem_bytes_int8(tn: int, d: int, kp: int) -> int:
    """The int8 kernel's scoped-VMEM byte model at point tile ``tn``.

    Calibrated by the 2026-08-01 silicon OOM (10000-row tiles die at
    16.23 MB): the compiler's scoped stack is ≈ tn·(2·d + 8·kp) B
    (double-buffered int8 in-blocks plus the [tn, kp] score/one-hot
    temporaries), + the [kp, d]-class operands, + a 64 KiB fixed floor.
    This is the expression the kernel-registry ``vmem_bytes``
    declaration pins at the registered shape (harplint HL205) and the
    memrec pre-dispatch VMEM gate prices explicit tiles with."""
    return tn * (2 * d + 8 * kp) + 5 * kp * d + (64 << 10)


def _tile_rows_int8(n: int, d: int, kp: int) -> int | None:
    """Largest sublane-aligned point tile dividing ``n`` that fits VMEM.

    Bigger tiles amortize the per-program centroid reload, and the int8
    kernel keeps winning with size until the scoped-VMEM wall: measured
    2026-08-01 (1M×300 k=100, 1× v5e) 557.9 iter/s @8000 vs 537.2
    @4000 / 521.5 @2000 / 464.9 @1000, while 10000 OOMs at 16.23 MB —
    which calibrates :func:`vmem_bytes_int8`.  14 MB budget leaves the
    same headroom the LDA kernel's estimator keeps.
    """
    for tn in (64000, 50000, 40000, 32000, 25000, 20000, 16000, 10000,
               8000, 5000, 4000, 2048, 2000, 1024, 1000, 512, 256, 200,
               128, 120, 64, 40, 16, 8):
        if n % tn or tn % 8:
            continue
        if vmem_bytes_int8(tn, d, kp) <= _VMEM_BUDGET_INT8:
            return tn
    return None


def int8_supported(n: int, d: int, k: int) -> bool:
    """Whether the fused int8 kernel can handle a local (n, d, k) shard:
    a sublane-aligned tile must divide n AND fit the VMEM budget, and d
    must stay inside the exact-f32-accumulation bound.  The dispatch
    gate (kmeans._use_pallas auto path) consults this and falls back to
    the XLA int8 path — shapes the kernel can't take must not start
    raising just because the default flipped (review finding, round 5)."""
    if 127 * 127 * d >= 1 << 24:  # d ≤ 1040
        return False
    return _tile_rows_int8(n, d, -(-k // _LANE) * _LANE) is not None


def kmeans_partials_int8(pts_q, c_q, c_scale, c2, col_scale, *,
                         interpret: bool = False,
                         tile_rows: int | None = None):
    """Fused int8 per-shard partials → (sums [k, d] f32, counts [k] f32,
    best_sum f32 scalar).

    ``pts_q`` [n, d] int8 with per-feature ``col_scale`` [d]; ``c_q`` /
    ``c_scale`` [k, d] int8 / [k] from the shared per-row centroid
    requantization (``kmeans._quantize_centroids``); ``c2`` [k] the
    ORIGINAL-space ‖c‖².  Returns dequantized sums (int32 accumulation ×
    col_scale) and the Σ over points of the assigned score;
    ``inertia = best_sum + Σ‖x‖²`` where the caller supplies the
    iteration-invariant second term.  int32 exactness bound: a cluster
    may absorb at most 2³¹/127 ≈ 16.9M local rows (same rule as the XLA
    path's ``_INT8_SUM_ROW_LIMIT``).

    ``tile_rows`` overrides the auto tile search (sweeps, tests); an
    explicit tile is priced through :func:`vmem_bytes_int8` and an
    over-VMEM choice is REFUSED before dispatch by
    :func:`harp_tpu.utils.memrec.require_vmem_fit` — the 2026-08-01
    silicon OOM as a pre-silicon MemoryError naming the predicted
    bytes."""
    n, d = pts_q.shape
    k = c_q.shape[0]
    kp = -(-k // _LANE) * _LANE
    if tile_rows is not None:
        tn = int(tile_rows)
        if n % tn or tn % 8:
            raise ValueError(
                f"tile_rows={tn} must divide n={n} and align to 8")
        from harp_tpu.utils import memrec

        memrec.require_vmem_fit(
            "kmeans.partials_int8", vmem_bytes_int8(tn, d, kp),
            budget=_VMEM_BUDGET_INT8)
    else:
        tn = _tile_rows_int8(n, d, kp)
    if tn is None:
        raise ValueError(f"no supported tile size divides n={n} "
                         f"within the VMEM budget (d={d}, kp={kp})")
    if 127 * 127 * d >= 1 << 24:  # d ≤ 1040
        # beyond this the bf16-operand dot's f32 partial sums exceed the
        # 2²⁴ exact-integer range and the bit-for-bit promise vs the XLA
        # int32 path silently breaks — refuse loudly, like the row limit
        raise ValueError(
            f"fused int8 kernel: d={d} exceeds the exact-f32-accumulation "
            f"bound (127²·d < 2²⁴ ⇒ d ≤ 1040); use the XLA int8 path")
    cq_pad = jnp.pad(c_q, ((0, kp - k), (0, 0)))
    cs_pad = jnp.pad(c_scale.reshape(-1, 1), ((0, kp - k), (0, 0)))
    c2_pad = jnp.pad(c2.reshape(-1, 1), ((0, kp - k), (0, 0)))

    sums_i, counts_i, best_sum = pl.pallas_call(
        functools.partial(_kernel_int8, k=k),
        grid=(n // tn,),
        in_specs=[
            pl.BlockSpec((tn, d), lambda i: (i, 0)),
            pl.BlockSpec((kp, d), lambda i: (0, 0)),
            pl.BlockSpec((kp, 1), lambda i: (0, 0)),
            pl.BlockSpec((kp, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((kp, d), lambda i: (0, 0)),
            pl.BlockSpec((kp, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((kp, d), jnp.int32),
            jax.ShapeDtypeStruct((kp, 1), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(pts_q, cq_pad, cs_pad, c2_pad)
    sums = sums_i[:k].astype(jnp.float32) * col_scale[None, :]
    return sums, counts_i[:k, 0].astype(jnp.float32), best_sum[0, 0]
