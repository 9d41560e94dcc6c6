"""Fused SMACOF distance + B(X)·X row block — Pallas TPU kernel.

Reference parity: Harp's ``edu.iu.wdamds`` unweighted Guttman transform
(SURVEY.md §3.4), in-tree as the XLA path (`models/wdamds.py:
make_smacof_fn`'s ``body``).  The PR-16 wall attribution billed the
committed wdamds iteration to gather_dus/HBM: XLA materialises the
[n_loc, N] distance block D, then the [n_loc, N] ratio block, each
round-tripping HBM between fusions before the B·X contraction reads
them back.  This kernel fuses the whole row-block update — x²/y² norms,
the Xl·Xᵀ cross matmul, sqrt, the guarded δ/D ratio, live masking, and
the −ratio·X + rowsum·Xl Guttman contraction — into one VMEM-resident
program per row tile: D and ratio never exist in HBM.

Layout (the `ops/kmeans_kernel.py` rules): the replicated coordinate
block rides TRANSPOSED as X^T [dimp, N] (dim zero-padded to one 128
lane register) and stays whole in VMEM with a constant index map, so
both matmuls contract over legal Mosaic patterns —

    cross [tn, N]   = Xl [tn, dimp] @ XT [dimp, N]  (A-lanes × B-sublanes)
    bx    [tn, dimp] −= ratio [tn, N] · XT [dimp, N]  (lanes of BOTH)

Grid/memory plan (1-D sequential grid over row tiles): X^T resident;
δ/Xl/row-mask stream tn rows at a time; each grid step writes its own
output tile (no accumulation across steps).  Zero-padded rows carry
row_mask = 0 and zero-padded dims are zero in both Xl and X^T, so pads
contribute nothing and are sliced off outside.  The bf16 arm composes
with ``MDSConfig.delta_dtype``: a bf16-staged δ streams half the tile
bytes and promotes to f32 in-kernel (same promotion as the XLA path).

Expected headroom (analytic, 2026-08-06 — NOT yet a measurement; the
tile comes from ``perfmodel.presize("wdamds.smacof_dist", ...)`` and
the kernel is Mosaic-proven via HL201 only): removes ~5 of the 7
[n_loc, N] HBM passes per iteration the perfmodel's WDAMDS_NN_PASSES
charges the XLA schedule.  First ran on a chip 2026-09-26
(chip_smoke.py: compiles, agrees with its reference; TPU v5 lite) —
correctness only.  A TPU speed measurement goes in BASELINE.md when flip
candidate ``wdamds_dist_pallas`` is measured — until then
prefer ``algo="xla"``, whose numbers are real.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

_LANE = 128
# resident X^T + streamed δ tiles + the in-flight D/ratio registers must
# fit beside Mosaic's own buffers; 14 MB leaves ~2 MB slack under the
# 16 MB/core ceiling the registry test pins.
VMEM_BUDGET = 14 << 20
TILE_CANDIDATES = (512, 256, 128, 64, 32, 16, 8)


def vmem_bytes(dimp: int, N: int, tn: int, dsize: int) -> int:
    """Analytic VMEM byte model (also what ``perfmodel.presize``
    consults): resident X^T + double-buffered δ tile + the cross/D/ratio
    intermediates + Xl/output tiles + fixed slack."""
    return (dimp * N * 4            # resident X^T
            + 2 * tn * N * dsize    # double-buffered δ tile
            + 3 * tn * N * 4        # cross / D / ratio registers
            + 4 * tn * dimp * 4     # Xl + output tiles (double-buffered)
            + (64 << 10))


def fit_tiles(N: int, dsize: int, budget: int = VMEM_BUDGET) -> list[int]:
    """Row-tile candidates whose working set fits the VMEM budget."""
    return [t for t in TILE_CANDIDATES
            if vmem_bytes(_LANE, N, t, dsize) <= budget]


def pick_tile(n_loc: int, N: int, dsize: int) -> int:
    """Largest fitting tile no taller than the (padded) local row count
    — the rule ``perfmodel.presize`` reproduces from the price model
    (per-grid-program overhead is monotone in 1/tn)."""
    fits = fit_tiles(N, dsize)
    if not fits:
        raise ValueError(
            f"pallas wdamds: no row tile fits N={N} (dsize={dsize}) under "
            f"the {VMEM_BUDGET >> 20} MB VMEM budget; use algo='xla' or "
            f"shard over more workers")
    cap = 8 * -(-max(n_loc, 1) // 8)
    small = [t for t in fits if t <= cap]
    return max(small) if small else min(fits)


def _kernel(xT_ref, xl_ref, dlt_ref, rm_ref, nr_ref, out_ref, *, eps):
    dot = functools.partial(lax.dot_general,
                            preferred_element_type=jnp.float32)
    XT = xT_ref[...]                                    # [dimp, N]
    Xl = xl_ref[...]                                    # [tn, dimp]
    dlt = dlt_ref[...].astype(jnp.float32)              # [tn, N]
    rm = rm_ref[...]                                    # [tn, 1]
    # keep nr a [1, 1] vector: a 0-d scalar read mixes vector<f32> with
    # f32 in arith.maximumf and fails Mosaic verification
    nr = nr_ref[...]                                    # [1, 1]
    tn, N = dlt.shape
    # distances, exactly dist_block's formula (models/wdamds.py): padded
    # dims are zero in BOTH Xl and X^T, so they add nothing to any norm
    x2 = (Xl * Xl).sum(axis=1, keepdims=True)           # [tn, 1]
    y2 = (XT * XT).sum(axis=0, keepdims=True)           # [1, N]
    cross = dot(Xl, XT, (((1,), (0,)), ((), ())))       # [tn, N]
    D = jnp.sqrt(jnp.maximum(x2 - 2.0 * cross + y2, 0.0))
    colm = (lax.broadcasted_iota(jnp.int32, (tn, N), 1).astype(jnp.float32)
            < nr).astype(jnp.float32)
    ratio = jnp.where(D > eps, dlt / jnp.maximum(D, eps), 0.0) * rm * colm
    # Guttman row block: off@X + diag_fix·Xl with off = −ratio
    bx = (-dot(ratio, XT, (((1,), (1,)), ((), ())))
          + ratio.sum(axis=1, keepdims=True) * Xl)      # [tn, dimp]
    out_ref[...] = bx / jnp.maximum(nr, 1.0)


def smacof_bx(delta_rows, row_mask, Xl, X, n_real, *, eps: float,
              tn: int | None = None, interpret: bool = False):
    """One fused Guttman row-block update: returns Xl_new [n_loc, dim].

    ``delta_rows`` [n_loc, N] f32/bf16, ``row_mask`` [n_loc] f32 (0 for
    padded rows), ``Xl`` [n_loc, dim] this worker's coordinate slice,
    ``X`` [N, dim] the replicated coordinates, ``n_real`` scalar live
    count — matching `models/wdamds.py:make_smacof_fn`'s ``body`` up to
    the coordinate reshard (which stays outside).
    """
    n_loc, N = delta_rows.shape
    dim = X.shape[1]
    dimp = _LANE
    dsize = jnp.dtype(delta_rows.dtype).itemsize
    if tn is None:
        tn = pick_tile(n_loc, N, dsize)
    if not interpret:
        if N % _LANE:
            raise ValueError(
                f"pallas wdamds: N={N} must be a multiple of {_LANE} on "
                f"TPU (use algo='xla' for odd shapes)")
        if tn % 8:
            raise ValueError(
                f"pallas wdamds: row tile tn={tn} must be a multiple of 8")
    if dim > dimp:
        raise ValueError(f"pallas wdamds: dim={dim} > {dimp} unsupported")
    if vmem_bytes(dimp, N, tn, dsize) > VMEM_BUDGET:
        raise ValueError(
            f"pallas wdamds: tile ({tn}, {N}) needs "
            f"{vmem_bytes(dimp, N, tn, dsize) / 2**20:.1f} MB > "
            f"{VMEM_BUDGET >> 20} MB VMEM budget; shrink tn "
            f"(perfmodel.presize picks a fitting tile)")
    nlp = tn * -(-n_loc // tn)
    Xt = jnp.pad(X.astype(jnp.float32),
                 ((0, 0), (0, dimp - dim))).T            # [dimp, N]
    Xl_p = jnp.pad(Xl.astype(jnp.float32),
                   ((0, nlp - n_loc), (0, dimp - dim)))
    dlt_p = jnp.pad(delta_rows, ((0, nlp - n_loc), (0, 0)))
    rm_p = jnp.pad(row_mask.astype(jnp.float32).reshape(n_loc, 1),
                   ((0, nlp - n_loc), (0, 0)))
    nr = jnp.asarray(n_real, jnp.float32).reshape(1, 1)
    out = pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=(nlp // tn,),
        in_specs=[
            pl.BlockSpec((dimp, N), lambda i: (0, 0)),
            pl.BlockSpec((tn, dimp), lambda i: (i, 0)),
            pl.BlockSpec((tn, N), lambda i: (i, 0)),
            pl.BlockSpec((tn, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tn, dimp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nlp, dimp), jnp.float32),
        interpret=interpret,
    )(Xt, Xl_p, dlt_p, rm_p, nr)
    return out[:n_loc, :dim]
