"""Dense one-hot MXU label histogram — Pallas TPU kernel for RF growth.

Reference parity: Harp's ``edu.iu.rf`` level-wise histogram growth
(SURVEY.md §3.4), in-tree as the XLA ``hist_algo="dense"`` path
(`models/rf.py:_grow_level`).  The dense arm already replaced the
25 GB/s TPU scatter with a one-hot int8 MXU matmul (CLAUDE.md trap
list), but XLA materialises the [n, node·C] one-hot lhs in HBM every
level before the contraction reads it back — and its contraction
``(((0,), (0,)), ((), ()))`` (sublanes of BOTH) is exactly the pattern
Mosaic has no legal lowering for, so it cannot be ported as-is.  This
kernel builds the one-hot TRANSPOSED per tile in VMEM and accumulates
bins on-chip: the [node·C, tn] one-hot never exists in HBM and the
contraction becomes the legal lanes × sublanes pattern —

    nc   [nodeCp, tn]  = (iota_rows == node·C + y) · w   (VPU, int8)
    hist [nodeCp, fB] += nc · BO [tn, fB]     (A-lanes × B-sublanes, MXU)

Grid/memory plan (1-D sequential grid over sample tiles): the int8 BO
bin one-hots and the fused row codes / weights stream tn samples at a
time; the [nodeCp, fB] int32 histogram output zero-inits at step 0 and
accumulates across the sequential grid (`ops/mfsgd_kernel.py`
precedent).  Integer products ≤ 127 summed in int32 — counts are
BIT-IDENTICAL to the dense XLA arm (asserted in tests/test_rf_kernel.py),
so the ``hist_algo="pallas"`` knob changes no model output, only the
memory schedule.  Padded samples carry the row-code sentinel nodeCp
(outside the iota range) AND weight 0, so they never count.

Expected headroom (analytic, 2026-08-06 — NOT yet a measurement; the
tile comes from ``perfmodel.presize("rf.hist_bins", ...)`` and the
kernel is Mosaic-proven via HL201 only): removes the per-level
[n, node·C] one-hot HBM round-trip (the operand traffic the mfsgd
kernel removed for the same pattern).  First ran on a chip 2026-09-26
(chip_smoke.py: compiles, agrees with its reference; TPU v5 lite) —
correctness only.  A TPU speed measurement goes in BASELINE.md when flip
candidate ``rf_hist_pallas`` is measured
— until then prefer ``hist_algo="dense"``, whose numbers are real.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

_LANE = 128
# streamed BO tiles + the transposed one-hot + the resident histogram
# must fit beside Mosaic's own buffers; 14 MB leaves ~2 MB slack under
# the 16 MB/core ceiling the registry test pins.
VMEM_BUDGET = 14 << 20
TILE_CANDIDATES = (4096, 2048, 1024, 512, 256, 128)


def vmem_bytes(tn: int, fB: int, nodeCp: int) -> int:
    """Analytic VMEM byte model (also what ``perfmodel.presize``
    consults): double-buffered int8 BO tile + row-code/weight streams +
    the iota/one-hot registers + resident int32 histogram + slack."""
    return (2 * tn * fB             # double-buffered int8 BO tile
            + 4 * tn * 4            # row-code + weight tiles (i32, ×2)
            + nodeCp * tn           # transposed int8 one-hot
            + nodeCp * tn * 4      # its int32 iota/compare register
            + nodeCp * fB * 4      # resident histogram accumulator
            + (64 << 10))


def fit_tiles(fB: int, nodeCp: int, budget: int = VMEM_BUDGET) -> list[int]:
    """Sample-tile candidates whose working set fits the VMEM budget."""
    return [t for t in TILE_CANDIDATES if vmem_bytes(t, fB, nodeCp) <= budget]


def pick_tile(n: int, fB: int, nodeCp: int) -> int:
    """Largest fitting tile no wider than the (padded) sample count —
    the rule ``perfmodel.presize`` reproduces from the price model
    (per-grid-program overhead is monotone in 1/tn)."""
    fits = fit_tiles(fB, nodeCp)
    if not fits:
        raise ValueError(
            f"pallas rf: no sample tile fits fB={fB}, nodeCp={nodeCp} "
            f"under the {VMEM_BUDGET >> 20} MB VMEM budget; use "
            f"hist_algo='dense'")
    cap = _LANE * -(-max(n, 1) // _LANE)
    small = [t for t in fits if t <= cap]
    return max(small) if small else min(fits)


def _kernel(bo_ref, rc_ref, w_ref, hist_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    nodeCp = hist_ref.shape[0]
    rc = rc_ref[...]                                    # [1, tn] i32
    wt = w_ref[...]                                     # [1, tn] i32
    tn = rc.shape[-1]
    # transposed weighted one-hot, built in VMEM: pad samples carry the
    # sentinel rc = nodeCp (never matches iota ∈ [0, nodeCp)) and w = 0
    nc = ((lax.broadcasted_iota(jnp.int32, (nodeCp, tn), 0) == rc)
          .astype(jnp.int32) * wt).astype(jnp.int8)     # [nodeCp, tn]
    hist_ref[...] += lax.dot_general(
        nc, bo_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)               # [nodeCp, fB]


def hist_bins(BO, rowcode, weights, n_node_classes: int, *,
              tn: int | None = None, interpret: bool = False):
    """Weighted label histogram over bin one-hots: returns
    ``hist [n_node_classes, fB] int32`` with
    hist[r, c] = Σ_i 1[rowcode_i == r] · w_i · BO[i, c] — bit-identical
    to `models/rf.py:_grow_level`'s dense int8 matmul arm.

    ``BO`` [n, fB] int8 bin one-hots, ``rowcode`` [n] int32
    (node·C + y), ``weights`` [n] int32 already clipped to [0, 127].
    """
    n, fB = BO.shape
    nodeCp = 8 * -(-n_node_classes // 8)
    if tn is None:
        tn = pick_tile(n, fB, nodeCp)
    if not interpret:
        for name, v, m in (("feature·bin width fB", fB, _LANE),
                           ("sample tile tn", tn, _LANE)):
            if v % m:
                raise ValueError(
                    f"pallas rf: {name}={v} must be a multiple of {m} on "
                    f"TPU (use hist_algo='dense' for odd shapes)")
    if vmem_bytes(tn, fB, nodeCp) > VMEM_BUDGET:
        raise ValueError(
            f"pallas rf: tile ({tn}, {fB}) at nodeCp={nodeCp} needs "
            f"{vmem_bytes(tn, fB, nodeCp) / 2**20:.1f} MB > "
            f"{VMEM_BUDGET >> 20} MB VMEM budget; shrink tn "
            f"(perfmodel.presize picks a fitting tile)")
    n_pad = tn * -(-n // tn)
    BO_p = jnp.pad(BO, ((0, n_pad - n), (0, 0)))
    rc_p = jnp.pad(rowcode.astype(jnp.int32), (0, n_pad - n),
                   constant_values=nodeCp).reshape(1, n_pad)
    w_p = jnp.pad(weights.astype(jnp.int32), (0, n_pad - n)).reshape(1, n_pad)
    hist = pl.pallas_call(
        _kernel,
        grid=(n_pad // tn,),
        in_specs=[
            pl.BlockSpec((tn, fB), lambda i: (i, 0)),
            pl.BlockSpec((1, tn), lambda i: (0, i)),
            pl.BlockSpec((1, tn), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((nodeCp, fB), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((nodeCp, fB), jnp.int32),
        interpret=interpret,
    )(BO_p, rc_p, w_p)
    return hist[:n_node_classes]
