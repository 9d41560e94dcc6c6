"""Fused MF-SGD dense-tile update — Pallas TPU kernel.

Reference parity: the MF-SGD inner loop Harp-DAAL ran inside Intel DAAL's
C++ kernel (SURVEY.md §3.2, §4.3).  The in-tree XLA ``algo="dense"`` path
(`models/mfsgd.py:_tile_block_update`) already replaced TPU scatter with
one-hot MXU matmuls; this kernel fuses one whole entry update — one-hot
build, two gather dots, error/gradient math, two scatter dots, W/H tile
apply — into a single VMEM-resident Pallas program, so the ~4 MB of
one-hot operands and [C, rank] intermediates per entry never round-trip
HBM between XLA fusions.

Layout (follows the hard-won notes in ``ops/kmeans_kernel.py``): all
arrays live transposed, rank-major — W^T [R, u_bound], H^T [R, ib2] —
so every matmul contracts over lanes (or A-lanes with B-sublanes, the
other legal Mosaic pattern) and only ONE one-hot orientation per side is
ever built:

    ohu  [u_tile, C]  = (iota_rows == cu_row)           (VPU, in VMEM)
    wuT  [R, C]   = WbT [R, u_tile] @ ohu                (A-lane × B-sublane)
    gWT  [R, u_tile] = gwT [R, C] @ ohu  (contract lanes of BOTH)

Grid/memory plan (2-D sequential grid: entries × token chunks — chunking
rides the grid because Mosaic supports neither value-level dynamic_slice
nor mixed int+ds ref reads in-kernel):
- The resident H half-slice rides whole in VMEM (copied in at step 0,
  flushed once at the end); entry ``oi`` offsets index it with ``pl.ds``.
- W streams as [R, u_tile] blocks chosen by a scalar-prefetched block
  index (``ou // u_tile``).  Host prep guarantees each W block occupies
  ONE contiguous run of grid steps (entries are tile-sorted u-major and
  ``insert_coverage_entries`` inserts no-op entries for empty blocks), so
  accumulated updates stay in the live VMEM output buffer for the whole
  run and every output block is written at least once — correctness never
  depends on buffer aliasing or on cross-run revisit ordering.
- Entry-snapshot state (tile snapshots + gradient accumulators) lives in
  VMEM scratch, which persists across the sequential grid: every chunk
  scores against the entry-start factors and ONE apply lands per entry —
  update order IDENTICAL to the XLA dense path (same entries, same
  sequence), so results match it to accumulation-order rounding.

Why it wins: the dense path's per-entry one-hot operands and [C, rank]
intermediates round-trip HBM between fusions, ~8 MB/entry at the ML-20M
tiling vs ~0.5 MB of tile traffic here.  Measured 2026-08-01 (1× v5e,
ML-20M shape, 256×256 tiles): 245.9M updates/s/chip = 2.96× dense at
identical RMSE (BASELINE.md) — the default ``MFSGDConfig.algo`` since.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANE = 128


def _kernel(ou_blk_ref, oi_ref, w_in, h_in, cu_ref, ci_ref, cv_ref,
            w_out, h_out, se_ref, cnt_ref, wsnap, hsnap, gw_acc, gh_acc,
            *, lr, reg, i_tile, compute_dtype):
    R, UR = w_in.shape
    IR = i_tile
    cc = cu_ref.shape[-1]
    e = pl.program_id(0)   # entry
    j = pl.program_id(1)   # chunk within entry
    nc = pl.num_programs(1)

    blk = ou_blk_ref[e]
    prev = ou_blk_ref[jnp.maximum(e - 1, 0)]

    @pl.when((e == 0) & (j == 0))
    def _init():
        h_out[...] = h_in[...]
        se_ref[...] = jnp.zeros_like(se_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    # First entry of this W block's contiguous run: seed the output buffer
    # from the pristine input block.  Later entries of the run read back
    # their predecessors' updates from the (still-resident) output buffer.
    @pl.when(((e == 0) | (blk != prev)) & (j == 0))
    def _start_run():
        w_out[...] = w_in[...]

    toi = pl.multiple_of(oi_ref[e], IR)

    # Entry start: snapshot the tiles (all chunks score against the
    # entry-start factors, matching the XLA dense path's whole-entry
    # snapshot) and zero the gradient accumulators.  Scratch persists
    # across the sequential grid, so the state survives the chunk steps.
    @pl.when(j == 0)
    def _start_entry():
        wsnap[...] = w_out[...]
        hsnap[...] = h_out[:, pl.ds(toi, IR)]
        gw_acc[...] = jnp.zeros_like(gw_acc)
        gh_acc[...] = jnp.zeros_like(gh_acc)

    cd = compute_dtype
    dot = functools.partial(lax.dot_general,
                            preferred_element_type=jnp.float32)
    Wb_c = wsnap[...].astype(cd)
    Hb_c = hsnap[...].astype(cd)
    cu = cu_ref[...].reshape(1, cc)                    # [1, cc] i32
    ci = ci_ref[...].reshape(1, cc)
    cv = cv_ref[...].reshape(1, cc)                    # [1, cc] f32

    ohu = (lax.broadcasted_iota(jnp.int32, (UR, cc), 0) == cu
           ).astype(cd)                                # [UR, cc]
    ohi = (lax.broadcasted_iota(jnp.int32, (IR, cc), 0) == ci
           ).astype(cd)                                # [IR, cc]
    wuT = dot(Wb_c, ohu, (((1,), (0,)), ((), ())))     # [R, cc] gather
    hiT = dot(Hb_c, ohi, (((1,), (0,)), ((), ())))
    cm = (cu < UR).astype(jnp.float32)                 # pad slots drop out
    err = cm * (cv - (wuT * hiT).sum(0, keepdims=True))
    gwT = (err * hiT - reg * cm * wuT).astype(cd)      # [R, cc]
    ghT = (err * wuT - reg * cm * hiT).astype(cd)
    gw_acc[...] += dot(gwT, ohu, (((1,), (1,)), ((), ())))  # [R, UR]
    gh_acc[...] += dot(ghT, ohi, (((1,), (1,)), ((), ())))
    se_ref[...] += (err * err).sum().reshape(1, 1)
    cnt_ref[...] += cm.sum().reshape(1, 1)

    # Entry end: one apply per entry, from the snapshot — identical update
    # order to the XLA dense path.
    @pl.when(j == nc - 1)
    def _end_entry():
        w_out[...] = wsnap[...] + lr * gw_acc[...]
        h_out[:, pl.ds(toi, IR)] = hsnap[...] + lr * gh_acc[...]


def sgd_tile_update(Wt, Ht, eu, ei, ev, ou, oi, *, lr, reg, u_tile, i_tile,
                    compute_dtype=jnp.bfloat16, chunk_c=512,
                    interpret: bool = False):
    """One rotation-step block update on transposed factors.

    ``Wt`` [R, u_bound] / ``Ht`` [R, ib2] f32; ``eu/ei`` [NE, C] tile-local
    ids (pad = tile width); ``ev`` [NE, C] values; ``ou/oi`` [NE] tile row
    offsets.  Entries MUST be u-major with full W-block coverage — run
    host arrays through :func:`insert_coverage_entries` first.
    Returns ``(Wt', Ht', se, cnt)`` matching
    ``mfsgd._tile_block_update``'s math entry-for-entry.
    """
    R, UB = Wt.shape
    _, IB = Ht.shape
    NE, C = eu.shape
    cc = min(C, chunk_c)
    if C % cc:
        raise ValueError(f"C={C} not a multiple of chunk_c={cc}; pad "
                         f"entries with insert_coverage_entries first")
    if not interpret:
        for name, v, m in (("u_tile", u_tile, _LANE),
                           ("i_tile", i_tile, _LANE), ("C chunk", cc, _LANE),
                           ("rank", R, 8)):
            if v % m:
                raise ValueError(
                    f"pallas mfsgd: {name}={v} must be a multiple of {m} "
                    f"on TPU (use algo='dense' for odd shapes)")
    # the kernel keeps TWO resident H copies in VMEM (h_in + h_out) plus
    # ~2 MB of W blocks/one-hots/entry streams — budget both copies
    if 2 * IB * R * 4 > 10 << 20:
        raise ValueError(
            f"pallas mfsgd: resident H half-slice is {IB * R * 4 / 2**20:.1f}"
            f" MB ×2 VMEM copies > 10 MB VMEM budget; shard over more "
            f"workers or use algo='dense'")

    # 2-D grid: entries × chunks.  Chunking rides the grid (not an
    # in-kernel loop — Mosaic supports neither value-level dynamic_slice
    # nor mixed int+ds ref reads); entry-snapshot state lives in scratch,
    # which persists across the sequential grid steps.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(NE, C // cc),
        in_specs=[
            pl.BlockSpec((R, u_tile), lambda e, j, ob, oo: (0, ob[e])),
            pl.BlockSpec((R, IB), lambda e, j, ob, oo: (0, 0)),
            # entry streams ride [NE, 1, C]: Mosaic requires block dim -2
            # to divide 8 or equal the array dim — (1, cc) over [NE, C]
            # is illegal, (1, 1, cc) over [NE, 1, C] is exact in dim -2
            pl.BlockSpec((1, 1, cc), lambda e, j, ob, oo: (e, 0, j)),
            pl.BlockSpec((1, 1, cc), lambda e, j, ob, oo: (e, 0, j)),
            pl.BlockSpec((1, 1, cc), lambda e, j, ob, oo: (e, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((R, u_tile), lambda e, j, ob, oo: (0, ob[e])),
            pl.BlockSpec((R, IB), lambda e, j, ob, oo: (0, 0)),
            pl.BlockSpec((1, 1), lambda e, j, ob, oo: (0, 0)),
            pl.BlockSpec((1, 1), lambda e, j, ob, oo: (0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((R, u_tile), jnp.float32),  # W snapshot
            pltpu.VMEM((R, i_tile), jnp.float32),  # H snapshot
            pltpu.VMEM((R, u_tile), jnp.float32),  # gW accumulator
            pltpu.VMEM((R, i_tile), jnp.float32),  # gH accumulator
        ],
    )
    ou_blk = (ou // u_tile).astype(jnp.int32)
    Wt2, Ht2, se, cnt = pl.pallas_call(
        functools.partial(_kernel, lr=lr, reg=reg, i_tile=i_tile,
                          compute_dtype=compute_dtype),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((R, UB), jnp.float32),
            jax.ShapeDtypeStruct((R, IB), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(ou_blk, oi.astype(jnp.int32),
      Wt, Ht, eu.reshape(NE, 1, C), ei.reshape(NE, 1, C),
      ev.reshape(NE, 1, C))
    return Wt2, Ht2, se[0, 0], cnt[0, 0]


def insert_coverage_entries(eu, ei, ev, ou, oi, u_bound, u_tile,
                            chunk_c=512):
    """Host prep: make entry lists kernel-safe (numpy, worker-major).

    Guarantees, per [WS, NE, C] row: (a) every W block ``0..u_bound/u_tile``
    appears at least once, (b) entries stay u-major so each block is one
    contiguous grid run, (c) trailing pads repeat the last entry's offsets
    (never jump back to block 0), (d) C is a multiple of ``chunk_c`` when
    it exceeds it.  Inserted entries are all-pad (ids = tile width) — the
    kernel's mask turns them into pure copy-through steps.
    """
    ws, ne, c = eu.shape
    # C must satisfy the kernel's TPU lane gate (multiples of 128) at ANY
    # size — small-corpus C values like 200 otherwise pass coverage
    # unpadded and fail at first Mosaic compile (caught by review,
    # 2026-07-31); above chunk_c it must also be a chunk multiple
    # (chunk_c is itself a 128-multiple, so both cases satisfy the gate)
    if c > chunk_c:
        c2 = chunk_c * -(-c // chunk_c)
    else:
        c2 = 128 * -(-c // 128)
    nblk = u_bound // u_tile
    # Per row: list of (src_entry_index | None, ou, oi); None = inserted pad.
    rows: list[list[tuple]] = []
    for w in range(ws):
        real = (eu[w] < u_tile).any(axis=-1)
        nreal = int(real.sum())
        assert real[:nreal].all(), "real entries must be a prefix"
        blks = ou[w, :nreal] // u_tile
        out: list[tuple] = []
        last_oi = 0
        for b in range(nblk):
            sel = np.nonzero(blks == b)[0]
            if sel.size:
                out.extend((int(s), int(ou[w, s]), int(oi[w, s]))
                           for s in sel)
                last_oi = int(oi[w, sel[-1]])
            else:
                out.append((None, b * u_tile, last_oi))
        rows.append(out)
    ne2 = max(len(r) for r in rows)
    # Pad slots need only eu = u_tile: the u-side mask (cm) and the all-zero
    # one-hot column zero out every W/H contribution whatever ei/ev hold.
    eu2 = np.full((ws, ne2, c2), u_tile, eu.dtype)
    ei2 = np.zeros((ws, ne2, c2), ei.dtype)
    ev2 = np.zeros((ws, ne2, c2), ev.dtype)
    ou2 = np.zeros((ws, ne2), np.int32)
    oi2 = np.zeros((ws, ne2), np.int32)
    for w, out in enumerate(rows):
        for j, (src, rou, roi) in enumerate(out):
            ou2[w, j], oi2[w, j] = rou, roi
            if src is not None:
                eu2[w, j, :c] = eu[w, src]
                ei2[w, j, :c] = ei[w, src]
                ev2[w, j, :c] = ev[w, src]
        # tail pads: repeat the last entry's offsets (never jump back to
        # block 0 — that would break run contiguity)
        if len(out) < ne2:
            ou2[w, len(out):] = out[-1][1]
            oi2[w, len(out):] = out[-1][2]
    return eu2, ei2, ev2, ou2, oi2
