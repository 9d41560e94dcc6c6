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

Grid/memory plan (1-D sequential grid over a CHUNK LIST — chunking rides
the grid because Mosaic supports neither value-level dynamic_slice nor
mixed int+ds ref reads in-kernel):
- An entry (≤ entry_cap ratings of one u_tile × i_tile sub-tile) is
  staged as only the ``chunk_c``-wide chunks that hold its ratings:
  ``ceil(count / chunk_c)`` adjacent grid steps, not the widest entry's
  worth.  A step costs ~0.7 µs whatever it holds, so the time of an
  epoch is its number of chunks (PERF.md §6, PR 26).  One packed i32 a
  chunk, scalar-prefetched, says what the step is: the W block, the H
  tile, and whether it opens / closes its entry (:func:`pack_chunk_meta`).
- The resident H half-slice rides whole in VMEM (copied in at step 0,
  flushed once at the end); the chunk's H tile index addresses it with
  ``pl.ds``.
- W streams as [R, u_tile] blocks chosen by the prefetched block index.
  Host prep guarantees each W block occupies ONE contiguous run of grid
  steps (entries are tile-sorted u-major and ``insert_coverage_entries``
  inserts a no-op chunk for each empty block), so accumulated updates
  stay in the live VMEM output buffer for the whole run and every output
  block is written at least once — correctness never depends on buffer
  aliasing or on cross-run revisit ordering.
- Entry-snapshot state (tile snapshots + gradient accumulators) lives in
  VMEM scratch, which persists across the sequential grid: every chunk
  scores against the entry-start factors and ONE apply lands per entry —
  update order IDENTICAL to the XLA dense path (same entries, same
  sequence), so results match it to accumulation-order rounding.  The
  chunks left out were all padding and added exact zeros.

Why it wins: the dense path's per-entry one-hot operands and [C, rank]
intermediates round-trip HBM between fusions, ~8 MB/entry at the ML-20M
tiling vs ~0.5 MB of tile traffic here.  Measured numbers: PERF.md.
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

# One i32 of metadata a chunk, scalar-prefetched whole into SMEM (0.53 MB
# for the 134k chunks of an ML-20M×4 half-slice, where three separate
# arrays would not fit beside it).  Bit budget, low to high: opens-entry,
# closes-entry, 12 bits of H tile index (4096 tiles; the VMEM check below
# stops a resident half-slice far sooner), 17 bits of W block index
# (131,072 blocks = 16.7M users a worker at the narrowest legal tile);
# the sign bit stays clear so the shifts below need no mask.
_OPENS, _CLOSES = 1, 2
_HT_SHIFT, _HT_BITS = 2, 12
_BLK_SHIFT, _BLK_BITS = 14, 17
# SMEM is 1 MiB on a v5e and the metadata is prefetched whole: 260,000
# chunks compile and 400,000 are refused (compile-only client, PR 26)
_MAX_CHUNKS = 250_000


def pack_chunk_meta(blk, hti, opens, closes):
    """Host side: per-chunk W block index, H tile index and entry
    open / close flags → one i32 each.  Raises over the bit budget."""
    blk, hti = np.asarray(blk, np.int64), np.asarray(hti, np.int64)
    for name, v, bits in (("W block", blk, _BLK_BITS),
                          ("H tile", hti, _HT_BITS)):
        if v.size and (v.min() < 0 or v.max() >> bits):
            raise ValueError(
                f"pallas mfsgd: {name} index {int(v.max())} does not fit "
                f"the {bits} bits the packed chunk metadata gives it "
                f"(shard over more workers or use algo='dense')")
    return (blk << _BLK_SHIFT | hti << _HT_SHIFT
            | np.where(closes, _CLOSES, 0) | np.where(opens, _OPENS, 0)
            ).astype(np.int32)


def unpack_chunk_meta(meta):
    """``(blk, hti, opens, closes)`` of packed metadata — numpy arrays on
    the host, a traced i32 scalar inside the kernel and its index maps."""
    return (meta >> _BLK_SHIFT, (meta >> _HT_SHIFT) & ((1 << _HT_BITS) - 1),
            (meta & _OPENS) != 0, (meta & _CLOSES) != 0)


def _kernel(meta_ref, w_in, h_in, cu_ref, ci_ref, cv_ref,
            w_out, h_out, se_ref, cnt_ref, wsnap, hsnap, gw_acc, gh_acc,
            *, lr, reg, i_tile, compute_dtype):
    R, UR = w_in.shape
    IR = i_tile
    cc = cu_ref.shape[-1]
    c = pl.program_id(0)   # chunk

    blk, hti, opens, closes = unpack_chunk_meta(meta_ref[c])
    prev = unpack_chunk_meta(meta_ref[jnp.maximum(c - 1, 0)])[0]

    @pl.when(c == 0)
    def _init():
        h_out[...] = h_in[...]
        se_ref[...] = jnp.zeros_like(se_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    # First chunk of this W block's contiguous run (it opens an entry: an
    # entry's chunks share its block): seed the output buffer from the
    # pristine input block.  Later entries of the run read back their
    # predecessors' updates from the (still-resident) output buffer.
    @pl.when((c == 0) | (blk != prev))
    def _start_run():
        w_out[...] = w_in[...]

    toi = pl.multiple_of(hti * IR, IR)

    # Entry start: snapshot the tiles (all chunks score against the
    # entry-start factors, matching the XLA dense path's whole-entry
    # snapshot) and zero the gradient accumulators.  Scratch persists
    # across the sequential grid, so the state survives the chunk steps.
    @pl.when(opens)
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
    @pl.when(closes)
    def _end_entry():
        w_out[...] = wsnap[...] + lr * gw_acc[...]
        h_out[:, pl.ds(toi, IR)] = hsnap[...] + lr * gh_acc[...]


def sgd_tile_update(Wt, Ht, cu, ci, cv, meta, *, lr, reg, u_tile, i_tile,
                    compute_dtype=jnp.bfloat16, interpret: bool = False):
    """One rotation-step block update on transposed factors.

    ``Wt`` [R, u_bound] / ``Ht`` [R, ib2] f32; ``cu/ci`` [NCH, cc]
    tile-local ids of one chunk each (pad = tile width); ``cv`` [NCH, cc]
    values; ``meta`` [NCH] packed per-chunk metadata
    (:func:`pack_chunk_meta`).  The chunk list MUST be u-major with full
    W-block coverage and each entry's chunks adjacent, the first opening
    and the last closing it — :func:`insert_coverage_entries` builds it
    from ``partition_ratings_tiles``' entries.
    Returns ``(Wt', Ht', se, cnt)`` matching
    ``mfsgd._tile_block_update``'s math entry-for-entry.
    """
    R, UB = Wt.shape
    _, IB = Ht.shape
    NCH, cc = cu.shape
    if not interpret:
        for name, v, m in (("u_tile", u_tile, _LANE),
                           ("i_tile", i_tile, _LANE), ("chunk", cc, _LANE),
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
    if NCH > _MAX_CHUNKS:
        raise ValueError(
            f"pallas mfsgd: {NCH} chunks a half-slice > {_MAX_CHUNKS}, the "
            f"metadata the kernel can prefetch into SMEM; shard over more "
            f"workers or use algo='dense'")
    if UB // u_tile > 1 << _BLK_BITS or IB // i_tile > 1 << _HT_BITS:
        raise ValueError(
            f"pallas mfsgd: {UB // u_tile} W blocks / {IB // i_tile} H tiles "
            f"exceed the packed chunk metadata's {_BLK_BITS} / {_HT_BITS} "
            f"bits; shard over more workers or use algo='dense'")

    # 1-D grid over the chunk list.  Chunking rides the grid (not an
    # in-kernel loop — Mosaic supports neither value-level dynamic_slice
    # nor mixed int+ds ref reads); entry-snapshot state lives in scratch,
    # which persists across the sequential grid steps.
    def w_block(c, m):
        return 0, unpack_chunk_meta(m[c])[0]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(NCH,),
        in_specs=[
            pl.BlockSpec((R, u_tile), w_block),
            pl.BlockSpec((R, IB), lambda c, m: (0, 0)),
            # chunk streams ride [NCH, 1, cc]: Mosaic requires block dim -2
            # to divide 8 or equal the array dim — (1, cc) over [NCH, cc]
            # is illegal, (1, 1, cc) over [NCH, 1, cc] is exact in dim -2
            pl.BlockSpec((1, 1, cc), lambda c, m: (c, 0, 0)),
            pl.BlockSpec((1, 1, cc), lambda c, m: (c, 0, 0)),
            pl.BlockSpec((1, 1, cc), lambda c, m: (c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((R, u_tile), w_block),
            pl.BlockSpec((R, IB), lambda c, m: (0, 0)),
            pl.BlockSpec((1, 1), lambda c, m: (0, 0)),
            pl.BlockSpec((1, 1), lambda c, m: (0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((R, u_tile), jnp.float32),  # W snapshot
            pltpu.VMEM((R, i_tile), jnp.float32),  # H snapshot
            pltpu.VMEM((R, u_tile), jnp.float32),  # gW accumulator
            pltpu.VMEM((R, i_tile), jnp.float32),  # gH accumulator
        ],
    )
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
    )(meta, Wt, Ht, cu.reshape(NCH, 1, cc), ci.reshape(NCH, 1, cc),
      cv.reshape(NCH, 1, cc))
    return Wt2, Ht2, se[0, 0], cnt[0, 0]


def insert_coverage_entries(eu, ei, ev, ou, oi, u_bound, u_tile, i_tile,
                            chunk_c=512):
    """Host prep: ``partition_ratings_tiles``' ``[WS, NE, C]`` entries →
    the kernel's chunk list ``cu/ci/cv [WS, NCH, cc]`` + ``meta [WS, NCH]``
    (numpy, worker-major).

    An entry contributes only the ``cc``-wide chunks that hold its
    ratings (``ceil(count / cc)``, adjacent, the first opening and the
    last closing it); ``cc`` is ``chunk_c``, or C rounded up to the 128
    lanes the kernel's TPU gate asks for when one chunk holds the widest
    entry.  Guarantees, per row: (a) every W block ``0..u_bound/u_tile``
    appears at least once — an empty one as ONE all-pad chunk (ids = tile
    width; the kernel's mask turns it into a pure copy-through step) at
    the H tile of the entry before it, (b) entries stay u-major so each
    block is one contiguous grid run, (c) rows shorter than the longest
    end in no-op chunks that repeat the last entry's offsets (never jump
    back to block 0).
    """
    ws, ne, c = eu.shape
    cc = chunk_c if c > chunk_c else _LANE * -(-c // _LANE)
    nblk = u_bound // u_tile
    valid = eu < u_tile
    counts = valid.sum(-1)
    # the chunks past ceil(count / cc) are dropped unread: they must hold
    # no rating, so the valid slots must lead each entry — its last one
    # sits at count - 1 (no [WS, NE, C] temporary: this runs on gigabytes)
    if (np.where(counts > 0, c - valid[..., ::-1].argmax(-1), 0)
            != counts).any():
        raise ValueError("valid slots must lead each entry")
    # Per row, its items in grid order — real entries and one no-op for
    # each empty W block (stable sort: a block with entries has no no-op,
    # so ties are entries of one block and keep their order) — as rows of
    # source entry (-1 = no-op), W block, H tile, chunks.
    rows = []
    for w in range(ws):
        nreal = int((counts[w] > 0).sum())
        if not (counts[w, :nreal] > 0).all():
            raise ValueError("real entries must be a prefix")
        blks = ou[w, :nreal] // u_tile
        empty = np.setdiff1d(np.arange(nblk), blks)
        before = np.searchsorted(blks, empty)  # entries ahead of the no-op
        items = np.concatenate([
            [np.arange(nreal), blks, oi[w, :nreal] // i_tile,
             -(-counts[w, :nreal] // cc)],
            [np.full(empty.size, -1), empty,
             np.where(before > 0, oi[w, before - 1] // i_tile, 0),
             np.ones(empty.size, np.int64)]], axis=1)
        rows.append(items[:, np.argsort(items[1], kind="stable")])
    nch = max(int(items[3].sum()) for items in rows)
    # Pad slots need only cu = u_tile: the u-side mask (cm) and the all-zero
    # one-hot column zero out every W/H contribution whatever ci/cv hold.
    cu = np.full((ws, nch, cc), u_tile, eu.dtype)
    ci = np.zeros((ws, nch, cc), ei.dtype)
    cv = np.zeros((ws, nch, cc), ev.dtype)
    meta = np.empty((ws, nch), np.int32)
    for w, items in enumerate(rows):
        # a shorter row ends in more no-ops, at its last item's offsets
        tail = nch - int(items[3].sum())
        src, blk, hti, k = np.concatenate(
            [items, np.tile([[-1], [items[1, -1]], [items[2, -1]], [1]],
                            tail)], axis=1)
        first = np.cumsum(k) - k                   # an item's first chunk
        item = np.repeat(np.arange(k.size), k)     # a chunk's item
        j = np.arange(nch) - first[item]           # its place in the entry
        meta[w] = pack_chunk_meta(blk[item], hti[item], j == 0,
                                  j == k[item] - 1)
        # the j-th chunks of every entry that has one, a pass each
        for jj in range(int(k.max())):
            sel = np.flatnonzero((src >= 0) & (k > jj))
            lo, hi = jj * cc, min((jj + 1) * cc, c)
            for dst, a in ((cu, eu), (ci, ei), (cv, ev)):
                dst[w][first[sel] + jj, :hi - lo] = a[w][src[sel], lo:hi]
    return cu, ci, cv, meta
