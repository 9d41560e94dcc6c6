"""MF-SGD (matrix factorization) — graded config #2: MovieLens-20M, rotate.

Reference parity (SURVEY.md §3.4, §4.3): Harp's ``edu.iu.sgd`` (and DAAL
variant ``edu.iu.daal_sgd``) factorizes the ratings matrix R ≈ W·Hᵀ with the
signature model-rotation pattern: each worker owns a user-range of R and W;
H is split into one slice per worker; slices travel the ring (``rotate``)
while ``edu.iu.dymoro.Rotator`` prefetches and a timer-bounded
``DynamicScheduler`` runs Hogwild-style SGD threads on the resident slice.

TPU-native design:
- Host preprocessing partitions the rating triples into an N×N grid of
  (user-range, item-slice) blocks, padded to a common size — the TPU
  analogue of Harp's per-worker rating store (static shapes for XLA).
- One epoch = ``rotate_pipeline`` over the H slices; at rotation step t a
  worker trains on the block matching its resident slice
  (``resident_slice_index``) — every rating is visited exactly once per
  epoch, just like Harp.
- Hogwild async updates become deterministic *mini-batched* SGD
  (SURVEY.md §8 hard parts).  Two formulations, selected by
  ``MFSGDConfig.algo``:

  * ``"dense"`` (default): each block re-tiles into (u_tile × i_tile)
    sub-tiles; row gathers AND duplicate-summing scatters are one-hot
    matmuls over ``dynamic_slice``\\ d W/H tiles — four MXU dots per entry,
    no XLA scatter anywhere.  TPU scatter of rank-64 rows moves ~25 GB/s;
    the same permutations as matmuls measured 84–102M updates/s/chip vs
    26.3M (ML-20M config, 1× v5e, 2026-07-30).
  * ``"scatter"``: direct ``lax.scan`` over fixed-size chunks with
    gather / scatter-add — the readable reference implementation, and the
    exact-equivalence target for the numpy golden tests.

  Convergence is validated by loss curve, not bitwise (the reference is
  nondeterministic anyway).
- The timer-bound lockstep is free: SPMD workers advance together.
"""

from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from harp_tpu.ops.pallas_compat import interpret_default
from harp_tpu.parallel import collective as C
from harp_tpu.parallel.mesh import WorkerMesh, current_mesh
from harp_tpu.parallel.rotate import (ROTATE_WIRES, resident_chunk_index,
                                      rotate_pipeline)
from harp_tpu.utils import flightrec, prng, skew, telemetry


@dataclasses.dataclass
class MFSGDConfig:
    rank: int = 64
    lr: float = 0.01
    reg: float = 0.05  # λ, applied to touched rows only (as SGD does)
    # Update algorithm.  "dense" (default) re-tiles each rating block into
    # (u_tile × i_tile) sub-tiles and runs every gather/scatter as a one-hot
    # MXU matmul over dynamic-sliced W/H tiles — no XLA scatter anywhere.
    # "scatter" is the direct gather/scatter-add formulation, kept as the
    # readable reference and for exact-equivalence tests.  Measured on the
    # ML-20M graded config (rank 64, 1× v5e, 2026-07-30): dense 84–102M
    # updates/s/chip vs scatter 26.3M — TPU scatter of 256 B rows runs at
    # ~25 GB/s while the same permutation as matmuls rides the MXU.
    # "pallas" fuses the dense entry update into one VMEM-resident kernel
    # (ops/mfsgd_kernel.py) — same data layout and update order as "dense",
    # minus the HBM round trips between XLA fusions; needs 128-multiple
    # tiles and rank % 8 == 0 on TPU.  Default since 2026-08-01
    # (1× v5e, FLIP_DECISIONS.jsonl): 246.5M ups/s/chip at the swept
    # 256×256 auto-tiles vs 83.1M dense = 2.97× at identical rmse_final
    # (0.366, silicon-equivalence-gated; 188.1M = 2.26× pre-sweep at
    # 512 tiles); the trace shows the kernel absorbing the one-hot
    # operand traffic that made dense memory-bound at ~11% of HBM peak.
    algo: str = "pallas"
    # Tiling, auto per algo (None).  dense: 512×512 measured best on v5e
    # (84–102M ups vs 60–80M at 1024/2048 — one-hot traffic grows with
    # tile width and dominates before scan-step overhead does).  pallas:
    # 256×256 measured best 2026-08-01 (SWEEP_pallas.jsonl, 1× v5e,
    # ML-20M shapes, identical rmse_final 0.366): 250.2M ups/s vs
    # 195.5M at 512 and 147.3M at 128 — the kernel keeps one-hots in
    # VMEM, so smaller W/H tiles (less slice traffic per entry) win
    # until grid overhead bites.
    # None = auto, resolved at READ time by :func:`tiles` — not baked in
    # at construction, so ``dataclasses.replace(cfg, algo=...)`` keeps
    # the auto default tracking the new algo instead of freezing the
    # old algo's resolved value.
    u_tile: int | None = None
    i_tile: int | None = None
    # max ratings per dense entry (one snapshot, one apply: the minibatch);
    # overfull tiles split into several entries.  algo="dense" stages every
    # entry this wide or as wide as the heaviest tile, whichever is less;
    # the pallas kernel stages only the 512-wide chunks that hold ratings
    # (ops/mfsgd_kernel.py), so there it caps the minibatch alone
    entry_cap: int = 2048
    # dense matmul operand dtype: bf16 is MXU-native (gather/scatter one-hots
    # are exact 0/1 either way; W/H operands round to bf16 — noise well under
    # SGD's own stochasticity, validated by the convergence tests).  Golden
    # tests pin float32 to match numpy bit-for-bit on CPU.
    compute_dtype: Any = jnp.bfloat16
    # scatter algo: minibatch size inside a block; 32768 measured best on
    # 1× v5e (26.3M vs 14.4M ups/chip at 8192, identical RMSE).  Small
    # datasets are safe: blocks narrower than this clamp themselves
    # (partition_ratings pads only to the real max block size).
    chunk: int = 32768
    # algo="dense" only: carry the W tile across its tou-run instead of
    # slice+DUS per entry (the LDA carry_db lever — entries are u-major,
    # so a hot W block's entries currently re-pay the [u_tile, r] in+out
    # per entry).  The pallas kernel already keeps W resident across its
    # block runs, so this applies to the XLA path alone.  MEASURED
    # 2026-08-01 (1× v5e): 1.01× vs dense — no win (the analytic 20%
    # byte saving is hidden behind other traffic) — and the kernel
    # default supersedes it anyway; stays OFF.
    carry_w: bool = False
    # Rotation pipeline knobs (the chunked double-buffered rotator,
    # parallel/rotate.py).  rotate_chunks: H sub-slices per worker that
    # alternate compute/in-flight roles — None = auto (2: the historical
    # two-halves schedule; the generic pipeline at 2 chunks is
    # equivalence-pinned against it by tests/test_rotate_chunked.py).
    # More chunks shrink each ring transfer and expose finer overlap at
    # the cost of more scan steps — 4 chunks are not measured on a chip
    # yet, and the default stays 2 until they win there at equal rmse.
    # None = auto, resolved at READ time by
    # :func:`rotate_chunks_resolved` (same contract as :func:`tiles`).
    rotate_chunks: int | None = None
    # Ring payload for the in-flight chunk: "exact" (default — bit-exact
    # f32 ppermute), "bf16" or "int8" (collective.rotate_quantized: one
    # rounding per hop, ring-size-independent — noise of the same order
    # as SGD's own stochasticity, but the default stays exact until a
    # chip measurement flips it).
    rotate_wire: str = "exact"

    def __post_init__(self):
        if self.algo not in ("dense", "scatter", "pallas"):
            raise ValueError(
                f"algo must be 'dense', 'scatter' or 'pallas', got {self.algo!r}")
        if self.carry_w and self.algo != "dense":
            raise ValueError(
                "carry_w applies to algo='dense' only (the pallas kernel "
                "already keeps W resident across its block runs; scatter "
                "has no tile slicing to amortize)")
        if self.rotate_chunks is not None and self.rotate_chunks < 1:
            raise ValueError(
                f"rotate_chunks must be >= 1, got {self.rotate_chunks}")
        if self.rotate_wire not in ROTATE_WIRES:
            raise ValueError(
                f"rotate_wire must be one of {ROTATE_WIRES}, "
                f"got {self.rotate_wire!r}")


def tiles(cfg: MFSGDConfig) -> tuple[int, int]:
    """Resolved ``(u_tile, i_tile)`` — None means auto per algo.

    pallas: 256×256 (measured best 2026-08-01, SWEEP_pallas.jsonl, 1×
    v5e ML-20M: 250.2M ups/s vs 195.5M@512 / 163.3M@1024 / 147.3M@128,
    identical rmse — smaller tiles win inside the kernel because the
    one-hots never leave VMEM, until grid overhead bites).  dense: 512
    (measured best vs 1024/2048, 2026-07-30).
    """
    auto = 256 if cfg.algo == "pallas" else 512
    return (cfg.u_tile if cfg.u_tile is not None else auto,
            cfg.i_tile if cfg.i_tile is not None else auto)


def rotate_chunks_resolved(cfg) -> int:
    """Resolved rotation chunk count — ``None`` means the incumbent 2
    (the two-halves schedule both rotation models shipped with).  Read-time
    resolution (not ``__post_init__``) so ``dataclasses.replace`` keeps the
    auto default, mirroring :func:`tiles`; shared with
    :class:`harp_tpu.models.lda.LDAConfig` (same field, same contract)."""
    return cfg.rotate_chunks if cfg.rotate_chunks is not None else 2


# ---------------------------------------------------------------------------
# Host preprocessing: triples → N×N padded block grid.
# ---------------------------------------------------------------------------

def partition_ratings(users, items, vals, n_users, n_items, n_workers, chunk,
                      n_slices: int | None = None):
    """Partition rating triples into the (user-range × item-slice) grid.

    ``n_slices`` defaults to ``2 * n_workers`` — two half-slices per worker,
    the incumbent double-buffer depth; the chunked epoch passes
    ``rotate_chunks * n_workers`` (one slice per rotation chunk).

    Returns per-worker arrays ``u[S, B], i[S, B], v[S, B], mask[S, B]`` with
    user/item ids **local** to their range/slice, stacked worker-major so
    dim 0 shards over the mesh (worker w's row is its ``[n_slices, B]``
    grid).  B is the global max block size rounded up to ``chunk``.

    (Harp stores the same thing as per-worker rating lists keyed by the H
    partition id; padding replaces the dynamic per-block sizes because XLA
    needs static shapes.)
    """
    n = n_workers
    ns = n_slices if n_slices is not None else 2 * n
    u_bound = -(-n_users // n)  # users per range (ceil)
    i_bound = -(-n_items // ns)  # items per slice

    with telemetry.span("mfsgd.partition.sort"):
        users = np.asarray(users)
        items = np.asarray(items)
        vals = np.asarray(vals, dtype=np.float32)
        wid = users // u_bound  # owning worker (user range)
        sid = items // i_bound  # item slice

        # bucket sort triples by (worker, slice)
        order = np.lexsort((items, sid, wid))
        users, items, vals, wid, sid = (
            a[order] for a in (users, items, vals, wid, sid)
        )
    with telemetry.span("mfsgd.partition.pack"):
        counts = np.zeros((n, ns), np.int64)
        np.add.at(counts, (wid, sid), 1)
        bmax = int(counts.max())
        if bmax >= chunk:
            B = -(-bmax // chunk) * chunk  # pad to chunk multiple
        else:
            # small data: don't pad every block up to a full chunk (400×
            # waste at the tuned 32768 default on 10k-rating datasets) —
            # one sublane-aligned sub-chunk suffices; the device side
            # clamps its scan chunk to the block width (see
            # _block_update).  Cap at chunk: sublane alignment may
            # otherwise overshoot it when chunk % 8 != 0, and the device
            # reshape needs B % min(chunk, B) == 0.
            B = min(chunk, max(8, -(-bmax // 8) * 8))

        u = np.zeros((n, ns, B), np.int32)
        i = np.zeros((n, ns, B), np.int32)
        v = np.zeros((n, ns, B), np.float32)
        m = np.zeros((n, ns, B), np.float32)
        starts = np.zeros((n, ns), np.int64)
        starts.flat[1:] = counts.cumsum()[:-1]
        for w in range(n):
            for s in range(ns):
                lo, c = starts[w, s], counts[w, s]
                sl = slice(lo, lo + c)
                u[w, s, :c] = users[sl] - w * u_bound
                i[w, s, :c] = items[sl] - s * i_bound
                v[w, s, :c] = vals[sl]
                m[w, s, :c] = 1.0
    return (
        u.reshape(n * ns, B), i.reshape(n * ns, B),
        v.reshape(n * ns, B), m.reshape(n * ns, B),
        u_bound, i_bound,
    )


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _dense_bounds(n_users, n_items, n_workers, n_slices, u_tile, i_tile):
    """Bounds for the dense algo, shared by partitioner and driver.

    Ownership (``u_own``/``i_own``) stays UNROUNDED — the same balanced
    ``id // ceil(size/N)`` placement Harp's partitioner and the scatter
    algo use; rounding ownership to tile multiples would dump every row
    on worker 0 whenever ``ceil(size/N) < tile``.  Storage per worker
    (``u_bound``/``ib2``) rounds up to tile multiples so dynamic slices
    are always full-size; the pad rows own no ids and stay untrained.
    """
    u_own = _ceil_div(n_users, n_workers)
    i_own = _ceil_div(n_items, n_slices)
    u_bound = u_tile * _ceil_div(u_own, u_tile)
    ib2 = i_tile * _ceil_div(i_own, i_tile)
    return u_own, i_own, u_bound, ib2


def partition_ratings_tiles(users, items, vals, n_users, n_items, n_workers,
                            u_tile, i_tile, entry_cap, n_slices=None):
    """Partition triples into dense (u_tile × i_tile) sub-tiles per
    (worker, half-slice) block — the layout the "dense" algo consumes.

    Each *entry* is up to ``entry_cap`` ratings of one sub-tile (overfull
    tiles split into several entries, so power-law item skew cannot blow up
    the padding).  Returns worker-major stacked arrays

    ``eu/ei/ev [n*ns, NE, C]`` — ids local to their tile (pad id = tile
    width, which one-hot maps to an all-zero row), values;
    ``ou/oi [n*ns, NE]`` — tile row offsets into the worker's W range /
    the resident half-slice;
    plus ``(u_own, i_own, u_bound, ib2)`` from :func:`_dense_bounds`
    (balanced ownership sizes + tile-rounded storage sizes).
    """
    n = n_workers
    ns = n_slices if n_slices is not None else 2 * n
    u_own, i_own, u_bound, ib2 = _dense_bounds(
        n_users, n_items, n, ns, u_tile, i_tile)
    ntu, nti = u_bound // u_tile, ib2 // i_tile

    with telemetry.span("mfsgd.partition.sort"):
        users = np.asarray(users)
        items = np.asarray(items)
        vals = np.asarray(vals, dtype=np.float32)
        wid = users // u_own
        sid = items // i_own
        lu = users - wid * u_own
        li = items - sid * i_own
        tu = lu // u_tile
        ti = li // i_tile

        # global tile id, sorted so each (worker, slice) lists tiles u-major
        gtile = ((wid * ns + sid) * ntu + tu) * nti + ti
        order = np.argsort(gtile, kind="stable")
        lu, li, vals, gtile = lu[order], li[order], vals[order], gtile[order]

    with telemetry.span("mfsgd.partition.pack"):
        n_tiles = n * ns * ntu * nti
        counts = np.bincount(gtile, minlength=n_tiles)
        C = int(min(entry_cap,
                    max(8, 8 * _ceil_div(int(counts.max(initial=0)), 8))))
        # elementwise ceil; 0 for empty tiles
        ent_per_tile = _ceil_div(counts, C)
        ws_of_tile = np.arange(n_tiles) // (ntu * nti)
        NE = max(1, int(np.bincount(ws_of_tile, weights=ent_per_tile,
                                    minlength=n * ns).max()))

        eu = np.full((n * ns, NE, C), u_tile, np.int32)
        ei = np.full((n * ns, NE, C), i_tile, np.int32)
        ev = np.zeros((n * ns, NE, C), np.float32)
        ou = np.zeros((n * ns, NE), np.int32)
        oi = np.zeros((n * ns, NE), np.int32)
        starts = np.zeros(n_tiles, np.int64)
        starts[1:] = counts.cumsum()[:-1]
        e_next = np.zeros(n * ns, np.int64)
        # Deliberately a per-entry loop: it copies CONTIGUOUS slices of
        # the tile-sorted data (memcpy-speed, ~15k iterations at ML-20M).
        # A fully vectorized fancy-index formulation measured 2× SLOWER
        # (12.6 s vs 6.3 s, 2026-07-30) — five 20M-element bounds-checked
        # scatters beat no Python loop but lose to 15k memcpys.
        for t in np.nonzero(counts)[0]:
            ws = t // (ntu * nti)
            t_u = (t // nti) % ntu
            t_i = t % nti
            lo, cnt = int(starts[t]), int(counts[t])
            for off in range(0, cnt, C):
                e = int(e_next[ws])
                e_next[ws] = e + 1
                c = min(C, cnt - off)
                sl = slice(lo + off, lo + off + c)
                eu[ws, e, :c] = lu[sl] - t_u * u_tile
                ei[ws, e, :c] = li[sl] - t_i * i_tile
                ev[ws, e, :c] = vals[sl]
                ou[ws, e] = t_u * u_tile
                oi[ws, e] = t_i * i_tile
    return eu, ei, ev, ou, oi, u_own, i_own, u_bound, ib2


# ---------------------------------------------------------------------------
# Device compute.
# ---------------------------------------------------------------------------

def _chunk_update(W, H, batch, cfg: MFSGDConfig):
    """One deterministic minibatch SGD step on (W, H-slice).

    Gradients of ½Σ m(r − w·h)² + ½λΣ(‖w‖²+‖h‖²) over the chunk; duplicate
    rows get summed gradients (scatter-add), the batched stand-in for
    Harp's sequential Hogwild updates.
    """
    bu, bi, bv, bm = batch
    wu = jnp.take(W, bu, axis=0)          # [c, r]
    hi = jnp.take(H, bi, axis=0)          # [c, r]
    err = bm * (bv - (wu * hi).sum(-1))   # [c]
    gw = err[:, None] * hi - cfg.reg * bm[:, None] * wu
    gh = err[:, None] * wu - cfg.reg * bm[:, None] * hi
    W = W.at[bu].add(cfg.lr * gw, mode="drop")
    H = H.at[bi].add(cfg.lr * gh, mode="drop")
    return W, H, (err * err).sum(), bm.sum()


def _block_update(W, H, block, cfg: MFSGDConfig):
    """Scan minibatch chunks over one (user-range × item-slice) block.

    The effective chunk is clamped to the (static) block width — small
    datasets produce blocks narrower than ``cfg.chunk`` (see
    ``partition_ratings``), which then run as a single minibatch.
    """
    bu, bi, bv, bm = block
    c = min(cfg.chunk, bu.shape[0])
    nchunk = bu.shape[0] // c
    chunks = jax.tree.map(lambda a: a.reshape(nchunk, c), (bu, bi, bv, bm))

    def body(carry, chunk):
        W, H, se, cnt = carry
        W, H, dse, dcnt = _chunk_update(W, H, chunk, cfg)
        return (W, H, se + dse, cnt + dcnt), None

    (W, H, se, cnt), _ = lax.scan(
        body, (W, H, jnp.float32(0.0), jnp.float32(0.0)), chunks
    )
    return W, H, se, cnt


def _entry_tiles_update(Wb, Hb, cu, ci, cv, cfg: MFSGDConfig):
    """Tile-level core of :func:`_tile_block_update`: one entry's update on
    pre-sliced ``Wb [u_tile, r]`` / ``Hb [i_tile, r]`` — no table slicing
    here, so the ``carry_w`` path can keep a W tile resident across its
    u-run (slicing strategy is the caller's concern; shared math keeps
    carry and non-carry chains bit-identical)."""
    UR, IR = tiles(cfg)
    cd = cfg.compute_dtype
    dot = partial(lax.dot_general, preferred_element_type=jnp.float32)
    ohu = jax.nn.one_hot(cu, UR, dtype=cd)          # [C, UR]
    ohi = jax.nn.one_hot(ci, IR, dtype=cd)          # [C, IR]
    wu = dot(ohu, Wb.astype(cd), (((1,), (0,)), ((), ())))  # gather
    hi = dot(ohi, Hb.astype(cd), (((1,), (0,)), ((), ())))
    cm = (cu < UR).astype(jnp.float32)
    err = cm * (cv - (wu * hi).sum(-1))
    gw = (err[:, None] * hi - cfg.reg * cm[:, None] * wu).astype(cd)
    gh = (err[:, None] * wu - cfg.reg * cm[:, None] * hi).astype(cd)
    gW = dot(ohu, gw, (((0,), (0,)), ((), ())))     # scatter-add
    gH = dot(ohi, gh, (((0,), (0,)), ((), ())))
    return (Wb + cfg.lr * gW, Hb + cfg.lr * gH,
            (err * err).sum(), cm.sum())


def carry_tile_switch(table, tile, cur, new_off, size, ax):
    """Run-carry tile switch shared by MF-SGD ``carry_w`` and LDA
    ``carry_db``: on an offset change, flush the carried tile back into
    the table BEFORE slicing the new region, so the result equals the
    slice-per-entry path even for overlapping (non-tile-aligned) offsets
    — not just the aligned ones current partitioners emit (ADVICE r4;
    overlap pinned by test_carry_w_exact_for_overlapping_tile_offsets).
    An unchanged offset pays zero tile HBM traffic via the ``lax.cond``.
    """
    def switch(opr):
        table, tile, cur = opr
        table = lax.dynamic_update_slice_in_dim(table, tile, cur, ax)
        new = lax.dynamic_slice_in_dim(table, new_off, size, ax)
        return table, new, new_off

    return lax.cond(new_off != cur, switch, lambda opr: opr,
                    (table, tile, cur))


def _tile_block_update(W, H, block, cfg: MFSGDConfig):
    """Scan dense-tile entries of one (user-range × item-half-slice) block.

    Per entry (≤ entry_cap ratings, all inside one u_tile × i_tile sub-tile):
    gather W/H tile rows by ``dynamic_slice``, run BOTH the row gather and
    the duplicate-summing scatter as one-hot matmuls — four MXU dots, zero
    XLA scatters.  Pad ids equal the tile width, so their one-hot rows are
    all-zero and they drop out of every product.

    ``cfg.carry_w``: entries are u-major (partition_ratings_tiles), so the
    W tile is carried across its tou-run and flushed/loaded only on a
    tou-change ``lax.cond`` — the LDA ``carry_db`` lever applied here
    (the switch always flushes before a region can be re-sliced, so this
    is exact under any entry order; bit-identical chains tested).
    """
    eu, ei, ev, ou, oi = block
    UR, IR = tiles(cfg)

    if cfg.carry_w:
        def body(carry, xs):
            W, H, se, cnt, wb, cur = carry
            cu, ci, cv, tou, toi = xs

            W, wb, cur = carry_tile_switch(W, wb, cur, tou, UR, 0)
            Hb = lax.dynamic_slice_in_dim(H, toi, IR, 0)
            wb, Hb, dse, dcnt = _entry_tiles_update(wb, Hb, cu, ci, cv, cfg)
            H = lax.dynamic_update_slice_in_dim(H, Hb, toi, 0)
            return (W, H, se + dse, cnt + dcnt, wb, cur), None

        wb0 = lax.dynamic_slice_in_dim(W, ou[0], UR, 0)
        (W, H, se, cnt, wb_f, cur_f), _ = lax.scan(
            body, (W, H, jnp.float32(0.0), jnp.float32(0.0), wb0, ou[0]),
            (eu, ei, ev, ou, oi))
        W = lax.dynamic_update_slice_in_dim(W, wb_f, cur_f, 0)
        return W, H, se, cnt

    def body(carry, xs):
        W, H, se, cnt = carry
        cu, ci, cv, tou, toi = xs
        Wb = lax.dynamic_slice_in_dim(W, tou, UR, 0)
        Hb = lax.dynamic_slice_in_dim(H, toi, IR, 0)
        Wb, Hb, dse, dcnt = _entry_tiles_update(Wb, Hb, cu, ci, cv, cfg)
        W = lax.dynamic_update_slice_in_dim(W, Wb, tou, 0)
        H = lax.dynamic_update_slice_in_dim(H, Hb, toi, 0)
        return (W, H, se + dse, cnt + dcnt), None

    (W, H, se, cnt), _ = lax.scan(
        body, (W, H, jnp.float32(0.0), jnp.float32(0.0)), (eu, ei, ev, ou, oi)
    )
    return W, H, se, cnt


def _pallas_tile_block_update(W, H, block, cfg: MFSGDConfig):
    """Fused-kernel twin of :func:`_tile_block_update` (same entries, same
    order, staged as the list of chunks that hold their ratings — see
    ops/mfsgd_kernel.py).  Factors transpose to rank-major at the block
    boundary; ~0.3 ms/epoch of HBM traffic at ML-20M scale."""
    from harp_tpu.ops.mfsgd_kernel import sgd_tile_update

    cu, ci, cv, meta = block
    with jax.named_scope("mfsgd.kernel"):
        Wt, Ht, se, cnt = sgd_tile_update(
            W.T, H.T, cu, ci, cv, meta,
            lr=cfg.lr, reg=cfg.reg, u_tile=tiles(cfg)[0],
            i_tile=tiles(cfg)[1], compute_dtype=cfg.compute_dtype,
            interpret=interpret_default())
        return Wt.T, Ht.T, se, cnt


_UPDATERS = {"dense": _tile_block_update, "scatter": _block_update,
             "pallas": _pallas_tile_block_update}

#: algos that consume the dense (u_tile × i_tile) entry layout
_DENSE_ALGOS = ("dense", "pallas")


def _epoch_device_fn(mesh: WorkerMesh, cfg: MFSGDConfig):
    """Build the device-view epoch callable (every rating visited once).

    This is the dymoro pipeline done the XLA way (SURVEY.md §4.3), on the
    generic chunked rotator: each worker's H slice splits into
    ``rotate_chunks_resolved(cfg)`` sub-slices that alternate compute /
    in-flight roles inside :func:`rotate_pipeline` — the chunk updated at
    step t-1 rides a ``ppermute`` with no data dependency on step t's
    compute, so XLA's async scheduler overlaps transfer with compute,
    while a whole-slice rotation would serialize (a mutated slice cannot
    leave before its update finishes — the constraint Harp's Rotator also
    has, which is why dymoro prefetches *next* slices rather than sending
    current ones).  The 2-chunk default IS the former bespoke two-halves
    schedule (n workers, 2n half-slices, 2n steps/epoch; equivalence
    pinned by the numpy goldens + tests/test_rotate_chunked.py);
    ``cfg.rotate_wire`` narrows the ring payload.
    """
    nc = rotate_chunks_resolved(cfg)
    update = _UPDATERS[cfg.algo]

    def epoch(W, H_slice, *blocks):
        # block arrays arrive as this worker's [nc·n chunk-slices, ...] row
        def step(st, chunk, t):
            W, se, cnt = st
            with jax.named_scope("mfsgd.slices"):
                block = jax.tree.map(
                    lambda a: a[resident_chunk_index(t, nc)], blocks)
            W, chunk, dse, dcnt = update(W, chunk, block, cfg)
            return (W, se + dse, cnt + dcnt), chunk

        # the whole pipeline: alone on an op's path it is the rotation's
        # own work (the carry's copies, the ring hop)
        with jax.named_scope("mfsgd.rotate"):
            (W, se, cnt), H_slice = rotate_pipeline(
                step, (W, jnp.float32(0.0), jnp.float32(0.0)), H_slice,
                n_chunks=nc, wire=cfg.rotate_wire)
        # per-worker visited-rating count BEFORE the psum — the skew
        # spine's execution counter (utils/skew.py), folded into the
        # epoch outputs so the driver's ONE stacked readback carries it
        # (flight budgets unchanged, tests/test_flightrec.py).
        with jax.named_scope("mfsgd.loss"):
            work_w = C.allgather(cnt[None])
            # loss partials are per-worker; combine before leaving SPMD
            # (the optional end-of-epoch allreduce-RMSE in Harp's MF-SGD
            # loop)
            se, cnt = C.allreduce((se, cnt))
        return W, H_slice, se, cnt, work_w

    return epoch


def _n_block_args(cfg: MFSGDConfig) -> int:
    # dense: eu/ei/ev/ou/oi; pallas: cu/ci/cv/meta; scatter: u/i/v/mask
    return 5 if cfg.algo == "dense" else 4


def make_epoch_fn(mesh: WorkerMesh, cfg: MFSGDConfig):
    """Compile one full rotation epoch — see :func:`_epoch_device_fn`."""
    return jax.jit(
        mesh.shard_map(
            _epoch_device_fn(mesh, cfg),
            in_specs=(mesh.spec(0),) * (2 + _n_block_args(cfg)),
            out_specs=(mesh.spec(0), mesh.spec(0), P(), P(), P()),
        )
    )


def make_multi_epoch_fn(mesh: WorkerMesh, cfg: MFSGDConfig, epochs: int):
    """Compile ``epochs`` rotation epochs as ONE device program.

    A single dispatch (and a single stacked readback) instead of one per
    epoch: a per-epoch host round trip is pure overhead against 42 ms of
    device time per ML-20M epoch (1× v5e, 2026-09-26).  Returns per-epoch
    ``(se[epochs], cnt[epochs])`` alongside the final W/H.
    """
    inner = _epoch_device_fn(mesh, cfg)

    def many(W, H_slice, *blocks):
        def body(carry, _):
            W, H = carry
            W, H, se, cnt, work = inner(W, H, *blocks)
            return (W, H), (se, cnt, work)

        (W, H_slice), (ses, cnts, works) = lax.scan(
            body, (W, H_slice), None, length=epochs)
        # per-sweep work vectors are identical — the last one suffices
        return W, H_slice, ses, cnts, works[-1]

    return jax.jit(
        mesh.shard_map(
            many,
            in_specs=(mesh.spec(0),) * (2 + _n_block_args(cfg)),
            out_specs=(mesh.spec(0), mesh.spec(0), P(), P(), P()),
        )
    )


class MFSGD:
    """Host driver (the ``mapCollective`` residue for edu.iu.sgd)."""

    def __init__(self, n_users, n_items, cfg: MFSGDConfig | None = None,
                 mesh: WorkerMesh | None = None, seed=0):
        self.mesh = mesh or current_mesh()
        self.cfg = cfg or MFSGDConfig()
        self.n_users, self.n_items = n_users, n_items
        n = self.mesh.num_workers
        nc = rotate_chunks_resolved(self.cfg)
        # rotate_chunks chunk-slices per worker (pipelined rotation)
        self._n_slices = nc * n
        if self.cfg.algo in _DENSE_ALGOS:
            self.u_own, self.i_own, self.u_bound, ibc = _dense_bounds(
                n_users, n_items, n, self._n_slices, *tiles(self.cfg))
            self.i_bound = nc * ibc
        else:
            self.u_bound = self.u_own = _ceil_div(n_users, n)
            self.i_bound = nc * _ceil_div(n_items, self._n_slices)
            self.i_own = self.i_bound // nc
        # raw key bits (utils.prng): a fresh seed must not cost a fresh
        # (remote) compile — CLAUDE.md PRNGKey-specialization trap
        k1, k2 = jax.random.split(jnp.asarray(prng.key_bits(seed)))
        scale = 1.0 / np.sqrt(self.cfg.rank)
        self.W = self.mesh.shard_array(
            np.asarray(jax.random.uniform(k1, (self.u_bound * n, self.cfg.rank),
                                          jnp.float32, 0, scale)), 0)
        self.H = self.mesh.shard_array(
            np.asarray(jax.random.uniform(k2, (self.i_bound * n, self.cfg.rank),
                                          jnp.float32, 0, scale)), 0)
        self._epoch_fn = flightrec.track(make_epoch_fn(self.mesh, self.cfg),
                                         "mfsgd.epoch")
        self._multi_fns: dict[int, Any] = {}
        self._blocks = None
        # movable pack grains for the skew spine's execution records
        # (PR 15): the elastic driver sets per-worker [(pack_id, load)]
        # lists here so the health sentinel's skew_trigger carries a
        # whole-unit, apply_rebalance-replayable plan.  None (default)
        # keeps the PR-4 per-worker-only records.
        self.skew_units = None

    def set_ratings(self, users, items, vals):
        n = self.mesh.num_workers
        nc = rotate_chunks_resolved(self.cfg)
        work = None  # valid ratings a worker, counted with telemetry on
        with telemetry.span("mfsgd.set_ratings"):
            if self.cfg.algo in _DENSE_ALGOS:
                u_tile, i_tile = tiles(self.cfg)
                eu, ei, ev, ou, oi, uo, io, ub, ibc = partition_ratings_tiles(
                    users, items, vals, self.n_users, self.n_items, n,
                    u_tile, i_tile, self.cfg.entry_cap,
                    n_slices=self._n_slices,
                )
                assert (uo, io) == (self.u_own, self.i_own)
                if telemetry.enabled():
                    # ingest skew record from the REAL ratings (before the
                    # pallas coverage entries, which carry no rating mass)
                    valid = eu < u_tile
                    work = valid.reshape(n, -1).sum(1)
                    skew.record_partition(
                        "mfsgd.partition", work, unit="ratings",
                        padded_total=valid.size)
                if self.cfg.algo == "pallas":
                    # outside the span: the module's first import brings
                    # Pallas in, which is no work on the ratings
                    from harp_tpu.ops.mfsgd_kernel import (
                        insert_coverage_entries)

                    with telemetry.span("mfsgd.coverage"):
                        blocks = insert_coverage_entries(
                            eu, ei, ev, ou, oi, ub, u_tile, i_tile)
                else:
                    blocks = (eu, ei, ev, ou, oi)
            else:
                bu, bi, bv, bm, ub, ibc = partition_ratings(
                    users, items, vals, self.n_users, self.n_items, n,
                    self.cfg.chunk, n_slices=self._n_slices,
                )
                if telemetry.enabled():
                    work = (bm > 0).reshape(n, -1).sum(1)
                    skew.record_partition(
                        "mfsgd.partition", work, unit="ratings",
                        padded_total=bm.size)
                blocks = (bu, bi, bv, bm)
            if work is not None:
                # the record that counts what runs: the same ratings over
                # the slots of the arrays as staged — for the kernel, the
                # chunks that hold ratings plus its no-op chunks.
                # Through the ledger, not the module hook: the health
                # monitor has judged this per-worker work once already,
                # under "mfsgd.partition"
                skew.ledger.record_partition(
                    "mfsgd.kernel_slots", work, unit="ratings",
                    padded_total=blocks[0].size)
            assert (ub, nc * ibc) == (self.u_bound, self.i_bound)
            self._blocks = tuple(self.mesh.shard_array(a, 0) for a in blocks)
        self._multi_fns.clear()  # compiled executables bind to block shapes
        self.nnz = len(np.asarray(vals))

    def train_epoch(self):
        """One rotation epoch; returns training RMSE over visited ratings."""
        if self._blocks is None:
            raise RuntimeError("call set_ratings() before train_epoch()")
        with telemetry.span("mfsgd.epoch"), \
                telemetry.ledger.run("mfsgd.epochs", steps=1):
            t0 = time.perf_counter()
            self.W, self.H, se, cnt, work_w = self._epoch_fn(
                self.W, self.H, *self._blocks)
            # one stacked readback, not one per scalar (readbacks
            # budget); the per-worker work vector rides the same fetch
            stats = flightrec.readback(
                jnp.concatenate([jnp.stack([se, cnt]), work_w]))
            skew.record_execution("mfsgd.epochs", stats[2:],
                                  unit="ratings",
                                  wall_s=time.perf_counter() - t0,
                                  units=self.skew_units)
            return float(np.sqrt(max(float(stats[0]), 0.0)
                                 / max(float(stats[1]), 1.0)))

    def compile_epochs(self, epochs: int):
        """AOT-compile the ``epochs``-epoch program WITHOUT running it.

        ``.lower().compile()`` is side-effect-free — benchmark warmup must
        not secretly train extra epochs, or the reported RMSE describes a
        different model than the epoch count claims.  The compiled
        executable is cached and reused by :meth:`train_epochs`.
        """
        if self._blocks is None:
            raise RuntimeError("call set_ratings() before compile_epochs()")
        fn = self._multi_fns.get(epochs)
        if fn is None:
            jitted = make_multi_epoch_fn(self.mesh, self.cfg, epochs)
            # steps=0: lowering traces the comm sites (attributed to the
            # same tag the executions count under) without executing them
            with telemetry.ledger.run("mfsgd.epochs", steps=0), \
                    telemetry.current_names():
                fn = self._multi_fns[epochs] = flightrec.track(
                    jitted.lower(self.W, self.H, *self._blocks).compile(),
                    "mfsgd.epochs")
        return fn

    def train_epochs(self, epochs: int):
        """Run ``epochs`` epochs as one device program; returns per-epoch RMSEs.

        One host→device dispatch instead of ``epochs`` (see
        :func:`make_multi_epoch_fn`).  Use
        ``fit()`` instead when checkpointing between epochs.
        """
        fn = self.compile_epochs(epochs)
        # the scan body's traced comm sites execute once per epoch
        with telemetry.span("mfsgd.epochs", epochs=epochs), \
                telemetry.ledger.run("mfsgd.epochs", steps=epochs):
            t0 = time.perf_counter()
            self.W, self.H, ses, cnts, work_w = fn(self.W, self.H,
                                                   *self._blocks)
            # ONE stacked readback for all epochs' stats (the ccd.py
            # idiom) — the flight-recorder budget for this loop pins
            # readbacks=1 per run, not one per stat array; the
            # per-worker work vector rides the same fetch (skew spine)
            stats = flightrec.readback(
                jnp.concatenate([ses, cnts, work_w]))
            skew.record_execution("mfsgd.epochs", stats[2 * epochs:],
                                  unit="ratings",
                                  wall_s=time.perf_counter() - t0,
                                  units=self.skew_units)
            ses, cnts = stats[:epochs], stats[epochs:2 * epochs]
        return [float(np.sqrt(max(s, 0.0) / max(c, 1.0)))
                for s, c in zip(ses, cnts)]

    def fit(self, epochs: int, ckpt_dir: str | None = None, *,
            ckpt_every: int = 5, max_restarts: int = 3, fault=None):
        """Train with optional checkpoint/resume — the SURVEY.md §6 driver.

        With ``ckpt_dir`` set, epochs checkpoint every ``ckpt_every`` and a
        crashed run (or a rerun pointing at the same dir) resumes from the
        latest saved epoch instead of epoch 0 — Harp's YARN whole-job retry,
        upgraded.  Returns the per-epoch RMSE list for the epochs this call
        actually ran.
        """
        from harp_tpu.utils.fault import factor_state_io, fit_epochs

        rmses: list[float] = []
        get_state, set_state = factor_state_io(self, {
            "W": lambda a: self.mesh.shard_array(a, 0),
            "H": lambda a: self.mesh.shard_array(a, 0),
        })
        fit_epochs(
            lambda: rmses.append(self.train_epoch()),
            get_state, set_state,
            epochs, ckpt_dir, ckpt_every=ckpt_every,
            max_restarts=max_restarts, fault=fault,
            phase="mfsgd.epochs",
        )
        return rmses

    def factors(self):
        """Global (W, H) with storage padding stripped.

        Dense storage pads each worker's W range (and each half-slice's H
        range) to a tile multiple; user ``g`` lives at row
        ``(g // u_own) * u_bound + g % u_own``, so the pad rows must be cut
        per range, not just at the tail.
        """
        n = self.mesh.num_workers
        W = np.asarray(self.W)
        H = np.asarray(self.H)
        if self.cfg.algo in _DENSE_ALGOS:
            nc = rotate_chunks_resolved(self.cfg)
            r = W.shape[-1]
            W = W.reshape(n, self.u_bound, r)[:, : self.u_own].reshape(-1, r)
            ibc = self.i_bound // nc
            H = H.reshape(nc * n, ibc, r)[:, : self.i_own].reshape(-1, r)
        return W[: self.n_users], H[: self.n_items]

    def predict_rmse(self, users, items, vals):
        W, H = self.factors()
        pred = (W[np.asarray(users)] * H[np.asarray(items)]).sum(-1)
        return float(np.sqrt(np.mean((pred - np.asarray(vals)) ** 2)))


# ---------------------------------------------------------------------------
# Synthetic MovieLens-20M-shaped data + benchmark.
# ---------------------------------------------------------------------------

def synthetic_ratings(n_users, n_items, nnz, rank=8, noise=0.1, seed=0):
    """Low-rank ground truth + noise, uniform random (u, i) pairs."""
    rng = np.random.default_rng(seed)
    Wt = rng.normal(size=(n_users, rank)) / np.sqrt(rank)
    Ht = rng.normal(size=(n_items, rank)) / np.sqrt(rank)
    u = rng.integers(0, n_users, nnz)
    i = rng.integers(0, n_items, nnz)
    v = (Wt[u] * Ht[i]).sum(-1) + noise * rng.normal(size=nnz)
    return u.astype(np.int32), i.astype(np.int32), v.astype(np.float32)


def algo_kwargs(algo: str, groups: dict) -> dict:
    """Validated algo-specific config kwargs (shared by mfsgd and lda).

    ``groups``: ``{owner_algo(s): {knob: value}}`` — the key is one algo
    name or a tuple of them (a knob like lda's ``chunk`` can belong to
    several).  ``None`` values inherit the config defaults; a non-None
    knob combined with a non-owning algo raises — a silently-ignored
    tuning flag wastes benchmark sweeps."""
    kw: dict[str, Any] = {"algo": algo}
    for owners, knobs in groups.items():
        owners_t = (owners,) if isinstance(owners, str) else tuple(owners)
        for name, val in knobs.items():
            if val is None:
                continue
            if algo not in owners_t:
                raise ValueError(
                    f"{name} is {'/'.join(owners_t)}-only; pass one of "
                    f"those algos or tune the {algo!r} knobs instead")
            kw[name] = val
    return kw


def _make_config(rank: int, chunk: int | None, algo: str = "dense",
                 u_tile: int | None = None, i_tile: int | None = None,
                 entry_cap: int | None = None,
                 carry_w: bool | None = None,
                 rotate_chunks: int | None = None,
                 rotate_wire: str | None = None) -> MFSGDConfig:
    return MFSGDConfig(rank=rank, **algo_kwargs(algo, {
        "scatter": {"chunk": chunk},
        _DENSE_ALGOS: {"u_tile": u_tile, "i_tile": i_tile,
                       "entry_cap": entry_cap},
        "dense": {"carry_w": carry_w},
        # every MF-SGD algo rotates, so the pipeline knobs have no
        # non-owning algo to reject — they still ride algo_kwargs for
        # the uniform None-inherits-default contract
        ("dense", "scatter", "pallas"): {"rotate_chunks": rotate_chunks,
                                         "rotate_wire": rotate_wire},
    }))


def benchmark(n_users=138_493, n_items=26_744, nnz=20_000_000, rank=64,
              epochs=3, mesh=None, seed=0, chunk=None, algo="dense",
              u_tile=None, i_tile=None, entry_cap=None, carry_w=None,
              rotate_chunks=None, rotate_wire=None):
    """updates/sec/chip on MovieLens-20M shapes (north-star metric #2).

    One 'update' = one rating visit (one (w_u, h_i) SGD update pair),
    matching Harp-DAAL's MF-SGD throughput accounting.

    Measured on this config (1× v5e): algo="dense" (default) — see the
    MFSGDConfig.algo comment and BASELINE.md for the dense-vs-scatter
    numbers.  For algo="scatter", chunk=None inherits the tuned 32768
    (2026-07-29: 26.3M ups/chip vs 14.4M at 8192; 65536 within noise;
    131072 hit an XLA scatter compile pathology (>9 min, killed) — do not
    default past 64k).
    """
    mesh = mesh or current_mesh()
    cfg = _make_config(rank, chunk, algo, u_tile, i_tile, entry_cap,
                       carry_w, rotate_chunks, rotate_wire)
    model = MFSGD(n_users, n_items, cfg, mesh, seed)
    u, i, v = synthetic_ratings(n_users, n_items, nnz, seed=seed)
    t0 = time.perf_counter()
    model.set_ratings(u, i, v)
    prep = time.perf_counter() - t0

    rmse0 = model.train_epoch()    # warmup (includes single-epoch compile)
    model.compile_epochs(epochs)   # AOT, off-clock, does NOT train
    t0 = time.perf_counter()
    rmse = model.train_epochs(epochs)[-1]
    dt = time.perf_counter() - t0
    ups = nnz * epochs / dt / mesh.num_workers
    return {
        "updates_per_sec_per_chip": ups,
        "sec_per_epoch": dt / epochs,
        "rmse_first_epoch": rmse0,
        "rmse_final": rmse,
        "prep_sec": prep,
        "nnz": nnz, "rank": rank, "num_workers": mesh.num_workers,
        "algo": algo,
    }


def main(argv=None):
    import argparse

    from harp_tpu.utils.metrics import benchmark_json

    p = argparse.ArgumentParser(description="harp-tpu MF-SGD (edu.iu.sgd parity)")
    p.add_argument("--users", type=int, default=None,
                   help="default: 138493 (ML-20M); with --input, raised to "
                        "max id + 1 as needed")
    p.add_argument("--items", type=int, default=None,
                   help="default: 26744 (ML-20M); with --input, raised to "
                        "max id + 1 as needed")
    p.add_argument("--nnz", type=int, default=20_000_000)
    p.add_argument("--rank", type=int, default=64)
    p.add_argument("--epochs", type=int, default=3)
    p.add_argument("--algo", choices=["dense", "scatter", "pallas"],
                   default="dense",
                   help="dense: one-hot MXU tiles (default); pallas: the "
                        "same update fused into one VMEM kernel; scatter: "
                        "direct gather/scatter-add reference")
    p.add_argument("--chunk", type=int, default=None,
                   help="scatter-only: minibatch size (default: tuned 32768); "
                        "errors under --algo dense instead of silently "
                        "doing nothing")
    p.add_argument("--u-tile", type=int, default=None,
                   help="dense/pallas: W tile rows (default 512)")
    p.add_argument("--i-tile", type=int, default=None,
                   help="dense/pallas: H tile rows (default 512)")
    p.add_argument("--entry-cap", type=int, default=None,
                   help="dense/pallas: max ratings per tile entry (default 2048)")
    p.add_argument("--rotate-chunks", type=int, default=None,
                   help="H sub-slices per worker in the chunked rotation "
                        "pipeline (default 2 — the double-buffered "
                        "two-halves schedule)")
    p.add_argument("--rotate-wire", choices=["exact", "bf16", "int8"],
                   default=None,
                   help="ring payload for in-flight chunks (default exact; "
                        "bf16/int8 halve/quarter the rotate bytes with one "
                        "rounding per hop)")
    p.add_argument("--ckpt-dir", default=None,
                   help="train with checkpoint/resume instead of benchmarking; "
                        "rerunning with the same dir resumes from the latest "
                        "saved epoch")
    p.add_argument("--ckpt-every", type=int, default=5)
    p.add_argument("--resume", action="store_true",
                   help="assert the run RESUMES from --ckpt-dir: fails "
                        "loudly when the dir holds no checkpoint (a "
                        "mistyped dir must not silently retrain from "
                        "epoch 0)")
    p.add_argument("--input", default=None, metavar="FILE_OR_GLOB",
                   help="rating triple files ('user item rating' rows, e.g. "
                        "MovieLens) — the Harp app's HDFS input; implies "
                        "training mode. --users/--items default to max id + 1")
    p.add_argument("--elastic", action="store_true",
                   help="elastic training (PR 15): consume mid-run "
                        "skew_trigger findings between epochs (rebalance "
                        "user packs over the reshard wire) and checkpoint "
                        "mesh-independent state")
    p.add_argument("--max-worker-loss", type=int, default=0,
                   help="elastic: survive up to N permanent worker "
                        "losses by shrinking to the survivors and "
                        "replaying the repartition plan from the last "
                        "checkpoint (implies --elastic; needs --ckpt-dir "
                        "to actually resume)")
    args = p.parse_args(argv)
    from harp_tpu.utils.fault import resolve_resume

    resumed_from = resolve_resume(args.ckpt_dir, args.resume)
    if args.elastic or args.max_worker_loss:
        if args.input:
            raise SystemExit(
                "--elastic currently pairs with the synthetic corpus; "
                "use --users/--items/--nnz (file inputs ride the "
                "non-elastic fit)")
        from harp_tpu.elastic.apps import mfsgd_elastic_fit

        n_users = args.users or 138_493
        n_items = args.items or 26_744
        u, i, v = synthetic_ratings(n_users, n_items, args.nnz)
        ad = mfsgd_elastic_fit(
            u, i, v, n_users=n_users, n_items=n_items,
            cfg=_make_config(args.rank, args.chunk, args.algo,
                             args.u_tile, args.i_tile, args.entry_cap,
                             rotate_chunks=args.rotate_chunks,
                             rotate_wire=args.rotate_wire),
            epochs=args.epochs, ckpt_dir=args.ckpt_dir,
            ckpt_every=args.ckpt_every,
            max_worker_loss=max(args.max_worker_loss, 0))
        print(benchmark_json("mfsgd_elastic_cli", {
            "epochs": args.epochs, "rmse_final": ad.metric(),
            "n_workers": ad.mesh.num_workers,
            "worker_losses": ad.losses, "ckpt_dir": args.ckpt_dir}))
        from harp_tpu.report import maybe_emit

        maybe_emit("mfsgd")
        return
    if args.input or args.ckpt_dir:
        if args.input:
            from harp_tpu.native.datasource import load_triples_glob

            try:
                u, i, v, has_rating = load_triples_glob(args.input)
            except ValueError as e:
                raise SystemExit(str(e))
            if not has_rating:
                raise SystemExit(
                    f"{args.input}: rows have no rating column — MF-SGD "
                    "needs 'user item rating' triples (training on the "
                    "implied zeros would silently fit nothing)")
            if int(u.min()) < 0 or int(i.min()) < 0:
                raise SystemExit(
                    f"{args.input}: negative user/item ids (ids index model "
                    "rows; JAX would silently clamp them to wrong rows)")
            # explicit sizes are raised to fit the data (out-of-range ids
            # would crash the partitioner deep inside otherwise)
            n_users = max(args.users or 0, int(u.max()) + 1)
            n_items = max(args.items or 0, int(i.max()) + 1)
        else:
            n_users = args.users or 138_493
            n_items = args.items or 26_744
            u, i, v = synthetic_ratings(n_users, n_items, args.nnz)
        model = MFSGD(n_users, n_items,
                      _make_config(args.rank, args.chunk, args.algo,
                                   args.u_tile, args.i_tile, args.entry_cap,
                                   rotate_chunks=args.rotate_chunks,
                                   rotate_wire=args.rotate_wire))
        model.set_ratings(u, i, v)
        rmses = model.fit(args.epochs, args.ckpt_dir,
                          ckpt_every=args.ckpt_every)
        print(benchmark_json("mfsgd_fit_cli", {"epochs_run": len(rmses),
               "rmse_final": rmses[-1] if rmses else None,
               "nnz": len(u), "users": n_users, "items": n_items,
               "ckpt_dir": args.ckpt_dir, "resumed_from": resumed_from}))
    else:
        print(benchmark_json("mfsgd_cli", benchmark(
            args.users or 138_493, args.items or 26_744,
            args.nnz, args.rank, args.epochs, chunk=args.chunk,
            algo=args.algo, u_tile=args.u_tile,
            i_tile=args.i_tile, entry_cap=args.entry_cap,
            rotate_chunks=args.rotate_chunks,
            rotate_wire=args.rotate_wire)))
    from harp_tpu.report import maybe_emit

    maybe_emit("mfsgd")


if __name__ == "__main__":
    main()
