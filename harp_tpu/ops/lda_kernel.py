"""Fused LDA-CGS resample over a chunk list — Pallas TPU kernel.

Reference parity: the CGS inner loop Harp ran in ``edu.iu.lda``'s
sampler threads (SURVEY.md §3.4 #3, §4.4).  The XLA ``algo="dense"``
path (`models/lda.py:_sample_entry`) materializes six-plus [C, K]
intermediates per tile entry in HBM (gathered count rows, the removed
self-assignment, posterior, noise) — ~30 MB per 2048-token entry at the
graded 1k topics.  This kernel runs the whole chain — count-row gathers,
posterior, topic draw, count-delta scatters — inside VMEM, so HBM sees
only the count tiles in and out plus the token stream.

Layout (the kmeans/mfsgd kernels' lane rules): everything is
**topic-major** — count tables arrive transposed ([K, docs]/[K, words]:
``models/lda.LDA`` stores them so, and a call may be handed the whole word
slice with its chunk list moved onto one half-slice of it,
:func:`shift_chunk_meta`), token ids/assignments ride rows
[1, cc], all one-hots are built in [tile, cc] orientation, and every
matmul contracts over lanes or A-lane×B-sublane.

Grid/memory plan (1-D sequential grid over a CHUNK LIST, PERF.md §6
PR 32 — the cut PR 26 made in ``ops/mfsgd_kernel.py``):
- One call resamples one (half-slice, document-tile) RUN: the tokens of
  one ``d_tile`` of documents against every word tile of the resident
  half-slice.  A (d_tile × w_tile) tile is staged as only the
  ``CHUNK``-slot chunks that hold its tokens (:func:`stage_chunk_list`):
  ``ceil(count / CHUNK)`` adjacent grid steps.  A step's one-hot dots
  cost the same for a chunk of padding as for a chunk of tokens (~5.8 µs
  at K = 1000, 512-wide tiles: MXU-bound), so the time of a sweep is its
  number of chunks.  One packed i32 a chunk, scalar-prefetched, says
  what the step is: its word tile, and whether it is a no-op
  (:func:`pack_chunk_meta`).
- The run's document tile [K, d_tile] is a block of the whole doc table
  chosen by the prefetched run index: copied in at step 0, resident for
  the call, flushed once at its end.
- Word tiles [K, w_tile] stream in and out as blocks chosen by the
  chunk's prefetched tile index (the ``w_block`` pattern of
  ``sgd_tile_update``).  Host prep guarantees each word tile is ONE
  contiguous group of grid steps inside a run, so its deltas stay in the
  live VMEM output buffer for the whole group and an output block is
  never revisited inside a call.  Both tables alias their outputs: the
  blocks a call does not visit keep their counts, so a run needs no
  coverage chunk for an empty tile.
- ``N_k``'s delta accumulates in VMEM across the whole call: every chunk
  samples against ``N_k`` plus all deltas before it, and against count
  tiles that already hold every earlier chunk's deltas.  Blocked-Gibbs
  granularity is one chunk, FINER than the XLA path's whole-entry
  snapshot (same approximation family the reference's timer-bounded
  scheduler sets; convergence tests cover it).
- Rows and runs shorter than the longest end in no-op chunks at the word
  tile of the chunk before them (no block switch, body skipped).

Sampling stack (fixed, by construction — the kernel exists because of
it): exponential-race draw (``LDAConfig.sampler="exprace"`` — identical
distribution to Gumbel-argmax) over hardware random bits
(``pltpu.prng_random_bits`` — the ``rng_impl="rbg"`` analogue), seeded
per run+chunk so runs are deterministic per backend.

Numerics — read before trusting counts:
- Count GATHERS are EXACT by default (``exact_gathers=True``, ADVICE r3):
  each table splits into base-256 planes (int16 doc tiles: 2 planes,
  exact to 2^15; f32 word tiles: 3 planes, exact to 2^24 — the f32
  table's own integer ceiling), every plane holds integers ≤ 256 (bf16-
  exact), one bf16 dot per plane, exact f32 recombination.  Cost: +1/+2
  gather dots and ~6·K·max(DR, WR) bytes of plane temporaries per tile.
  Static ``ndk/nwk_count_bound``\\ s shrink the plane counts (chain
  invariants — doc-topic ≤ doc length, word-topic ≤ word frequency;
  ``LDA._install_pack`` derives them per corpus): enwiki-shape doc
  lengths ≤ 256 make the Db gather ONE plain bf16 dot, still exact.
  ``exact_gathers=False`` keeps the single-dot bf16 gather — counts >
  256 round (≤ 0.4% relative, *in the posterior only*); the
  ``lda_pallas_approx`` sweep config measures whether that buys ≥10% at
  equal chain likelihood (the flip gate's job).
- Count UPDATES stay exact on both paths: deltas are 0/±1 (bf16-exact),
  scatter dots accumulate in f32, int16 tables round-trip exactly.
  Tables remain integer-valued — the invariant the tests pin.
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

#: slots a staged chunk: one grid step resamples this many tokens against
#: one snapshot.  Chosen by measurement (PERF.md §6, PR 32): at K = 1000
#: a step is MXU-bound by its slots, and 256-slot chunks stage 70% more
#: slots for the same tokens at the source's vocabulary.  The 128 lanes
#: are also the narrowest chunk the TPU gate accepts.
CHUNK = 128

# One i32 of metadata a chunk, scalar-prefetched whole into SMEM: bit 0
# says the chunk is a no-op, the 30 bits above it hold its word tile's
# index in the resident half-slice; the sign bit stays clear so the shift
# needs no mask.
_NOOP = 1
_WT_SHIFT, _WT_BITS = 1, 30
# SMEM is 1 MiB on a v5e and a call's metadata is prefetched whole:
# 260,000 chunks compile and 400,000 are refused (compile-only client,
# PR 26).  A call is one document-tile run, so this bounds a run and not
# the corpus.
_MAX_CHUNKS = 250_000
# What a call may ask of the chip's 128 MiB of VMEM: the estimate is held
# under the budget, and the compiler is given the budget plus room for
# what the estimate does not itemise.  The default scoped limit (16 MiB)
# does not hold two count tiles in and out, double-buffered, at K = 1000.
_VMEM_BUDGET = 48 << 20
_VMEM_LIMIT = 64 << 20


def pack_chunk_meta(wt, noop):
    """Host side: per-chunk word tile index and no-op flag → one i32
    each.  Raises over the bit budget."""
    wt = np.asarray(wt, np.int64)
    if wt.size and (wt.min() < 0 or wt.max() >> _WT_BITS):
        raise ValueError(
            f"pallas lda: word tile index {int(wt.max())} does not fit "
            f"the {_WT_BITS} bits the packed chunk metadata gives it "
            f"(shard over more workers or use algo='dense')")
    return (wt << _WT_SHIFT | np.where(noop, _NOOP, 0)).astype(np.int32)


def unpack_chunk_meta(meta):
    """``(wt, noop)`` of packed metadata — numpy arrays on the host, a
    traced i32 scalar inside the kernel and its index maps."""
    return meta >> _WT_SHIFT, (meta & _NOOP) != 0


def shift_chunk_meta(meta, tiles):
    """Packed metadata with every chunk's word tile index moved on by
    ``tiles`` (host or traced; the no-op flag stays): how a chunk list
    staged against one half-slice addresses that half-slice inside the
    whole resident slice, ``tiles`` being the tiles ahead of it."""
    return meta + (tiles << _WT_SHIFT)


def _gather_planes(tbl_f32, oh, dot, nplanes: int):
    """One-hot gather ``tbl @ oh`` with bf16 dots, exact for integer
    tables below ``256 ** nplanes``.

    ``nplanes == 0``: single bf16 dot of the raw table (values > 256
    round).  Otherwise the table splits into base-256 digit planes —
    every plane holds integers in [0, 256], which bf16 represents
    exactly — each plane gathers with its own bf16 dot (one-hot columns
    select single values, so the f32 accumulation is exact), and the
    digits recombine in f32 (exact below 2^24).  Plain jnp/lax math, so
    the same function runs inside the Pallas kernel and in numpy-backed
    unit tests.
    """
    if nplanes == 0:
        return dot(tbl_f32.astype(jnp.bfloat16), oh)
    acc = None
    rem = tbl_f32
    scale = 1.0
    for _ in range(nplanes - 1):
        hi = jnp.floor(rem * (1.0 / 256.0))
        lo = rem - hi * 256.0           # integer in [0, 255]: bf16-exact
        part = dot(lo.astype(jnp.bfloat16), oh) * scale
        acc = part if acc is None else acc + part
        rem = hi
        scale = scale * 256.0
    top = dot(rem.astype(jnp.bfloat16), oh) * scale
    # nplanes == 1: the caller proved values ≤ 256 (bf16-exact), so the
    # top "plane" IS the whole gather
    return top if acc is None else acc + top


def _kernel(meta_ref, sc_ref, db_in, wb_in, nk_in, z_in, cd_in, cw_in, *rest,
            alpha, beta, vbeta, has_noise, nplanes_d, nplanes_w):
    if has_noise:
        # CPU/interpret test path: pltpu.prng_random_bits is stubbed to
        # zeros off-TPU, so uniforms arrive as a sliced input instead
        noise_in, db_out, wb_out, z_out, dnk_out = rest
    else:
        db_out, wb_out, z_out, dnk_out = rest
    K, DR = db_in.shape
    _, WR = wb_in.shape
    cc = z_in.shape[-1]
    c = pl.program_id(0)   # chunk

    wt, noop = unpack_chunk_meta(meta_ref[c])
    prev = unpack_chunk_meta(meta_ref[jnp.maximum(c - 1, 0)])[0]

    @pl.when(c == 0)
    def _init():
        db_out[...] = db_in[...]
        dnk_out[...] = jnp.zeros_like(dnk_out)

    # First chunk of this word tile's contiguous group: seed the output
    # buffer from the pristine input block.  Later chunks of the group
    # read back their predecessors' deltas from the (still-resident)
    # output buffer.
    @pl.when((c == 0) | (wt != prev))
    def _start_group():
        wb_out[...] = wb_in[...]

    @pl.when(noop)
    def _skip():
        z_out[...] = z_in[...]

    @pl.when(jnp.logical_not(noop))
    def _sample():
        cd = cd_in[...].reshape(1, cc)                       # [1, cc] i32
        cw = cw_in[...].reshape(1, cc)
        z = z_in[...].reshape(1, cc)
        m = (cd < DR).astype(jnp.float32)                    # pad slots drop out

        ohd = (lax.broadcasted_iota(jnp.int32, (DR, cc), 0) == cd
               ).astype(jnp.bfloat16)                        # [DR, cc]
        ohw = (lax.broadcasted_iota(jnp.int32, (WR, cc), 0) == cw
               ).astype(jnp.bfloat16)
        rows_k = lax.broadcasted_iota(jnp.int32, (K, cc), 0)
        oh_old = (rows_k == z).astype(jnp.float32) * m       # [K, cc]

        dot = functools.partial(lax.dot_general,
                                preferred_element_type=jnp.float32)
        gdot = functools.partial(dot,
                                 dimension_numbers=(((1,), (0,)), ((), ())))
        # snapshot gathers — exact digit planes or single rounded bf16 dot
        # per the nplanes_* statics (see module doc / _gather_planes)
        ndkT = _gather_planes(db_out[...].astype(jnp.float32), ohd, gdot,
                              nplanes_d) - oh_old            # [K, cc]
        nwkT = _gather_planes(wb_out[...].astype(jnp.float32), ohw, gdot,
                              nplanes_w) - oh_old
        nkT = (nk_in[...] + dnk_out[...]) - oh_old           # [K, 1] bcast

        a = jnp.maximum(ndkT + alpha, 1e-10)
        b = jnp.maximum(nwkT + beta, 1e-10)
        cden = jnp.maximum(nkT + vbeta, 1e-10)
        # exponential race: argmin E/p, E ~ Exp(1), p ∝ a·b/c
        if has_noise:
            u = noise_in[...]                                # [K, cc] in (0,1)
        else:
            # distinct stream per (run, chunk).  The real TPU compiler
            # accepts at most TWO seed words ("Setting seed with more than
            # 2 values is not supported", silicon 2026-08-01; the CPU
            # Mosaic lowering pass does NOT enforce this), so the chunk id
            # is folded into the second run-key word with an odd-constant
            # multiply (golden-ratio 0x9E3779B9, int32 wraparound) + xor —
            # distinct chunks stay distinct, streams stay decorrelated
            pltpu.prng_seed(sc_ref[1],
                            sc_ref[2] ^ (c * jnp.int32(-1640531527)))
            bits = pltpu.prng_random_bits((K, cc))
            # logical shift keeps int32 (Mosaic has no uint32->f32 cast):
            # 24 uniform bits -> (0, 1)
            u = lax.shift_right_logical(bits, 8).astype(jnp.float32) \
                * (2.0 ** -24) + 2.0 ** -25
        ratio = -jnp.log(u) * cden / (a * b)                 # [K, cc]

        best = ratio.min(axis=0, keepdims=True)              # [1, cc]
        # tie-break min runs in f32 (exact for indices ≤ K < 2^24): Mosaic
        # has no integer reduce_min on older toolchains
        z_new = jnp.where(ratio == best, rows_k, K).astype(jnp.float32) \
            .min(axis=0, keepdims=True).astype(jnp.int32)
        z_new = jnp.where(m > 0, z_new, z)
        z_out[...] = z_new.reshape(z_out.shape)

        oh_new = (rows_k == z_new).astype(jnp.float32) * m
        delta = (oh_new - oh_old).astype(jnp.bfloat16)       # 0/±1: exact
        dDb = dot(delta, ohd, (((1,), (1,)), ((), ())))      # [K, DR] exact
        dWb = dot(delta, ohw, (((1,), (1,)), ((), ())))
        db_out[...] = (db_out[...].astype(jnp.float32) + dDb
                       ).astype(db_out.dtype)
        wb_out[...] = wb_out[...] + dWb
        dnk_out[...] += delta.astype(jnp.float32).sum(axis=1, keepdims=True)


def _planes_for(count_bound, dtype) -> int:
    """Fewest base-256 digit planes that gather a count table EXACTLY.

    ``count_bound`` is a static upper bound on any table value — a chain
    INVARIANT when supplied (doc-topic counts ≤ doc length, word-topic
    counts ≤ word frequency; row sums never change under Gibbs), so the
    caller may derive it once from the initial tables.  None falls back
    to what the dtype can hold.
    """
    if count_bound is not None:
        if count_bound <= 256:
            return 1        # bf16 holds 0..256 exactly: one plain dot
        if count_bound < 2 ** 16:
            return 2
        return 3
    return 2 if jnp.dtype(dtype) == jnp.int16 else 3


def vmem_bytes(K, DR, WR, cc, db_itemsize, nplanes_d, nplanes_w) -> int:
    """What one call asks of VMEM: both count tiles in and out, each
    double-buffered by the block pipeline (the word tile has to be, to
    stream; the doc tile is, because every block is), ~6 live [K, cc]
    f32 temporaries, and the exact-gather plane temporaries (f32
    remainder + bf16 plane of the currently-gathered table: ~6 B/elem,
    tables gathered in turn; single-plane and single-dot gathers only pay
    the bf16 cast)."""
    per_elem = 6 if max(nplanes_d, nplanes_w) >= 2 else 2
    return (4 * db_itemsize * K * DR + 4 * 4 * K * WR
            + 6 * 4 * K * cc + per_elem * K * max(DR, WR))


def cgs_run_update(NdkT, NwkT, nk, z, cd, cw, meta, run, seed2, *,
                   alpha, beta, vbeta, d_tile: int, w_tile: int,
                   interpret: bool = False, exact_gathers: bool = True,
                   ndk_count_bound=None, nwk_count_bound=None,
                   uniforms=None):
    """Resample one document-tile run's chunk list; return updated tables.

    ``NdkT`` [K, docs] (float32 or int16), ``NwkT`` [K, words] float32 —
    topic-major count tables, of which the call touches doc tile ``run``
    and the word tiles its chunks name; ``nk`` [K] topic totals the run
    should sample against; ``z/cd/cw`` [NCH, cc] current topics +
    tile-local ids of one chunk each (pad id = tile width); ``meta``
    [NCH] packed per-chunk metadata (:func:`pack_chunk_meta`); ``run``
    i32 scalar; ``seed2`` [2] int32.  The chunk list MUST keep each word
    tile's chunks adjacent — :func:`stage_chunk_list` builds it from
    ``partition_ratings_tiles``' entries.  Returns
    ``(NdkT', NwkT', z_new [NCH, cc], dnk [K])``; both tables alias
    their outputs, so the blocks the run does not visit keep their counts.

    ``uniforms`` [K, NCH * cc] (interpret mode only) replaces the
    uniforms drawn from ``seed2``, chunk ``c`` reading columns
    ``c * cc`` onward: how a test feeds two layouts of the same tokens
    the same draws.
    """
    K, _ = NdkT.shape
    NCH, cc = z.shape
    DR, WR = d_tile, w_tile
    # digit planes sized by the tightest static bound available: a
    # corpus-derived count bound (see _planes_for — chain-invariant),
    # else what the dtype can hold
    nplanes_d = (_planes_for(ndk_count_bound, NdkT.dtype)
                 if exact_gathers else 0)
    nplanes_w = (_planes_for(nwk_count_bound, NwkT.dtype)
                 if exact_gathers else 0)
    if not interpret:
        for name, v, mlt in (("d_tile", DR, _LANE), ("w_tile", WR, _LANE),
                             ("chunk", cc, _LANE), ("n_topics", K, 8)):
            if v % mlt:
                raise ValueError(
                    f"pallas lda: {name}={v} must be a multiple of {mlt} "
                    f"on TPU (use algo='dense' for odd shapes)")
    est = vmem_bytes(K, DR, WR, cc, NdkT.dtype.itemsize, nplanes_d,
                     nplanes_w)
    if est > _VMEM_BUDGET:
        raise ValueError(
            f"pallas lda: ~{est >> 20} MB VMEM estimate exceeds the "
            f"{_VMEM_BUDGET >> 20} MB budget at chunk {cc}; lower "
            f"d_tile/w_tile or use algo='dense'")
    if NCH > _MAX_CHUNKS:
        raise ValueError(
            f"pallas lda: {NCH} chunks a document-tile run > {_MAX_CHUNKS}, "
            f"the metadata the kernel can prefetch into SMEM; shard over "
            f"more workers or use algo='dense'")

    # index maps see the grid index and both prefetched operands
    def d_block(c, m, s):
        return 0, s[0]

    def w_block(c, m, s):
        return 0, unpack_chunk_meta(m[c])[0]

    def fixed(c, m, s):
        return 0, 0

    # chunk streams ride [NCH, 1, cc]: Mosaic requires block dim -2 to
    # divide 8 or equal the array dim — (1, cc) over [NCH, cc] is
    # illegal, (1, 1, cc) over [NCH, 1, cc] is exact in dim -2
    def stream(c, m, s):
        return c, 0, 0

    in_specs = [
        pl.BlockSpec((K, DR), d_block),
        pl.BlockSpec((K, WR), w_block),
        pl.BlockSpec((K, 1), fixed),
        pl.BlockSpec((1, 1, cc), stream),
        pl.BlockSpec((1, 1, cc), stream),
        pl.BlockSpec((1, 1, cc), stream),
    ]
    operands = [NdkT, NwkT, nk.reshape(K, 1), z.reshape(NCH, 1, cc),
                cd.reshape(NCH, 1, cc), cw.reshape(NCH, 1, cc)]
    if interpret:
        # off-TPU the hardware PRNG is unavailable (pltpu.prng_random_bits
        # stubs to zeros in interpret mode) — draw the uniforms outside
        # and stream them in per chunk; the TPU path never pays this HBM
        if uniforms is None:
            key = jax.random.wrap_key_data(seed2.astype(jnp.uint32)[:2])
            uniforms = jax.random.uniform(key, (K, NCH * cc), jnp.float32,
                                          minval=2.0 ** -25, maxval=1.0)
        in_specs.append(pl.BlockSpec((K, cc), lambda c, m, s: (0, c)))
        operands.append(uniforms)
    elif uniforms is not None:
        raise ValueError("pallas lda: uniforms are an interpret-mode input; "
                         "the compiled kernel draws the chip's own bits")
    sc = jnp.concatenate([jnp.asarray(run, jnp.int32).reshape(1),
                          seed2.astype(jnp.int32)[:2]])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # meta, (run, seed words)
        grid=(NCH,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((K, DR), d_block),
            pl.BlockSpec((K, WR), w_block),
            pl.BlockSpec((1, 1, cc), stream),
            pl.BlockSpec((K, 1), fixed),
        ],
    )
    Ndk2, Nwk2, z_new, dnk = pl.pallas_call(
        functools.partial(_kernel, alpha=alpha, beta=beta, vbeta=vbeta,
                          has_noise=bool(interpret),
                          nplanes_d=nplanes_d, nplanes_w=nplanes_w),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(NdkT.shape, NdkT.dtype),
            jax.ShapeDtypeStruct(NwkT.shape, jnp.float32),
            jax.ShapeDtypeStruct((NCH, 1, cc), jnp.int32),
            jax.ShapeDtypeStruct((K, 1), jnp.float32),
        ],
        # operand numbering counts the two prefetched arrays
        input_output_aliases={2: 0, 3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(meta, sc, *operands)
    return Ndk2, Nwk2, z_new.reshape(NCH, cc), dnk.reshape(K)


def cgs_entry_update(DbT, WbT, nk, z, cd, cw, seed2, *, alpha, beta, vbeta,
                     chunk_c: int = 256, interpret: bool = False,
                     exact_gathers: bool = True, ndk_count_bound=None,
                     nwk_count_bound=None):
    """Resample one dense tile entry's tokens; return updated tiles — the
    one-entry face of :func:`cgs_run_update` (the same kernel body on a
    run of one tile: what the exactness tests, the kernel registry and
    ``chip_smoke`` call).

    ``DbT`` [K, d_tile] (float32 or int16), ``WbT`` [K, w_tile] float32;
    ``nk`` [K]; ``z/cd/cw`` [C] current topics + tile-local ids (pad
    id = tile width), cut into ``chunk_c``-slot chunks; ``seed2`` [2]
    int32.  Returns ``(DbT', WbT', z_new [C], dnk [K])``.
    """
    C = z.shape[0]
    cc = min(C, chunk_c)
    if C % cc:
        raise ValueError(f"C={C} must be a multiple of chunk_c={cc} "
                         f"(pad entries with DR/WR ids)")
    nch = C // cc
    Db2, Wb2, z_new, dnk = cgs_run_update(
        DbT, WbT, nk, z.reshape(nch, cc), cd.reshape(nch, cc),
        cw.reshape(nch, cc), jnp.zeros(nch, jnp.int32), 0, seed2,
        alpha=alpha, beta=beta, vbeta=vbeta, d_tile=DbT.shape[1],
        w_tile=WbT.shape[1], interpret=interpret,
        exact_gathers=exact_gathers, ndk_count_bound=ndk_count_bound,
        nwk_count_bound=nwk_count_bound)
    return Db2, Wb2, z_new.reshape(C), dnk


def stage_chunk_list(ed, ew, ez, od, ow, n_runs, d_tile, w_tile, cc=CHUNK):
    """Host prep: ``partition_ratings_tiles``' ``[WS, NE, C]`` entries →
    the kernel's chunk list ``cd/cw/z [WS, NCH, cc]`` + ``meta [WS, NCH]``
    (numpy, worker-major; ``z`` int32 from the entries' topic "values").

    A row is ``n_runs`` document-tile runs of ``NCH // n_runs`` chunks
    each (run ``r`` = doc tile ``r``; the longest run of any row sets the
    length).  An entry contributes only the ``cc``-wide chunks that hold
    its tokens (``ceil(count / cc)``, adjacent), entries keep their
    order, and every token keeps its place in it: the chunks left out
    were all padding.  A run shorter than the longest — an empty one
    too — ends in no-op chunks at its last chunk's word tile (tile 0 for
    an empty run), which switch no block and skip the kernel's body.
    Raises where the entries are not document-tile-major with each word
    tile one contiguous group inside a run.
    """
    ws, ne, c = ed.shape
    valid = ed < d_tile
    counts = valid.sum(-1)
    # the chunks past ceil(count / cc) are dropped unread: they must hold
    # no token, so the valid slots must lead each entry — its last one
    # sits at count - 1 (no [WS, NE, C] temporary: this runs on gigabytes)
    if (np.where(counts > 0, c - valid[..., ::-1].argmax(-1), 0)
            != counts).any():
        raise ValueError("valid slots must lead each entry")
    k = -(-counts // cc)                       # chunks an entry
    run, wt = od // d_tile, ow // w_tile
    rows = []
    for w in range(ws):
        nreal = int((counts[w] > 0).sum())
        if not (counts[w, :nreal] > 0).all():
            raise ValueError("real entries must be a prefix")
        order = run[w, :nreal].astype(np.int64) << _WT_BITS | wt[w, :nreal]
        if nreal and (run[w, :nreal].max() >= n_runs
                      or (np.diff(order) < 0).any()):
            raise ValueError(
                "entries must be document-tile-major, each word tile one "
                "contiguous group inside a run")
        per_run = np.bincount(run[w, :nreal], weights=k[w, :nreal],
                              minlength=n_runs).astype(np.int64)
        rows.append((nreal, per_run))
    nchr = max(1, max(int(per_run.max()) for _, per_run in rows))
    nch = n_runs * nchr
    # Pad slots need only cd = d_tile: the doc-side mask and the all-zero
    # one-hot column zero out every count contribution whatever cw/z hold.
    cd = np.full((ws, nch, cc), d_tile, ed.dtype)
    cw = np.full((ws, nch, cc), w_tile, ew.dtype)
    z = np.zeros((ws, nch, cc), np.int32)
    meta = np.empty((ws, nch), np.int32)
    for w, (nreal, per_run) in enumerate(rows):
        kw, rw, ww = k[w, :nreal], run[w, :nreal], wt[w, :nreal]
        # an entry's first chunk: its run's slab + the chunks of the
        # run's entries before it
        ahead = np.cumsum(kw) - kw
        first = rw * nchr + ahead - (np.cumsum(per_run) - per_run)[rw]
        # a run's no-ops sit at its last entry's word tile
        last_wt = np.zeros(n_runs, np.int64)
        last_wt[rw] = ww                       # later entries overwrite
        m = pack_chunk_meta(np.repeat(last_wt, nchr), True)
        entry = np.repeat(np.arange(nreal), kw)
        j = np.arange(entry.size) - ahead[entry]   # its place in the entry
        m[first[entry] + j] = pack_chunk_meta(ww[entry], False)
        meta[w] = m
        # the j-th chunks of every entry that has one, a pass each
        for jj in range(int(kw.max(initial=0))):
            sel = np.flatnonzero(kw > jj)
            lo, hi = jj * cc, min((jj + 1) * cc, c)
            for dst, a in ((cd, ed), (cw, ew), (z, ez)):
                dst[w][first[sel] + jj, :hi - lo] = a[w][sel, lo:hi]
    return cd, cw, z, meta
