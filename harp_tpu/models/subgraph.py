"""Subgraph counting via color-coding — graded config #5a (irregular).

Reference parity (SURVEY.md §3.4): Harp's ``edu.iu.subgraph`` (and
``edu.iu.daal_subgraph``) counts tree-shaped templates (u3-1, u5-x, u7-x …)
in a large graph with the color-coding dynamic program: randomly color
vertices with s colors (s = template size), count *colorful* embeddings
(all colors distinct) by DP over a rooted decomposition of the template,
then unbias by the colorfulness probability ``s!/sˢ``.  Harp parallelizes
by vertex partition and exchanges per-vertex count tables with
``allgather``/``regroup`` each DP level — the "irregular" workload.

TPU-native design: the per-vertex count table for a partial absorbing j
template vertices is stored **compactly over the C(k, j) size-j color
subsets** (a colorful partial uses exactly j distinct colors — every
other bitmask column is identically zero), so each DP level becomes

  ``counts_t[v, S] = Σ_{S₁⊎S₂=S} counts_{t₁}[v, S₁] · (A @ counts_{t₂})[v, S₂]``

— a sparse-neighbor aggregation (a gather + mask over the compact
columns: each vertex's first ``max_degree`` neighbors from a padded
table, rows taken in degree order so that a tile gathers only as many
slots as its widest row holds, and an exact tail for the rest, cut into
rows of at most ``max_degree`` slots and summed the same way) followed
by a subset convolution through static position maps.  The distributed
step is one ``allgather`` of the compact partner table per DP level,
matching Harp's communication pattern verb-for-verb at the C(k, j)/2ᵏ
fraction of the naive dense wire (u5-tree: 5–10 of 32 columns per
level; u7-tree ≤ 35 of 128).

A chunk of colorings shares every gather: tables are 2-D,
``[vertices, C(k, j) * trials]`` with the trial the minor index, so one
neighbor-row gather serves the whole chunk, and children that are the
same rooted sub-template (u5-tree's three leaves) share one allgather
and one neighbor sum.  The sum runs over row tiles, the padded part and
the exact tail alike, so the largest gathered intermediate is a tile's
(:func:`_gather_tiles`), never ``[n, max_degree, columns]``; the tiles
of both follow the installed graph's own counts (:func:`degree_plan`: no
knob): on the cell's graph the padded part gathers 48% of the
``n x max_degree`` slots that are resident, and the tail's 54.9M entries
go as 718,773 rows of 56.6M slots, each row's sum added to its owner.

Two entry points, one implementation: :class:`SubgraphCounter` installs
a graph once (``set_graph``) and then counts chunk after chunk of fresh
colorings (``count_colorings``: one dispatch, one readback), drawn on
the device from the seed and the block index; :func:`count_template` is
a thin caller of the pair.  What the job does on a chip is in PERF.md
(cell ``subgraph-colorings``); the rates this docstring and BASELINE.md
carried before were pre-chip claims and are no baseline.
"""

from __future__ import annotations

import dataclasses
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from harp_tpu.parallel import collective as C
from harp_tpu.parallel.mesh import WorkerMesh, current_mesh
from harp_tpu.utils import flightrec, prng, skew, telemetry


# ---------------------------------------------------------------------------
# Templates: rooted trees given as parent lists; decomposition into
# (root-keeps-child-subtree) partial templates, exactly the color-coding DP.
# ---------------------------------------------------------------------------

TEMPLATES = {
    # name: parent list (parent[i] < i, parent[0] = -1 root)
    "u3-path": [-1, 0, 1],          # path on 3 vertices
    "u3-star": [-1, 0, 0],          # star (same graph, different rooting)
    "u5-path": [-1, 0, 1, 2, 3],
    "u5-star": [-1, 0, 0, 0, 0],
    "u5-tree": [-1, 0, 0, 1, 1],    # balanced binary-ish tree
    "u7-tree": [-1, 0, 0, 1, 1, 2, 2],
    # the deep end of the reference's template ladder (upstream shipped
    # 10-15-vertex trees): DP table width is 2^k subset columns, so
    # u10 = 1024 and u12 = 4096 columns — the compact C(k, j) storage
    # keeps memory at the size-j support only
    "u10-tree": [-1, 0, 0, 1, 1, 2, 2, 3, 3, 4],
    "u12-tree": [-1, 0, 0, 1, 1, 2, 2, 3, 3, 4, 4, 5],
}


def template_size(tpl) -> int:
    return len(tpl)


def _children(tpl):
    ch = [[] for _ in tpl]
    for i, p in enumerate(tpl):
        if p >= 0:
            ch[p].append(i)
    return ch


def _subtree_sizes(tpl):
    ch = _children(tpl)
    size = [1] * len(tpl)
    for i in reversed(range(len(tpl))):
        for c in ch[i]:
            size[i] += size[c]
    return size


_FN_CACHE: dict = {}

# the largest intermediate one neighbor-sum tile may gather, as the chip
# lays it out (float32, the minor dimension padded to 128 lanes)
_GATHER_TILE_BYTES = 256 << 20


def _gather_tiles(slots: int, width: int) -> tuple[int, int]:
    """``(rows, entries)`` a tile of the neighbor sum: the largest powers
    of two whose gathered ``[rows, slots, width]`` (padded part: ``slots``
    is what the tile's segment gathers of a row, :func:`degree_plan`) and
    ``[entries, width]`` (exact tail) stay under ``_GATHER_TILE_BYTES``;
    a tile's rows stop at 32,768 (a loop over tiles of 65,536 rows x 8
    slots took the v5e compiler 10.5 s where one of 32,768 takes 3.7)."""
    row_bytes = 4 * 128 * -(-width // 128)

    def pow2(x):
        return 1 << (max(int(x), 1).bit_length() - 1)

    return (min(pow2(_GATHER_TILE_BYTES // (row_bytes * slots)), 1 << 15),
            pow2(_GATHER_TILE_BYTES // row_bytes))


def _segment_tile(rows: int, slots: int, most: int) -> int:
    """The rows of a tile of a segment of ``rows`` rows summed at ``slots``
    slots each: at most ``most``, evened out over the segment's tiles (the
    last tile is moved back to end at the last row and repeats what the
    one before it did), and the next more whose ids, rows x slots, come in
    whole groups of 128 and not in a multiple of 8 groups: XLA's row
    gather on a v5e takes 10.7 ns a row when they do and 4.7 when not, a
    ragged last group 6.5 (PERF.md section 6, PR 39)."""
    fewest = -(-rows // most)
    for tiles in (fewest, fewest + 1):
        even = -(-rows // tiles)
        for tile in range(even, min(even + 128, most) + 1):
            if tile * slots % 128 == 0 and tile * slots // 128 % 8:
                return tile
    return -(-rows // fewest)


def degree_plan(counts, max_degree: int) -> tuple:
    """The static plan of a degree-ordered neighbor sum: segments
    ``(start, stop, width)`` over positions of the order, from
    ``counts [workers, rows]``, each worker's rows' real entries in the
    padded part, ascending.  A segment gathers ``width`` slots of each of
    its rows: the count at its last position, the largest over the
    workers (under ``shard_map`` they run one program), rounded up to a
    multiple of 8 (the sublane) and never over ``max_degree``.  A segment
    shorter than one tile rides with the next wider one.  Rows that are
    all full give one segment of the whole width."""
    widest = np.maximum(np.asarray(counts).max(0), 1)
    width = np.minimum(-(-widest // 8) * 8, max_degree)
    stops = np.append(np.flatnonzero(np.diff(width)) + 1, len(width))
    plan, start = [], 0
    for stop in stops:
        w = int(width[stop - 1])
        if stop - start >= _gather_tiles(w, 128)[0] or stop == len(width):
            plan.append((start, int(stop), w))
            start = int(stop)
    return tuple(plan)


def plan_slots(plan) -> int:
    """The padded-part slots one worker gathers in a neighbor sum."""
    return sum((stop - start) * width for start, stop, width in plan)


def _canon(tpl, i=0) -> str:
    """Canonical form of the sub-template rooted at template vertex i."""
    return "(" + "".join(sorted(_canon(tpl, c) for c in _children(tpl)[i])) + ")"


def _sum_scope_names(tpl) -> dict:
    """The last part of a neighbour sum's scope ``subgraph.sum.<name>``,
    one stable name per distinct child shape: ``leaf`` for a leaf, else
    ``t<size>`` of the child's subtree, with a letter where two shapes
    share a size (in the order of their canonical forms)."""
    sizes = _subtree_sizes(tpl)
    by_size: dict[int, set] = {}
    for c in range(1, len(tpl)):
        by_size.setdefault(sizes[c], set()).add(_canon(tpl, c))
    names = {}
    for size, shapes in by_size.items():
        for j, shape in enumerate(sorted(shapes)):
            names[shape] = "leaf" if shape == "()" else f"t{size}" + (
                "abcdefghijklmnopqrstuvwxyz"[j] if len(shapes) > 1 else "")
    return names


def _alone(colors, k):
    """The table of a vertex by itself, ``[..., k * T]`` from colors
    ``[..., T]``: compact singleton — supp[1] is [1<<0, 1<<1, ...]
    ascending, so the position of color c's mask is c, a plain one-hot
    (column ``c * T + t``: trial t has color c)."""
    T = colors.shape[-1]
    return (jnp.concatenate([colors] * k, axis=-1)
            == jnp.repeat(jnp.arange(k, dtype=colors.dtype), T)
            ).astype(jnp.float32)


def _color_bits(k: int) -> tuple[int, int]:
    """``(bits a color takes, colors a uint32 word holds)``."""
    bits = max(1, (k - 1).bit_length())
    return bits, 32 // bits


def _pack_colors(colors, k):
    """Colors ``[n, T]`` → uint32 words ``[n, ceil(T / per)]``, trial t
    in word ``t // per`` at bit ``bits * (t % per)``: what a leaf's
    neighbors are asked for, 4 bytes where its one-hot table row is
    ``4 * k * T``."""
    bits, per = _color_bits(k)
    T = colors.shape[1]
    words = -(-T // per)
    c = jnp.pad(colors.astype(jnp.uint32), ((0, 0), (0, words * per - T)))
    shifts = bits * jnp.arange(per, dtype=jnp.uint32)
    return (c.reshape(-1, words, per) << shifts).sum(-1, dtype=jnp.uint32)


def _unpack_colors(words, k, T):
    """The inverse, on any leading shape: ``[..., words]`` → ``[..., T]``
    int32."""
    bits, per = _color_bits(k)
    return jnp.stack(
        [(words[..., t // per] >> jnp.uint32(bits * (t % per)))
         & jnp.uint32((1 << bits) - 1) for t in range(T)],
        axis=-1).astype(jnp.int32)


def block_colors(key_bits, block, n_rows: int, trials: int, k: int):
    """The colors of one block of colorings, ``[n_rows, trials]`` int32:
    vertex v of the block's t-th coloring draws from (seed, block, v, t)
    alone, so the draw is the same on any mesh and for any padding of the
    rows.  THE definition: the block program draws with it, and
    :meth:`SubgraphCounter.block_colors` answers with it."""
    key = jax.random.fold_in(jax.random.wrap_key_data(key_bits), block)
    return jax.random.randint(key, (n_rows, trials), 0, k, jnp.int32)


def make_colorful_count_fn(tpl, k, mesh: WorkerMesh,
                           overflow_algo: str = "segment",
                           row_tile: int = 512, draw_trials: int = 0,
                           plan: tuple | None = None,
                           tail_plan: tuple | None = None):
    """Compile the color-coding DP:
    (nbr [n, deg], msk [n, deg], *overflow, colors [trial_chunk, n]) →
    [trial_chunk] colorful rooted counts — a chunk of trials per program
    (the driver chunks, see SubgraphConfig.trial_chunk).  With
    ``draw_trials`` the last argument is ``(key_bits uint32[2], block
    int32)`` instead and the program draws its own ``draw_trials``
    colorings (:func:`block_colors`): nothing crosses to the device.
    With a ``plan`` (:func:`degree_plan`; static) the padded part is
    summed in degree order, each segment at its own width, and the
    program takes ``order [n]`` (each worker's local rows, fewest real
    entries first) after the overflow arrays; without one every row is
    summed at the whole ``deg``, in vertex order.
    ``overflow_algo`` picks the exact tail
    for past-max_degree adjacency (see SubgraphConfig): "segment" takes
    the 3 flattened arrays of :func:`_partition_overflow`, "onehot" the
    4 tiled arrays of :func:`_partition_overflow_tiles`.  With a plan
    "segment" takes the 3 arrays of :func:`_tail_rows` in their place and
    sums them by ``tail_plan`` (theirs; static; ``()`` where the graph has
    no tail: a program with no tail loop).

    Counts maps φ: template→graph with all image colors distinct (hence
    injective), rooted at template vertex 0 — the quantity Harp's DP
    levels accumulate before unbiasing.  Compiled fns are cached per
    (template, colors, mesh, overflow formulation); jit re-specializes
    per trials count.
    """
    # key on the underlying jax Mesh (hashable, identity-stable), not the
    # WorkerMesh wrapper, whose id could be reused after collection;
    # row_tile only shapes the onehot trace — keying it under "segment"
    # would cache duplicate byte-identical programs
    by_rows = plan is not None and overflow_algo == "segment"
    if by_rows and tail_plan is None:
        raise ValueError("a planned program sums its tail as rows: it "
                         "needs the tail's plan too (() for no tail)")
    cache_key = (tuple(tpl), k, mesh.mesh, overflow_algo,
                 row_tile if overflow_algo == "onehot" else None,
                 draw_trials, plan, tail_plan if by_rows else None)
    if cache_key in _FN_CACHE:
        return _FN_CACHE[cache_key]
    ch = _children(tpl)
    sizes = _subtree_sizes(tpl)
    combos = _dp_subset_tables(tpl, k)
    n_subsets = 1 << k
    n_ovf_args = 3 if overflow_algo == "segment" else 4
    sum_names = _sum_scope_names(tpl)

    def over_tiles(fn, rows, tile, width, *arrays):
        """``fn`` over tiles of ``tile`` rows of ``arrays``, written into
        a ``[rows, width]`` result; the last tile is moved back to end at
        the last row, so it recomputes (to the same values) what the
        tile before it already wrote."""
        if rows <= tile:
            return fn(*arrays)

        def body(i, out):
            lo = jnp.minimum(i * tile, rows - tile)
            blk = fn(*(jax.lax.dynamic_slice_in_dim(a, lo, tile, 0)
                       for a in arrays))
            return jax.lax.dynamic_update_slice_in_dim(out, blk, lo, 0)

        return jax.lax.fori_loop(0, -(-rows // tile), body,
                                 jnp.zeros((rows, width), jnp.float32))

    def in_order(fn, out, rows, tile):
        """``fn`` over tiles of ``tile`` of the row ids ``rows`` (a
        stretch of a permutation: distinct and in bounds), each tile's
        result set into those rows of ``out``; the last tile is moved
        back as in ``over_tiles``."""
        def put(out, ids):
            sums = fn(ids)
            # never indices_are_sorted: on rows that were, that scatter
            # took some 670 ns a row on a v5e where this one takes 71
            with jax.named_scope("subgraph.order.put"):
                return out.at[ids].set(sums, unique_indices=True,
                                       mode="promise_in_bounds")

        count = rows.shape[0]
        if count <= tile:
            return put(out, rows)

        def body(i, out):
            with jax.named_scope("subgraph.order.take"):
                ids = jax.lax.dynamic_slice_in_dim(
                    rows, jnp.minimum(i * tile, count - tile), tile)
            return put(out, ids)

        return jax.lax.fori_loop(0, -(-count // tile), body, out)

    def tail_by_rows(rows_of, lanes, out, t_nbr, t_own, t_msk):
        """``out`` + the exact tail as :func:`_tail_rows` staged it: for
        each segment of the tail's plan, tiles of its rows, each row's
        first ``width`` slots gathered, masked and summed as the padded
        part's are, and the sums added into ``out`` at the rows' owners.
        A vertex's tail may be several rows (a hub's, hundreds), in one
        tile or in several: an add, and no ``unique_indices``."""
        def add(out, lo, rows, width, first):
            with jax.named_scope("subgraph.tail.rows"):
                nb, mk = (jax.lax.dynamic_slice(a, (lo, 0), (rows, width))
                          for a in (t_nbr, t_msk))
                # a last tile moved back: what the tile before it added
                # already is masked out
                mk = mk * (lo + jnp.arange(rows) >= first)[:, None]
                sums = (rows_of(nb) * mk[:, :, None]).sum(1)
            # never indices_are_sorted, as in ``in_order``: with owners
            # ascending in every tile it made the cell's block 4.85 s
            # where this one is 3.72 (PERF.md section 6, PR 41)
            with jax.named_scope("subgraph.tail.add"):
                return out.at[jax.lax.dynamic_slice_in_dim(t_own, lo, rows)
                              ].add(sums, mode="promise_in_bounds")

        for start, stop, width in tail_plan:
            count = stop - start
            tile = _segment_tile(count, width, _gather_tiles(width, lanes)[0])
            if count <= tile:
                out = add(out, start, count, width, start)
                continue

            def body(i, out, start=start, stop=stop, width=width, tile=tile):
                first = start + i * tile
                return add(out, jnp.minimum(first, stop - tile), tile, width,
                           first)

            out = jax.lax.fori_loop(0, -(-count // tile), body, out)
        return out

    def spmv_gather(rows_of, lanes, nbr, msk, order, ovf):
        # Σ_{u∈N(v)} rows_of(u): the padded part, each vertex's first
        # max_degree neighbors as dense [rows, slots] id tiles, + an
        # EXACT tail for entries past max_degree — no adjacency is ever
        # dropped (round-1 VERDICT weak #4: power-law hubs).  Both run
        # tile by tile (_gather_tiles), so no [n, deg, S] is ever
        # materialized.  With a plan the padded part goes in degree
        # order: a segment's tiles take their rows of nbr and msk by
        # ``order`` and only the segment's ``width`` slots of them (the
        # slots past it hold mask 0 on every row of the segment), and
        # set their sums back into vertex order; and the "segment" tail
        # goes as rows too (tail_by_rows).
        # ``rows_of(ids)`` gives the float32 ``[..., lanes]`` rows of
        # vertices ``ids``; the result is ``[n_loc, lanes]``.
        n_loc = nbr.shape[0]
        r_tile, e_tile = _gather_tiles(nbr.shape[1], lanes)

        def padded(nb, mk):
            with jax.named_scope("subgraph.padded"):  # [tile, deg, S]
                return (rows_of(nb) * mk[:, :, None]).sum(1)

        if plan is None:
            out = over_tiles(padded, n_loc, r_tile, lanes, nbr, msk)
        else:
            def summed(width):
                def slots(a, ids):  # whole rows, cut to the segment
                    with jax.named_scope("subgraph.order.take"):
                        return a.at[ids].get(
                            unique_indices=True,
                            mode="promise_in_bounds")[:, :width]

                return lambda ids: padded(slots(nbr, ids), slots(msk, ids))

            out = jnp.zeros((n_loc, lanes), jnp.float32)
            for start, stop, width in plan:
                with jax.named_scope("subgraph.order.take"):
                    segment = order[start:stop]
                out = in_order(summed(width), out, segment,
                               _segment_tile(stop - start, width,
                                             _gather_tiles(width, lanes)[0]))
        with jax.named_scope("subgraph.tail"):
            if by_rows:
                return tail_by_rows(rows_of, lanes, out, *ovf)
            if overflow_algo == "segment":
                # the program without a plan alone (the planned one
                # returned above): the flat tail, an add an entry
                o_nbr, o_row, o_msk = ovf
                m = o_nbr.shape[0]
                if m <= e_tile:
                    og = rows_of(o_nbr) * o_msk[:, None]
                    # _partition_overflow emits o_row ascending (padding
                    # id 0 first), so the sorted segment-sum lowering
                    # applies — the cheap mitigant for the v5e ~25 GB/s
                    # small-row scatter floor (CLAUDE.md)
                    return out + jax.ops.segment_sum(
                        og, o_row, num_segments=n_loc,
                        indices_are_sorted=True)

                def body(i, acc):
                    # the same sorted scatter-add, e_tile entries at a
                    # time; the last tile ends at the last entry and masks
                    # what the tile before it already added
                    lo = jnp.minimum(i * e_tile, m - e_tile)
                    nb, rw, mk = (
                        jax.lax.dynamic_slice_in_dim(a, lo, e_tile)
                        for a in (o_nbr, o_row, o_msk))
                    mk = mk * (lo + jnp.arange(e_tile) >= i * e_tile)
                    return acc.at[rw].add(rows_of(nb) * mk[:, None],
                                          indices_are_sorted=True)

                return out + jax.lax.fori_loop(
                    0, -(-m // e_tile), body,
                    jnp.zeros((n_loc, lanes), jnp.float32))
            # "onehot": no scatter at all — each (entry × row-window) tile
            # is one one-hot MXU matmul into a dynamic-sliced block (the
            # mfsgd/lda pattern); acc is padded by row_tile so the last
            # window's slice stays in bounds
            t_nbr, t_loc, t_msk, t_lo = ovf
            acc = jnp.concatenate(
                [out, jnp.zeros((row_tile, out.shape[1]), out.dtype)], 0)

            def body(a, tile):
                nb, lc, mk, lo = tile
                og = rows_of(nb) * mk[:, None]                     # [TE, S]
                oh = jax.nn.one_hot(lc, row_tile, dtype=og.dtype)  # [TE, R]
                contrib = jax.lax.dot_general(  # ohᵀ @ og → [R, S], MXU
                    oh, og, (((0,), (0,)), ((), ())))
                blk = jax.lax.dynamic_slice_in_dim(a, lo, row_tile, 0)
                return jax.lax.dynamic_update_slice_in_dim(
                    a, blk + contrib, lo, 0), None

            acc, _ = jax.lax.scan(body, acc, (t_nbr, t_loc, t_msk, t_lo))
            return acc[: out.shape[0]]

    # Colorful counting: a partial rooted at i with j template vertices
    # absorbed uses EXACTLY j distinct colors, so its table is supported
    # on the C(k, j) size-j subsets alone.  Tables therefore live
    # COMPACTLY over that support (round 3 session 2) — u5-tree keeps
    # 5–10 columns instead of 2^5 everywhere: the per-level allgather
    # wire, the neighbor gathers (the dominant cost), the overflow
    # tails, the subset convolution and the HBM footprint all shrink by
    # the support ratio.  Counts are
    # bit-identical: the dropped columns were identically zero.
    supp = {sz: [m for m in range(n_subsets)
                 if bin(m).count("1") == sz] for sz in range(k + 1)}
    pos = {sz: {m: j for j, m in enumerate(cols)}
           for sz, cols in supp.items()}

    def prog(nbr, msk, *rest):
        # rest: the overflow arrays, the order where there is a plan,
        # colors [n_loc, T]: a chunk of T trials per program — a
        # per-trial host loop would pay one dispatch+readback round
        # trip per trial and dominate multi-trial estimates; chunking
        # (not all trials at once) bounds the compact DP tables' HBM
        # footprint.  A table is [n_loc, C(k, j) * T], column c·T + t
        # the c-th size-j subset of trial t: 2-D, so the chip pads one
        # minor dimension, and one row gather serves all T trials
        ovf, colors = rest[:n_ovf_args], rest[-1]
        order = rest[n_ovf_args] if plan is not None else None
        T = colors.shape[1]

        def cols(table, c):
            return table[:, c * T:(c + 1) * T]

        with jax.named_scope("subgraph.singleton"):
            singleton = _alone(colors, k)

        # post-order DP: table[i] = counts for subtree rooted at i.
        # Sub-templates of one rooted shape have one table (every leaf's
        # is the singleton), so it is built, allgathered and
        # neighbor-summed once per SHAPE, not per template vertex
        tables, nbr_sums = {}, {}

        def nbr_sum(shape, table):
            """Σ over neighbors of the rows of ``table``, once per
            shape.  The chip keeps a float32 table's rows whole only
            from 64 columns up (under that it lays the vertices minor,
            and a row gather becomes one element gather a column: 17 s
            against 4.2 s for com-Orkut's 393M slots, PERF.md section 6,
            PR 38), so the rows that are gathered and scattered are
            widened to whole 128-lane rows: the chip pads them so
            anyway.  A leaf's table is the one-hot of its color: its
            neighbors are asked for their packed colors instead (one
            word where the row is ``k * T`` floats), and the one-hot is
            made of what arrives: the same whole numbers are summed."""
            if shape in nbr_sums:
                return nbr_sums[shape]
            width = table.shape[1]
            lanes = 128 * -(-width // 128)
            widen = ((0, lanes - width),)
            with jax.named_scope("subgraph.sum." + sum_names[shape]):
                if shape == "()":
                    with jax.named_scope("subgraph.allgather"):
                        packed = C.allgather(  # Harp step
                            _pack_colors(colors, k))

                    # one word a vertex (a chunk's colourings fit 32
                    # bits): a vector; XLA re-lays a [n, 1] table out
                    # inside every loop that gathers from it, 33 in the
                    # cell's program (0.10 s of its 3.72 s block, PERF.md
                    # section 6, PR 41).  The program without a plan and
                    # the "onehot" arm stay as they were
                    if by_rows and packed.shape[1] == 1:
                        packed = packed[:, 0]

                    def rows_of(ids):
                        got = _unpack_colors(
                            jnp.take(packed, ids, axis=0).reshape(
                                ids.shape + (-1,)), k, T)
                        return jnp.pad(_alone(got, k),
                                       ((0, 0),) * (got.ndim - 1) + widen)
                else:
                    with jax.named_scope("subgraph.allgather"):
                        child_full = jnp.pad(  # compact Harp step
                            C.allgather(table), ((0, 0),) + widen)

                    def rows_of(ids):
                        return jnp.take(child_full, ids, axis=0)

                nbr_sums[shape] = spmv_gather(
                    rows_of, lanes, nbr, msk, order, ovf)[:, :width]
            return nbr_sums[shape]

        for i in reversed(range(len(tpl))):
            shape = _canon(tpl, i)
            if shape in tables:
                continue
            acc = singleton  # root-of-subtree alone
            acc_size = 1
            for c in ch[i]:
                child = _canon(tpl, c)
                nbr_counts = nbr_sum(child, tables[child])
                new_size = acc_size + sizes[c]
                # subset convolution: output column S sums, in the
                # plan's order, acc[S1] · nbr_counts[S2] over S1 ⊎ S2 = S
                with jax.named_scope("subgraph.convolve"):
                    out_cols = [None] * len(supp[new_size])
                    for S, S1, S2 in combos(acc_size, sizes[c]):
                        term = (cols(acc, pos[acc_size][S1])
                                * cols(nbr_counts, pos[sizes[c]][S2]))
                        j = pos[new_size][S]
                        out_cols[j] = term if out_cols[j] is None \
                            else out_cols[j] + term
                    acc = jnp.concatenate(out_cols, axis=1)
                acc_size = new_size
            tables[shape] = acc

        # the root table's support IS the size-s subsets (one column when
        # k == s): summing the compact table covers both cases
        root = tables[_canon(tpl, 0)]
        with jax.named_scope("subgraph.count"):
            rooted = cols(root, 0)
            for c in range(1, root.shape[1] // T):
                rooted = rooted + cols(root, c)
            return C.allreduce(rooted.sum(0))  # [trial_chunk], replicated

    body = mesh.shard_map(
        prog,
        in_specs=(mesh.spec(0),) * (2 + n_ovf_args + (plan is not None))
        + (mesh.spec(0, ndim=2),),
        out_specs=P(),
    )
    rows = mesh.sharding(mesh.spec(0, ndim=2))

    if draw_trials:
        def program(nbr, msk, *rest):
            with jax.named_scope("subgraph.draw"):
                colors = jax.lax.with_sharding_constraint(block_colors(
                    *rest[-1], nbr.shape[0], draw_trials, k), rows)
            return body(nbr, msk, *rest[:-1], colors)
    else:
        def program(nbr, msk, *rest):
            return body(nbr, msk, *rest[:-1], rest[-1].T)

    fn = flightrec.track(jax.jit(program), "subgraph.count")
    _FN_CACHE[cache_key] = fn
    return fn


@dataclasses.dataclass
class SubgraphConfig:
    template: str = "u5-tree"
    n_colors: int = 0        # 0 → template size (standard color-coding)
    n_trials: int = 1        # average over colorings (variance reduction)
    # trials per device program: chunking bounds the DP tables' HBM use at
    # [trial_chunk, n, C(k, j)] floats (compact support — at most
    # C(k, floor(k/2)) columns, e.g. 10 for u5 / 35 for u7, NOT 2^k)
    # while still amortizing the per-dispatch round trip over a chunk
    # (vmapping ALL trials would OOM large graphs at high n_trials)
    trial_chunk: int = 8
    # where the exact tail begins: a vertex's first max_degree neighbors
    # are resident as a row of the padded [n, max_degree] table, the rest
    # in the tail.  What is resident, not what is gathered: a neighbor
    # sum gathers of a row only its segment's width (degree_plan)
    max_degree: int = 64
    seed: int = 0
    # The exact tail for adjacency past max_degree, two formulations
    # (bitwise-equal keeps per tile/segment ordering aside; tested):
    # "segment" — the shipped default.  In the installed program (a graph
    # whose rows differ in degree: degree_plan) the tail goes as rows of
    # at most max_degree slots, staged by their entries and gathered and
    # summed as the padded part is, one add a row into its owner
    # (_tail_rows; the cell's 54.9M tail entries: 0.79 s a block where
    # the per-entry scatter-add below took 2.84, PERF.md section 6, PR
    # 41).  In the program without a plan, a sorted segment-sum over the
    # overflow edge list, entry by entry (24-27 ns an entry on a v5e; the
    # sorted lowering is the cheap mitigant of its ~25 GB/s small-row
    # scatter floor): the plain arm the tests compare against;
    # "onehot"  — the mfsgd/lda pattern: overflow entries grouped into
    # (entry_tile × row_tile) tiles, each applied as ONE one-hot MXU
    # matmul into a dynamic-sliced block (trades ~2·TE·R·S flops per
    # tile for no scatter at all).  Which wins on TPU is the profile
    # question queued since round 2 (BASELINE.md "Pallas headroom") —
    # both are resident so the answer is one --overflow-algo flag away.
    overflow_algo: str = "segment"
    overflow_row_tile: int = 512    # onehot: rows per tile block
    overflow_entry_tile: int = 2048  # onehot: max entries per tile

    def __post_init__(self):
        if self.overflow_algo not in ("segment", "onehot"):
            raise ValueError(f"overflow_algo must be 'segment' or "
                             f"'onehot', got {self.overflow_algo!r}")


def pad_csr(edges, n_vertices, max_degree):
    """Edge list → padded neighbor table [n, max_degree] + mask + overflow.

    Adjacency entries past ``max_degree`` are returned as an
    ``overflow [m, 2]`` array of (vertex, neighbor) rows — handled
    EXACTLY by the DP's segment-sum side path, never dropped (Harp's
    irregular memory reuse becomes a static-shape pad + exact tail).
    """
    e = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    src = np.concatenate([e[:, 0], e[:, 1]])
    dst = np.concatenate([e[:, 1], e[:, 0]])
    order = np.argsort(src, kind="stable")
    src, dst = src[order], dst[order]
    # position of each entry within its source-vertex run
    starts = np.searchsorted(src, np.arange(n_vertices))
    pos = np.arange(len(src)) - starts[src]
    keep = pos < max_degree
    nbr = np.zeros((n_vertices, max_degree), np.int32)
    msk = np.zeros((n_vertices, max_degree), np.float32)
    nbr[src[keep], pos[keep]] = dst[keep]
    msk[src[keep], pos[keep]] = 1.0
    overflow = np.stack([src[~keep], dst[~keep]], 1).astype(np.int64)
    return nbr, msk, overflow


def _partition_overflow(overflow, n_pad, nw):
    """Overflow edges → per-worker padded arrays, sharded like the rows.

    Worker w owns padded vertex rows [w·loc, (w+1)·loc); its overflow
    entries land in its block, padded to the max per-worker count (≥ 1 so
    shapes stay static even with no overflow).  Returns flattened
    ``(o_nbr [nw·m], o_row [nw·m] worker-LOCAL rows, o_msk [nw·m])``.
    """
    loc = n_pad // nw
    rows, nbrs = overflow[:, 0], overflow[:, 1]
    owner = rows // loc
    counts = np.bincount(owner, minlength=nw) if len(rows) else np.zeros(nw, int)
    m_pad = max(1, int(counts.max()))
    o_nbr = np.zeros((nw, m_pad), np.int32)
    o_row = np.zeros((nw, m_pad), np.int32)
    o_msk = np.zeros((nw, m_pad), np.float32)
    for w in range(nw):
        idx = np.flatnonzero(owner == w)
        t = len(idx)
        # padding FIRST (id 0), then rows ascending: the device side
        # relies on this to use the sorted segment-sum lowering
        order = np.argsort(rows[idx], kind="stable")
        o_row[w, m_pad - t:] = rows[idx][order] - w * loc
        o_nbr[w, m_pad - t:] = nbrs[idx][order]
        o_msk[w, m_pad - t:] = 1.0
    return o_nbr.reshape(-1), o_row.reshape(-1), o_msk.reshape(-1)


def _tail_rows(ovf, nw, max_degree):
    """The flat tail of :func:`_partition_overflow` cut into *tail rows*
    for the planned program: every vertex's tail entries, in the order
    they have there, fill rows of at most ``max_degree`` slots (the last
    of a vertex partial), and each worker's rows are staged by their
    real entries, fewest first (stable: equal counts by owner), padded
    with empty rows in front to the most any worker has (>= 1).

    Returns ``((t_nbr [nw*R, max_degree], t_own [nw*R] worker-LOCAL
    vertex rows, t_msk [nw*R, max_degree]), plan, rows)``: the plan is
    :func:`degree_plan`'s over the staged rows' counts (``()`` for no
    tail), so a segment is a contiguous slice of rows and of their first
    ``width`` columns; ``rows`` counts the rows that hold entries."""
    cut = []
    for nbrs, owners, real in zip(*(a.reshape(nw, -1) for a in ovf)):
        t = int(np.count_nonzero(real))  # padding first, then the entries
        nbrs, owners = nbrs[len(nbrs) - t:], owners[len(owners) - t:]
        # the run of each vertex: where it starts, how long it is, and
        # the rows it fills, each full but the last
        starts = np.flatnonzero(np.concatenate(
            [[t > 0], owners[1:] != owners[:-1]]))
        lengths = np.diff(starts, append=t)
        n_rows = -(-lengths // max_degree)
        counts = np.full(int(n_rows.sum()), max_degree)
        counts[np.cumsum(n_rows) - 1] = lengths - (n_rows - 1) * max_degree
        cut.append((nbrs, counts, np.repeat(owners[starts], n_rows)))
    most = max(1, max(len(counts) for _, counts, _ in cut))
    slots = np.arange(max_degree)
    t_nbr = np.zeros((nw, most, max_degree), np.int32)
    t_own = np.zeros((nw, most), np.int32)
    ranked = np.zeros((nw, most), np.int64)
    for w, (nbrs, counts, owners) in enumerate(cut):
        front = (most - len(counts), 0)  # empty rows: the fewest of all
        counts, owners = np.pad(counts, front), np.pad(owners, front)
        # by owner first: the entries fill their rows one after another,
        # each row from its first slot
        by_owner = np.zeros((most, max_degree), np.int32)
        by_owner[slots < counts[:, None]] = nbrs
        order = np.argsort(counts, kind="stable")
        t_nbr[w], t_own[w], ranked[w] = (
            by_owner[order], owners[order], counts[order])
    rows = sum(len(counts) for _, counts, _ in cut)
    # a row's entries come first: its mask is its count
    t_msk = np.empty((nw * most, max_degree), np.float32)
    np.less(slots, ranked.reshape(-1, 1), out=t_msk)
    return ((t_nbr.reshape(nw * most, max_degree), t_own.reshape(-1), t_msk),
            degree_plan(ranked, max_degree) if rows else (), rows)


def _partition_overflow_tiles(overflow, n_pad, nw, row_tile, entry_tile):
    """Overflow edges → per-worker (entry × row-window) tiles for the
    one-hot MXU tail: each tile holds ≤ ``entry_tile`` entries whose
    LOCAL rows all lie in one ``[lo, lo + row_tile)`` window (entries
    arrive row-ascending, so tiles are contiguous windows).  Returns
    ``(t_nbr [nw·NT, TE], t_loc [nw·NT, TE]`` — row offsets within the
    window, ``row_tile`` for padding (one-hot maps it to a zero row),
    ``t_msk [nw·NT, TE], t_lo [nw·NT])`` with NT the max per-worker tile
    count (≥ 1) and TE ≤ entry_tile sublane-rounded to the max fill.
    """
    loc = n_pad // nw
    rows, nbrs = overflow[:, 0], overflow[:, 1]
    owner = rows // loc if len(rows) else np.zeros(0, np.int64)
    per_w = []
    for w in range(nw):
        idx = np.flatnonzero(owner == w)
        order = np.argsort(rows[idx], kind="stable")
        r = (rows[idx][order] - w * loc).astype(np.int64)
        nb = nbrs[idx][order].astype(np.int32)
        tiles = []
        i = 0
        while i < len(r):
            lo = int(r[i])
            j = i
            while j < len(r) and j - i < entry_tile and r[j] < lo + row_tile:
                j += 1
            tiles.append((lo, (r[i:j] - lo).astype(np.int32), nb[i:j]))
            i = j
        per_w.append(tiles)
    NT = max(1, max((len(t) for t in per_w), default=1))
    max_e = max((len(locs) for tiles in per_w for _, locs, _ in tiles),
                default=0)
    TE = min(entry_tile, max(8, -(-max_e // 8) * 8))
    t_nbr = np.zeros((nw, NT, TE), np.int32)
    t_loc = np.full((nw, NT, TE), row_tile, np.int32)
    t_msk = np.zeros((nw, NT, TE), np.float32)
    t_lo = np.zeros((nw, NT), np.int32)
    for w, tiles in enumerate(per_w):
        for t, (lo, locs, nb) in enumerate(tiles):
            e = len(locs)
            t_lo[w, t] = lo
            t_nbr[w, t, :e] = nb
            t_loc[w, t, :e] = locs
            t_msk[w, t, :e] = 1.0
    return (t_nbr.reshape(nw * NT, TE), t_loc.reshape(nw * NT, TE),
            t_msk.reshape(nw * NT, TE), t_lo.reshape(nw * NT))


def _dp_subset_tables(tpl, n_colors):
    """Static DP plan: for each template vertex i (post-order), the list of
    (S, S1, S2) bitmask triples combining the partial at i with a child
    subtree, restricted to |S| == accumulated size.  Returns per-combine
    dense index arrays for a one-hot 'subset convolution' on device."""
    s = n_colors
    masks = list(range(1 << s))
    popcnt = [bin(m).count("1") for m in masks]

    def combos(sz1, sz2):
        out = []
        for S1 in masks:
            if popcnt[S1] != sz1:
                continue
            for S2 in masks:
                if popcnt[S2] != sz2 or (S1 & S2):
                    continue
                out.append((S1 | S2, S1, S2))
        return out

    return combos


class SubgraphCounter:
    """A graph installed once on the mesh, then chunk after chunk of
    independent colorings counted on it (the ``set_ratings`` /
    ``train_epochs`` shape of MF-SGD, for ``edu.iu.subgraph``).

    ``set_graph`` does the host's work once: the padded CSR, the exact
    tail's partition, the rows' degree order with its plan, the tail's
    rows with theirs, and the placement.  ``count_colorings`` is one
    dispatch and one readback: the block's colorings are drawn on the
    device from ``(cfg.seed, block)`` (:func:`block_colors`), and
    ``block_colors(b)`` says which colors block ``b`` uses.
    """

    def __init__(self, cfg: SubgraphConfig, mesh: WorkerMesh | None = None):
        self.cfg = cfg
        self.mesh = mesh or current_mesh()
        tpl = cfg.template
        self.tpl = TEMPLATES[tpl] if isinstance(tpl, str) else tpl
        s = template_size(self.tpl)
        self.k = k = cfg.n_colors or s
        if k < s:
            raise ValueError(
                f"n_colors={k} must be >= template size {s} for color-coding")
        # one colorful rooted count → one estimate of the unrooted count
        self.p_colorful = math.factorial(k) / (math.factorial(k - s) * k ** s)
        self.n_auto = _count_automorphism_roots(self.tpl)
        self.chunk = max(1, min(cfg.n_trials, cfg.trial_chunk))
        self.blocks_run = 0      # the next block's index
        self.colorings_run = 0
        self._graph = None       # (nbr, msk) on the mesh
        self._key = prng.key_bits(cfg.seed)
        self._fn = None          # built by set_graph, for the graph's plan
        self._colors_fn = None

    # -- install ------------------------------------------------------------
    def set_graph(self, edges, n_vertices: int) -> int:
        """Install an undirected edge list ``[m, 2]``; returns the number
        of adjacency entries past ``cfg.max_degree`` (the exact tail's)."""
        cfg, mesh = self.cfg, self.mesh
        nw = mesh.num_workers
        n_pad = -(-n_vertices // nw) * nw
        tiled = cfg.overflow_algo == "onehot"
        with telemetry.span("subgraph.install", vertices=n_vertices,
                            entries=2 * len(edges)) as attrs:
            with telemetry.span("subgraph.pad_csr"):
                nbr, msk, overflow = pad_csr(edges, n_vertices,
                                             cfg.max_degree)
                if n_pad > n_vertices:
                    pad = ((0, n_pad - n_vertices), (0, 0))
                    nbr, msk = np.pad(nbr, pad), np.pad(msk, pad)
            with telemetry.span("subgraph.overflow"):
                if tiled:
                    ovf = _partition_overflow_tiles(
                        overflow, n_pad, nw, cfg.overflow_row_tile,
                        cfg.overflow_entry_tile)
                else:
                    ovf = _partition_overflow(overflow, n_pad, nw)
            with telemetry.span("subgraph.order"):
                # each worker's rows by their real entries in the padded
                # part, as staged; stable, so equal rows keep vertex order
                counts = np.count_nonzero(msk, axis=1).reshape(nw, -1)
                order = np.argsort(counts, axis=1, kind="stable")
                plan = self.plan = degree_plan(
                    np.take_along_axis(counts, order, 1), cfg.max_degree)
                # rows all full: one segment of the whole width, which is
                # the program without a plan, and no order to hand it
                if plan == ((0, n_pad // nw, cfg.max_degree),):
                    plan, order = None, ()
                else:
                    order = (order.astype(np.int32).reshape(-1),)
            tail = ovf[2]
            # the planned program sums the "segment" tail as rows: its
            # own three arrays, and the flat ones wait on the host for
            # whoever asks installed() for them
            self.tail_plan, staged, n_tail_rows = None, ovf, 0
            if plan is not None and not tiled:
                with telemetry.span("subgraph.tail_rows"):
                    staged, self.tail_plan, n_tail_rows = _tail_rows(
                        ovf, nw, cfg.max_degree)
            tail_executed = tail.size if self.tail_plan is None \
                else nw * plan_slots(self.tail_plan)
            executed = nw * plan_slots(self.plan) + tail_executed
            if attrs is not None:  # telemetry on
                # known only now: the tail's size, what is placed, and
                # the slots a neighbor sum gathers of those staged
                attrs.update(
                    overflow_entries=len(overflow), bytes=sum(
                        a.nbytes for a in (nbr, msk, *staged, *order)),
                    slots_staged=msk.size + staged[2].size,
                    slots_executed=executed, segments=len(self.plan),
                    tail_rows=n_tail_rows, tail_slots_executed=tail_executed,
                    tail_segments=len(self.tail_plan or ()))
                # ingest skew record (utils/skew.py): real adjacency
                # entries per vertex-partition worker vs the slots a
                # neighbor sum executes for them, padded part and tail
                # together — powerlaw graphs are exactly where "one
                # worker holds the hub" shows up (it widens every
                # worker's last segment, and the tail's)
                skew.record_partition(
                    "subgraph.partition",
                    # the tail's in float64: a float32 total of 1e8 ones
                    # is not exact
                    counts.sum(1)
                    + tail.reshape(nw, -1).sum(1, dtype=np.float64),
                    unit="edges", padded_total=executed)
            self._graph = tuple(mesh.shard_array(a, 0) for a in (nbr, msk))
            self._tail = tuple(mesh.shard_array(a, 0) for a in staged)
            self._flat = ovf if staged is not ovf else None
            self._order = tuple(mesh.shard_array(a, 0) for a in order)
        self._fn = make_colorful_count_fn(
            self.tpl, self.k, mesh, cfg.overflow_algo,
            cfg.overflow_row_tile, draw_trials=self.chunk, plan=plan,
            tail_plan=self.tail_plan)
        self.n_vertices, self.n_pad = n_vertices, n_pad
        self.overflow_entries = len(overflow)
        return self.overflow_entries

    def _held(self):
        if self._graph is None:
            raise RuntimeError("call set_graph() first")
        return self._graph

    def _args(self):
        """What the counter's own program takes before the colours."""
        return self._held() + self._tail + self._order

    def installed(self):
        """The installed arrays on the mesh: ``(nbr, msk, *tail)``, in
        vertex order, the tail as :func:`_partition_overflow` (or
        ``_tiles``) made it: what the program without a plan takes.  The
        degree order and the tail's rows are the counter's own: where
        its program reads those, the flat tail waits on the host and is
        placed for whoever asks here."""
        graph = self._held()
        return graph + (self._tail if self._flat is None else tuple(
            self.mesh.shard_array(a, 0) for a in self._flat))

    # -- run ----------------------------------------------------------------
    def block_colors(self, block: int | None = None):
        """The colors block ``block`` (default: the next one) counts
        under: int32 ``[chunk, n_vertices]`` on the device, by the
        program's own :func:`block_colors`."""
        self._held()
        if self._colors_fn is None:
            n, n_pad, chunk, k = self.n_vertices, self.n_pad, self.chunk, self.k
            self._colors_fn = flightrec.track(jax.jit(
                lambda key, b: block_colors(key, b, n_pad, chunk, k)[:n].T),
                "subgraph.block_colors")
        return self._colors_fn(
            self._key, np.int32(self.blocks_run if block is None else block))

    def _dispatch(self):
        out = self._fn(*self._args(),
                       (self._key, np.int32(self.blocks_run)))
        self.blocks_run += 1
        self.colorings_run += self.chunk
        return out

    def count_colorings(self) -> np.ndarray:
        """Count the next block of ``chunk`` fresh colorings: float32
        ``[chunk]`` colorful rooted counts (:meth:`estimates` unbiases
        them).  One dispatch, one readback."""
        # every DP level's sites are traced for the whole chunk (the
        # trial is a table's minor index), so a block executes each once
        with telemetry.span("subgraph.colorings", trials=self.chunk), \
                telemetry.ledger.run("subgraph.colorings", steps=1):
            return flightrec.readback(self._dispatch())

    def estimates(self, rooted) -> list[float]:
        """Colorful rooted counts → estimates of the template's count:
        over the colorfulness probability and |Aut(template)| (the
        rooted DP counts each unrooted embedding once per
        automorphism)."""
        return [float(r) / self.p_colorful / self.n_auto for r in rooted]


def count_template(edges, n_vertices, cfg: SubgraphConfig,
                   mesh: WorkerMesh | None = None):
    """Estimate the number of (unrooted) embeddings of the template.

    Returns ``(estimate, per_trial_estimates, overflow_edges)`` —
    ``overflow_edges`` counts adjacency entries past ``cfg.max_degree``,
    which are handled EXACTLY by the tail (nothing is dropped; the count
    is a perf diagnostic — in the installed program a tail slot costs
    what a padded slot costs and a tail row one add more, PERF.md
    section 5; only the program without a plan still pays 24-27 ns an
    entry — and since both parts gather only the slots their rows hold,
    raising ``max_degree`` costs resident bytes, ``8 * n`` a slot, and
    no gathers).  A thin caller of :class:`SubgraphCounter`:
    install, then ``n_trials`` colorings in equal chunks (one compile).
    """
    counter = SubgraphCounter(cfg, mesh)
    overflow = counter.set_graph(edges, n_vertices)
    with telemetry.ledger.run("subgraph.colorings",
                              steps=-(-cfg.n_trials // counter.chunk)):
        outs = [counter._dispatch()
                for _ in range(0, cfg.n_trials, counter.chunk)]  # async
        rooted = flightrec.readback(            # ONE readback
            jnp.concatenate(outs))[: cfg.n_trials]
    estimates = counter.estimates(rooted)
    return float(np.mean(estimates)), estimates, overflow


def _count_automorphism_roots(tpl):
    """Number of automorphisms of the template tree (each unrooted colorful
    embedding is counted once per automorphism by the rooted DP)."""
    ch = _children(tpl)

    def autos(i):
        subs = [_canon(tpl, c) for c in ch[i]]
        a = 1
        for c in ch[i]:
            a *= autos(c)
        from collections import Counter

        for cnt in Counter(subs).values():
            a *= math.factorial(cnt)
        return a

    # rooted automorphisms of the tree as rooted at 0, times the number of
    # vertices whose rooted canonical form equals the root's (root orbit)
    root_form = _canon(tpl)
    # re-root at each vertex to find the root orbit size
    orbit = 0
    n = len(tpl)
    adj = [[] for _ in range(n)]
    for i, p in enumerate(tpl):
        if p >= 0:
            adj[i].append(p)
            adj[p].append(i)

    def canon_rerooted(v, parent):
        return "(" + "".join(
            sorted(canon_rerooted(u, v) for u in adj[v] if u != parent)
        ) + ")"

    for v in range(n):
        if canon_rerooted(v, -1) == root_form:
            orbit += 1
    return autos(0) * orbit


def benchmark(n_vertices=100_000, avg_degree=16, template="u5-tree",
              mesh=None, seed=0, max_degree=64, graph="uniform",
              overflow_algo="segment"):
    """Vertices/sec through one color-coding trial on a graph already
    installed (graded config #5a): the run half of :class:`SubgraphCounter`
    alone is timed, the install is reported beside it (``install_sec``).

    ``graph="powerlaw"`` draws edge sources zipf-1.3 (hub-heavy, the
    realistic web/social degree distribution) so the exact overflow
    segment-sum path carries real mass — the graded-scale regime where
    a truncating implementation would be silently biased; the reported
    ``overflow_share`` is the fraction of adjacency entries riding it.
    """
    rng = np.random.default_rng(seed)
    n_edges = n_vertices * avg_degree // 2
    if graph == "powerlaw":
        src = (rng.zipf(1.3, n_edges).astype(np.int64) - 1) % n_vertices
        dst = rng.integers(0, n_vertices, n_edges)
        edges = np.stack([src, dst], 1)
    elif graph == "uniform":
        edges = np.stack([
            rng.integers(0, n_vertices, n_edges),
            rng.integers(0, n_vertices, n_edges),
        ], 1)
    else:
        raise ValueError(f"graph must be 'uniform' or 'powerlaw', got {graph!r}")
    cfg = SubgraphConfig(template=template, seed=seed, max_degree=max_degree,
                         overflow_algo=overflow_algo)
    counter = SubgraphCounter(cfg, mesh)
    t0 = time.perf_counter()
    overflow = counter.set_graph(edges, n_vertices)
    install_s = time.perf_counter() - t0
    counter.count_colorings()  # warmup: compile
    t0 = time.perf_counter()
    est = counter.estimates(counter.count_colorings())[0]
    dt = time.perf_counter() - t0
    return {
        "vertices_per_sec": n_vertices / dt,
        "estimate": est,
        "sec_per_trial": dt,
        "install_sec": install_s,  # pad_csr + tail + placement, once
        "overflow_edges": overflow,  # handled exactly; 0 edges dropped
        "overflow_share": overflow / (2 * n_edges),
        "dropped_edges": 0,
        "template": template,
        "n_vertices": n_vertices,
        "graph": graph,
        "overflow_algo": overflow_algo,
    }


def main(argv=None):
    import argparse

    p = argparse.ArgumentParser(description="harp-tpu subgraph counting (edu.iu.subgraph parity)")
    p.add_argument("--vertices", type=int, default=100_000)
    p.add_argument("--avg-degree", type=int, default=16)
    p.add_argument("--template", default="u5-tree", choices=sorted(TEMPLATES))
    p.add_argument("--max-degree", type=int, default=64)
    p.add_argument("--graph", choices=["uniform", "powerlaw"],
                   default="uniform")
    p.add_argument("--overflow-algo", choices=["segment", "onehot"],
                   default="segment",
                   help="exact tail for adjacency past max-degree: "
                        "sorted segment-sum (default) or tiled one-hot "
                        "MXU matmuls — same counts, different hardware "
                        "path (profile on TPU to pick)")
    args = p.parse_args(argv)
    # JSON, not dict-repr: the line is teed into BENCH_local.jsonl
    import json

    print(json.dumps({"config": "subgraph_cli",
                      **benchmark(args.vertices, args.avg_degree,
                                  args.template, max_degree=args.max_degree,
                                  graph=args.graph,
                                  overflow_algo=args.overflow_algo)}))


if __name__ == "__main__":
    main()
