"""Colour-coding subgraph counting, plain: the dynamic program of Alon,
Yuster and Zwick as Fascia, SAHAD and Harp's ``edu.iu.subgraph`` run it,
for a tree template and one colouring (or a few, each by itself, side by
side), in ``jax.numpy`` float32.  It imports nothing from ``harp_tpu``.

A template is a parent list (``parent[0] = -1`` the root, ``parent[i] <
i``).  For every template vertex ``i``, bottom up, ``table[i][v, S]`` is
the number of maps of the sub-template rooted at ``i`` into the graph
that send ``i`` to ``v``, respect its edges and use exactly the colours
of the set ``S``, each once: a vertex alone has ``1`` at ``S = {colour of
v}``; a child ``c`` is joined by

    ``joined[v, S] = sum over S1 + S2 = S, disjoint, of
                     so_far[v, S1] * (sum over u adjacent to v of table[c][u, S2])``

Tables hold ALL ``2**k`` colour sets as columns (no compact support), the
sum over neighbours is a ``segment_sum`` over the directed edge list,
walked in blocks of edges so that the gathered rows fit beside whatever
else the device holds, and every child of every template vertex gets its
own sum (nothing is shared between equal sub-templates).  The rooted
colourful count is the root's table summed over the colour sets of the
template's size and over vertices (the last in float64, on the host);
:func:`estimate` unbiases it.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

EDGE_BLOCK = 1 << 20
LANES = 128


@functools.partial(jax.jit, static_argnames=("n", "block"))
def neighbour_sum(table, src, dst, n, block=EDGE_BLOCK):
    """``out[v] = sum of table[u] over the directed edges (v, u)``, for a
    table ``[n, ...]``; ``src`` and ``dst`` are padded to a multiple of
    ``block`` with edges ``(n, 0)``, which fall outside the ``n``
    segments.

    The one concession to the chip: a vertex's row is summed flat and
    widened by zero columns to a whole number of 128 lanes.  A TPU lays a
    float32 ``[n, 32]`` array out with ``n`` minor, where a row gather is
    32 element gathers and a row scatter the same (38.5 s a sum over
    234M entries against 6.7 s: PERF.md section 6, PR 38); the columns
    that count are summed exactly as they would be, and a caller with
    several colourings fills the lanes with them (``[n, 4, 32]`` is one
    whole row) where one colouring would leave three quarters empty."""
    flat = table.reshape(n, -1)
    width = flat.shape[1]
    wide = jnp.pad(flat, ((0, 0), (0, -width % LANES)))

    def body(acc, sd):
        s, d = sd
        return acc + jax.ops.segment_sum(jnp.take(wide, d, axis=0), s,
                                         num_segments=n), None

    acc, _ = jax.lax.scan(
        body, jnp.zeros((n, wide.shape[1]), jnp.float32),
        (src.reshape(-1, block), dst.reshape(-1, block)))
    return acc[:, :width].reshape(table.shape)


@functools.partial(jax.jit, static_argnames=("k",))
def alone(colours, k):
    """A vertex by itself: one map, using its own colour.  ``colours``
    is ``[n]``, or ``[n, T]`` for ``T`` colourings side by side: the
    colour sets are the last axis."""
    return (jnp.left_shift(1, colours)[..., None]
            == jnp.arange(1 << k)).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("k",))
def join(so_far, below, k):
    """The subset convolution, column by column, over every disjoint
    pair of colour sets."""
    cols = []
    for S in range(1 << k):
        total = jnp.zeros(so_far.shape[:-1], jnp.float32)
        S1 = S
        while True:  # every subset S1 of S, with S2 the rest
            total = total + so_far[..., S1] * below[..., S ^ S1]
            if S1 == 0:
                break
            S1 = (S1 - 1) & S
        cols.append(total)
    return jnp.stack(cols, axis=-1)


def stage_edges(src, dst, n, block=EDGE_BLOCK):
    """The directed edge list on the device, padded to whole blocks with
    edges that no segment takes."""
    pad = -len(src) % block
    return (jnp.asarray(np.concatenate([src, np.full(pad, n, np.int32)])),
            jnp.asarray(np.concatenate([dst, np.zeros(pad, np.int32)])))


def rooted_colourful_count(template, k, colours, src, dst, n,
                           block=EDGE_BLOCK):
    """The colourful maps of the template, rooted at its vertex 0, under
    one colouring ``colours [n]`` with ``k`` colours (a float), or under
    each of ``T`` colourings ``[n, T]`` (float64 ``[T]``), every one
    counted by itself; ``src``/``dst`` as :func:`stage_edges` gives
    them."""
    colours = jnp.asarray(colours)

    def table(i):
        """Template vertex ``i`` alone, then each child's table summed
        over the neighbours and joined on, a child at a time (depth
        first: a table lives only until it is joined)."""
        so_far = alone(colours, k)
        for c in (c for c, p in enumerate(template) if p == i):
            so_far = join(so_far, neighbour_sum(table(c), src, dst, n, block),
                          k)
        return so_far

    size = np.asarray([bin(S).count("1") == len(template)
                       for S in range(1 << k)])
    # a vertex's colour sets of the template's size on the device (one
    # when k is the template's size); the vertices on the host, in
    # float64: what the program's float32 total is held to is the sum
    # itself, not another float32 order of 3M terms
    counts = np.asarray(jnp.sum(table(0) * size, axis=-1), np.float64).sum(0)
    return float(counts) if colours.ndim == 1 else counts


def automorphisms(template) -> int:
    """|Aut| of the tree: relabelings of its vertices that keep its edges,
    counted by brute force (templates have a handful of vertices)."""
    import itertools

    edges = {frozenset((i, p)) for i, p in enumerate(template) if p >= 0}
    return sum(
        all(frozenset((perm[a], perm[b])) in edges
            for a, b in map(tuple, edges))
        for perm in itertools.permutations(range(len(template))))


def estimate(rooted: float, template, k) -> float:
    """A rooted colourful count as an estimate of how often the template
    occurs: over the chance that a given occurrence is colourful and over
    the template's automorphisms."""
    s = len(template)
    colourful = math.factorial(k) / (math.factorial(k - s) * k ** s)
    return rooted / colourful / automorphisms(template)
