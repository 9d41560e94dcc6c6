"""One vertex through the whole colour-coding dynamic program of one
colouring, whatever implements it.  For every DISTINCT rooted
sub-template that is some template vertex's child (u5-tree: the leaf and
the three-vertex star), its table lives on the C(k, size) colour sets of
its size, and the vertex sums it over its neighbours once: mean-degree
rows of that many float32 gathered, the neighbours' int32 ids read.  Each
such table is written once, each neighbour sum written once and read once,
the vertex's colour read once.  A sum repeated for an equal sub-template
(the three leaves), padded slots, the mask and full 2^k-column tables are
one implementation's and are never counted.  Arithmetic: the adds of the
neighbour sums and two operations for every term of the subset
convolutions, at the float32 rate.  HBM binds: 6.6 ns a vertex-colouring
at com-Orkut's mean degree, against 0.03 ns of arithmetic."""

import math


def _children(template):
    return [[c for c, p in enumerate(template) if p == i]
            for i in range(len(template))]


def _shape(template, i):
    return "(" + "".join(sorted(
        _shape(template, c) for c in _children(template)[i])) + ")"


def per_item(work: dict) -> dict:
    template, k = work["template"], work["n_colors"]
    degree = work["entries"] / work["vertices"]
    children = _children(template)
    sizes = [1] * len(template)
    for i in reversed(range(len(template))):
        sizes[i] += sum(sizes[c] for c in children[i])
    # columns of each distinct child shape's table
    summed = {_shape(template, c): math.comb(k, sizes[c])
              for i in range(len(template)) for c in children[i]}
    cols = sum(summed.values())
    # terms of the subset convolutions, once per distinct shape
    terms, seen = 0, set()
    for i in range(len(template)):
        if _shape(template, i) in seen:
            continue
        seen.add(_shape(template, i))
        have = 1
        for c in children[i]:
            terms += math.comb(k, have) * math.comb(k - have, sizes[c])
            have += sizes[c]
    return {"flops": degree * cols + 2.0 * terms,
            "bytes": (4.0 * degree * cols            # rows gathered
                      + 4.0 * degree * len(summed)   # neighbour ids
                      + 12.0 * cols + 4.0),          # tables, sums, colour
            "peak": "f32_flops"}
