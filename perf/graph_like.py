"""An undirected graph of a published size and degree shape, made from
seeds: a degree sequence with the source's vertex count, edge count and
largest and smallest degree, and its stubs paired at random (the
configuration model).  Driven by the ``data`` block of a configuration
file.

What is the data set's and what the run's: the degree sequence, and with
it WHICH ids are the hubs, is the data set (``id_seed``): every seed gets
the same degrees on the same ids, so the split of the adjacency between a
padded part and a tail, and with it the work of a block, does not move
with the seed (PERF.md section 6, PR 28).  ``seed`` pairs the stubs into
edges.  Self-loops are re-drawn; multi-edges stay, and count as often as
they occur (the program and the reference both sum adjacency ENTRIES).

The degree law is log-normal (``degree_law: "lognormal"``,
``degree_sigma``): ``exp(mu + sigma z)`` rounded and held to
``[degree_min, degree_max]``, ``mu`` found by bisection so that the
degrees sum to ``2 x n_edges``, the last few units dealt one each over
vertices in the sequence's own random order; the largest draw is raised
to ``degree_max`` and the smallest held at ``degree_min``, so the
source's published ends are there.
"""

from __future__ import annotations

import functools

import numpy as np


def degree_sequence(data: dict) -> np.ndarray:
    """int64 ``[n_vertices]``, read-only: sums to ``2 * n_edges``,
    smallest ``degree_min``, largest ``degree_max``; a function of the
    ``data`` block alone, made once for a block (the generator and the
    check both ask)."""
    return _degree_sequence(tuple(sorted(data.items())))


@functools.lru_cache(maxsize=2)
def _degree_sequence(items: tuple) -> np.ndarray:
    data = dict(items)
    if data["degree_law"] != "lognormal":
        raise ValueError(f"unknown degree law {data['degree_law']!r}")
    n, target = int(data["n_vertices"]), 2 * int(data["n_edges"])
    lo_d, hi_d = int(data["degree_min"]), int(data["degree_max"])
    rng = np.random.default_rng(data["id_seed"])
    raw = np.exp(float(data["degree_sigma"]) * rng.standard_normal(n))
    order = rng.permutation(n)  # who takes the sum's last units
    ends = [int(np.argmin(raw)), int(np.argmax(raw))]

    def degrees(mu):
        d = np.clip(np.rint(np.exp(mu) * raw), lo_d, hi_d).astype(np.int64)
        d[ends] = lo_d, hi_d
        return d

    lo, hi = -20.0, 20.0
    for _ in range(80):  # the sum steps up with mu
        mid = 0.5 * (lo + hi)
        lo, hi = (mid, hi) if degrees(mid).sum() <= target else (lo, mid)
    d = degrees(lo)
    # what is left: one each, to vertices that keep inside the ends, as
    # often around as it takes
    order = order[~np.isin(order, ends)]
    while (left := target - int(d.sum())) > 0:
        free = order[d[order] < hi_d][:left]
        if not len(free):
            break
        d[free] += 1
    if d.sum() != target or d.min() != lo_d or d.max() != hi_d:
        raise ValueError("no degree sequence of that sum and those ends "
                         f"(sum {d.sum()} of {target}, {d.min()}..{d.max()})")
    d.flags.writeable = False
    return d


def edges(data: dict, seed: int) -> np.ndarray:
    """int32 ``[n_edges, 2]``: the sequence's stubs paired at random by
    ``seed``, no self-loop."""
    deg = degree_sequence(data)
    rng = np.random.default_rng(seed)
    stubs = np.repeat(np.arange(len(deg), dtype=np.int32), deg)
    rng.shuffle(stubs)
    e = stubs.reshape(-1, 2)
    while len(loops := np.flatnonzero(e[:, 0] == e[:, 1])):
        # a self-loop trades its second end with a random edge's (a few
        # hundred at most: one at a time, so that no stub is lost)
        for i, j in zip(loops, rng.integers(0, len(e), len(loops))):
            e[i, 1], e[j, 1] = e[j, 1], e[i, 1]
    return e


def directed(e: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Both directions of every edge, ``(src, dst)`` int32
    ``[2 * n_edges]`` each: the adjacency entries, one each."""
    return (np.concatenate([e[:, 0], e[:, 1]]),
            np.concatenate([e[:, 1], e[:, 0]]))
