"""Seeded input generators, driven by the ``data`` block of a
configuration file.  One function per kind of data; a driver names the
kind it wants and passes the block through, so a new shape or skew is a
new configuration file and not new code.

Everything here is numpy on the host except :func:`normal_points_device`,
which makes its array on the devices it is given (the KMeans cells keep
1.2 GB a million points off the host link that way).
"""

from __future__ import annotations

import numpy as np


def lognormal_degrees(n: int, total: int, dmin: int, dmax: int,
                      median: float) -> np.ndarray:
    """``n`` integer degrees, descending, with ``min == dmin``,
    ``max == dmax`` and ``sum == total`` exactly.

    The body is log-normal about ``median`` — ``d(q) = median *
    exp(sigma * z(q))`` over evenly spaced quantiles, clipped to
    ``[dmin, dmax]`` — which is the shape rating counts have (many near
    the floor, a long tail, a few at the cap).  ``sigma`` is bisected
    until the clipped degrees sum to ``total``; the rounding remainder
    goes one apiece to the ranks between the two ends.
    """
    from scipy.special import ndtri

    if not (n * dmin <= total <= n * dmax) or n < 3:
        raise ValueError(f"no degree sequence: n={n} dmin={dmin} "
                         f"dmax={dmax} total={total}")
    z = ndtri((np.arange(n, dtype=np.float64)[::-1] + 0.5) / n)

    def degrees(sigma):
        return np.clip(median * np.exp(sigma * z), dmin, dmax)

    lo, hi = 0.0, 20.0  # the clipped sum rises with sigma while the
    for _ in range(100):  # median sits nearer dmin than dmax
        mid = 0.5 * (lo + hi)
        if degrees(mid).sum() < total:
            lo = mid
        else:
            hi = mid
    d = np.floor(degrees(lo)).astype(np.int64)
    d[0], d[-1] = dmax, dmin
    rem = int(total - d.sum())
    sign = 1 if rem > 0 else -1
    inner = np.arange(1, n - 1)
    # ranks that can still move that way without leaving [dmin, dmax]
    room = inner[(d[inner] < dmax) if rem > 0 else (d[inner] > dmin)]
    step, extra = divmod(abs(rem), max(room.size, 1))
    d[room] += sign * step
    d[room[:extra]] += sign
    if d.sum() != total or d.min() < dmin or d.max() > dmax:
        raise ValueError(
            f"degree sequence missed its total or its ends (median "
            f"{median}, total {total}): choose a median between them")
    return d


BANDS = 64    # fixed, so that a result does not depend on the thread count
THREADS = 8


def _in_bands(fill, n_bands: int = BANDS) -> None:
    from concurrent.futures import ThreadPoolExecutor

    with ThreadPoolExecutor(max_workers=THREADS) as pool:
        list(pool.map(fill, range(n_bands)))


def _repeat_ids(ids: np.ndarray, counts: np.ndarray):
    """``np.repeat(ids, counts)`` filled band by band; also returns the
    offsets (``out[off[j]:off[j + 1]] == ids[j]``) and the band edges in
    ``ids`` that cut the output into near-equal parts."""
    off = np.zeros(len(ids) + 1, np.int64)
    np.cumsum(counts, out=off[1:])
    out = np.empty(int(off[-1]), ids.dtype)
    edges = np.searchsorted(off, np.linspace(0, off[-1], BANDS + 1))
    edges[0], edges[-1] = 0, len(ids)

    def fill(b):
        lo, hi = edges[b], edges[b + 1]
        out[off[lo]:off[hi]] = np.repeat(ids[lo:hi], counts[lo:hi])

    _in_bands(fill)
    return out, off, edges


def _truth(n_users: int, n_items: int, r: int, rng):
    scale = np.float32(1.0 / np.sqrt(r))
    return (rng.standard_normal((n_users, r), dtype=np.float32) * scale,
            rng.standard_normal((n_items, r), dtype=np.float32) * scale)


def skewed_ratings(data: dict, seed: int):
    """Rating triples ``(users, items, values)`` with the marginals the
    ``data`` block states: ``n_users``, ``n_items``, ``nnz`` and, for each
    side, the lightest, median and heaviest degree.

    The user side is met exactly: user ``u`` has ``deg_u`` ratings, so
    ``nnz`` is exact and no user falls under the floor.  Each rating's
    item is drawn, independently, from the multiset that holds item ``i``
    ``deg_i`` times, so the item side is met in expectation (the heaviest
    item to about its square root) and a pair can repeat.  Ids are
    permuted so that weight does not follow id.  Values are a
    rank-``truth_rank`` ground truth plus ``noise`` x N(0,1), float32
    throughout.  Ratings come out user-major, as the source's files are.
    Made in ``BANDS`` bands of users, one child seed each, by a few
    threads.

    What is the data set's and what the run's: WHICH ids are heavy (the
    two id permutations) is a property of a data set — in ml-20m the same
    movies are the popular ones in whatever sample is drawn — so a
    ``data`` block may pin it with ``id_seed``; the ground truth, every
    rating's item and every value always come from ``seed``.  Without
    ``id_seed`` the permutations come from ``seed`` as well: each seed
    then deals the heavy ids into other tiles, and the partition's tile
    and chunk counts move with it by percents.
    """
    n_users, n_items, nnz = data["n_users"], data["n_items"], data["nnz"]
    ss = np.random.SeedSequence(seed)
    head, *children = ss.spawn(BANDS + 1)
    rng = np.random.default_rng(head)
    ids = (np.random.default_rng(data["id_seed"]) if "id_seed" in data
           else rng)
    du = lognormal_degrees(n_users, nnz, data["user_min"], data["user_max"],
                           data["user_median"])
    di = lognormal_degrees(n_items, nnz, data["item_min"], data["item_max"],
                           data["item_median"])
    du_by_id = np.empty(n_users, np.int64)
    du_by_id[ids.permutation(n_users)] = du
    iid = ids.permutation(n_items).astype(np.int32)
    wt, ht = _truth(n_users, n_items, data["truth_rank"], rng)
    noise = np.float32(data["noise"])
    users, off, edges = _repeat_ids(np.arange(n_users, dtype=np.int32),
                                    du_by_id)
    pool_of_items, _, _ = _repeat_ids(iid, di)
    items = np.empty(nnz, np.int32)
    vals = np.empty(nnz, np.float32)

    def fill(b):
        lo, hi = int(off[edges[b]]), int(off[edges[b + 1]])
        if hi == lo:
            return
        band = np.random.default_rng(children[b])
        items[lo:hi] = pool_of_items[band.integers(0, nnz, hi - lo)]
        v = band.standard_normal(hi - lo, dtype=np.float32)
        v *= noise
        v += np.einsum("nr,nr->n", wt[users[lo:hi]], ht[items[lo:hi]])
        vals[lo:hi] = v

    _in_bands(fill)
    return users, items, vals


def uniform_ratings(data: dict, seed: int):
    """Uniform random ``(u, i)`` pairs with the values of
    :func:`skewed_ratings` — the control a later cell can take."""
    n_users, n_items, nnz = data["n_users"], data["n_items"], data["nnz"]
    rng = np.random.default_rng(seed)
    users = np.sort(rng.integers(0, n_users, nnz, dtype=np.int32))
    items = rng.integers(0, n_items, nnz, dtype=np.int32)
    wt, ht = _truth(n_users, n_items, data["truth_rank"], rng)
    vals = np.float32(data["noise"]) * rng.standard_normal(
        nnz, dtype=np.float32)
    step = 1 << 22  # bound the two gathered [step, r] temporaries
    for lo in range(0, nnz, step):
        sl = slice(lo, lo + step)
        vals[sl] += np.einsum("nr,nr->n", wt[users[sl]], ht[items[sl]])
    return users, items, vals


RATINGS = {"skewed": skewed_ratings, "uniform": uniform_ratings}


def normal_points_host(n: int, d: int, seed: int) -> np.ndarray:
    """``[n, d]`` float32 standard-normal points on the host, made by a
    float32 generator straight into the array, in ``BANDS`` row bands
    (one child seed each) by a few threads."""
    out = np.empty((n, d), np.float32)
    edges = np.linspace(0, n, BANDS + 1).astype(np.int64)
    seeds = np.random.SeedSequence(seed).spawn(BANDS)

    def fill(b):
        lo, hi = int(edges[b]), int(edges[b + 1])
        if hi > lo:
            np.random.default_rng(seeds[b]).standard_normal(
                dtype=np.float32, out=out[lo:hi])

    _in_bands(fill)
    return out


def normal_points_device(n_per_device: int, d: int, seed: int, devices):
    """``[n_per_device * len(devices), d]`` float32 standard-normal points
    made on the devices, row-sharded one band a device, each band from
    its own key.  The seed is data, so a new seed compiles nothing."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(devices), ("w",))
    keys = jax.device_put(
        jax.random.key_data(jax.random.split(jax.random.key(seed),
                                             len(devices))),
        NamedSharding(mesh, P("w")))

    def band(k):
        return jax.random.normal(jax.random.wrap_key_data(k[0]),
                                 (n_per_device, d), jnp.float32)

    return jax.jit(jax.shard_map(band, mesh=mesh, in_specs=P("w"),
                                 out_specs=P("w")))(keys)
