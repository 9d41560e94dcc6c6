"""Plain Lloyd's algorithm — the reference the KMeans cells are held to.

Straight ``jax.numpy`` in float32 with every matmul at ``HIGHEST``
precision (on a TPU a float32 dot otherwise runs as one bf16 pass).  It
imports nothing from ``harp_tpu``: same points and same initial
centroids in, centroids and inertia out.  Exact full-batch Lloyd: every
point scored against every centroid in every iteration, an empty cluster
keeps its centroid.  Points are walked in chunks only to bound the score
matrix; the arithmetic is the whole batch's.

A host array ``[n, 300]`` is brought onto the device by
:func:`stage_host` feature-major and transposed back there: staged
row-major, the runtime re-lays it on the host at 0.2 GB/s (PR 22).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


@functools.partial(jax.jit, static_argnames=("chunk",))
def _partials(points, centroids, chunk):
    """Sums, counts and inertia of one resident band ``[n, d]``, walked
    ``chunk`` rows at a time by slices (no second copy of the band)."""
    n, _ = points.shape
    k = centroids.shape[0]
    c2 = (centroids ** 2).sum(-1)

    def part(i):
        block = jax.lax.dynamic_slice_in_dim(points, i * chunk, chunk, 0)
        scores = c2[None, :] - 2.0 * jnp.dot(block, centroids.T,
                                             precision=HI)   # [chunk, k]
        assign = jnp.argmin(scores, axis=1)
        onehot = jax.nn.one_hot(assign, k, dtype=jnp.float32)
        sums = jnp.dot(onehot.T, block, precision=HI)        # [k, d]
        inertia = (block ** 2).sum() + scores.min(axis=1).sum()
        return sums, onehot.sum(0), inertia

    sums, counts, inertia = jax.lax.map(part, jnp.arange(n // chunk))
    return sums.sum(0), counts.sum(0), inertia.sum()


def _chunk(n: int, limit: int = 1 << 19) -> int:
    """The largest divisor of ``n`` that is at most ``limit`` points."""
    for c in range(min(n, limit), 0, -1):
        if n % c == 0:
            return c
    return n


def stage_host(points: np.ndarray, threads: int = 8):
    """A host ``[n, d]`` array onto the default device: transposed here
    in bands by a few threads, sent feature-major, transposed back on
    the device."""
    from concurrent.futures import ThreadPoolExecutor

    n, d = points.shape
    out = np.empty((d, n), np.float32)
    edges = np.linspace(0, n, 8 * threads + 1).astype(np.int64)

    def band(b):
        lo, hi = edges[b], edges[b + 1]
        out[:, lo:hi] = points[lo:hi].T

    with ThreadPoolExecutor(max_workers=threads) as pool:
        list(pool.map(band, range(len(edges) - 1)))
    return [jnp.transpose(jnp.asarray(out))]


def stage_device(points):
    """The bands of a device array ``[n, d]``, one a device it lives
    on, as they are."""
    return [s.data for s in points.addressable_shards]


def step(bands, centroids):
    """One iteration over the bands: the new centroids and the
    cost of the assignment step against ``centroids`` (the inertia).
    Each band is reduced where it lives and the partials are added on the
    host in float64, so the reference needs no collective."""
    centroids = np.asarray(centroids, np.float32)
    sums = np.zeros(centroids.shape, np.float64)
    counts = np.zeros(centroids.shape[0], np.float64)
    inertia = 0.0
    for band in bands:
        c = jax.device_put(centroids, band.sharding)
        s, n, i = _partials(band, c, _chunk(band.shape[0]))
        sums += np.asarray(s, np.float64)
        counts += np.asarray(n, np.float64)
        inertia += float(i)
    new = np.where(counts[:, None] > 0,
                   sums / np.maximum(counts[:, None], 1.0),
                   centroids).astype(np.float32)
    return new, inertia


def lloyd(bands, centroids, iters: int):
    """``iters`` iterations from ``centroids``.  Returns the centroids
    after the last update and the inertia the last iteration measured
    (against the centroids it started from) — the pair the program
    reports."""
    inertia = 0.0
    for _ in range(iters):
        centroids, inertia = step(bands, centroids)
    return np.asarray(centroids, np.float32), inertia


def cost(bands, centroids) -> float:
    """The inertia ``centroids`` achieve on the points: what a set of
    centroids is worth, however near-tied points were assigned on the way
    to it."""
    return step(bands, centroids)[1]
