"""Plain matrix-factorisation SGD — the reference the MF-SGD cells are
held to.  Straight ``jax.numpy`` gather / scatter-add in float32; imports
nothing from ``harp_tpu``.

The model is ``r_ui ~ w_u . h_i`` with the loss ``1/2 (r - w.h)^2 +
reg/2 (|w|^2 + |h|^2)`` on visited rows, stepped by minibatch SGD: within
one minibatch every rating reads the factors as they stood before it and
the gradients of repeated rows add.  The program walks the same ratings
in another order (tile by tile), so the two agree within a band and not
to rounding; the band and its reason are in the configuration file.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit, static_argnames=("chunk",))
def _sq_err(W, H, users, items, vals, chunk):
    def part(args):
        u, i, v = args
        e = v - (W[u] * H[i]).sum(-1)
        return (e * e).sum()

    n = users.shape[0]
    shape = (n // chunk, chunk)
    return jax.lax.map(part, (users.reshape(shape), items.reshape(shape),
                              vals.reshape(shape))).sum()


def rmse(W, H, users, items, vals, chunk: int = 1 << 20) -> float:
    """RMSE of the factors over all the ratings given, on the device in
    chunks; the tail that does not fill a chunk is summed apart."""
    W, H = jnp.asarray(W, jnp.float32), jnp.asarray(H, jnp.float32)
    n = len(vals)
    body = (n // chunk) * chunk
    se = 0.0
    if body:
        se += float(_sq_err(W, H, jnp.asarray(users[:body]),
                            jnp.asarray(items[:body]),
                            jnp.asarray(vals[:body]), chunk))
    if n > body:
        se += float(_sq_err(W, H, jnp.asarray(users[body:]),
                            jnp.asarray(items[body:]),
                            jnp.asarray(vals[body:]), n - body))
    return float(np.sqrt(se / n))


@functools.partial(jax.jit, static_argnames=("batch",), donate_argnums=(0, 1))
def _epoch(W, H, users, items, vals, lr, reg, batch):
    n = users.shape[0]
    # minibatch j holds ratings j, j + n/batch, j + 2n/batch, ...: every
    # minibatch spans the whole (user-major) list, at no cost on the host
    shape = (batch, n // batch)

    def step(carry, b):
        W, H, se = carry
        u, i, v = b
        wu, hi = W[u], H[i]
        err = v - (wu * hi).sum(-1)
        W = W.at[u].add(lr * (err[:, None] * hi - reg * wu))
        H = H.at[i].add(lr * (err[:, None] * wu - reg * hi))
        return (W, H, se + (err * err).sum()), None

    (W, H, se), _ = jax.lax.scan(
        step, (W, H, jnp.float32(0.0)),
        (users.reshape(shape).T, items.reshape(shape).T,
         vals.reshape(shape).T))
    return W, H, se


def sgd_epoch(W, H, users, items, vals, lr: float, reg: float, batch: int):
    """One epoch over every rating, in minibatches of ``batch`` that each
    stride the whole list (the tail that does not fill the strides is
    visited last, as one minibatch).  Returns the new factors and the
    running RMSE — the root mean of each rating's squared error as it was
    visited, which is what the program reports for an epoch."""
    n = len(vals)
    W, H = jnp.asarray(W, jnp.float32), jnp.asarray(H, jnp.float32)
    body = (n // batch) * batch
    se = 0.0
    if body:
        W, H, s = _epoch(W, H, jnp.asarray(users[:body]),
                         jnp.asarray(items[:body]), jnp.asarray(vals[:body]),
                         lr, reg, batch)
        se += float(s)
    if n > body:
        W, H, s = _epoch(W, H, jnp.asarray(users[body:]),
                         jnp.asarray(items[body:]), jnp.asarray(vals[body:]),
                         lr, reg, n - body)
        se += float(s)
    return W, H, float(np.sqrt(se / n))
