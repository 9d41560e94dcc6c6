"""Plain minibatch SGD on a ReLU MLP with softmax cross-entropy — the
reference the MLP cell is held to.

Straight ``jax.numpy`` in float32 with every matmul at ``HIGHEST``
precision (on a TPU a float32 dot otherwise runs as one bf16 pass).
:func:`logits` and :func:`loss_and_grads` also take ``precision=AS_STATED``:
the same longhand arithmetic with the dots at the default precision,
which is what a configuration that states float32 activations and
default-precision matmuls runs on the chip, so that rounding the
activations to bfloat16 shows against it.  It
imports nothing from ``harp_tpu``: parameters (a list of ``{"w": [fan_in,
fan_out], "b": [fan_out]}``, hidden layers ReLU, the last one linear),
rows and labels in; logits, the mean loss, its gradients written out
longhand, and the parameters after plain synchronous SGD steps out.  No
autodiff, no optimizer library, no kernels.  Rows are walked in blocks
only to bound the activations; the arithmetic is the whole batch's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
AS_STATED = jax.lax.Precision.DEFAULT


def _activations(params, x, precision=HI):
    """Every layer's input, and the logits."""
    inputs, h = [], x
    for layer in params[:-1]:
        inputs.append(h)
        h = jnp.maximum(
            jnp.dot(h, layer["w"], precision=precision) + layer["b"], 0.0)
    inputs.append(h)
    return inputs, jnp.dot(h, params[-1]["w"],
                           precision=precision) + params[-1]["b"]


def _cross_entropy(logits, y):
    """Per-row ``-log softmax(logits)[y]`` and ``softmax - onehot``."""
    z = logits - logits.max(axis=1, keepdims=True)
    lse = jnp.log(jnp.exp(z).sum(axis=1, keepdims=True))
    onehot = y[:, None] == jnp.arange(logits.shape[1])[None, :]
    ce = (lse - jnp.where(onehot, z, 0.0).sum(axis=1, keepdims=True))[:, 0]
    return ce, jnp.exp(z - lse) - onehot.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("precision",))
def logits(params, x, precision=HI):
    return _activations(params, x, precision)[1]


def _loss_and_grads(params, x, y, precision=HI):
    """The mean loss of one batch and its gradient, leaf for leaf like
    ``params``."""
    inputs, out = _activations(params, x, precision)
    ce, delta = _cross_entropy(out, y)
    delta = delta / x.shape[0]              # d(mean loss) / d(logits)
    grads = []
    for layer, h in zip(params[::-1], inputs[::-1]):
        grads.append({"w": jnp.dot(h.T, delta, precision=precision),
                      "b": delta.sum(axis=0)})
        # through the layer's weights, then through the ReLU that made h
        # (h is the table's rows at the first layer: no ReLU, and unused)
        delta = jnp.where(
            h > 0.0, jnp.dot(delta, layer["w"].T, precision=precision), 0.0)
    return ce.mean(), grads[::-1]


loss_and_grads = jax.jit(_loss_and_grads, static_argnames=("precision",))


def _sgd(params, grads, lr):
    return [{k: layer[k] - lr * g[k] for k in layer}
            for layer, g in zip(params, grads)]


@functools.partial(jax.jit, static_argnames=("batch_per_worker", "workers"))
def sgd(params, xs, ys, order, lr, *, batch_per_worker, workers=1):
    """Plain SGD from ``params`` over the batches ``order`` names, one
    synchronous step each.  The table ``xs [n, d]``, ``ys [n]`` is
    ``workers`` equal bands of rows; batch ``i`` is rows ``[i * b,
    (i + 1) * b)`` of every band together (the deployment's global
    batch; with one worker: of the table)."""
    n, d = xs.shape
    b = batch_per_worker

    def rows(a, i):
        band = a.reshape(workers, n // workers, *a.shape[1:])
        return jax.lax.dynamic_slice_in_dim(band, i * b, b, 1).reshape(
            workers * b, *a.shape[1:])

    def step(p, i):
        loss, g = _loss_and_grads(p, rows(xs, i), rows(ys, i))
        return _sgd(p, g, lr), loss

    return jax.lax.scan(step, params, order)


@functools.partial(jax.jit, static_argnames=("block",))
def _band_loss(params, x, y, block):
    def part(i):
        xb = jax.lax.dynamic_slice_in_dim(x, i * block, block, 0)
        yb = jax.lax.dynamic_slice_in_dim(y, i * block, block, 0)
        return _cross_entropy(_activations(params, xb)[1], yb)[0].sum()

    return jax.lax.map(part, jnp.arange(x.shape[0] // block)).sum()


def _block(n: int, limit: int = 1 << 16) -> int:
    """The largest divisor of ``n`` that is at most ``limit`` rows."""
    return next(c for c in range(min(n, limit), 0, -1) if n % c == 0)


def bands(xs, ys):
    """The bands of a device table, one a device it lives on, as they
    are."""
    return [(a.data, b.data) for a, b in zip(xs.addressable_shards,
                                             ys.addressable_shards)]


def table_loss(params, table) -> float:
    """The mean loss over every row of ``table`` (its :func:`bands`).
    Each band is reduced where it lives, in blocks, and the partial sums
    are added on the host in float64."""
    total = rows = 0
    for x, y in table:
        p = jax.device_put(params, x.sharding)
        total += float(_band_loss(p, x, y, _block(x.shape[0])))
        rows += x.shape[0]
    return total / rows


def rel_l2(got, want) -> float:
    """``|got - want| / |want|`` over one array."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))
