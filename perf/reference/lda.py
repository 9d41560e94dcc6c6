"""Plain collapsed Gibbs sampling for LDA — the reference the LDA cells
are held to.  Straight ``jax.numpy`` in float32 (no matrix product
anywhere, so the ``HIGHEST`` set around every call changes nothing and
says so): count tables, row gathers, ``jax.random.categorical`` over its
own threefry keys, scatter-adds; no kernel, no tiles, no one-hot
matmuls; imports nothing from ``harp_tpu``.

The sampler is the published one (Griffiths & Steyvers 2004): a token of
word ``w`` in document ``d`` leaves the counts, draws its topic from
``(N_dk + alpha)(N_wk + beta) / (N_k + V beta)`` and joins them again.
Departures from the sequential sampler, each one Harp's ``edu.iu.lda``
makes too and the program with it:

* **blocks**: the tokens of one block all draw against the counts as
  they stood before the block (each with its own count taken out), and
  their deltas are applied together.  A block is ``block`` tokens that
  follow each other in the order the caller gives; given the program's
  own token order and its kernel's 256, the two are one blocked sampler
  on two random streams.  (Blocks that stride the list instead, about one
  token a document each, climb faster: 0.035 a token ahead after the
  first sweep at 1.2M tokens; my chip runs, PR 27.)  ``[block, K]`` is
  1 MB at 1k topics: it fits beside any table.
* **table width**: a table of 1000 columns is laid out column by column
  on a TPU, and gathering a row then moves one element at a time (80 s a
  sweep at a 1M-word vocabulary against 0.5 s; my chip runs, PR 27).  The
  sweep's tables are a multiple of 128 columns wide; the columns past
  ``n_topics`` hold no count and draw with probability 0.

The likelihood is a function of the count tables alone: the mean over
the tokens of ``log(theta_dk phi_kw)`` at each token's own topic is
``(sum_dk N_dk log theta_dk + sum_wk N_wk log phi_wk) / N``, because
``N_dk`` tokens of document ``d`` carry topic ``k``.  The program's
``log_likelihood()`` walks the tokens instead; the two agree to rounding
exactly when the tables are the counts of the chain.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


@functools.partial(jax.jit, static_argnames=("n_rows", "n_topics"))
def counts(ids, z, lo, n_rows: int, n_topics: int):
    """``[n_rows, n_topics]`` float32 counts of the tokens whose id lies
    in ``[lo, lo + n_rows)``: entry ``[i, k]`` is how many tokens of id
    ``lo + i`` are assigned topic ``k``.  A table too large to hold twice
    is rebuilt a block of rows at a time."""
    row = ids - lo
    row = jnp.where((row >= 0) & (row < n_rows), row, n_rows)  # dropped
    return jnp.zeros((n_rows, n_topics), jnp.float32).at[row, z].add(
        1.0, mode="drop")


def tables(doc, word, z, n_docs: int, vocab_size: int, n_topics: int,
           width: int | None = None):
    """``(N_dk, N_wk, N_k)`` rebuilt from the assignments, ``width``
    columns wide (the columns past ``n_topics`` stay zero)."""
    doc, word, z = (jnp.asarray(a, jnp.int32) for a in (doc, word, z))
    width = width or n_topics
    Nwk = counts(word, z, 0, vocab_size, width)
    return counts(doc, z, 0, n_docs, width), Nwk, Nwk.sum(0)


@functools.partial(jax.jit, static_argnames=("n_topics",))
def _table_terms(Ndk, Nwk, Nk, alpha, beta, vbeta, n_topics):
    """Per-row sums of ``N log(theta)`` and ``N log(phi)``; a row or a
    column that holds no count adds an exact 0."""
    Ndk, Nwk = Ndk.astype(jnp.float32), Nwk.astype(jnp.float32)
    theta = (Ndk + alpha) / (Ndk.sum(1, keepdims=True) + n_topics * alpha)
    phi = (Nwk + beta) / (Nk[None, :] + vbeta)
    return (Ndk * jnp.log(theta)).sum(1), (Nwk * jnp.log(phi)).sum(1)


def log_likelihood(Ndk, Nwk, Nk, vocab_size: int, n_topics: int,
                   alpha: float, beta: float) -> float:
    """Mean log-likelihood a token of the chain whose counts the tables
    are, from the tables alone.  They may carry rows and columns of
    padding (all zero), as the program's storage and this file's sweeps
    do.  Rows are summed in float32 on the device, the rows' sums in
    float64 on the host."""
    with jax.default_matmul_precision("highest"):
        by_doc, by_word = _table_terms(
            Ndk, Nwk, jnp.asarray(Nk, jnp.float32), jnp.float32(alpha),
            jnp.float32(beta), jnp.float32(vocab_size * beta), n_topics)
    total = np.asarray(by_doc, np.float64).sum() + np.asarray(
        by_word, np.float64).sum()
    return float(total / float(np.asarray(Nk, np.float64).sum()))


def _row_major_width(n_topics: int) -> int:
    return -(-n_topics // 128) * 128


@functools.partial(jax.jit, static_argnames=("block", "n_topics"),
                   donate_argnums=(0, 1, 2))
def _sweep(Ndk, Nwk, Nk, doc, word, z, mask, key, alpha, beta, vbeta,
           block, n_topics):
    n_blocks = doc.shape[0] // block
    blocked = lambda a: a.reshape(n_blocks, block)  # noqa: E731
    width = Nk.shape[0]
    is_topic = jnp.arange(width) < n_topics

    def step(carry, inp):
        Ndk, Nwk, Nk = carry
        d, w, zb, m, k = inp
        own = jax.nn.one_hot(zb, width, dtype=jnp.float32) * m[:, None]
        ndk, nwk, nk = Ndk[d] - own, Nwk[w] - own, Nk[None, :] - own
        logits = (jnp.log(ndk + alpha) + jnp.log(nwk + beta)
                  - jnp.log(nk + vbeta))
        drawn = jax.random.categorical(
            k, jnp.where(is_topic, logits, -jnp.inf), axis=-1)
        new = jnp.where(m > 0, drawn.astype(jnp.int32), zb)
        delta = jax.nn.one_hot(new, width, dtype=jnp.float32) \
            * m[:, None] - own
        return (Ndk.at[d].add(delta), Nwk.at[w].add(delta),
                Nk + delta.sum(0)), new

    (Ndk, Nwk, Nk), new = lax.scan(
        step, (Ndk, Nwk, Nk),
        (blocked(doc), blocked(word), blocked(z), blocked(mask),
         jax.random.split(key, n_blocks)))
    return Ndk, Nwk, Nk, new.reshape(-1)


def chain(doc, word, z, n_sweeps: int, n_docs: int, vocab_size: int,
          n_topics: int, alpha: float, beta: float, seed: int,
          block: int = 256):
    """``n_sweeps`` sweeps of blocked collapsed Gibbs from the
    assignments ``z``, every token resampled once a sweep.  Returns the
    assignments after the last sweep (numpy, in the order given) and the
    mean log-likelihood a token before the first sweep and after each
    (``n_sweeps + 1`` numbers)."""
    n = len(z)
    model = dict(vocab_size=vocab_size, n_topics=n_topics, alpha=alpha,
                 beta=beta)
    Ndk, Nwk, Nk = tables(doc, word, z, n_docs, vocab_size, n_topics,
                          _row_major_width(n_topics))
    pad = -n % block  # the last block is filled with tokens that stay put
    padded = lambda a: jnp.asarray(  # noqa: E731
        np.concatenate([np.asarray(a, np.int32), np.zeros(pad, np.int32)]))
    mask = jnp.asarray(np.concatenate(
        [np.ones(n, np.float32), np.zeros(pad, np.float32)]))
    doc, word, z = padded(doc), padded(word), padded(z)
    key = jax.random.key(seed, impl="threefry2x32")
    lls = [log_likelihood(Ndk, Nwk, Nk, **model)]
    for s in range(n_sweeps):
        with jax.default_matmul_precision("highest"):
            Ndk, Nwk, Nk, z = _sweep(
                Ndk, Nwk, Nk, doc, word, z, mask,
                jax.random.fold_in(key, s), jnp.float32(alpha),
                jnp.float32(beta), jnp.float32(vocab_size * beta), block,
                n_topics)
        lls.append(log_likelihood(Ndk, Nwk, Nk, **model))
    return np.asarray(z)[:n], lls
