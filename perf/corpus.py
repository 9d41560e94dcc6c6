"""A seeded text corpus with a natural-language shape, driven by the
``data`` block of a configuration file: word frequencies Zipf by rank
over the whole vocabulary, document lengths log-normal.  numpy on the
host; the generator beside ``generators.py``, which is the accepted
cells' yardstick and is not edited.

What is the data set's and what the run's (as ``generators.py`` says of
ratings): a data set has one dictionary and one length for every
document, whatever sample of it a run draws.  ``data["id_seed"]`` pins:

* which word id each frequency rank has (a permutation: a frequent word
  sits anywhere in the id range, as in a real dictionary);
* every document's length (their sum, ``n_tokens``, with them);
* where the caller names a shard size (``shard_docs``), the bag of words
  of every shard of that many documents that follow each other (how
  often each word occurs there), drawn from the Zipf law.

``seed`` draws every token's word: from the Zipf law or, with the bags
pinned, by dealing every shard's bag over the shard's token positions
(which document a given occurrence falls in, and where).  Words are
drawn independently of the document: what a sweep of a topic sampler
costs follows from how many tokens a (documents x words) tile holds, not
from which topics generated them.

Why a cell pins the bags: a partition that cuts documents into ranges of
``shard_docs`` then stages the same tiles, entry for entry, at every
seed.  With only the dictionary and the lengths pinned, a tile of the
hottest words crosses a multiple of the entry width at one seed and not
at the next, the staged entry count moves by one, and LDA's sweep steps
with that count's lowest bit by 0.66% (PERF.md section 6, PR 29).  The
shard size is the partition's to state (a driver passes its layout's
document tile), never a second literal in the data block.
"""

from __future__ import annotations

import numpy as np


def zipf_probabilities(vocab_size: int, exponent: float) -> np.ndarray:
    """``p[r] ~ (r + 1) ** -exponent`` over ranks ``0 .. vocab_size - 1``,
    float64, summing to 1."""
    p = np.arange(1, vocab_size + 1, dtype=np.float64) ** -float(exponent)
    return p / p.sum()


def document_lengths(n_docs: int, total: int, sigma: float, least: int,
                     rng) -> np.ndarray:
    """``n_docs`` integer lengths, each at least ``least``, log-normal
    with log-spread ``sigma`` and scaled so that they sum to ``total``
    exactly."""
    if total < least * n_docs:
        raise ValueError(f"{total} tokens are under {n_docs} documents of "
                         f"the least length {least}")
    raw = rng.lognormal(0.0, sigma, n_docs)
    lo, hi = 0.0, total / raw.min()
    for _ in range(200):  # the floored sum rises with the scale
        mid = 0.5 * (lo + hi)
        if np.maximum(least, raw * mid).sum() < total:
            lo = mid
        else:
            hi = mid
    exact = np.maximum(float(least), raw * lo)
    lengths = np.floor(exact).astype(np.int64)
    # the rounding remainder goes one apiece to the largest fractions
    short = total - int(lengths.sum())
    lengths[np.argsort(lengths - exact, kind="stable")[:short]] += 1
    if lengths.sum() != total or lengths.min() < least:
        raise ValueError("document lengths missed their total")
    return lengths


def zipf_corpus(data: dict, seed: int, shard_docs: int | None = None):
    """``(doc_ids, word_ids)`` int32, one entry a token, documents in
    order.  Keys of ``data``: ``n_docs``, ``n_tokens``, ``vocab_size``,
    ``zipf_exponent``, ``doc_len_sigma``, ``doc_len_min``, ``id_seed``
    (the module docstring says what each seed draws).  With
    ``shard_docs`` the bag of words of every shard of that many documents
    is the data set's too; without, ``seed`` draws every word from the
    Zipf law."""
    n_docs, vocab = int(data["n_docs"]), int(data["vocab_size"])
    pinned = np.random.default_rng(int(data["id_seed"]))
    ids_of_rank = pinned.permutation(vocab).astype(np.int32)
    lengths = document_lengths(n_docs, int(data["n_tokens"]),
                               data["doc_len_sigma"],
                               int(data["doc_len_min"]), pinned)
    cdf = np.cumsum(zipf_probabilities(vocab, data["zipf_exponent"]))
    run = np.random.default_rng(seed)
    draws = (pinned if shard_docs else run).random(int(lengths.sum()))
    words = ids_of_rank[np.minimum(
        np.searchsorted(cdf, draws, side="right"), vocab - 1)]
    if shard_docs:  # the bags are pinned: the seed deals each over its shard
        ends = np.cumsum(lengths)[shard_docs - 1::shard_docs].tolist()
        start = 0
        for end in [*ends, len(words)]:
            run.shuffle(words[start:end])
            start = end
    return np.repeat(np.arange(n_docs, dtype=np.int32), lengths), words
