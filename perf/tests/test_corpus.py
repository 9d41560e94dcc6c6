"""The seeded text corpus of ``perf/corpus.py``: what ``id_seed`` pins
(dictionary, document lengths and, for a caller that names a shard size,
every shard's bag of words), what the run's seed draws (every token's
word), and the Zipf shape."""

import json
import os

import numpy as np
import pytest

from perf import corpus

HERE = os.path.dirname(os.path.abspath(__file__))


def _config():
    with open(os.path.join(HERE, "..", "configs",
                           "lda-enwiki-v1m-k1k.json")) as fh:
        return json.load(fh)


def _enwiki():
    return _config()["data"]


#: a shard is one document tile of the cell's layout, as its driver says
SHARD = _config()["knobs"]["d_tile"]


def _cut(n_docs=1024, vocab_size=20_000):
    """The cell's data block with its documents and vocabulary cut, at
    the source's tokens a document."""
    data = _enwiki()
    per_doc = data["n_tokens"] // data["n_docs"]
    return dict(data, n_docs=n_docs, n_tokens=per_doc * n_docs,
                vocab_size=vocab_size)


def test_the_cell_pins_its_corpus():
    data = _enwiki()
    assert isinstance(data["id_seed"], int)
    assert "bag_docs" not in data  # the shard size is the layout's
    assert SHARD == 512 and data["n_docs"] % SHARD == 0
    assert data["n_tokens"] == 290 * data["n_docs"]  # the source's ~290
    assert data["vocab_size"] == 1_000_000


def test_same_seed_same_corpus_and_the_shape_it_says():
    data = _cut()
    doc, word = corpus.zipf_corpus(data, 2147484001)
    again = corpus.zipf_corpus(data, 2147484001)
    assert (doc == again[0]).all() and (word == again[1]).all()
    assert doc.dtype == word.dtype == np.int32
    assert len(doc) == len(word) == data["n_tokens"]
    assert (np.diff(doc) >= 0).all() and doc[-1] == data["n_docs"] - 1
    assert 0 <= word.min() and word.max() < data["vocab_size"]
    lengths = np.bincount(doc, minlength=data["n_docs"])
    assert lengths.min() >= data["doc_len_min"]
    assert lengths.max() > 4 * lengths.mean()  # a log-normal tail


def test_id_seed_pins_dictionary_lengths_and_bags_the_seed_deals_words():
    data = _cut()
    doc, word = corpus.zipf_corpus(data, 7, shard_docs=SHARD)
    doc2, word2 = corpus.zipf_corpus(data, 8, shard_docs=SHARD)
    # every document's length is the data set's
    assert (doc == doc2).all()
    # the same ids are the frequent ones, to the count
    V = data["vocab_size"]
    assert (np.bincount(word, minlength=V)
            == np.bincount(word2, minlength=V)).all()
    # every shard of documents holds the same bag of words
    ends = np.searchsorted(doc, np.arange(SHARD, data["n_docs"] + 1, SHARD))
    start = 0
    for end in ends:
        assert (np.sort(word[start:end]) == np.sort(word2[start:end])).all()
        start = end
    # and every token's word comes from the seed
    assert (word != word2).mean() > 0.8
    # a document's own words move: the bags are the shards', not its own
    first = slice(0, np.searchsorted(doc, 1))
    assert sorted(word[first]) != sorted(word2[first])
    # another id_seed is another data set
    doc3, word3 = corpus.zipf_corpus(dict(data, id_seed=data["id_seed"] + 1),
                                     7, shard_docs=SHARD)
    assert not (doc == doc3).all()
    assert np.bincount(word).argmax() != np.bincount(word3).argmax()


def test_without_a_shard_size_the_seed_draws_every_word():
    """Dictionary and lengths stay the data set's; the word counts are
    the seed's own sample of the Zipf law."""
    data = _cut()
    doc, word = corpus.zipf_corpus(data, 7)
    doc2, word2 = corpus.zipf_corpus(data, 8)
    assert (doc == doc2).all()
    V = data["vocab_size"]
    counts, counts2 = (np.bincount(w, minlength=V) for w in (word, word2))
    assert (counts != counts2).any()
    assert counts.argmax() == counts2.argmax()  # the same dictionary
    assert (word != word2).mean() > 0.8


def test_a_partition_by_shards_stages_the_same_tiles_at_every_seed():
    """Why the bags are pinned: the tokens a (shard of documents x 512
    words) tile holds are the same at every seed, so a partition that
    cuts documents into those ranges stages the same entries; loose,
    they are not."""
    data = _cut(n_docs=1024, vocab_size=20_000)

    def tile_counts(seed, shard_docs):
        doc, word = corpus.zipf_corpus(data, seed, shard_docs=shard_docs)
        tile = (doc // SHARD) * 40 + word // 512
        return np.bincount(tile, minlength=2 * 40)

    a, b = tile_counts(301, SHARD), tile_counts(302, SHARD)
    assert (a == b).all() and a.min() > 0
    assert (tile_counts(301, None) != tile_counts(302, None)).any()


def test_word_frequencies_are_zipf():
    """Head share within a stated band: the most frequent word and the
    ten most frequent hold what Zipf(1.07) gives them, within four
    standard deviations of a binomial count."""
    data = _cut(n_docs=2048, vocab_size=50_000)
    _, word = corpus.zipf_corpus(data, 5)
    p = corpus.zipf_probabilities(data["vocab_size"], data["zipf_exponent"])
    assert p.sum() == pytest.approx(1.0)
    assert p[0] / p[1] == pytest.approx(2 ** data["zipf_exponent"])
    freq = np.sort(np.bincount(word, minlength=data["vocab_size"]))[::-1]
    n = len(word)
    assert abs(freq[0] - n * p[0]) < 4 * np.sqrt(n * p[0])
    assert abs(freq[:10].sum() - n * p[:10].sum()) < 4 * np.sqrt(n)
    # the band: 12.2% at these 50,000 words (10.6% at the cell's 1M)
    assert 0.11 < freq[0] / n < 0.135
    # the ids are permuted: the hottest word is nowhere near id 0
    assert np.bincount(word).argmax() > 100


def test_document_lengths_meet_their_total_and_refuse_the_impossible():
    rng = np.random.default_rng(0)
    lengths = corpus.document_lengths(1000, 290_000, 0.9, 8, rng)
    assert lengths.sum() == 290_000 and lengths.min() >= 8
    assert 150 < np.median(lengths) < 290  # log-normal: median under mean
    with pytest.raises(ValueError):
        corpus.document_lengths(10, 40, 0.9, 8, rng)  # total < n * least
