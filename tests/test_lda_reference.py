"""LDA-CGS against the benchmark's plain reference (``perf/reference/
lda.py``) at toy sizes, through the three checks the cell ``lda-sweeps``
decides ``correct`` by, as its driver runs them: (a) the tables are a
recount of the chain, (b) the program's likelihood is the reference's
likelihood of the program's tables, both on the state the window left;
(c) the likelihood of the chain as it stood after the configuration's
``reference.chain_sweeps`` = 4 sweeps (a window that ended sooner, as
every one here does: at its last sweep) lies within 0.6 of the plain
sampler's step there of the mean of the plain sampler's four keys.  A
planted fault each for (a) and (c) and the precision below the
configuration's (what no statistic of the chain can show, exact count
gathers, is held token for token in ``tests/test_lda_kernel.py``); the
spans and the ``lda.kernel_slots`` / ``lda.kernel_chunks`` records
``set_tokens`` leaves.  That (c) stays at sweep 4 however long the run
goes on, and every fault of ``perf/tests/lda_faults.py``, is
``perf/tests/test_lda_check.py``'s."""

import copy
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harp_tpu.models import lda as L
from harp_tpu.parallel.mesh import WorkerMesh
from harp_tpu.utils import skew, telemetry
from perf import corpus, harness, spec
from perf.reference import lda as reference

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# 20,000 tokens over 2 x 8 word tiles and 2 document tiles.  Read at this
# size over four seeds and both arms, 2 and 4 sweeps (CPU, PR 29): the
# program stands 0.001-0.083 of a plain sweep's step from the mean of
# the plain sampler's four keys, whose range is 0.04-0.10 of a step; the
# band is 0.6.  The tests keep to 2 or 3 sweeps, so (c) is taken at the
# window's last; at sweep 4 of a longer run the same size reads
# 0.011-0.027 against a band of 0.058-0.061 (CPU, PR 31;
# perf/tests/test_lda_check.py).
TOY = {"n_docs": 200, "n_tokens": 20_000, "vocab_size": 2000,
       "n_topics": 16}
TILES = {"d_tile": 128, "w_tile": 128, "entry_cap": 256}
ARMS = {"pallas": {}, "dense": {"algo": "dense"}}


def _driver(seed=11, blocks=2, n_tokens=TOY["n_tokens"], **knobs):
    """The cell's driver at the toy size, ``blocks`` sweeps run;
    ``knobs`` put another path of the program in the default's place."""
    cell = spec.Cell(ROOT, "lda-sweeps")
    config = copy.deepcopy(cell.config)
    config["data"].update(TOY, n_tokens=n_tokens)
    config["knobs"].update(TILES, **knobs)
    driver = cell.driver_module().Driver(
        config, {**cell.traffic, "steps": 1}, jax.devices()[:1], seed,
        harness.Recorder())
    driver.setup()
    for _ in range(blocks):
        items, ok = driver.block()
        assert ok and items == n_tokens
    return driver


def _exact(verdict):
    return [verdict[k] for k in ("count_mismatches", "row_sum_mismatches",
                                 "nk_mismatches", "nk_total_off")]


# -- the three checks, as the driver runs them --------------------------------

@pytest.mark.parametrize("arm", sorted(ARMS))
def test_checks_hold_on_the_programs_chain(arm):
    verdict = _driver(**ARMS[arm]).check()
    assert verdict["correct"], verdict
    assert _exact(verdict) == [0, 0, 0, 0]                        # (a)
    assert verdict["ll_tables_rel"] <= 1e-6                       # (b)
    assert verdict["ll_chain_abs"] <= verdict["ll_chain_abs_limit"]  # (c)
    # the band is narrower than a sweep's step and wider than the plain
    # sampler's own keys stand apart
    assert verdict["ll_plain_key_range"] < verdict["ll_chain_abs_limit"] \
        < verdict["ll_plain_step"]
    assert verdict["ll_initial"] < verdict["ll_plain"]
    assert verdict["sweeps"] == 2
    # every compared number stands beside its limit
    for name in ("count_mismatches", "ll_tables_rel", "ll_chain_abs"):
        assert name + "_limit" in verdict


def test_a_topic_changed_behind_the_tables_fails_the_recount():
    """Planted fault for (a): one token of the chain changes its topic
    and the tables are not told.  Four entries differ (the old and the
    new topic, in both tables); no row sum does; the likelihoods cannot
    see one token of 20,000."""
    driver = _driver()
    m = driver.model
    ed = np.asarray(m._tokens[0])
    row, entry, slot = np.argwhere(ed < TILES["d_tile"])[17]
    old = int(m.z_grid[row, entry, slot])
    m.z_grid = m.z_grid.at[row, entry, slot].set((old + 1) % TOY["n_topics"])
    verdict = driver.check()
    assert not verdict["correct"]
    assert _exact(verdict) == [4, 0, 0, 0]
    assert verdict["ll_chain_abs"] <= verdict["ll_chain_abs_limit"]


def test_a_sampler_fed_no_word_counts_fails_the_band():
    """Planted fault for (c): every sweep samples against ``N_wk = 0``.
    With the tables rebuilt from the chain it leaves, (a) and (b) hold
    and the band alone refuses it."""
    driver = _driver(blocks=0)
    m = driver.model
    for _ in range(3):
        m.Nwk = jnp.zeros_like(m.Nwk)
        driver.block()
    doc, word, z = m.token_state()
    m._install_pack(m.pack_tokens(doc, word, z0=z))
    verdict = driver.check()
    assert not verdict["correct"]
    assert _exact(verdict) == [0, 0, 0, 0]
    assert verdict["ll_tables_rel"] <= 1e-6
    assert verdict["ll_chain_abs"] > verdict["ll_chain_abs_limit"]


def test_a_sampler_that_moves_nothing_fails_the_band():
    driver = _driver(blocks=0)
    m = driver.model

    def identity(epochs):
        m.last_work = np.asarray([float(m.n_tokens)])

    m.sample_epochs = identity
    for _ in range(2):
        driver.block()
    verdict = driver.check()
    assert not verdict["correct"]
    assert _exact(verdict) == [0, 0, 0, 0]  # the tables are still exact
    assert verdict["ll_of_tables"] == pytest.approx(verdict["ll_initial"],
                                                    abs=1e-5)
    assert verdict["ll_chain_abs"] > verdict["ll_plain_step"]


def test_tables_through_bfloat16_fail_the_recount():
    """The nearest precision below the configuration's: a table that
    went through bfloat16 (a store, a wire) cannot hold a count above 256
    that is no multiple of its spacing there.  (a) sees it; the band
    does not."""
    driver = _driver(n_tokens=60_000)  # the hottest word passes 256
    m = driver.model
    exact = np.asarray(m.Nwk)
    assert exact.max() > 256
    m.Nwk = m.Nwk.astype(jnp.bfloat16).astype(jnp.float32)
    off = int((np.asarray(m.Nwk) != exact).sum())
    assert off > 0
    verdict = driver.check()
    assert not verdict["correct"]
    assert verdict["count_mismatches"] == off
    assert verdict["ll_chain_abs"] <= verdict["ll_chain_abs_limit"]


# -- the reference by itself ---------------------------------------------------

@pytest.fixture(scope="module")
def toy_corpus():
    return corpus.zipf_corpus({**TOY, "zipf_exponent": 1.07,
                               "doc_len_sigma": 0.9, "doc_len_min": 8,
                               "id_seed": 13}, 7,
                              shard_docs=TILES["d_tile"])


def test_reference_counts_are_a_bincount(toy_corpus):
    doc, word = toy_corpus
    K = TOY["n_topics"]
    z = np.random.default_rng(0).integers(0, K, len(doc)).astype(np.int32)
    Ndk, Nwk, Nk = reference.tables(doc, word, z, TOY["n_docs"],
                                    TOY["vocab_size"], K)
    want = np.bincount(word.astype(np.int64) * K + z,
                       minlength=TOY["vocab_size"] * K).reshape(-1, K)
    np.testing.assert_array_equal(np.asarray(Nwk), want)
    np.testing.assert_array_equal(np.asarray(Nk), np.bincount(z, minlength=K))
    np.testing.assert_array_equal(np.asarray(Ndk).sum(1),
                                  np.bincount(doc, minlength=TOY["n_docs"]))
    # a block of rows is that block of the whole
    part = reference.counts(jnp.asarray(word), jnp.asarray(z),
                            jnp.int32(500), 300, K)
    np.testing.assert_array_equal(np.asarray(part), want[500:800])


def test_reference_likelihood_is_the_token_walk(toy_corpus):
    """The likelihood from the tables alone against the definition,
    token by token, in float64."""
    doc, word = toy_corpus
    K, V, a, b = TOY["n_topics"], TOY["vocab_size"], 0.1, 0.01
    z = np.random.default_rng(1).integers(0, K, len(doc)).astype(np.int32)
    Ndk, Nwk, Nk = (np.asarray(t, np.float64) for t in reference.tables(
        doc, word, z, TOY["n_docs"], V, K))
    theta = (Ndk[doc, z] + a) / (Ndk.sum(1)[doc] + K * a)
    phi = (Nwk[word, z] + b) / (Nk[z] + V * b)
    want = np.log(theta * phi).mean()
    got = reference.log_likelihood(Ndk, Nwk, Nk, V, K, a, b)
    assert got == pytest.approx(want, rel=1e-6)
    # rows and columns of padding change nothing
    wide = reference.log_likelihood(
        np.pad(Ndk, ((0, 56), (0, 112))), np.pad(Nwk, ((0, 48), (0, 112))),
        np.pad(Nk, (0, 112)), V, K, a, b)
    assert wide == pytest.approx(got, rel=1e-7)


def test_reference_chain_keeps_counts_and_climbs(toy_corpus):
    doc, word = toy_corpus
    K, V, n_docs = TOY["n_topics"], TOY["vocab_size"], TOY["n_docs"]
    z0 = np.random.default_rng(0).integers(0, K, len(doc)).astype(np.int32)
    z3, lls = reference.chain(doc, word, z0, 3, n_docs, V, K, 0.1, 0.01,
                              seed=2147484001, block=256)
    assert len(lls) == 4 and lls[0] < lls[1] < lls[2] < lls[3]
    assert z3.shape == z0.shape and (z3 != z0).mean() > 0.5
    # a block that is no divisor of the token count pads, and drops it
    assert len(doc) % 256 and z3.min() >= 0 and z3.max() < K
    # the likelihood it reports is that of a recount of its chain
    recount = reference.log_likelihood(
        *reference.tables(doc, word, z3, n_docs, V, K), V, K, 0.1, 0.01)
    assert lls[-1] == pytest.approx(recount, rel=1e-6)
    # same key, same chain; another key, another
    again, _ = reference.chain(doc, word, z0, 3, n_docs, V, K, 0.1, 0.01,
                               seed=2147484001, block=256)
    other, _ = reference.chain(doc, word, z0, 3, n_docs, V, K, 0.1, 0.01,
                               seed=2147484002, block=256)
    assert (again == z3).all() and (other != z3).mean() > 0.3


def test_reference_imports_nothing_from_the_program():
    import perf.reference.lda as ref_module

    with open(ref_module.__file__) as fh:
        assert "harp_tpu" not in fh.read().replace(
            "imports nothing from ``harp_tpu``", "")


# -- what set_tokens leaves in the tracing -------------------------------------

@pytest.fixture(scope="module")
def one_worker():
    return WorkerMesh(jax.devices()[:1])


def _set_tokens(mesh, toy_corpus, **cfg):
    model = L.LDA(TOY["n_docs"], TOY["vocab_size"],
                  L.LDAConfig(n_topics=TOY["n_topics"], **TILES, **cfg),
                  mesh, seed=3)
    model.set_tokens(*toy_corpus)
    return model


@pytest.mark.parametrize("algo", ["pallas", "dense"])
def test_kernel_slots_is_a_count_of_the_staged_arrays(algo, one_worker,
                                                      toy_corpus):
    with telemetry.scope():
        model = _set_tokens(one_worker, toy_corpus, algo=algo)
        rec = skew.ledger.summary()["lda.kernel_slots"]
        chunks = skew.ledger.summary().get("lda.kernel_chunks")
    # dense: [2, NE, C] entries; pallas: [2, NCH, 128], the chunk list
    ed = np.asarray(model._tokens[0])
    valid = int((ed < TILES["d_tile"]).sum())
    assert valid == TOY["n_tokens"] == rec["total"]
    # the summary rounds the share to six places
    assert rec["padding_frac"] == pytest.approx(1 - valid / ed.size,
                                                abs=1e-6)
    assert ed.shape[0] == 2  # one worker, two half-slices
    if algo == "dense":
        assert chunks is None  # the kernel's record, not the layout's
        return
    # the chunks that hold tokens over the chunks staged: the rest are
    # the no-ops that end the shorter runs, and what padding is left is
    # inside the chunks that run
    from harp_tpu.ops.lda_kernel import CHUNK, unpack_chunk_meta

    noop = unpack_chunk_meta(np.asarray(model._tokens[2]))[1]
    assert ed.shape[2] == CHUNK and noop.shape == ed.shape[:2]
    assert chunks["total"] == int((~noop).sum()) > 0
    assert chunks["padding_frac"] == pytest.approx(noop.mean(), abs=1e-6)
    assert not (ed[noop] < TILES["d_tile"]).any()
    assert chunks["padding_frac"] < rec["padding_frac"]


def test_set_tokens_spans_nest_and_cost_nothing_when_off(one_worker,
                                                         toy_corpus):
    with telemetry.scope():
        _set_tokens(one_worker, toy_corpus)
        paths = {r["path"]: r for r in telemetry.tracer.records}
    for path in ("lda.pack_tokens", "lda.pack_tokens/lda.pack.partition",
                 "lda.pack_tokens/lda.pack.partition/mfsgd.partition.sort",
                 "lda.pack_tokens/lda.pack.partition/mfsgd.partition.pack",
                 "lda.pack_tokens/lda.pack.counts", "lda.install"):
        assert path in paths, (path, sorted(paths))
    assert paths["lda.pack_tokens"]["tokens"] == TOY["n_tokens"]
    tables = 4 * TOY["n_topics"] * (256 + 2 * 1024)  # Ndk, Nwk as stored
    assert paths["lda.install"]["bytes"] > tables
    whole = paths["lda.pack_tokens"]["dur"]
    assert whole >= paths["lda.pack_tokens/lda.pack.counts"]["dur"] > 0
    # off: no record, no ledger entry, the same arrays
    telemetry.tracer.reset()
    skew.ledger.reset()
    quiet = _set_tokens(one_worker, toy_corpus)
    assert telemetry.tracer.records == []
    assert "lda.kernel_slots" not in skew.ledger.summary()
    assert "lda.kernel_chunks" not in skew.ledger.summary()
    with telemetry.scope():
        loud = _set_tokens(one_worker, toy_corpus)
    for a, b in zip(quiet._tokens, loud._tokens):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(quiet.Nwk), np.asarray(loud.Nwk))
