"""LDA-CGS tests: count invariants, likelihood ascent, topic recovery."""

import numpy as np
import pytest

from harp_tpu.models import lda as L

N = 8


@pytest.fixture(params=["dense", "scatter", "pushpull", "pallas"])
def small_model(mesh, request):
    """Fresh model per test (all four count-update algos — dense/scatter
    rotation, the pull/push variant, and the fused kernel): shared state
    would make assertions depend on test execution order."""
    extra = ({"sampler": "exprace", "rng_impl": "rbg"}
             if request.param == "pallas" else {})
    cfg = L.LDAConfig(n_topics=8, algo=request.param, chunk=64,
                      d_tile=16, w_tile=16, entry_cap=64,
                      alpha=0.5, beta=0.1, **extra)
    d, w = L.synthetic_corpus(n_docs=96, vocab_size=64, n_topics_true=4,
                              tokens_per_doc=50, seed=0)
    model = L.LDA(96, 64, cfg, mesh, seed=1)
    model.set_tokens(d, w)
    return model, d, w


def counts_consistent(model):
    Ndk = np.asarray(model.Ndk)
    Nwk = np.asarray(model.Nwk)
    Nk = np.asarray(model.Nk)
    assert Ndk.sum() == model.n_tokens
    assert Nwk.sum() == model.n_tokens
    np.testing.assert_allclose(Nwk.sum(0), Nk)
    np.testing.assert_allclose(Ndk.sum(1).max(), 50)  # tokens per doc
    assert (Ndk >= 0).all() and (Nwk >= 0).all() and (Nk >= 0).all()


def test_initial_counts_consistent(small_model):
    counts_consistent(small_model[0])


def test_counts_invariant_after_epochs(small_model):
    model, _, _ = small_model
    for _ in range(2):
        model.sample_epoch()
    counts_consistent(model)


def test_likelihood_improves(small_model):
    model, _, _ = small_model
    ll0 = model.log_likelihood()
    for _ in range(10):
        model.sample_epoch()
    ll1 = model.log_likelihood()
    assert ll1 > ll0 + 0.1, (ll0, ll1)


def test_topic_recovery(small_model):
    """Vocab bands are disjoint per true topic: learned word-topic rows
    should become concentrated (low entropy vs uniform init)."""
    model, _, _ = small_model
    for _ in range(5):
        model.sample_epoch()
    Nwk = model.word_topic_table()
    p = (Nwk + 1e-9) / (Nwk.sum(1, keepdims=True) + 1e-6)
    ent = -(p * np.log(p + 1e-12)).sum(1).mean()
    assert ent < 0.7 * np.log(model.cfg.n_topics)


def test_sample_before_set_raises(mesh):
    model = L.LDA(16, 16, L.LDAConfig(n_topics=4, chunk=16), mesh)
    with pytest.raises(RuntimeError, match="set_tokens"):
        model.sample_epoch()


def test_resume_rejects_mismatched_checkpoint_shapes(mesh, tmp_path):
    """A checkpoint from a different algo/tile config must refuse to resume
    (same contract as MF-SGD's guard)."""
    d, w = L.synthetic_corpus(32, 24, 2, tokens_per_doc=6, seed=0)
    ckpt = str(tmp_path / "lda")
    m1 = L.LDA(32, 24, L.LDAConfig(n_topics=4, algo="scatter", chunk=16),
               mesh, seed=0)
    m1.set_tokens(d, w)
    m1.fit(2, ckpt, ckpt_every=1)

    m2 = L.LDA(32, 24, L.LDAConfig(n_topics=4, algo="dense", d_tile=8,
                                   w_tile=8, entry_cap=16), mesh, seed=0)
    m2.set_tokens(d, w)
    with pytest.raises(ValueError, match="checkpoint shapes"):
        m2.fit(2, ckpt, ckpt_every=1)


def test_donated_state_survives_restart_and_checkpoint(mesh, tmp_path):
    """The sweep programs donate the chain's state (``_STATE_ARGS``): a
    handle taken before a sweep is gone after it where the backend
    honours donation, and ``fit``'s entry snapshot, its checkpoints and
    a restart from them still sample the chain an undisturbed run does."""
    from harp_tpu.utils.fault import FaultInjector

    d, w = L.synthetic_corpus(32, 24, 2, tokens_per_doc=6, seed=0)
    cfg = L.LDAConfig(n_topics=4, algo="dense", d_tile=8, w_tile=8,
                      entry_cap=16)

    def model():
        m = L.LDA(32, 24, cfg, mesh, seed=0)
        m.set_tokens(d, w)
        return m

    crashed, plain = model(), model()
    held = crashed.Nwk
    crashed.sample_epoch()
    plain.sample_epoch()
    if not held.is_deleted():
        pytest.skip("this backend does not honour donation")
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(held)
    crashed.sample_epochs(2)
    plain.sample_epochs(2)
    # a crash before the first checkpoint (restart from the entry
    # snapshot) and one after it (restart from the file)
    crashed.fit(4, str(tmp_path / "lda"), ckpt_every=2,
                fault=FaultInjector(fail_at=(1, 3)))
    plain.fit(4)
    assert float(crashed.Nwk.sum()) == crashed.n_tokens == len(d)
    for name in ("Ndk", "Nwk", "Nk", "z_grid"):
        np.testing.assert_array_equal(np.asarray(getattr(crashed, name)),
                                      np.asarray(getattr(plain, name)))


def test_sample_epochs_matches_convergence_contract(small_model):
    """Multi-epoch single-dispatch sampling keeps the count invariants and
    improves likelihood like per-epoch dispatches."""
    model, _, _ = small_model
    ll0 = model.log_likelihood()
    model.sample_epochs(6)
    counts_consistent(model)
    assert model.log_likelihood() > ll0


def test_pushpull_word_table_never_materialized_contract(mesh):
    """The pushpull variant's word-topic table is row-sharded and exchanged
    only through the sparse pull/push verbs — counts stay exact integers
    and the chain converges, matching the rotation algos' invariants."""
    d, w = L.synthetic_corpus(n_docs=96, vocab_size=64, n_topics_true=4,
                              tokens_per_doc=50, seed=0)
    model = L.LDA(96, 64, L.LDAConfig(n_topics=8, algo="pushpull", chunk=64,
                                      alpha=0.5, beta=0.1), mesh, seed=1)
    model.set_tokens(d, w)
    ll0 = model.log_likelihood()
    for _ in range(6):
        model.sample_epoch()
    counts_consistent(model)
    Nwk = model.word_topic_table()
    assert np.all(Nwk == np.round(Nwk))  # pull/push kept counts integral
    assert model.log_likelihood() > ll0 + 0.2


def test_pushpull_small_pull_cap_still_valid_chain(mesh):
    """A pull_cap below the worst-case demand drops tokens (they keep
    their topic that sweep — still a valid Gibbs chain): count invariants
    must hold exactly and likelihood must still ascend."""
    d, w = L.synthetic_corpus(n_docs=64, vocab_size=32, n_topics_true=2,
                              tokens_per_doc=32, seed=1)
    model = L.LDA(64, 32, L.LDAConfig(n_topics=4, algo="pushpull", chunk=64,
                                      pull_cap=16), mesh, seed=1)
    model.set_tokens(d, w)
    ll0 = model.log_likelihood()
    for _ in range(8):
        model.sample_epoch()
    Ndk = np.asarray(model.Ndk)
    Nwk = np.asarray(model.Nwk)
    assert Ndk.sum() == model.n_tokens and Nwk.sum() == model.n_tokens
    np.testing.assert_allclose(Nwk.sum(0), np.asarray(model.Nk))
    assert model.log_likelihood() > ll0
    assert model.last_dropped >= 0  # surfaced, not swallowed


def test_pushpull_drop_counter_surfaces_capacity_pressure(mesh):
    """All tokens share one word → every request targets one owner; a
    tiny pull_cap must DROP most of them and say so via last_dropped.
    (dedup_pulls=False: the raw per-token wire is the one under pressure —
    the companion dedup test shows the same corpus needs ONE slot.)"""
    n_tok_per_doc = 8
    d = np.repeat(np.arange(16, dtype=np.int32), n_tok_per_doc)
    w = np.zeros(16 * n_tok_per_doc, np.int32)  # one hot word
    model = L.LDA(16, 16, L.LDAConfig(n_topics=4, algo="pushpull",
                                      chunk=16, pull_cap=1,
                                      dedup_pulls=False), mesh, seed=0)
    model.set_tokens(d, w)
    model.sample_epoch()
    assert model.last_dropped > 0
    # dropped tokens kept their topics; counts stay exactly consistent
    assert np.asarray(model.Ndk).sum() == model.n_tokens
    np.testing.assert_allclose(np.asarray(model.Nwk).sum(0),
                               np.asarray(model.Nk))


def test_pushpull_dedup_serves_hot_word_in_one_slot(mesh):
    """The Zipf mitigation (VERDICT r2 item 5): duplicates of a hot word
    collapse to one request, so the corpus that chokes the raw wire at
    pull_cap=1 samples with ZERO drops under dedup — and the exact
    sizing helper says cap=1 suffices."""
    n_tok_per_doc = 8
    d = np.repeat(np.arange(16, dtype=np.int32), n_tok_per_doc)
    w = np.zeros(16 * n_tok_per_doc, np.int32)  # one hot word
    model = L.LDA(16, 16, L.LDAConfig(n_topics=4, algo="pushpull",
                                      chunk=16, pull_cap=1), mesh, seed=0)
    model.set_tokens(d, w)
    assert model.suggest_pull_cap() == 1
    model.sample_epoch()
    assert model.last_dropped == 0
    assert np.asarray(model.Ndk).sum() == model.n_tokens
    np.testing.assert_allclose(np.asarray(model.Nwk).sum(0),
                               np.asarray(model.Nk))


def test_pushpull_dedup_bit_identical_at_zero_drops(mesh):
    """dedup_pulls rearranges the wire, not the math: at the zero-drop
    default cap the sampled chain is BIT-IDENTICAL to the raw exchange
    (pulled rows are the same values; pushed deltas are exact ±1 integer
    sums, so summation order cannot matter)."""
    dw = L.synthetic_corpus(n_docs=96, vocab_size=64, n_topics_true=4,
                            tokens_per_doc=50, seed=0)
    tables = []
    for dedup in (True, False):
        model = L.LDA(96, 64, L.LDAConfig(n_topics=8, algo="pushpull",
                                          chunk=64, dedup_pulls=dedup),
                      mesh, seed=1)
        model.set_tokens(*dw)
        for _ in range(3):
            model.sample_epoch()
        assert model.last_dropped == 0
        tables.append((model.doc_topic_table(), model.word_topic_table()))
    np.testing.assert_array_equal(tables[0][0], tables[1][0])
    np.testing.assert_array_equal(tables[0][1], tables[1][1])


def test_pushpull_zipf_corpus_dedup_vs_raw_drops(mesh):
    """A Zipf-1.1 corpus under a tight cap: the deduped wire must drop
    strictly fewer tokens than the raw wire, and the suggest_pull_cap
    rule must deliver ZERO drops when applied."""
    rng = np.random.default_rng(0)
    n_docs, vocab, tpd = 64, 256, 32
    d = np.repeat(np.arange(n_docs, dtype=np.int32), tpd)
    w = ((rng.zipf(1.1, size=n_docs * tpd) - 1) % vocab).astype(np.int32)
    drops = {}
    for dedup in (True, False):
        model = L.LDA(n_docs, vocab,
                      L.LDAConfig(n_topics=4, algo="pushpull", chunk=64,
                                  pull_cap=8, dedup_pulls=dedup),
                      mesh, seed=1)
        model.set_tokens(d, w)
        model.sample_epoch()
        drops[dedup] = model.last_dropped
        # drops never corrupt counts
        assert np.asarray(model.Ndk).sum() == model.n_tokens
    assert drops[True] < drops[False]

    model = L.LDA(n_docs, vocab,
                  L.LDAConfig(n_topics=4, algo="pushpull", chunk=64),
                  mesh, seed=1)
    model.set_tokens(d, w)
    cap = model.suggest_pull_cap(apply=True)
    assert model.cfg.pull_cap == cap < 64  # dedup: below the chunk size
    model.sample_epoch()
    assert model.last_dropped == 0


def test_suggest_pull_cap_exact_small_case():
    """Hand-checkable sizing: nw=2 workers, T_pad=8 each, chunk=4 → two
    chunks per worker; vocab=8 → owner 0 owns words 0-3, owner 1 owns
    4-7.  Per-(chunk, owner) loads, computed by hand:
      worker0 chunk [0,0,0,1]: raw 4 → owner0, distinct {0,1} = 2
      worker0 chunk [4,4,5,6]: raw 4 → owner1, distinct {4,5,6} = 3
      worker1 chunk [3,3,3,3]: raw 4 → owner0, distinct {3} = 1
      worker1 chunk [0,1,2,3]: raw 4 → owner0, distinct {0,1,2,3} = 4
    """
    w = np.array([0, 0, 0, 1,   4, 4, 5, 6,
                  3, 3, 3, 3,   0, 1, 2, 3], np.int32)
    m = np.ones(16, np.float32)
    assert L.suggest_pull_cap(w, m, 2, 4, 8, dedup=False) == 4
    assert L.suggest_pull_cap(w, m, 2, 4, 8, dedup=True) == 4
    # masking out worker1's second chunk removes the distinct-4 load:
    # the dedup max falls to worker0-chunk1's 3; raw stays 4
    m2 = m.copy()
    m2[12:] = 0.0
    assert L.suggest_pull_cap(w, m2, 2, 4, 8, dedup=True) == 3
    assert L.suggest_pull_cap(w, m2, 2, 4, 8, dedup=False) == 4


@pytest.mark.parametrize("algo", ["dense", "scatter", "pushpull"])
def test_int16_ndk_bit_identical_to_f32(mesh, algo):
    """ndk_dtype='int16' halves the doc-topic HBM (the 1M-doc × 1k-topic
    graded config: 2 GB vs 4 GB) and must be EXACT: counts are integers
    bounded by doc length and deltas are ±1, so the sampled chain —
    same corpus, same seed — is bit-identical to f32."""
    d, w = L.synthetic_corpus(n_docs=48, vocab_size=32, n_topics_true=3,
                              tokens_per_doc=24, seed=2)
    kw = dict(n_topics=6, algo=algo, chunk=32, d_tile=8, w_tile=8,
              entry_cap=32)
    models = []
    for ndk_dtype in ("float32", "int16"):
        m = L.LDA(48, 32, L.LDAConfig(ndk_dtype=ndk_dtype, **kw),
                  mesh, seed=3)
        m.set_tokens(d, w)
        m.sample_epochs(4)
        models.append(m)
    f32m, i16m = models
    assert np.asarray(i16m.Ndk).dtype == np.int16
    np.testing.assert_array_equal(f32m.doc_topic_table(),
                                  i16m.doc_topic_table().astype(np.float32))
    np.testing.assert_array_equal(np.asarray(f32m.z_grid),
                                  np.asarray(i16m.z_grid))
    np.testing.assert_array_equal(np.asarray(f32m.Nwk), np.asarray(i16m.Nwk))


@pytest.mark.parametrize("algo", ["dense", "pallas"])
def test_carry_db_bit_identical_chain(mesh, algo):
    """dense: carry_db=True (VERDICT r3 item 2's Db-carry) shares the
    tile core with the slice-per-entry path, so the sampled chain — same
    corpus, same seed — must be BIT-identical: same z trajectory, same
    tables.  The corpus has more docs than one d_tile so real od changes
    exercise the flush/load cond, and pad entries jump od back to 0 (the
    re-slice case the switch-ordering argument covers).

    pallas: the carry is the kernel's (one call a document-tile run keeps
    the doc tile in VMEM), there is no slice-per-entry arm to compare
    with, and asking for one raises; None and True are one program.  The
    bit-identity this case held is
    tests/test_lda_kernel.py::test_chunk_list_chain_equals_the_padded_entry_chain."""
    extra = ({"sampler": "exprace", "rng_impl": "rbg"}
             if algo == "pallas" else {})
    d, w = L.synthetic_corpus(n_docs=96, vocab_size=48, n_topics_true=4,
                              tokens_per_doc=30, seed=6)
    kw = dict(n_topics=8, algo=algo, d_tile=16, w_tile=16, entry_cap=64,
              **extra)
    if algo == "pallas":
        with pytest.raises(ValueError, match="carry_db=False"):
            L.LDAConfig(carry_db=False, **kw)
    models = []
    for carry in ((None, True) if algo == "pallas" else (False, True)):
        m = L.LDA(96, 48, L.LDAConfig(carry_db=carry, **kw), mesh, seed=5)
        m.set_tokens(d, w)
        m.sample_epochs(3)
        models.append(m)
    base, carry = models
    np.testing.assert_array_equal(np.asarray(base.z_grid),
                                  np.asarray(carry.z_grid))
    np.testing.assert_array_equal(np.asarray(base.Ndk),
                                  np.asarray(carry.Ndk))
    np.testing.assert_array_equal(np.asarray(base.Nwk),
                                  np.asarray(carry.Nwk))
    np.testing.assert_array_equal(np.asarray(base.Nk),
                                  np.asarray(carry.Nk))


def test_carry_db_rejects_non_tiled_algos():
    with pytest.raises(ValueError, match="carry_db"):
        L.LDAConfig(algo="scatter", carry_db=True)
    with pytest.raises(ValueError, match="carry_db"):
        L.LDAConfig(algo="pushpull", carry_db=True)


def test_defaults_are_the_measured_winners():
    """The defaults follow what was measured (1x v5e, 2026-08-01): the
    fused kernel stack with its carry is the default, and the dense-stack
    carry, which lost its A/B, stays off."""
    cfg = L.LDAConfig()
    assert (cfg.algo, cfg.sampler, cfg.rng_impl) == (
        "pallas", "exprace", "rbg")
    # carry_db resolves at READ time: None stays stored, the resolver
    # turns it on for the pallas stack only
    assert cfg.carry_db is None
    assert L.carry_db_resolved(cfg) is True
    assert L.carry_db_resolved(L.LDAConfig(algo="dense")) is False


def test_ndk_dtype_validation():
    with pytest.raises(ValueError, match="ndk_dtype"):
        L.LDAConfig(ndk_dtype="int8")


def test_int16_rejects_overlong_document(mesh, monkeypatch):
    # a doc longer than int16 max would WRAP counts silently; set_tokens
    # must refuse (real limit needs 33k tokens — shrink via monkeypatch
    # is impossible for np.iinfo, so build the real thing, tiny vocab)
    n_tok = np.iinfo(np.int16).max + 1
    d = np.zeros(n_tok, np.int32)
    w = np.zeros(n_tok, np.int32)
    model = L.LDA(8, 8, L.LDAConfig(n_topics=2, algo="scatter", chunk=64,
                                    ndk_dtype="int16"), mesh, seed=0)
    with pytest.raises(ValueError, match="would[\\s\\S]*wrap|wrap"):
        model.set_tokens(d, w)


def test_pushpull_rejects_dense_knobs():
    with pytest.raises(ValueError, match="pull_cap only applies"):
        L.LDAConfig(algo="dense", pull_cap=8)
    with pytest.raises(ValueError, match="dense.pallas-only"):
        L._make_cfg(4, algo="pushpull", d_tile=8)
    with pytest.raises(ValueError, match="pushpull-only"):
        L._make_cfg(4, algo="scatter", chunk=16, pull_cap=8)
    with pytest.raises(ValueError, match="pull_cap must be >= 1"):
        L.LDAConfig(algo="pushpull", pull_cap=0)


def test_exprace_sampler_draws_from_posterior():
    """The exponential race must land on topic k with probability
    p_k/Σp — same distribution as Gumbel-argmax, fewer transcendentals
    (LDAConfig.sampler).  Frequency test over many rows of a known
    posterior."""
    import jax
    import jax.numpy as jnp

    K, n = 4, 8000
    cfg = L.LDAConfig(n_topics=K, alpha=0.0, beta=0.0, sampler="exprace")
    # posterior p ∝ (ndk)(nwk)/nk with nk constant → p ∝ ndk·nwk
    ndk = jnp.broadcast_to(jnp.array([1.0, 2.0, 3.0, 4.0]), (n, K))
    nwk = jnp.broadcast_to(jnp.array([4.0, 1.0, 2.0, 1.0]), (n, K))
    nk = jnp.ones((n, K))
    z0 = jnp.zeros(n, jnp.int32)
    m = jnp.ones(n)
    z = np.asarray(L._cgs_resample(ndk, nwk, nk, z0, m,
                                   jax.random.key(7), cfg, vocab_size=0))
    p = np.array([4.0, 2.0, 6.0, 4.0])
    p /= p.sum()
    freq = np.bincount(z, minlength=K) / n
    # n=8000 → se ≈ sqrt(p(1-p)/n) ≤ 0.0056; 4σ window
    np.testing.assert_allclose(freq, p, atol=4 * 0.0056)


def test_exprace_full_chain_converges(mesh):
    """Likelihood ascent + count invariants hold on the exprace chain."""
    cfg = L.LDAConfig(n_topics=8, algo="dense", d_tile=16, w_tile=16,
                      entry_cap=64, alpha=0.5, beta=0.1, sampler="exprace")
    d, w = L.synthetic_corpus(n_docs=96, vocab_size=64, n_topics_true=4,
                              tokens_per_doc=50, seed=0)
    model = L.LDA(96, 64, cfg, mesh, seed=1)
    model.set_tokens(d, w)
    lls = [model.log_likelihood()]
    for _ in range(6):
        model.sample_epoch()
        lls.append(model.log_likelihood())
    assert lls[-1] > lls[0]
    Ndk = np.asarray(model.Ndk)
    assert Ndk.sum() == model.n_tokens and (Ndk >= 0).all()


@pytest.mark.parametrize("sampler", ["gumbel", "exprace"])
def test_rbg_rng_full_chain_converges(mesh, sampler):
    """Hardware-RNG bits (rng_impl='rbg') keep the chain valid under BOTH
    samplers: counts invariant, likelihood ascends."""
    cfg = L.LDAConfig(n_topics=8, algo="dense", d_tile=16, w_tile=16,
                      entry_cap=64, alpha=0.5, beta=0.1,
                      sampler=sampler, rng_impl="rbg")
    d, w = L.synthetic_corpus(n_docs=96, vocab_size=64, n_topics_true=4,
                              tokens_per_doc=50, seed=0)
    model = L.LDA(96, 64, cfg, mesh, seed=1)
    model.set_tokens(d, w)
    ll0 = model.log_likelihood()
    for _ in range(6):
        model.sample_epoch()
    assert model.log_likelihood() > ll0
    Ndk = np.asarray(model.Ndk)
    Nwk = np.asarray(model.Nwk)
    assert Ndk.sum() == model.n_tokens and (Ndk >= 0).all()
    assert Nwk.sum() == model.n_tokens and (Nwk >= 0).all()


def test_rng_impl_validation():
    # algo="dense" so the rng_impl whitelist itself is reached — on the
    # default (pallas since 2026-08-01) the pallas-stack check fires
    # first and would mask a deleted whitelist branch
    with pytest.raises(ValueError, match="rng_impl"):
        L.LDAConfig(n_topics=4, algo="dense", rng_impl="philox")
