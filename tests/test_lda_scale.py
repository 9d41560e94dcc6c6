"""enwiki-1M graded-shape proofs (SURVEY.md §3.4 #3; VERDICT r2 item 3).

The graded LDA corpus is 1M docs × 1k topics (~100M tokens).  Executing
that needs TPU hours; what CAN be pinned on CPU, the way the 1B-point
KMeans program was pinned (tests/test_kmeans_stream.py), is that the
epoch programs TRACE AND LOWER at the true shapes — int16 doc-topic
table, 8-way shard — via jax.ShapeDtypeStruct (zero host memory).

``epoch_arg_shapes`` supplies the shapes; the first tests prove it
mirrors the real partitioners exactly on corpora small enough to build.
"""

import numpy as np
import pytest

import jax

from harp_tpu.models import lda as L


def _even_corpus(n_docs, vocab, tokens_per_doc):
    """Perfectly even corpus: every (worker, slice) block equally loaded,
    so the even-fill model in epoch_arg_shapes is EXACT, not approximate."""
    T = n_docs * tokens_per_doc
    d = np.repeat(np.arange(n_docs, dtype=np.int32), tokens_per_doc)
    w = (np.arange(T, dtype=np.int32)) % vocab
    return d, w


def _actual_args(model):
    # what the sweep programs take: the tables as the device stores them
    # (topic-major under algo="pallas", see LDA._Nwk)
    return [*model._epoch_args()[:-1], model._keys]


def _check_shapes(model, predicted):
    actual = _actual_args(model)
    assert len(actual) == len(predicted)
    for a, (shape, dt) in zip(actual, predicted):
        assert tuple(a.shape) == tuple(shape), (a.shape, shape)
        assert np.dtype(a.dtype) == np.dtype(dt), (a.dtype, dt)


@pytest.mark.parametrize("chunk", [16, 2])
def test_shape_model_matches_partitioner_pushpull(mesh, chunk):
    n_docs, vocab, tpd = 64, 32, 4
    cfg = L.LDAConfig(n_topics=6, algo="pushpull", chunk=chunk)
    model = L.LDA(n_docs, vocab, cfg, mesh)
    model.set_tokens(*_even_corpus(n_docs, vocab, tpd))
    _check_shapes(model, L.epoch_arg_shapes(
        8, n_docs, vocab, cfg, n_tokens=n_docs * tpd))


@pytest.mark.parametrize("chunk", [16, 2])
def test_shape_model_matches_partitioner_scatter(mesh, chunk):
    # chunk=16 > bmax exercises the sublane-pad branch; chunk=2 the
    # chunk-multiple branch — both must mirror partition_ratings' B rule
    n_docs, vocab, tpd = 64, 32, 4
    cfg = L.LDAConfig(n_topics=6, algo="scatter", chunk=chunk)
    model = L.LDA(n_docs, vocab, cfg, mesh)
    model.set_tokens(*_even_corpus(n_docs, vocab, tpd))
    _check_shapes(model, L.epoch_arg_shapes(
        8, n_docs, vocab, cfg, n_tokens=n_docs * tpd))


def test_shape_model_matches_partitioner_dense(mesh):
    # entry_cap small enough that the real partitioner's entry width C
    # saturates at the cap (the regime the 1M model assumes); NE is
    # corpus-dependent, so the real partitioner's NE is passed through
    # and everything else must match
    n_docs, vocab, tpd = 64, 32, 8
    cfg = L.LDAConfig(n_topics=6, algo="dense", d_tile=4, w_tile=4,
                      entry_cap=8, ndk_dtype="int16")
    model = L.LDA(n_docs, vocab, cfg, mesh)
    model.set_tokens(*_even_corpus(n_docs, vocab, tpd))
    ne_real = model._tokens[0].shape[1]
    assert model._tokens[0].shape[2] == cfg.entry_cap  # C hit the cap
    _check_shapes(model, L.epoch_arg_shapes(
        8, n_docs, vocab, cfg, n_tokens=n_docs * tpd,
        entries_per_row=ne_real))
    # the tight-packing default is a lower bound on the real NE
    default_ne = L.epoch_arg_shapes(
        8, n_docs, vocab, cfg, n_tokens=n_docs * tpd)[4][0][1]
    assert default_ne <= ne_real


def test_shape_model_takes_the_real_partitioners_entries_on_a_zipf_corpus():
    """A Zipf vocabulary over several word tiles (the benchmark's corpus
    generator at a toy size, one worker, the pallas layout): the real
    ``stage_chunk_list`` count fed through ``entries_per_row`` gives
    ``pack_tokens``' shapes (a grid row is runs x chunks of
    ``lda_kernel.CHUNK`` slots; ``entry_width`` is no parameter there and
    raises), and the tight-packing default undercounts the chunks by
    what the light tiles and the shorter runs leave empty: 2.4x at the
    1,954 word tiles of a 1M-word vocabulary (13 x 1,381 = 17,953 chunks
    a half-slice against 13 x 581 = 7,553: PERF.md section 6, PR 32)."""
    from harp_tpu.ops.lda_kernel import CHUNK
    from harp_tpu.parallel.mesh import WorkerMesh
    from perf import corpus

    n_docs, vocab, n_tokens = 200, 2000, 20_000
    doc, word = corpus.zipf_corpus(
        {"n_docs": n_docs, "n_tokens": n_tokens, "vocab_size": vocab,
         "zipf_exponent": 1.07, "doc_len_sigma": 0.9, "doc_len_min": 8,
         "id_seed": 13}, 7, shard_docs=128)
    cfg = L.LDAConfig(n_topics=16, d_tile=128, w_tile=128, entry_cap=256)
    model = L.LDA(n_docs, vocab, cfg, WorkerMesh(jax.devices()[:1]))
    model.set_tokens(doc, word)
    _, nch_real, c_real = model._tokens[0].shape
    runs = model.d_bound // cfg.d_tile
    assert c_real == CHUNK and nch_real % runs == 0
    _check_shapes(model, L.epoch_arg_shapes(
        1, n_docs, vocab, cfg, n_tokens=n_tokens, entries_per_row=nch_real))
    default_nch = L.epoch_arg_shapes(
        1, n_docs, vocab, cfg, n_tokens=n_tokens)[4][0][1]
    assert default_nch == runs * 40 and nch_real > 1.2 * default_nch
    with pytest.raises(ValueError, match="entry_width"):
        L.epoch_arg_shapes(1, n_docs, vocab, cfg, n_tokens=n_tokens,
                           entry_width=256)
    with pytest.raises(ValueError, match="document-tile runs"):
        L.epoch_arg_shapes(1, n_docs, vocab, cfg, n_tokens=n_tokens,
                           entries_per_row=nch_real + 1)


def _sds(mesh, shapes, cfg):
    # sharded as the programs take them: a worker's rows are a block of a
    # count table's dim 0, or of dim 1 where it is topic-major
    return [jax.ShapeDtypeStruct(shape, dt, sharding=mesh.sharding(spec))
            for (shape, dt), spec in zip(shapes,
                                         L._epoch_in_specs(mesh, cfg))]


N_DOCS, VOCAB, K, N_TOK = 1_000_000, 50_000, 1000, 100_000_000


@pytest.mark.parametrize("algo", ["pushpull", "dense"])
def test_enwiki_1m_program_lowers(mesh, algo):
    """The REAL graded-shape program — 1M docs × 1k topics, 100M token
    slots, int16 Ndk, 8-way shard, 5 Gibbs sweeps in one scan — must
    trace and lower without executing (execution needs the TPU)."""
    cfg = L.LDAConfig(n_topics=K, algo=algo, ndk_dtype="int16")
    shapes = L.epoch_arg_shapes(8, N_DOCS, VOCAB, cfg, n_tokens=N_TOK)

    # the modeled layout really carries the corpus: >= 100M token slots
    if algo == "pushpull":
        slots = shapes[4][0][0]
    else:
        _, ne, c = shapes[4][0]
        slots = 16 * 8 * ne * c
    assert slots >= N_TOK

    # int16 halves the Ndk footprint: the whole 1M-doc table is 2 GB
    ndk_shape, ndk_dt = shapes[0]
    ndk_gb = np.prod(ndk_shape) * np.dtype(ndk_dt).itemsize / 1e9
    assert np.dtype(ndk_dt) == np.int16 and ndk_gb < 2.1

    fn = L.make_multi_epoch_fn(mesh, cfg, VOCAB, epochs=5)
    text = fn.lower(*_sds(mesh, shapes, cfg)).as_text()
    assert "while" in text       # the chunk/entry scans lowered
    assert "xi16" in text        # the int16 table is in the program


@pytest.mark.parametrize("carry_db", [False, True])
def test_enwiki_1m_pallas_program_lowers(mesh, monkeypatch, carry_db):
    """The fused-kernel epoch at the TRUE graded shapes, MOSAIC-compiled:
    HARP_PALLAS_FORCE_MOSAIC routes the kernel through the real Pallas→
    Mosaic lowering (not interpret), and the whole program — topic-major
    tables in and out, the scan over document-tile runs, the scalar-prefetched
    chunk metadata, the kernel itself with both tables aliased — lowers
    for TPU on this CPU host.  The doc-tile carry is the kernel's:
    ``carry_db=False`` has no program to lower and raises."""
    monkeypatch.setenv("HARP_PALLAS_FORCE_MOSAIC", "1")
    kw = dict(n_topics=K, algo="pallas", ndk_dtype="int16",
              sampler="exprace", rng_impl="rbg", carry_db=carry_db)
    if not carry_db:
        with pytest.raises(ValueError, match="carry_db=False"):
            L.LDAConfig(**kw)
        return
    cfg = L.LDAConfig(**kw)
    shapes = L.epoch_arg_shapes(8, N_DOCS, VOCAB, cfg, n_tokens=N_TOK)
    fn = L.make_multi_epoch_fn(mesh, cfg, VOCAB, epochs=2)
    lowered = fn.trace(*_sds(mesh, shapes, cfg)).lower(
        lowering_platforms=("tpu",))
    text = lowered.as_text()
    assert "tpu_custom_call" in text  # the Mosaic kernel is in the program
    assert "xi16" in text             # on the int16 table


@pytest.mark.parametrize("exact", [True, False])
def test_hot_count_ab_shape_lowers_mosaic(mesh, monkeypatch, exact):
    """The likelihood A/B pair of FLIP_DECISIONS.jsonl (`lda_pallas_hot` /
    `_approx_hot`) ran at 20k docs x 256 vocab x 32 topics x 200
    tok/doc — avg Nwk cell ~488 > 256, where bf16 gather rounding CAN
    show.  The sprint must not discover a lowering error inside a scarce
    chip run: pin that BOTH gather variants Mosaic-compile at the
    exact sweep shape."""
    monkeypatch.setenv("HARP_PALLAS_FORCE_MOSAIC", "1")
    cfg = L.LDAConfig(n_topics=32, algo="pallas", d_tile=128, w_tile=128,
                      sampler="exprace", rng_impl="rbg",
                      pallas_exact_gathers=exact)
    shapes = L.epoch_arg_shapes(8, 20_000, 256, cfg,
                                n_tokens=20_000 * 200)
    fn = L.make_multi_epoch_fn(mesh, cfg, 256, epochs=2)
    text = fn.trace(*_sds(mesh, shapes, cfg)).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text
