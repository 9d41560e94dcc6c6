"""``algo="pallas"`` keeps both count tables topic-major on the device,
from installation to read-out, and its sweep carries ONE buffer through
the rotation (PR 35).  Held here, on the CPU (the kernel in interpret
mode):

- the chain is the one the sweep sampled while it still took row-major
  tables, transposed them at its top and its end and handed the pipeline
  half-slices: a reference written below, around the same
  ``_sample_runs_pallas`` calls, bit for bit;
- ``rotate_pipeline_resident`` is ``rotate_pipeline``'s schedule and
  data, whatever the ring, the chunk count and the wire;
- every reader and writer of ``Ndk`` / ``Nwk`` still sees ``[rows, K]``.

That the copies are gone is tests/test_chip_compile.py's (the compiled
program for a v5e, no chip).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from harp_tpu.models import lda as L
from harp_tpu.parallel import collective as C
from harp_tpu.parallel.mesh import WorkerMesh
from harp_tpu.parallel.rotate import (resident_chunk_index, rotate_pipeline,
                                      rotate_pipeline_resident)

N_DOCS, VOCAB = 48, 96


def _cfg(**kw):
    return L.LDAConfig(n_topics=8, d_tile=8, w_tile=8, entry_cap=16, **kw)


def _model(n_workers, cfg, seed=3):
    d, w = L.synthetic_corpus(N_DOCS, VOCAB, 3, tokens_per_doc=20, seed=1)
    m = L.LDA(N_DOCS, VOCAB, cfg, WorkerMesh(jax.devices()[:n_workers]),
              seed=seed)
    m.set_tokens(d, w)
    return m, (d, w)


def _row_major_sweep(mesh, cfg, vocab_size, count_bounds):
    """One sweep as it was before PR 35: row-major tables in and out,
    transposed around a ``rotate_pipeline`` that hands the step the
    resident half-slice."""
    nc = L.rotate_chunks_resolved(cfg)

    def sweep(Ndk, Nwk, Nk, z_grid, cd, cw, meta, keys):
        NdkT, NwkT = Ndk.T, Nwk.T

        def step(st, half, t):
            NdkT, Nk, z_grid, key = st
            i = resident_chunk_index(t, nc)
            key, sub = jax.random.split(key)
            NdkT, half, dNk, z_new = L._sample_runs_pallas(
                NdkT, half, Nk, z_grid[i], cd[i], cw[i], meta[i], sub, cfg,
                vocab_size, count_bounds)
            return (NdkT, Nk + C.allreduce(dNk), z_grid.at[i].set(z_new),
                    key), half

        (NdkT, Nk, z_grid, _), NwkT = rotate_pipeline(
            step, (NdkT, Nk, z_grid, keys[0]), NwkT, n_chunks=nc,
            wire=cfg.rotate_wire, chunk_axis=1)
        return NdkT.T, NwkT.T, Nk, z_grid

    rows = mesh.spec(0)
    return jax.jit(mesh.shard_map(
        sweep, in_specs=(rows, rows, P(), rows) + (rows,) * 4,
        out_specs=(rows, rows, P(), rows)))


def _reference_chain(n_workers, cfg, corpus, sweeps, multi):
    """``sweeps`` row-major sweeps from the same pack and the same keys:
    ``sample_epoch``'s (a fresh split a sweep) or ``sample_epochs``' (the
    sweep's index folded into the base key on the device)."""
    src = L.LDA(N_DOCS, VOCAB, cfg,
                WorkerMesh(jax.devices()[:n_workers]), seed=3)
    pack = src.pack_tokens(*corpus)
    src._install_pack(pack)
    mesh, sh = src.mesh, src.mesh.shard_array
    fn = _row_major_sweep(mesh, cfg, VOCAB, src._count_bounds)
    state = (sh(pack["Ndk"], 0), sh(pack["Nwk"], 0),
             jnp.asarray(pack["Nk"]), sh(pack["z_grid"], 0))
    tokens = tuple(sh(a, 0) for a in pack["tokens"])
    for e in range(sweeps):
        keys = src._keys
        if multi:
            keys = np.stack([np.asarray(jax.random.key_data(
                jax.random.fold_in(jax.random.wrap_key_data(
                    jnp.asarray(k)), e))) for k in src._keys])
        else:
            src._advance_keys()
        state = fn(*state, *tokens, sh(keys, 0))
    return [np.asarray(a) for a in state]


@pytest.mark.parametrize("multi", [False, True],
                         ids=["sample_epoch", "sample_epochs"])
@pytest.mark.parametrize("rotate_chunks", [None, 3])
@pytest.mark.parametrize("n_workers", [1, 4])
def test_the_chain_is_the_row_major_sweeps(n_workers, rotate_chunks, multi):
    cfg = _cfg(rotate_chunks=rotate_chunks)
    m, corpus = _model(n_workers, cfg)
    if multi:
        m.sample_epochs(3)
    else:
        for _ in range(3):
            m.sample_epoch()
    Ndk, Nwk, Nk, z = _reference_chain(n_workers, cfg, corpus, 3, multi)
    assert Nwk.sum() == m.n_tokens and (z != np.asarray(
        m.pack_tokens(*corpus)["z_grid"])).any()  # the chain moved
    np.testing.assert_array_equal(np.asarray(m.z_grid), z)
    np.testing.assert_array_equal(np.asarray(m.Ndk), Ndk)
    np.testing.assert_array_equal(np.asarray(m.Nwk), Nwk)
    np.testing.assert_array_equal(np.asarray(m.Nk), Nk)
    # the device holds them topic-major, a worker's rows its columns
    assert m._Nwk.shape == Nwk.shape[::-1] and m._Ndk.shape == Ndk.shape[::-1]
    np.testing.assert_array_equal(np.asarray(m._Nwk), Nwk.T)


@pytest.mark.parametrize("n_workers", [1, 4])
def test_readers_see_row_major_tables(n_workers):
    """``word_topic_table`` / ``doc_topic_table`` / ``log_likelihood`` /
    ``token_state`` read the chain's own counts through the new storage,
    and a read leaves what the device holds as it was."""
    m, (d, w) = _model(n_workers, _cfg())
    m.sample_epochs(2)
    held = m._Nwk
    doc, word, z = m.token_state()
    want_w = np.zeros((VOCAB, 8), np.float32)
    np.add.at(want_w, (word, z), 1)
    want_d = np.zeros((N_DOCS, 8), np.float32)
    np.add.at(want_d, (doc, z), 1)
    np.testing.assert_array_equal(m.word_topic_table(), want_w)
    np.testing.assert_array_equal(m.doc_topic_table(), want_d)
    np.testing.assert_array_equal(np.asarray(m.Nk), want_w.sum(0))
    theta = (want_d[doc, z] + 0.1) / (want_d.sum(1)[doc] + 8 * 0.1)
    phi = (want_w[word, z] + 0.01) / (want_w.sum(0)[z] + VOCAB * 0.01)
    assert m.log_likelihood() == pytest.approx(
        float(np.mean(np.log(theta * phi))), rel=1e-6)
    assert m._Nwk is held and not held.is_deleted()
    assert sorted(zip(doc, word)) == sorted(zip(d, w))


@pytest.mark.parametrize("source", ["host", "device"])
@pytest.mark.parametrize("n_workers", [1, 4])
def test_an_assignment_goes_through_the_relayout(n_workers, source):
    """``model.Nwk = rows`` (a checkpoint's numpy table, or a device
    array as the benchmark's fault drivers assign): stored topic-major,
    read back as given, and the giver keeps its array."""
    m, _ = _model(n_workers, _cfg(ndk_dtype="int16"))
    rng = np.random.default_rng(0)
    for name in ("Ndk", "Nwk"):
        was = np.asarray(getattr(m, name))
        rows = rng.integers(0, 99, was.shape).astype(was.dtype)
        given = rows if source == "host" else m.mesh.shard_array(rows, 0)
        setattr(m, name, given)
        stored = getattr(m, "_" + name)
        assert stored.shape == rows.shape[::-1] and stored.dtype == was.dtype
        np.testing.assert_array_equal(np.asarray(stored), rows.T)
        np.testing.assert_array_equal(np.asarray(getattr(m, name)), rows)
        np.testing.assert_array_equal(np.asarray(given), rows)
        # a worker's rows are its columns: same blocks, same devices
        for shard in stored.addressable_shards:
            lo = shard.index[1].start or 0
            np.testing.assert_array_equal(
                np.asarray(shard.data), rows[lo:lo + shard.data.shape[1]].T)


def test_fit_checkpoints_and_restarts_through_the_storage(tmp_path):
    """``fit``'s ``get_state`` → ``set_state``: the entry snapshot (numpy,
    row-major), a checkpoint file and the restarts from both sample the
    chain an undisturbed run does; a checkpoint of another shape is
    still refused by its row-major shape."""
    from harp_tpu.utils.fault import FaultInjector

    crashed, _ = _model(4, _cfg())
    plain, _ = _model(4, _cfg())
    crashed.fit(4, str(tmp_path / "lda"), ckpt_every=2,
                fault=FaultInjector(fail_at=(1, 3)))
    plain.fit(4)
    for name in ("Ndk", "Nwk", "Nk", "z_grid"):
        np.testing.assert_array_equal(np.asarray(getattr(crashed, name)),
                                      np.asarray(getattr(plain, name)))
    assert float(crashed.Nwk.sum()) == crashed.n_tokens
    other = L.LDA(N_DOCS, 2 * VOCAB, _cfg(),
                  WorkerMesh(jax.devices()[:4]), seed=3)
    other.set_tokens(*L.synthetic_corpus(N_DOCS, 2 * VOCAB, 3, 20, seed=1))
    with pytest.raises(ValueError, match="checkpoint shapes Nwk"):
        other.fit(5, str(tmp_path / "lda"))


# -- the pipeline by itself -------------------------------------------------

def _toy_epoch(mesh, resident, n_chunks, wire, chunk_axis):
    """A slice-updating toy step through either pipeline: the resident
    chunk is scaled by a number of the step and the worker, and the carry
    keeps what every step saw (so order and data both show)."""
    def seen(chunk, t):
        return jnp.sum(chunk * (1 + t)) + jnp.max(chunk)

    def update(chunk, t):
        w = jax.lax.axis_index(mesh.axis)
        return chunk * 0.5 + (t * 8 + w).astype(chunk.dtype)

    def epoch(x):
        x = x[0]
        if resident:
            m = x.shape[chunk_axis] // n_chunks

            def step(c, buf, t, slot):
                cur = jax.lax.dynamic_slice_in_dim(buf, slot * m, m,
                                                   chunk_axis)
                return c + seen(cur, t), jax.lax.dynamic_update_slice_in_dim(
                    buf, update(cur, t), slot * m, chunk_axis)

            c, x = rotate_pipeline_resident(
                step, jnp.float32(0), x, n_chunks=n_chunks, wire=wire,
                chunk_axis=chunk_axis)
        else:
            c, x = rotate_pipeline(
                lambda c, cur, t: (c + seen(cur, t), update(cur, t)),
                jnp.float32(0), x, n_chunks=n_chunks, wire=wire,
                chunk_axis=chunk_axis)
        return c[None], x[None]

    return jax.jit(mesh.shard_map(epoch, in_specs=mesh.spec(0),
                                  out_specs=(mesh.spec(0), mesh.spec(0))))


@pytest.mark.parametrize("wire", ["exact", "bf16", "int8"])
@pytest.mark.parametrize("n_chunks,chunk_axis", [(1, 0), (2, 1), (2, 0),
                                                 (3, 1)])
@pytest.mark.parametrize("n_workers", [1, 4, 8])
def test_resident_pipeline_is_rotate_pipelines_schedule(n_workers, n_chunks,
                                                        chunk_axis, wire):
    mesh = WorkerMesh(jax.devices()[:n_workers])
    x = np.random.default_rng(n_workers).normal(
        size=(n_workers, 6, 12)).astype(np.float32)
    want = _toy_epoch(mesh, False, n_chunks, wire, chunk_axis)(x)
    got = _toy_epoch(mesh, True, n_chunks, wire, chunk_axis)(x)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert not np.array_equal(np.asarray(got[1]), x)


def test_resident_pipeline_refuses_what_rotate_pipeline_refuses():
    mesh = WorkerMesh(jax.devices()[:4])

    def run(**kw):
        return jax.jit(mesh.shard_map(
            lambda x: rotate_pipeline_resident(
                lambda c, b, t, s: (c, b), jnp.float32(0), x[0], **kw)[1][None],
            in_specs=mesh.spec(0), out_specs=mesh.spec(0)))(
                np.zeros((4, 6, 12), np.float32))

    with pytest.raises(ValueError, match="shares a factor"):
        run(n_chunks=2, shift=2)
    with pytest.raises(ValueError, match="does not split into 4"):
        run(n_chunks=4)
    with pytest.raises(ValueError, match="n_chunks must be >= 1"):
        run(n_chunks=0)
    with pytest.raises(ValueError, match="wire must be one of"):
        run(n_chunks=2, wire="fp8")
