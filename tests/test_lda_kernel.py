"""Fused Pallas LDA-CGS kernel (ops/lda_kernel.py) + algo="pallas".

Interpret mode streams externally-drawn uniforms (the TPU hardware PRNG
is unavailable off-TPU), so the distributional tests exercise the exact
posterior/race math the TPU path runs — only the bit source differs.
"""

import numpy as np
import pytest

from harp_tpu.models import lda as L

N = 8


def _pallas_cfg(**kw):
    base = dict(n_topics=8, algo="pallas", d_tile=16, w_tile=16,
                entry_cap=64, alpha=0.5, beta=0.1,
                sampler="exprace", rng_impl="rbg")
    base.update(kw)
    return L.LDAConfig(**base)


def test_kernel_draws_from_posterior():
    """Direct kernel calls on a flat tile: frequencies must match
    p ∝ (ndk+α)(nwk+β)/(nk+Vβ).

    One 256-token chunk per call (all tokens score against the entry
    snapshot — no within-call drift), repeated over fresh seeds from the
    SAME initial counts; counts are large so the bf16-rounded gathers
    (module doc) shift p well under the statistical window."""
    import jax.numpy as jnp

    from harp_tpu.ops.lda_kernel import cgs_entry_update

    K, DR, WR, C = 8, 8, 8, 256
    av = np.array([1.0, 2, 3, 4, 1, 1, 1, 3]) * 10_000
    bv = np.array([4.0, 1, 2, 1, 1, 2, 1, 1]) * 10_000
    DbT = jnp.zeros((K, DR), jnp.float32).at[:, 0].set(jnp.asarray(av))
    WbT = jnp.zeros((K, WR), jnp.float32).at[:, 0].set(jnp.asarray(bv))
    nk = jnp.full((K,), 1e6)
    z = jnp.zeros(C, jnp.int32)  # current topic 0 (consistent: av[0] ≫ C)
    cd = jnp.zeros(C, jnp.int32)
    cw = jnp.zeros(C, jnp.int32)

    # remove-current: topic 0 scores (a0−1)(b0−1)/(c0−1)
    a, b, c = av.copy(), bv.copy(), np.full(K, 1e6)
    a[0] -= 1; b[0] -= 1; c[0] -= 1
    p = (a * b) / c
    p /= p.sum()

    reps = 24
    counts = np.zeros(K)
    for r in range(reps):
        _, _, z_new, dnk = cgs_entry_update(
            DbT, WbT, nk, z, cd, cw, jnp.array([3, 100 + r], jnp.int32),
            alpha=0.0, beta=0.0, vbeta=0.0, interpret=True)
        zn = np.asarray(z_new)
        counts += np.bincount(zn, minlength=K)
        # count bookkeeping: dnk ≡ assignment histogram delta, every call
        np.testing.assert_allclose(
            np.asarray(dnk),
            np.bincount(zn, minlength=K) - np.array([C] + [0] * (K - 1)))
    freq = counts / (reps * C)
    se = np.sqrt(p * (1 - p) / (reps * C)).max()
    np.testing.assert_allclose(freq, p, atol=5 * se + 0.005)


@pytest.mark.parametrize("ndk_dtype", ["float32", "int16"])
def test_pallas_chain_converges_counts_exact(mesh, ndk_dtype):
    cfg = _pallas_cfg(ndk_dtype=ndk_dtype)
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
    Nk = np.asarray(model.Nk)
    # the scatter side is exact: tables stay integer-valued invariants
    assert Ndk.sum() == model.n_tokens
    assert Nwk.sum() == model.n_tokens
    np.testing.assert_allclose(Nwk.sum(0), Nk)
    np.testing.assert_array_equal(Nwk, np.round(Nwk))
    assert (Ndk >= 0).all() and (Nwk >= 0).all()


def test_pallas_multi_epoch_program(mesh):
    """sample_epochs (one scanned device program) through the kernel."""
    cfg = _pallas_cfg()
    d, w = L.synthetic_corpus(n_docs=64, vocab_size=32, n_topics_true=4,
                              tokens_per_doc=40, seed=2)
    model = L.LDA(64, 32, cfg, mesh, seed=3)
    model.set_tokens(d, w)
    model.sample_epochs(3)
    Ndk = np.asarray(model.Ndk)
    assert Ndk.sum() == model.n_tokens and (Ndk >= 0).all()


def test_gather_planes_exact_above_256():
    """ADVICE r3: single-dot bf16 gathers round counts > 256; the base-256
    digit planes must reproduce the table values EXACTLY up to the f32
    integer ceiling (2 planes to 2^16, 3 planes to 2^24)."""
    import functools

    import jax.numpy as jnp
    from jax import lax

    from harp_tpu.ops.lda_kernel import _gather_planes

    # values chosen to be bf16-UNrepresentable: 257 (ties to 256),
    # 16385, 65537, 10_000_019 (prime > 2^23)
    vals = np.array([0, 1, 255, 256, 257, 16385, 65535, 65537, 10_000_019],
                    np.float64)
    K = 4
    tbl = np.tile(vals, (K, 1)).astype(np.float32)          # [K, R]
    ids = np.arange(len(vals), dtype=np.int32)              # gather all
    oh = (ids[:, None] == np.arange(len(vals))[None, :]).astype(np.float32)
    dot = functools.partial(lax.dot_general,
                            dimension_numbers=(((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    exact3 = np.asarray(_gather_planes(jnp.asarray(tbl),
                                       jnp.asarray(oh, jnp.bfloat16), dot, 3))
    np.testing.assert_array_equal(exact3, tbl)
    # 2 planes: exact for everything below 2^16 (the int16 doc-tile case)
    small = tbl.copy()
    small[:, vals > 65535] = 0
    exact2 = np.asarray(_gather_planes(jnp.asarray(small),
                                       jnp.asarray(oh, jnp.bfloat16), dot, 2))
    np.testing.assert_array_equal(exact2, small)
    # the single-dot path really does round 257 (this is what exact mode
    # fixes — if this ever passes, bf16 grew a mantissa and the planes
    # can be retired)
    approx = np.asarray(_gather_planes(jnp.asarray(tbl),
                                       jnp.asarray(oh, jnp.bfloat16), dot, 0))
    assert approx[0, list(vals).index(257)] != 257.0


def _exact_draws(DbT, WbT, nk, z, cd, cw, seed2, alpha, beta, vbeta):
    """What one 256-token chunk must draw: the race over the uniforms the
    interpret path streams in, on the counts as they stand, in float32
    and in the kernel's order of operations."""
    import jax
    import jax.numpy as jnp

    K, C = DbT.shape[0], len(z)
    key = jax.random.wrap_key_data(jnp.asarray(seed2, jnp.uint32))
    u = np.asarray(jax.random.uniform(key, (K, C), jnp.float32,
                                      minval=2.0 ** -25, maxval=1.0))
    own = (np.arange(K)[:, None] == z[None, :]).astype(np.float32)
    a = np.maximum(DbT[:, cd] - own + np.float32(alpha), np.float32(1e-10))
    b = np.maximum(WbT[:, cw] - own + np.float32(beta), np.float32(1e-10))
    c = np.maximum(nk[:, None] - own + np.float32(vbeta), np.float32(1e-10))
    return (-np.log(u) * c / (a * b)).argmin(0)


def test_kernel_draws_the_exact_posterior_above_256():
    """The guard the benchmark's ``correct`` cannot be (PERF.md section
    7): on counts above 256 the kernel's every draw is the race's winner
    on the EXACT counts, token for token, from the same uniforms; the
    single-dot path, which sees 257 as 256 and 259 as 260, draws another
    topic for some of them.  A kernel that drops exactness fails here."""
    import jax.numpy as jnp

    from harp_tpu.ops.lda_kernel import cgs_entry_update

    K, DR, WR, C = 8, 8, 128, 256
    rng = np.random.default_rng(3)
    # word-topic counts that bfloat16 cannot hold: odd, in [257, 511]
    # and [513, 1023]; document counts small (exact on both paths)
    WbT = (2 * rng.integers(128, 512, (K, WR)) + 1).astype(np.float32)
    DbT = rng.integers(20, 60, (K, DR)).astype(np.float32)
    nk = WbT.sum(1) + 1000.0
    assert (np.asarray(jnp.asarray(WbT).astype(jnp.bfloat16), np.float32)
            != WbT).all()
    kw = dict(alpha=0.1, beta=0.01, vbeta=10.0, interpret=True)
    flipped = 0
    for r in range(12):
        z = rng.integers(0, K, C).astype(np.int32)
        cd = rng.integers(0, DR, C).astype(np.int32)
        cw = rng.integers(0, WR, C).astype(np.int32)
        seed2 = np.array([5, 40 + r], np.int32)
        want = _exact_draws(DbT, WbT, nk, z, cd, cw, seed2, 0.1, 0.01, 10.0)
        args = [jnp.asarray(x) for x in (DbT, WbT, nk, z, cd, cw, seed2)]
        got = np.asarray(cgs_entry_update(
            *args, nwk_count_bound=1023, ndk_count_bound=60, **kw)[2])
        np.testing.assert_array_equal(got, want)
        rounded = np.asarray(cgs_entry_update(*args, exact_gathers=False,
                                              **kw)[2])
        flipped += int((rounded != want).sum())
    assert flipped > 0  # read: 5 of the 3,072 draws (0.16%)


def test_count_bounds_pick_fewer_planes_identically():
    """A static count bound lets the kernel gather with fewer digit
    planes (1 when every count ≤ 256 — the enwiki doc-length case);
    outputs must be IDENTICAL to the unbounded 2/3-plane paths when the
    bound really holds."""
    import jax.numpy as jnp

    from harp_tpu.ops.lda_kernel import _planes_for, cgs_entry_update

    assert _planes_for(256, jnp.float32) == 1
    assert _planes_for(257, jnp.float32) == 2
    assert _planes_for(2**16, jnp.float32) == 3
    assert _planes_for(None, jnp.int16) == 2
    assert _planes_for(None, jnp.float32) == 3

    K, DR, WR, C = 8, 8, 8, 256
    rng = np.random.default_rng(0)
    DbT = jnp.asarray(rng.integers(0, 200, (K, DR)).astype(np.float32))
    WbT = jnp.asarray(rng.integers(0, 200, (K, WR)).astype(np.float32))
    nk = jnp.asarray(DbT.sum(1) + 1000.0)
    z = jnp.zeros(C, jnp.int32)
    cd = jnp.asarray(rng.integers(0, DR, C).astype(np.int32))
    cw = jnp.asarray(rng.integers(0, WR, C).astype(np.int32))
    kw = dict(alpha=0.5, beta=0.1, vbeta=3.2, interpret=True)
    outs = {}
    for bounds in ((None, None), (200, 200)):
        outs[bounds] = cgs_entry_update(
            DbT, WbT, nk, z, cd, cw, jnp.array([7, 9], jnp.int32),
            ndk_count_bound=bounds[0], nwk_count_bound=bounds[1], **kw)
    for a, b in zip(outs[(None, None)], outs[(200, 200)]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# hypothesis is optional in some images: without it only this property
# test skips — a bare module-level import would fail the whole module's
# collection and take the deterministic kernel tests above down with it
try:
    from hypothesis import given, settings, strategies as st  # noqa: E402
except ImportError:  # pragma: no cover
    given = None


def _property_case(fn):
    if given is None:  # pragma: no cover
        return pytest.mark.skip(reason="hypothesis not installed")(fn)
    return settings(max_examples=25, deadline=None)(
        given(st.lists(st.integers(0, 2**24 - 1),
                       min_size=1, max_size=32))(fn))


@_property_case
def test_gather_planes_exact_for_arbitrary_f32_integers(vals):
    """Property form of the plane-exactness claim: ANY integer table the
    f32 count tables can represent (< 2^24) gathers exactly through 3
    bf16 digit planes."""
    import functools

    import jax.numpy as jnp
    from jax import lax

    from harp_tpu.ops.lda_kernel import _gather_planes

    tbl = np.asarray(vals, np.float32)[None, :]            # [1, R]
    oh = np.eye(len(vals), dtype=np.float32)               # gather all
    dot = functools.partial(lax.dot_general,
                            dimension_numbers=(((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    got = np.asarray(_gather_planes(jnp.asarray(tbl),
                                    jnp.asarray(oh, jnp.bfloat16), dot, 3))
    np.testing.assert_array_equal(got, tbl)


def test_pallas_exact_gathers_chain_quality_at_hot_counts(mesh):
    """ADVICE r3's likelihood A/B: a small vocab drives word-topic counts
    well past 256 (where bf16 gathers round), and the exact-gather pallas
    chain must track the dense chain's likelihood."""
    cfg_p = _pallas_cfg(ndk_dtype="int16")
    cfg_d = L.LDAConfig(n_topics=8, algo="dense", d_tile=16, w_tile=16,
                        entry_cap=1024, alpha=0.5, beta=0.1,
                        ndk_dtype="int16")
    d, w = L.synthetic_corpus(n_docs=64, vocab_size=16, n_topics_true=4,
                              tokens_per_doc=200, seed=5)
    lls = {}
    hot = {}
    for name, cfg in (("dense", cfg_d), ("pallas", cfg_p)):
        m = L.LDA(64, 16, cfg, mesh, seed=7)
        m.set_tokens(d, w)
        for _ in range(6):
            m.sample_epoch()
        lls[name] = m.log_likelihood()
        hot[name] = np.asarray(m.Nwk).max()
    # the corpus really reaches the rounding regime (12.8k tokens over a
    # 16-word vocab -> hot (word, topic) cells far beyond 256)
    assert hot["pallas"] > 256, hot
    # different random streams: same ballpark is the contract (the gate
    # drive_check uses); a rounding-biased sampler drifts well past this
    assert abs(lls["pallas"] - lls["dense"]) / abs(lls["dense"]) < 0.25, lls


def test_pallas_approx_gathers_still_converge(mesh):
    """The opt-out single-dot path stays a working chain (it is a sweep
    candidate, not dead code)."""
    cfg = _pallas_cfg(pallas_exact_gathers=False)
    d, w = L.synthetic_corpus(n_docs=64, vocab_size=32, n_topics_true=4,
                              tokens_per_doc=40, seed=4)
    m = L.LDA(64, 32, cfg, mesh, seed=2)
    m.set_tokens(d, w)
    ll0 = m.log_likelihood()
    for _ in range(5):
        m.sample_epoch()
    assert m.log_likelihood() > ll0
    Nwk = np.asarray(m.Nwk)
    assert Nwk.sum() == m.n_tokens  # updates stay exact even when
    np.testing.assert_array_equal(Nwk, np.round(Nwk))  # gathers round


def test_pallas_requires_fused_sampling_stack():
    # since the 2026-08-01 flip the DEFAULT stack is the kernel's own
    # (exprace + rbg), so a bare pallas config is valid...
    assert L.LDAConfig(n_topics=8, algo="pallas").sampler == "exprace"
    # ...but an EXPLICIT mismatched stack still refuses: the config must
    # never claim a sampler the kernel doesn't run
    with pytest.raises(ValueError, match="exprace"):
        L.LDAConfig(n_topics=8, algo="pallas", sampler="gumbel",
                    rng_impl="threefry")


def test_pallas_benchmark_defaults_upgrade(mesh):
    """benchmark(algo='pallas') silently upgrades the DEFAULT sampler
    knobs (an explicit gumbel request still errors)."""
    out = L.benchmark(n_docs=64, vocab_size=32, n_topics=8,
                      tokens_per_doc=8, epochs=1, mesh=mesh,
                      algo="pallas", d_tile=16, w_tile=16, entry_cap=64)
    assert out["tokens_per_sec_per_chip"] > 0
    with pytest.raises(ValueError, match="exprace"):
        L.benchmark(n_docs=64, vocab_size=32, n_topics=8,
                    tokens_per_doc=8, epochs=1, mesh=mesh,
                    algo="pallas", sampler="gumbel")


def test_kernel_vmem_gate():
    import jax.numpy as jnp

    from harp_tpu.ops.lda_kernel import cgs_entry_update

    K = 4096
    DbT = jnp.zeros((K, 512), jnp.float32)
    WbT = jnp.zeros((K, 512), jnp.float32)
    with pytest.raises(ValueError, match="VMEM"):
        cgs_entry_update(DbT, WbT, jnp.zeros(K), jnp.zeros(256, jnp.int32),
                         jnp.zeros(256, jnp.int32),
                         jnp.zeros(256, jnp.int32),
                         jnp.zeros(2, jnp.int32), alpha=0.1, beta=0.1,
                         vbeta=1.0, interpret=True)


@pytest.mark.parametrize("ndk_dtype", ["float32", "int16"])
@pytest.mark.parametrize("shape", [
    # (K, DR, WR, C) — graded enwiki tiling and the 128-tile smoke
    # shapes the driver bench compiles FIRST on real TPU
    (1000, 512, 512, 2048),
    (8, 128, 128, 256),
])
@pytest.mark.parametrize("bounds", [
    (None, None),   # dtype-based planes (2-3)
    (100, 2100),    # the bounds the sprint's graded corpora derive
                    # (doc length ≤ 256 → 1 Db plane; word freq → 2 Wb)
])
def test_kernel_lowers_for_tpu(ndk_dtype, shape, bounds):
    """Pallas->Mosaic verification at the graded tile shapes, no hardware
    (caught the uint32->f32 cast Mosaic rejects, before any chip run)."""
    import functools

    import jax
    import jax.numpy as jnp

    from harp_tpu.ops.lda_kernel import cgs_entry_update

    K, DR, WR, C = shape
    f = functools.partial(cgs_entry_update, alpha=0.1, beta=0.01,
                          vbeta=500.0, interpret=False,
                          ndk_count_bound=bounds[0],
                          nwk_count_bound=bounds[1])
    lowered = jax.jit(f).trace(
        jnp.zeros((K, DR), jnp.dtype(ndk_dtype)), jnp.zeros((K, WR)),
        jnp.zeros((K,)), jnp.zeros(C, jnp.int32), jnp.zeros(C, jnp.int32),
        jnp.zeros(C, jnp.int32),
        jnp.zeros(2, jnp.int32)).lower(lowering_platforms=("tpu",))
    assert "tpu_custom_call" in lowered.as_text()


# ---------------------------------------------------------------------------
# The chunk list (PR 32): what the host step stages, and that the kernel
# run over it samples the chain the fully padded entry list samples.
# ---------------------------------------------------------------------------

def _entries(doc, word, n_docs, vocab, d_tile, w_tile, entry_cap, n_workers=1,
             seed=0):
    """``partition_ratings_tiles``' entries for a corpus, topics drawn
    from ``seed`` riding as the values, and the worker's runs a row."""
    from harp_tpu.models.mfsgd import partition_ratings_tiles

    z0 = np.random.default_rng(seed).integers(0, 8, len(doc)).astype(
        np.float32)
    ed, ew, ez, od, ow, _, _, d_bound, _ = partition_ratings_tiles(
        doc, word, z0, n_docs, vocab, n_workers, d_tile, w_tile, entry_cap,
        n_slices=2 * n_workers)
    return (ed, ew, ez, od, ow), d_bound // d_tile


def _loop_chunk_list(entries, n_runs, d_tile, w_tile, cc, keep_padding):
    """The layout by plain loops, the reference ``stage_chunk_list`` is
    held to: an entry's chunks that hold tokens — or, ``keep_padding``,
    every chunk of the entry's full width, the all-padding ones run as
    real chunks: the fully padded entry list.  Returns the four arrays
    and, a row, the ``(run, entry, chunk of the entry)`` of every staged
    chunk that is not a tail no-op."""
    from harp_tpu.ops.lda_kernel import pack_chunk_meta

    ed, ew, ez, od, ow = entries
    ws, _, c = ed.shape
    full = -(-c // cc)
    per_row = []
    for w in range(ws):
        runs = [[] for _ in range(n_runs)]
        for e in range(ed.shape[1]):
            count = int((ed[w, e] < d_tile).sum())
            if count:
                k = full if keep_padding else -(-count // cc)
                runs[od[w, e] // d_tile] += [(e, j) for j in range(k)]
        per_row.append(runs)
    nchr = max(1, max(len(r) for runs in per_row for r in runs))
    shape = (ws, n_runs * nchr, cc)
    cd = np.full(shape, d_tile, np.int32)
    cw = np.full(shape, w_tile, np.int32)
    z = np.zeros(shape, np.int32)
    meta = np.zeros(shape[:2], np.int32)
    where = []
    for w, runs in enumerate(per_row):
        src = {}
        for r, chunks in enumerate(runs):
            wt = 0
            for i in range(nchr):
                pos = r * nchr + i
                if i < len(chunks):
                    e, j = chunks[i]
                    sl = slice(j * cc, min((j + 1) * cc, c))
                    n = sl.stop - sl.start
                    cd[w, pos, :n] = ed[w, e, sl]
                    cw[w, pos, :n] = ew[w, e, sl]
                    z[w, pos, :n] = ez[w, e, sl]
                    wt = ow[w, e] // w_tile
                    src[pos] = (r, e, j)
                meta[w, pos] = pack_chunk_meta(wt, i >= len(chunks))
        where.append(src)
    return (cd, cw, z, meta), where


def _check_chunk_list(entries, staged, n_runs, d_tile, w_tile):
    """The host step's contract, whatever the case."""
    from harp_tpu.ops.lda_kernel import pack_chunk_meta, unpack_chunk_meta

    ed, ew, ez, od, ow = entries
    cd, cw, z, meta = staged
    ws, nch, cc = cd.shape
    assert cw.shape == z.shape == cd.shape and meta.shape == (ws, nch)
    assert z.dtype == np.int32 and meta.dtype == np.int32
    assert nch % n_runs == 0
    nchr = nch // n_runs
    wt, noop = unpack_chunk_meta(meta)
    # the metadata round-trips
    np.testing.assert_array_equal(pack_chunk_meta(wt, noop), meta)
    for w in range(ws):
        # each token once and in the parent's order: the valid slots of
        # the entries, entry by entry, are the valid slots of the chunks,
        # chunk by chunk, as (doc row, word row, topic)
        ve, vc = ed[w] < d_tile, cd[w] < d_tile
        run_of = np.arange(nch) // nchr
        want = np.stack([(ed[w] + od[w][:, None])[ve],
                         (ew[w] + ow[w][:, None])[ve], ez[w][ve]])
        got = np.stack([(cd[w] + (run_of * d_tile)[:, None])[vc],
                        (cw[w] + (wt[w] * w_tile)[:, None])[vc], z[w][vc]])
        np.testing.assert_array_equal(got, want)
        # valid slots lead a chunk; a no-op holds none
        np.testing.assert_array_equal(
            vc, np.arange(cc)[None, :] < vc.sum(1)[:, None])
        assert not vc[noop[w]].any()
        for r in range(n_runs):
            sl = slice(r * nchr, (r + 1) * nchr)
            real = ~noop[w, sl]
            n_real = int(real.sum())
            # tail no-ops: the real chunks are a prefix of the run's
            # slab, every one holds a token, the no-ops stay at the last
            # real chunk's word tile (tile 0 in an empty run)
            assert real[:n_real].all()
            assert vc[sl][:n_real].any(1).all()
            tiles = wt[w, sl]
            assert (tiles[n_real:] == (tiles[n_real - 1] if n_real else 0)
                    ).all()
            # a tile's chunks adjacent, each word tile one contiguous
            # group inside the run: the groups' tiles strictly increase
            changes = np.flatnonzero(np.diff(tiles[:n_real]))
            assert (np.diff(tiles[:n_real])[changes] > 0).all()


def _layout_cases():
    """name → (doc ids, word ids, n_docs, vocab, entry_cap, workers): tiles
    of 8 documents x 8 words, chunks of 8 slots."""
    rng = np.random.default_rng(11)

    def tile(dt, wt, n):
        return (dt * 8 + rng.integers(0, 8, n), wt * 8 + rng.integers(0, 8, n))

    def corpus(*tiles):
        d, w = (np.concatenate(x) for x in zip(*tiles))
        order = rng.permutation(len(d))
        return d[order].astype(np.int32), w[order].astype(np.int32)

    # 32 words = two half-slices of two word tiles; 24 documents = 3 runs
    return {
        # tiles of 1, 2 and many chunks, one over the entry cap (two entries)
        "one_two_many_chunks": (*corpus(
            tile(0, 0, 5), tile(0, 1, 13), tile(1, 0, 41), tile(1, 1, 8),
            tile(2, 0, 70), tile(2, 1, 1), tile(0, 2, 9), tile(1, 3, 17),
            tile(2, 2, 3)), 24, 32, 64, 1),
        # word tile 1 of the first half-slice holds nothing anywhere
        "empty_word_tile": (*corpus(
            tile(0, 0, 12), tile(1, 0, 3), tile(2, 0, 20), tile(0, 2, 4),
            tile(0, 3, 30), tile(2, 3, 6)), 24, 32, 64, 1),
        # document tile 1 holds nothing: a run of no-ops in every row
        "empty_doc_tile_run": (*corpus(
            tile(0, 0, 12), tile(0, 1, 9), tile(2, 0, 25), tile(2, 1, 2),
            tile(0, 2, 7), tile(2, 3, 11)), 24, 32, 64, 1),
        # rows of unequal length: two workers, four half-slices, one heavy
        "unequal_rows": (*corpus(
            tile(0, 0, 60), tile(0, 1, 33), tile(1, 0, 18), tile(2, 1, 2),
            tile(3, 2, 5), tile(4, 3, 1), tile(5, 0, 9), tile(1, 2, 4)),
            48, 32, 32, 2),
        # a tile of exactly one chunk, of one token more, of exactly two
        # and of two and one: ceil(count / chunk) at its edges
        "exactly_a_chunk_and_one_over": (*corpus(
            tile(0, 0, 8), tile(0, 1, 9), tile(1, 0, 16), tile(2, 1, 17),
            tile(1, 2, 7)), 24, 32, 64, 1),
        # an entry cap that is no multiple of the chunk: full entries end
        # in a partly filled chunk
        "cap_not_a_chunk_multiple": (*corpus(
            tile(0, 0, 50), tile(1, 1, 21), tile(2, 0, 12), tile(0, 3, 31)),
            24, 32, 20, 1),
    }


_CASES = _layout_cases()


@pytest.mark.parametrize("case", sorted(_CASES))
def test_stage_chunk_list_keeps_the_contract(case):
    """(ii): every token once and in the parent's order, a tile's chunks
    adjacent, each word tile one contiguous group inside a run, tail
    no-ops, the metadata round trip — and the arrays are the plain-loop
    layout's, byte for byte."""
    from harp_tpu.ops.lda_kernel import stage_chunk_list

    doc, word, n_docs, vocab, cap, nw = _CASES[case]
    entries, n_runs = _entries(doc, word, n_docs, vocab, 8, 8, cap, nw)
    staged = stage_chunk_list(*entries, n_runs, 8, 8, cc=8)
    _check_chunk_list(entries, staged, n_runs, 8, 8)
    by_loops, _ = _loop_chunk_list(entries, n_runs, 8, 8, 8, False)
    for a, b in zip(staged, by_loops):
        np.testing.assert_array_equal(a, b)
    # the fully padded entry list is a chunk list too, and keeps the
    # same contract but for its all-padding chunks
    assert int((staged[0] < 8).sum()) == len(doc)


@pytest.mark.parametrize("count", [1, 127, 128, 129, 256, 257, 700])
def test_a_tile_stages_the_chunks_that_hold_its_tokens(count):
    """At the kernel's own width (``CHUNK`` = 128 slots, 128-wide tiles,
    the program's ``entry_cap``): a tile of ``count`` tokens beside a
    one-token tile in a second run stages ``ceil(count / 128)`` chunks,
    the other run one and no-ops up to the same length; the fixed-width
    entries before it staged ``ceil(count / 256) * 256`` slots an entry
    whatever they held."""
    from harp_tpu.ops.lda_kernel import (CHUNK, stage_chunk_list,
                                         unpack_chunk_meta)

    rng = np.random.default_rng(count)
    doc = np.r_[rng.integers(0, 128, count), 128 + 5].astype(np.int32)
    word = np.r_[rng.integers(128, 256, count), 3].astype(np.int32)
    entries, n_runs = _entries(doc, word, 256, 512, 128, 128, 2048)
    assert n_runs == 2
    cd, cw, z, meta = stage_chunk_list(*entries, n_runs, 128, 128)
    _check_chunk_list(entries, (cd, cw, z, meta), n_runs, 128, 128)
    want = -(-count // CHUNK)
    assert cd.shape == (2, 2 * want, CHUNK)
    wt, noop = unpack_chunk_meta(meta)
    # half-slice 0 holds both tiles (words 0..255): run 0 the heavy one
    # at word tile 1, run 1 the single token at word tile 0
    np.testing.assert_array_equal(noop[0], np.r_[[False] * want, False,
                                                 [True] * (want - 1)])
    np.testing.assert_array_equal(wt[0], np.r_[[1] * want, [0] * want])
    np.testing.assert_array_equal((cd[0] < 128).sum(1)[:want],
                                  np.r_[[CHUNK] * (want - 1),
                                        count - (want - 1) * CHUNK])
    assert noop[1].all() and not (cd[1] < 128).any()  # an empty row


def test_stage_chunk_list_refuses_what_the_kernel_cannot_run():
    """Entries out of document-tile-major order, a word tile index over
    the metadata's bits, a run longer than SMEM holds: a ValueError each,
    before anything is staged or dispatched."""
    import jax
    import jax.numpy as jnp

    from harp_tpu.ops import lda_kernel as LK

    doc, word, n_docs, vocab, cap, nw = _CASES["one_two_many_chunks"]
    (ed, ew, ez, od, ow), n_runs = _entries(doc, word, n_docs, vocab, 8, 8,
                                            cap, nw)
    nreal = int(((ed[0] < 8).sum(-1) > 0).sum())
    swap = np.r_[nreal - 1, np.arange(1, nreal - 1), 0,
                 np.arange(nreal, ed.shape[1])]
    with pytest.raises(ValueError, match="document-tile-major"):
        LK.stage_chunk_list(ed[:, swap], ew[:, swap], ez[:, swap],
                            od[:, swap], ow[:, swap], n_runs, 8, 8, cc=8)
    with pytest.raises(ValueError, match="document-tile-major"):
        LK.stage_chunk_list(ed, ew, ez, od, ow, n_runs - 1, 8, 8, cc=8)
    with pytest.raises(ValueError, match="lead each entry"):
        LK.stage_chunk_list(ed[..., ::-1], ew[..., ::-1], ez[..., ::-1],
                            od, ow, n_runs, 8, 8, cc=8)
    with pytest.raises(ValueError, match="30 bits"):
        LK.pack_chunk_meta(np.array([0, 1 << 30]), False)
    with pytest.raises(ValueError, match="30 bits"):
        LK.pack_chunk_meta(np.array([-1]), False)
    nch = LK._MAX_CHUNKS + 1
    i32 = jnp.int32
    with pytest.raises(ValueError, match="SMEM"):
        jax.eval_shape(
            lambda *a: LK.cgs_run_update(
                *a, alpha=0.1, beta=0.1, vbeta=1.0, d_tile=8, w_tile=8,
                interpret=True),
            *(jax.ShapeDtypeStruct(s, d) for s, d in (
                ((8, 8), jnp.float32), ((8, 16), jnp.float32),
                ((8,), jnp.float32), ((nch, 8), i32), ((nch, 8), i32),
                ((nch, 8), i32), ((nch,), i32), ((), i32), ((2,), i32))))


def _run_chunk_list(staged, row, K, n_runs, tables, nk, uniforms):
    """One rotation step of ``lda._sample_runs_pallas`` by hand: the
    row's runs in order through ``cgs_run_update``, ``uniforms[r]``
    [K, chunks a run, cc] feeding run ``r``."""
    import jax.numpy as jnp

    from harp_tpu.ops.lda_kernel import cgs_run_update

    cd, cw, z, meta = (jnp.asarray(a[row]) for a in staged)
    nchr = cd.shape[0] // n_runs
    NdkT, NwkT = (jnp.asarray(t) for t in tables)
    dnk = jnp.zeros(K, jnp.float32)
    z_out = []
    for r in range(n_runs):
        sl = slice(r * nchr, (r + 1) * nchr)
        NdkT, NwkT, z_new, d = cgs_run_update(
            NdkT, NwkT, jnp.asarray(nk) + dnk, z[sl], cd[sl], cw[sl],
            meta[sl], r, jnp.zeros(2, jnp.int32), alpha=0.5, beta=0.1,
            vbeta=3.2, d_tile=8, w_tile=8, interpret=True,
            nwk_count_bound=600, ndk_count_bound=600,
            uniforms=jnp.asarray(uniforms[r].reshape(K, -1)))
        dnk = dnk + d
        z_out.append(np.asarray(z_new))
    return (np.asarray(NdkT), np.asarray(NwkT), np.asarray(dnk),
            np.concatenate(z_out))


@pytest.mark.parametrize("case", sorted(_CASES))
def test_chunk_list_chain_equals_the_padded_entry_chain(case):
    """(i) layout equivalence, interpret mode: the chunk list against the
    fully padded entry list (every chunk of every entry's full width,
    the all-padding ones executed) through the same kernel body, the
    same uniforms chunk for chunk: every token's new topic, both count
    tables and ``dN_k`` bit-identical.  The chunks dropped were masked
    slots that changed no count and no topic."""
    K = 8
    doc, word, n_docs, vocab, cap, nw = _CASES[case]
    entries, n_runs = _entries(doc, word, n_docs, vocab, 8, 8, cap, nw)
    compact, c_src = _loop_chunk_list(entries, n_runs, 8, 8, 8, False)
    padded, p_src = _loop_chunk_list(entries, n_runs, 8, 8, 8, True)
    from harp_tpu.ops.lda_kernel import stage_chunk_list

    for a, b in zip(stage_chunk_list(*entries, n_runs, 8, 8, cc=8), compact):
        np.testing.assert_array_equal(a, b)
    assert sum(map(len, p_src)) > sum(map(len, c_src))  # chunks that run
    rng = np.random.default_rng(5)
    d_rows, w_rows, moved = n_runs * 8, 16, 0
    for row in range(compact[0].shape[0]):
        # tables with counts on both sides of 256, as the chain could
        # hold them (the draws read them; their sums are not the corpus')
        NdkT = rng.integers(0, 40, (K, d_rows)).astype(np.float32)
        NwkT = rng.integers(0, 600, (K, w_rows)).astype(np.float32)
        nk = NwkT.sum(1) + 50.0
        c_nchr = compact[0].shape[1] // n_runs
        p_nchr = padded[0].shape[1] // n_runs
        u_c = rng.uniform(2.0 ** -25, 1.0, (n_runs, K, c_nchr, 8)).astype(
            np.float32)
        # the padded layout's uniforms: each real chunk its twin's, the
        # padding's whatever
        u_p = rng.uniform(2.0 ** -25, 1.0, (n_runs, K, p_nchr, 8)).astype(
            np.float32)
        at = {src: pos for pos, src in p_src[row].items()}
        for pos, src in c_src[row].items():
            r = src[0]
            u_p[r, :, at[src] - r * p_nchr] = u_c[r, :, pos - r * c_nchr]
        got = _run_chunk_list(compact, row, K, n_runs, (NdkT, NwkT), nk, u_c)
        want = _run_chunk_list(padded, row, K, n_runs, (NdkT, NwkT), nk, u_p)
        for a, b in zip(got[:3], want[:3]):
            np.testing.assert_array_equal(a, b)
        for pos, src in c_src[row].items():
            np.testing.assert_array_equal(got[3][pos], want[3][at[src]])
        assert got[0].sum() == NdkT.sum() and got[1].sum() == NwkT.sum()
        moved += int((got[3] != compact[2][row]).sum())
    assert moved > len(doc) // 2  # and the chain was sampled


def test_unvisited_tiles_keep_their_counts():
    """Both tables alias the kernel's outputs: a run visits one doc tile
    and the word tiles its chunks name, and every other block comes back
    as it went in — no coverage chunk needed for an empty tile."""
    import jax.numpy as jnp

    from harp_tpu.ops.lda_kernel import cgs_run_update, pack_chunk_meta

    K, rng = 8, np.random.default_rng(2)
    NdkT = rng.integers(1, 30, (K, 24)).astype(np.float32)
    NwkT = rng.integers(1, 30, (K, 32)).astype(np.float32)
    # run 1 (doc tile 1): two chunks at word tile 2, a no-op behind them
    cd = np.full((3, 8), 8, np.int32)
    cw = np.full((3, 8), 8, np.int32)
    cd[0], cw[0] = rng.integers(0, 8, 8), rng.integers(0, 8, 8)
    cd[1, :3], cw[1, :3] = rng.integers(0, 8, 3), rng.integers(0, 8, 3)
    z = rng.integers(0, K, (3, 8)).astype(np.int32)
    meta = pack_chunk_meta([2, 2, 2], [False, False, True])
    Ndk2, Nwk2, z_new, dnk = (np.asarray(a) for a in cgs_run_update(
        jnp.asarray(NdkT), jnp.asarray(NwkT), jnp.asarray(NwkT.sum(1)),
        jnp.asarray(z), jnp.asarray(cd), jnp.asarray(cw), jnp.asarray(meta),
        1, jnp.array([4, 2], jnp.int32), alpha=0.5, beta=0.1, vbeta=3.2,
        d_tile=8, w_tile=8, interpret=True))
    doc_tile, word_tile = slice(8, 16), slice(16, 24)
    for new, old, sl in ((Ndk2, NdkT, doc_tile), (Nwk2, NwkT, word_tile)):
        keep = np.ones(old.shape[1], bool)
        keep[sl] = False
        np.testing.assert_array_equal(new[:, keep], old[:, keep])
        np.testing.assert_array_equal(new[:, sl].sum(1) - old[:, sl].sum(1),
                                      dnk)
    assert (z_new[:2][cd[:2] < 8] != z[:2][cd[:2] < 8]).any()
    np.testing.assert_array_equal(z_new[cd == 8], z[cd == 8])  # pads, no-op
