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
