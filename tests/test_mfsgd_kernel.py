"""Fused Pallas MF-SGD kernel (ops/mfsgd_kernel.py) vs the XLA dense algo.

The kernel promises the SAME update order as ``algo="dense"`` — these
tests pin equivalence through the full rotation epoch on the 8-worker
mesh (interpret mode on CPU), plus the host-prep contract the kernel's
W-block streaming depends on.
"""

import numpy as np
import pytest

from harp_tpu.models import mfsgd as MF
from harp_tpu.ops import mfsgd_kernel as MF_K
from harp_tpu.ops.mfsgd_kernel import insert_coverage_entries

N = 8


def _cfg(algo, **kw):
    import jax.numpy as jnp

    base = dict(rank=4, u_tile=8, i_tile=8, entry_cap=16,
                compute_dtype=jnp.float32, lr=0.02, reg=0.01)
    base.update(kw)
    return MF.MFSGDConfig(algo=algo, **base)


def _run_epochs(mesh, algo, u, i, v, n_users, n_items, epochs=1, **kw):
    m = MF.MFSGD(n_users, n_items, _cfg(algo, **kw), mesh, seed=3)
    m.set_ratings(u, i, v)
    rmses = [m.train_epoch() for _ in range(epochs)]
    return np.asarray(m.W), np.asarray(m.H), rmses


def test_pallas_epoch_matches_dense(mesh):
    rng = np.random.default_rng(5)
    n_users, n_items, nnz = 64, 48, 600
    u = rng.integers(0, n_users, nnz).astype(np.int32)
    i = rng.integers(0, n_items, nnz).astype(np.int32)
    v = rng.normal(size=nnz).astype(np.float32)

    Wd, Hd, rd = _run_epochs(mesh, "dense", u, i, v, n_users, n_items, 2)
    Wp, Hp, rp = _run_epochs(mesh, "pallas", u, i, v, n_users, n_items, 2)
    np.testing.assert_allclose(Wp, Wd, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(Hp, Hd, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(rp, rd, rtol=1e-5)


def test_pallas_multi_epoch_program_matches_dense(mesh):
    """train_epochs (one scanned device program) through the kernel."""
    u, i, v = MF.synthetic_ratings(96, 64, 3000, rank=4, noise=0.05, seed=1)
    out = {}
    for algo in ("dense", "pallas"):
        m = MF.MFSGD(96, 64, _cfg(algo), mesh, seed=0)
        m.set_ratings(u, i, v)
        out[algo] = (m.train_epochs(3), np.asarray(m.W))
    np.testing.assert_allclose(out["pallas"][0], out["dense"][0], rtol=1e-4)
    np.testing.assert_allclose(out["pallas"][1], out["dense"][1],
                               rtol=1e-4, atol=1e-5)


def test_pallas_multi_chunk_entries_match_dense(mesh):
    """C > chunk_c=512 drives the chunk axis of the kernel's 2-D grid
    through multiple steps — the path the full-scale ML-20M config
    (C=2048) runs; a chunk-slicing bug passes the small-entry tests but
    corrupts factors only at scale."""
    rng = np.random.default_rng(11)
    # all ratings in ONE (worker, slice, tile) cell (n_items=128 → 8 items
    # per half-slice, so i<8 is slice 0 / tile 0) → one entry holding 600
    # ratings, padded to C=1024 by insert_coverage_entries → 2 chunks
    n_users, n_items, nnz = 8 * 8, 128, 600
    u = rng.integers(0, 8, nnz).astype(np.int32)  # worker 0, tile 0
    i = rng.integers(0, 8, nnz).astype(np.int32)
    v = rng.normal(size=nnz).astype(np.float32)

    kw = dict(entry_cap=1024)
    Wd, Hd, rd = _run_epochs(mesh, "dense", u, i, v, n_users, n_items, **kw)
    Wp, Hp, rp = _run_epochs(mesh, "pallas", u, i, v, n_users, n_items, **kw)
    # the prep must actually have produced a multi-chunk entry
    eu, ei, ev, ou, oi, *_ = MF.partition_ratings_tiles(
        u, i, v, n_users, n_items, N, 8, 8, 1024)
    assert insert_coverage_entries(eu, ei, ev, ou, oi, 8, 8)[0].shape[-1] \
        > 512
    np.testing.assert_allclose(Wp, Wd, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(Hp, Hd, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(rp, rd, rtol=1e-5)


def test_pallas_unvisited_w_blocks_pass_through(mesh):
    """W blocks with zero ratings must come out bit-identical, not garbage
    (the kernel writes every output block only because host prep inserts
    coverage entries — this is the test that breaks if that contract
    does)."""
    rng = np.random.default_rng(7)
    n_users, n_items, nnz = 128, 16, 200
    u = rng.integers(0, 8, nnz).astype(np.int32)  # only block 0 per worker
    i = rng.integers(0, n_items, nnz).astype(np.int32)
    v = rng.normal(size=nnz).astype(np.float32)

    m = MF.MFSGD(n_users, n_items, _cfg("pallas"), mesh, seed=9)
    W0 = np.asarray(m.W).copy()
    m.set_ratings(u, i, v)
    m.train_epoch()
    W1 = np.asarray(m.W)
    u_bound = m.u_bound
    touched = np.zeros(len(W1), bool)
    for w in range(N):
        lo = w * u_bound
        touched[lo:lo + 8] = True  # block 0 of each worker's range
    np.testing.assert_array_equal(W1[~touched], W0[~touched])
    assert not np.allclose(W1[:8], W0[:8])  # block 0 did train


def test_insert_coverage_entries_contract():
    rng = np.random.default_rng(3)
    nnz, n_users, n_items, u_tile, i_tile = 400, 64, 48, 8, 8
    u = rng.integers(0, 16, nnz).astype(np.int32)  # leaves blocks empty
    i = rng.integers(0, n_items, nnz).astype(np.int32)
    v = rng.normal(size=nnz).astype(np.float32)
    eu, ei, ev, ou, oi, uo, io, ub, ib2 = MF.partition_ratings_tiles(
        u, i, v, n_users, n_items, N, u_tile, i_tile, 16)
    eu2, ei2, ev2, ou2, oi2 = insert_coverage_entries(
        eu, ei, ev, ou, oi, ub, u_tile)

    nblk = ub // u_tile
    for w in range(eu2.shape[0]):
        blks = ou2[w] // u_tile
        # coverage: every W block appears
        assert set(range(nblk)) <= set(blks.tolist())
        # contiguity: each block id is one contiguous run
        change = np.flatnonzero(np.diff(blks) != 0)
        assert len(set(blks.tolist())) == len(change) + 1
        # the real ratings survive with their values
        real2 = ev2[w][eu2[w] < u_tile]
        real1 = ev[w][eu[w] < u_tile]
        np.testing.assert_array_equal(np.sort(real2), np.sort(real1))


def test_insert_coverage_pads_c_to_chunk_multiple():
    rng = np.random.default_rng(4)
    eu = rng.integers(0, 8, (2, 3, 520)).astype(np.int32)
    ei = rng.integers(0, 8, (2, 3, 520)).astype(np.int32)
    ev = rng.normal(size=(2, 3, 520)).astype(np.float32)
    ou = np.zeros((2, 3), np.int32)
    oi = np.zeros((2, 3), np.int32)
    eu2, *_ = insert_coverage_entries(eu, ei, ev, ou, oi, 8, 8, chunk_c=512)
    assert eu2.shape[-1] % 512 == 0


def test_pallas_rejects_oversized_resident_h():
    import jax.numpy as jnp

    from harp_tpu.ops.mfsgd_kernel import sgd_tile_update

    Wt = jnp.zeros((8, 128), jnp.float32)
    Ht = jnp.zeros((8, 1 << 19), jnp.float32)  # 16 MB half-slice
    e = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="VMEM budget"):
        sgd_tile_update(Wt, Ht, e, e, e.astype(jnp.float32),
                        jnp.zeros(1, jnp.int32), jnp.zeros(1, jnp.int32),
                        lr=0.1, reg=0.0, u_tile=128, i_tile=128,
                        interpret=True)


@pytest.mark.parametrize("shape", [
    # (R, UB, IB, NE, C, tile) — graded ML-20M tiling, the REAL smoke
    # shapes the driver bench compiles FIRST on real TPU (captured from
    # the smoke bench: C=200 pads to 256 by insert_coverage_entries'
    # 128-multiple rule), and the 8-worker-sim smoke shape
    (64, 2048, 13440, 8, 2048, 256),  # DEFAULT tiles since the
                                      # 2026-08-01 sweep (250.2M@256)
    (64, 2048, 13440, 8, 2048, 512),  # explicit 512 stays supported
    (8, 512, 128, 2, 256, 128),    # 1-worker TPU smoke (u_bound=512)
    (8, 128, 128, 1, 256, 128),    # 8-worker sim smoke (u_bound=128)
])
def test_kernel_lowers_for_tpu(shape):
    """Cross-platform lowering runs the Pallas->Mosaic verification
    (layouts, block shapes, casts) without hardware — the check that
    caught the [1, C]-block constraint before any chip time was spent."""
    import functools

    import jax
    import jax.numpy as jnp

    R, UB, IB, NE, C, tile = shape
    f = functools.partial(MF_K.sgd_tile_update, lr=0.01, reg=0.05,
                          u_tile=tile, i_tile=tile, interpret=False)
    lowered = jax.jit(f).trace(
        jnp.zeros((R, UB)), jnp.zeros((R, IB)),
        jnp.zeros((NE, C), jnp.int32), jnp.zeros((NE, C), jnp.int32),
        jnp.zeros((NE, C)), jnp.zeros(NE, jnp.int32),
        jnp.zeros(NE, jnp.int32)).lower(lowering_platforms=("tpu",))
    assert "tpu_custom_call" in lowered.as_text()


def test_ml20m_pallas_epoch_lowers_for_tpu(mesh, monkeypatch):
    """The fused-kernel ML-20M epoch (138,493×26,744 grid, rank 64,
    the auto-resolved default tiles — 256×256 since the 2026-08-01
    sweep — 8-way mesh), MOSAIC-compiled, lowers for TPU on this CPU
    host — transposes, rotation scan, scalar-prefetch grids and the
    kernel itself at the true graded shapes."""
    import jax
    import jax.numpy as jnp

    monkeypatch.setenv("HARP_PALLAS_FORCE_MOSAIC", "1")
    cfg = MF.MFSGDConfig(rank=64, algo="pallas")
    n, ns = 8, 16
    _, _, u_bound, ib2 = MF._dense_bounds(
        138_493, 26_744, n, ns, *MF.tiles(cfg))
    NE, C = 96, 2048  # ~20M ratings / (n·ns) rows at C=2048 + coverage
    i32, f32 = jnp.int32, jnp.float32
    shapes = [((u_bound * n, 64), f32), ((2 * ib2 * n, 64), f32),
              ((n * ns, NE, C), i32), ((n * ns, NE, C), i32),
              ((n * ns, NE, C), f32), ((n * ns, NE), i32),
              ((n * ns, NE), i32)]
    sds = [jax.ShapeDtypeStruct(s, d, sharding=mesh.sharding(mesh.spec(0)))
           for s, d in shapes]
    fn = MF.make_multi_epoch_fn(mesh, cfg, epochs=2)
    text = fn.trace(*sds).lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text  # the Mosaic kernel is in the program


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
    return settings(max_examples=40, deadline=None)(given(
        nnz=st.integers(1, 300),
        n_users=st.sampled_from([16, 40, 64]),
        n_items=st.sampled_from([16, 48]),
        u_tile=st.sampled_from([8, 16]),
        entry_cap=st.sampled_from([8, 16, 64]),
        seed=st.integers(0, 2**31 - 1),
    )(fn))


@_property_case
def test_insert_coverage_entries_properties(nnz, n_users, n_items,
                                            u_tile, entry_cap, seed):
    """The kernel's streaming correctness rests on this host prep: for
    ANY rating set — coverage (every W block appears), contiguity (one
    run per block), value preservation (real ratings survive exactly
    once), C a 128-multiple (the Mosaic lane gate), and in-bounds
    offsets."""
    rng = np.random.default_rng(seed)
    u = rng.integers(0, n_users, nnz).astype(np.int32)
    i = rng.integers(0, n_items, nnz).astype(np.int32)
    v = rng.normal(size=nnz).astype(np.float32)
    eu, ei, ev, ou, oi, uo, io, ub, ib2 = MF.partition_ratings_tiles(
        u, i, v, n_users, n_items, N, u_tile, u_tile, entry_cap)
    eu2, ei2, ev2, ou2, oi2 = insert_coverage_entries(
        eu, ei, ev, ou, oi, ub, u_tile)

    nblk = ub // u_tile
    assert eu2.shape[-1] % 128 == 0          # Mosaic lane gate, any size
    for w in range(eu2.shape[0]):
        blks = ou2[w] // u_tile
        assert set(range(nblk)) <= set(blks.tolist())          # coverage
        change = np.flatnonzero(np.diff(blks) != 0)
        assert len(set(blks.tolist())) == len(change) + 1      # contiguity
        assert (ou2[w] >= 0).all() and (ou2[w] + u_tile <= ub).all()
        assert (oi2[w] >= 0).all() and (oi2[w] + u_tile <= ib2).all()
        # every real rating survives exactly once, with its value
        real2 = np.sort(ev2[w][eu2[w] < u_tile])
        real1 = np.sort(ev[w][eu[w] < u_tile])
        np.testing.assert_array_equal(real2, real1)
