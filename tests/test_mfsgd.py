"""MF-SGD golden tests: deterministic equivalence vs a numpy model of the
same rotation schedule, plus convergence on synthetic low-rank data."""

import numpy as np
import pytest

from harp_tpu.models import mfsgd as MF

N = 8


def numpy_rotation_epoch(W, H, blocks, n, chunk, lr, reg):
    """Exact replica of one device epoch (pipelined half-slice schedule):
    at step t worker w trains half-slice 2*((w-t//2)%n) (t even) or
    2*((w-t//2-1)%n)+1 (t odd); computing halves are disjoint across
    workers at every step, so this sequential order equals the parallel one."""
    bu, bi, bv, bm, u_bound, ib2 = blocks
    ns = 2 * n
    bu = bu.reshape(n, ns, -1)
    bi = bi.reshape(n, ns, -1)
    bv = bv.reshape(n, ns, -1)
    bm = bm.reshape(n, ns, -1)
    se = cnt = 0.0
    for t in range(ns):
        for w in range(n):
            if t % 2 == 0:
                s = 2 * ((w - t // 2) % n)
            else:
                s = 2 * ((w - t // 2 - 1) % n) + 1
            Wv = W[w * u_bound:(w + 1) * u_bound]
            Hv = H[s * ib2:(s + 1) * ib2]
            B = bu.shape[-1]
            for lo in range(0, B, chunk):
                sl = slice(lo, lo + chunk)
                u, i, v, m = bu[w, s, sl], bi[w, s, sl], bv[w, s, sl], bm[w, s, sl]
                wu, hi = Wv[u], Hv[i]
                err = m * (v - (wu * hi).sum(-1))
                gw = err[:, None] * hi - reg * m[:, None] * wu
                gh = err[:, None] * wu - reg * m[:, None] * hi
                np.add.at(Wv, u, lr * gw)
                np.add.at(Hv, i, lr * gh)
                se += (err ** 2).sum()
                cnt += m.sum()
    return W, H, np.sqrt(se / max(cnt, 1))


def test_partition_ratings_small_data_does_not_pad_to_chunk(mesh):
    """Blocks narrower than chunk pad to the real max block size, not chunk."""
    rng = np.random.default_rng(1)
    nnz = 200
    u = rng.integers(0, 64, nnz).astype(np.int32)
    i = rng.integers(0, 48, nnz).astype(np.int32)
    v = rng.normal(size=nnz).astype(np.float32)
    bu, *_ = MF.partition_ratings(u, i, v, 64, 48, N, 32768)
    assert bu.shape[1] <= max(8, -(-nnz // 8) * 8)  # not 32768

    # non-multiple-of-8 chunk with bmax just below it: sublane alignment
    # must not overshoot chunk (device reshape needs B % min(chunk, B) == 0)
    u97 = np.zeros(97, np.int32)
    i97 = np.arange(97, dtype=np.int32) % 3
    b97, *_ = MF.partition_ratings(u97, i97, np.ones(97, np.float32),
                                   64, 48, N, 100)
    B = b97.shape[1]
    assert B % min(100, B) == 0

    # and training still works at the clamped width (single sub-chunk scan)
    model = MF.MFSGD(64, 48, MF.MFSGDConfig(rank=4, algo="scatter"), mesh=mesh)
    model.set_ratings(u, i, v)
    r0 = model.train_epoch()
    for _ in range(3):
        r = model.train_epoch()
    assert r < r0  # converging, not corrupted


def test_partition_ratings_roundtrip():
    rng = np.random.default_rng(0)
    nnz, n_users, n_items = 500, 64, 48
    u = rng.integers(0, n_users, nnz).astype(np.int32)
    i = rng.integers(0, n_items, nnz).astype(np.int32)
    v = rng.normal(size=nnz).astype(np.float32)
    bu, bi, bv, bm, ub, ib = MF.partition_ratings(u, i, v, n_users, n_items, N, 32)
    assert bm.sum() == nnz  # every rating lands in exactly one block
    ns = 2 * N
    # reconstruct global ids and check the multiset of triples survives
    bu2 = bu.reshape(N, ns, -1)
    bi2 = bi.reshape(N, ns, -1)
    got = []
    for w in range(N):
        for s in range(ns):
            mask = bm.reshape(N, ns, -1)[w, s] > 0
            got += list(zip(
                (bu2[w, s][mask] + w * ub).tolist(),
                (bi2[w, s][mask] + s * ib).tolist(),
                bv.reshape(N, ns, -1)[w, s][mask].tolist(),
            ))
    expect = sorted(zip(u.tolist(), i.tolist(), v.tolist()))
    assert sorted(got) == expect


def test_epoch_matches_numpy_model(mesh):
    rng = np.random.default_rng(1)
    n_users, n_items, nnz, rank, chunk = 64, 48, 600, 4, 16
    u = rng.integers(0, n_users, nnz).astype(np.int32)
    i = rng.integers(0, n_items, nnz).astype(np.int32)
    v = rng.normal(size=nnz).astype(np.float32)

    cfg = MF.MFSGDConfig(rank=rank, chunk=chunk, lr=0.02, reg=0.01, algo="scatter")
    model = MF.MFSGD(n_users, n_items, cfg, mesh, seed=3)
    W0 = np.asarray(model.W).copy()
    H0 = np.asarray(model.H).copy()
    model.set_ratings(u, i, v)
    rmse = model.train_epoch()

    blocks = MF.partition_ratings(u, i, v, n_users, n_items, N, chunk)
    Wr, Hr, rmse_ref = numpy_rotation_epoch(
        W0.copy(), H0.copy(), blocks, N, chunk, cfg.lr, cfg.reg
    )
    np.testing.assert_allclose(np.asarray(model.W), Wr, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(model.H), Hr, rtol=2e-4, atol=2e-5)
    assert abs(rmse - rmse_ref) < 1e-3


def test_convergence_on_low_rank(mesh):
    n_users, n_items, nnz = 256, 192, 20_000
    u, i, v = MF.synthetic_ratings(n_users, n_items, nnz, rank=4, noise=0.01, seed=0)
    cfg = MF.MFSGDConfig(rank=8, chunk=512, lr=0.05, reg=0.002, algo="scatter")
    model = MF.MFSGD(n_users, n_items, cfg, mesh, seed=0)
    model.set_ratings(u, i, v)
    first = model.train_epoch()
    last = None
    for _ in range(15):
        last = model.train_epoch()
    assert last < 0.55 * first, (first, last)
    # held-out-ish check: prediction RMSE approaches the noise floor scale
    assert model.predict_rmse(u, i, v) < 0.2


def test_second_epoch_slices_home(mesh):
    """H slices must be back home after each epoch (factors() correctness):
    running two epochs must keep improving, which fails if slices misalign."""
    u, i, v = MF.synthetic_ratings(128, 96, 6_000, rank=4, noise=0.0, seed=2)
    cfg = MF.MFSGDConfig(rank=8, chunk=256, lr=0.05, reg=0.0, algo="scatter")
    model = MF.MFSGD(128, 96, cfg, mesh, seed=1)
    model.set_ratings(u, i, v)
    r1 = model.train_epoch()
    r5 = None
    for _ in range(6):
        r5 = model.train_epoch()
    assert r5 < r1


# -- dense (one-hot MXU tile) algo ------------------------------------------

def numpy_dense_epoch(W, H, tiles, n, u_tile, i_tile, lr, reg):
    """Numpy replica of the dense algo's epoch: same half-slice rotation
    schedule, per-entry batched tile updates with duplicate gradients
    summed (what the one-hot matmuls compute)."""
    eu, ei, ev, ou, oi, u_own, i_own, u_bound, ib2 = tiles
    ns = 2 * n
    NE, C = eu.shape[1], eu.shape[2]
    eu = eu.reshape(n, ns, NE, C); ei = ei.reshape(n, ns, NE, C)
    ev = ev.reshape(n, ns, NE, C)
    ou = ou.reshape(n, ns, NE); oi = oi.reshape(n, ns, NE)
    se = cnt = 0.0
    for t in range(ns):
        for w in range(n):
            s = 2 * ((w - t // 2) % n) if t % 2 == 0 else \
                2 * ((w - t // 2 - 1) % n) + 1
            Wv = W[w * u_bound:(w + 1) * u_bound]
            Hv = H[s * ib2:(s + 1) * ib2]
            for e in range(NE):
                cu, ci, cv = eu[w, s, e], ei[w, s, e], ev[w, s, e]
                m = (cu < u_tile).astype(np.float32)
                Wb = Wv[ou[w, s, e]:ou[w, s, e] + u_tile]
                Hb = Hv[oi[w, s, e]:oi[w, s, e] + i_tile]
                wu = np.where(m[:, None] > 0, Wb[np.minimum(cu, u_tile - 1)], 0.0)
                hi = np.where(m[:, None] > 0, Hb[np.minimum(ci, i_tile - 1)], 0.0)
                err = m * (cv - (wu * hi).sum(-1))
                gw = err[:, None] * hi - reg * m[:, None] * wu
                gh = err[:, None] * wu - reg * m[:, None] * hi
                gW = np.zeros_like(Wb); gH = np.zeros_like(Hb)
                valid = m > 0
                np.add.at(gW, cu[valid], gw[valid])
                np.add.at(gH, ci[valid], gh[valid])
                Wb += lr * gW
                Hb += lr * gH
                se += (err ** 2).sum()
                cnt += m.sum()
    return W, H, np.sqrt(se / max(cnt, 1))


def test_partition_ratings_tiles_roundtrip():
    rng = np.random.default_rng(0)
    nnz, n_users, n_items = 700, 64, 48
    u = rng.integers(0, n_users, nnz).astype(np.int32)
    i = rng.integers(0, n_items, nnz).astype(np.int32)
    v = rng.normal(size=nnz).astype(np.float32)
    eu, ei, ev, ou, oi, uo, io, ub, ib2 = MF.partition_ratings_tiles(
        u, i, v, n_users, n_items, N, u_tile=8, i_tile=8, entry_cap=16)
    ns = 2 * N
    got = []
    for ws in range(N * ns):
        w, s = ws // ns, ws % ns
        for e in range(eu.shape[1]):
            mask = eu[ws, e] < 8
            got += list(zip(
                (eu[ws, e][mask] + ou[ws, e] + w * uo).tolist(),
                (ei[ws, e][mask] + oi[ws, e] + s * io).tolist(),
                ev[ws, e][mask].tolist(),
            ))
    assert sorted(got) == sorted(zip(u.tolist(), i.tolist(), v.tolist()))


def test_dense_epoch_matches_numpy_model(mesh):
    import jax.numpy as jnp

    rng = np.random.default_rng(5)
    n_users, n_items, nnz = 64, 48, 600
    u = rng.integers(0, n_users, nnz).astype(np.int32)
    i = rng.integers(0, n_items, nnz).astype(np.int32)
    v = rng.normal(size=nnz).astype(np.float32)

    cfg = MF.MFSGDConfig(rank=4, algo="dense", u_tile=8, i_tile=8,
                         entry_cap=16, compute_dtype=jnp.float32,
                         lr=0.02, reg=0.01)
    model = MF.MFSGD(n_users, n_items, cfg, mesh, seed=3)
    W0 = np.asarray(model.W).copy()
    H0 = np.asarray(model.H).copy()
    model.set_ratings(u, i, v)
    rmse = model.train_epoch()

    tiles = MF.partition_ratings_tiles(u, i, v, n_users, n_items, N,
                                       u_tile=8, i_tile=8, entry_cap=16)
    Wr, Hr, rmse_ref = numpy_dense_epoch(
        W0.copy(), H0.copy(), tiles, N, 8, 8, cfg.lr, cfg.reg)
    np.testing.assert_allclose(np.asarray(model.W), Wr, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(model.H), Hr, rtol=2e-4, atol=2e-5)
    assert abs(rmse - rmse_ref) < 1e-3


def test_defaults_are_the_measured_winners():
    """The defaults follow what was measured (1x v5e, 2026-08-01): the
    fused tile kernel is the default; carry_w, within noise in its A/B,
    stays off."""
    cfg = MF.MFSGDConfig()
    assert cfg.algo == "pallas"
    assert cfg.carry_w is False


def test_carry_w_bit_identical_chain(mesh):
    """carry_w=True (the LDA carry_db lever on MF-SGD's dense path)
    shares the entry core with the slice-per-entry path, so the trained
    factors — same ratings, same seed — must be BIT-identical.  More
    users than one u_tile per worker so real tou changes exercise the
    flush/load cond."""
    import jax.numpy as jnp

    rng = np.random.default_rng(8)
    n_users, n_items, nnz = 8 * 24, 48, 2000
    u = rng.integers(0, n_users, nnz).astype(np.int32)
    i = rng.integers(0, n_items, nnz).astype(np.int32)
    v = rng.normal(size=nnz).astype(np.float32)
    out = {}
    for carry in (False, True):
        cfg = MF.MFSGDConfig(rank=4, algo="dense", u_tile=8, i_tile=8,
                             entry_cap=16, compute_dtype=jnp.float32,
                             lr=0.02, reg=0.01, carry_w=carry)
        m = MF.MFSGD(n_users, n_items, cfg, mesh, seed=3)
        m.set_ratings(u, i, v)
        rm = m.train_epochs(3)
        out[carry] = (np.asarray(m.W), np.asarray(m.H), np.asarray(rm))
    np.testing.assert_array_equal(out[True][0], out[False][0])
    np.testing.assert_array_equal(out[True][1], out[False][1])
    np.testing.assert_array_equal(out[True][2], out[False][2])


def test_carry_w_exact_for_overlapping_tile_offsets():
    """Pin the ADVICE r4 fix: the carry switch flushes the old tile BEFORE
    slicing the new region, so carry vs slice-per-entry stays bit-identical
    even for OVERLAPPING (non-tile-aligned) offsets no current partitioner
    emits.  Reverting to slice-before-flush makes offset 4 read rows 4..7
    stale after the offset-0 run updated them, and this fails."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(11)
    UR = IR = 8
    cap = 4
    W0 = rng.normal(size=(24, 3)).astype(np.float32)
    H0 = rng.normal(size=(16, 3)).astype(np.float32)
    # u-runs at offsets 0 → 4 → 0: both transitions overlap the prior tile
    ou = np.array([0, 0, 4, 4, 0], np.int32)
    oi = np.array([0, 8, 0, 8, 0], np.int32)
    eu = rng.integers(0, UR, (5, cap)).astype(np.int32)
    ei = rng.integers(0, IR, (5, cap)).astype(np.int32)
    ev = rng.normal(size=(5, cap)).astype(np.float32)
    block = (jnp.asarray(eu), jnp.asarray(ei), jnp.asarray(ev),
             jnp.asarray(ou), jnp.asarray(oi))
    out = {}
    for carry in (False, True):
        cfg = MF.MFSGDConfig(rank=3, algo="dense", u_tile=UR, i_tile=IR,
                             entry_cap=cap, compute_dtype=jnp.float32,
                             lr=0.05, reg=0.01, carry_w=carry)
        W, H, se, cnt = jax.jit(
            lambda W, H, b: MF._tile_block_update(W, H, b, cfg))(
            jnp.asarray(W0), jnp.asarray(H0), block)
        out[carry] = (np.asarray(W), np.asarray(H),
                      float(se), float(cnt))
    np.testing.assert_array_equal(out[True][0], out[False][0])
    np.testing.assert_array_equal(out[True][1], out[False][1])
    assert out[True][2:] == out[False][2:]


def test_carry_w_rejects_non_dense_algos():
    import pytest

    with pytest.raises(ValueError, match="carry_w"):
        MF.MFSGDConfig(algo="scatter", carry_w=True)
    with pytest.raises(ValueError, match="carry_w"):
        MF.MFSGDConfig(algo="pallas", carry_w=True)


def test_dense_matches_scatter_convergence(mesh):
    """Same data, same seed: both algos must converge to the same ballpark
    (they batch differently, so trajectories differ only slightly)."""
    import jax.numpy as jnp

    u, i, v = MF.synthetic_ratings(200, 150, 8_000, rank=4, noise=0.01, seed=0)
    finals = {}
    for algo in ("dense", "scatter"):
        cfg = MF.MFSGDConfig(rank=8, lr=0.05, reg=0.002, algo=algo,
                             u_tile=16, i_tile=16, entry_cap=64, chunk=64,
                             compute_dtype=jnp.float32)
        m = MF.MFSGD(200, 150, cfg, mesh, seed=0)
        m.set_ratings(u, i, v)
        for _ in range(8):
            r = m.train_epoch()
        finals[algo] = r
    assert abs(finals["dense"] - finals["scatter"]) < 0.05, finals


def test_dense_ownership_stays_balanced():
    """Tile rounding must not change worker placement: with
    ceil(n_users/N) < u_tile every rating would otherwise land on worker 0."""
    rng = np.random.default_rng(2)
    nnz = 4000
    u = rng.integers(0, 512, nnz).astype(np.int32)
    i = rng.integers(0, 256, nnz).astype(np.int32)
    v = rng.normal(size=nnz).astype(np.float32)
    eu, *_ = MF.partition_ratings_tiles(
        u, i, v, 512, 256, N, u_tile=512, i_tile=512, entry_cap=2048)
    per_worker = (eu.reshape(N, -1) < 512).sum(axis=1)
    assert (per_worker > 0).all(), per_worker  # every worker owns ratings
    assert per_worker.max() < 2 * per_worker.min(), per_worker


def test_dense_factors_strip_storage_padding(mesh):
    """factors() must cut the per-range tile padding, not just the tail."""
    import jax.numpy as jnp

    u, i, v = MF.synthetic_ratings(100, 70, 2_000, rank=3, seed=4)
    cfg = MF.MFSGDConfig(rank=4, u_tile=8, i_tile=8, entry_cap=32,
                         compute_dtype=jnp.float32, lr=0.05)
    m = MF.MFSGD(100, 70, cfg, mesh, seed=0)
    m.set_ratings(u, i, v)
    m.train_epoch()
    W, H = m.factors()
    assert W.shape == (100, 4) and H.shape == (70, 4)
    # predict_rmse goes through factors(); a misaligned strip would blow it up
    assert m.predict_rmse(u, i, v) < 2.0


def test_resume_rejects_mismatched_checkpoint_shapes(mesh, tmp_path):
    """A checkpoint from a different algo/tile config must refuse to resume
    (dynamic slices would clamp and silently train wrong rows)."""
    u, i, v = MF.synthetic_ratings(64, 48, 500, rank=2, seed=0)
    ckpt = str(tmp_path / "mf")
    m1 = MF.MFSGD(64, 48, MF.MFSGDConfig(rank=4, algo="scatter"), mesh, seed=0)
    m1.set_ratings(u, i, v)
    m1.fit(2, ckpt, ckpt_every=1)

    m2 = MF.MFSGD(64, 48, MF.MFSGDConfig(rank=4, algo="dense", u_tile=16,
                                         i_tile=16), mesh, seed=0)
    m2.set_ratings(u, i, v)
    with pytest.raises(ValueError, match="checkpoint shapes"):
        m2.fit(2, ckpt, ckpt_every=1)
