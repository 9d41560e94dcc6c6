"""Fused Pallas MF-SGD kernel (ops/mfsgd_kernel.py) vs the XLA dense algo.

The kernel promises the SAME update order as ``algo="dense"`` — these
tests pin equivalence through the full rotation epoch on the 8-worker
mesh (interpret mode on CPU), plus the host-prep contract the kernel's
W-block streaming depends on: the chunk list, the only layout it runs.
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
    """An entry of more than chunk_c=512 ratings spans several steps of
    the kernel's chunk grid — the path the full-scale ML-20M config
    runs; a chunk-slicing bug passes the small-entry tests but corrupts
    factors only at scale."""
    rng = np.random.default_rng(11)
    # all ratings in ONE (worker, slice, tile) cell (n_items=128 → 8 items
    # per half-slice, so i<8 is slice 0 / tile 0) → one entry holding 600
    # ratings, staged by insert_coverage_entries as 2 chunks of 512
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
    cu, _, _, meta = insert_coverage_entries(eu, ei, ev, ou, oi, 8, 8, 8)
    _, _, opens, closes = MF_K.unpack_chunk_meta(meta)
    assert cu.shape[-1] == 512 and (opens & ~closes).sum() == 1
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


def _chunk_list_loops(eu, ei, ev, ou, oi, u_bound, u_tile, i_tile,
                      chunk_c=512, every_chunk=False):
    """``insert_coverage_entries`` the slow way, one Python step a chunk:
    the reference the vectorised pass is held to.  ``every_chunk`` gives
    each entry as many chunks as the widest could need — the padded
    layout, whose extra chunks are all padding.  Also returns each row's
    own length, before the tail no-ops."""
    ws, ne, c = eu.shape
    cc = chunk_c if c > chunk_c else 128 * -(-c // 128)

    def chunk(blk, hti, opens, closes, src=None):
        cu = np.full(cc, u_tile, eu.dtype)
        ci, cv = np.zeros(cc, ei.dtype), np.zeros(cc, ev.dtype)
        if src is not None:
            w, e, lo = src
            n = min(cc, c - lo)
            cu[:n], ci[:n], cv[:n] = (a[w, e, lo:lo + n]
                                      for a in (eu, ei, ev))
        return blk, hti, opens, closes, cu, ci, cv

    rows = []
    for w in range(ws):
        row, last_hti = [], 0
        counts = [(e, int((eu[w, e] < u_tile).sum())) for e in range(ne)]
        for b in range(u_bound // u_tile):
            mine = [(e, n) for e, n in counts
                    if n and ou[w, e] // u_tile == b]
            if not mine:
                row.append(chunk(b, last_hti, True, True))
            for e, n in mine:
                k = -(-c // cc) if every_chunk else -(-n // cc)
                last_hti = int(oi[w, e]) // i_tile
                row += [chunk(b, last_hti, j == 0, j == k - 1,
                              (w, e, j * cc)) for j in range(k)]
        rows.append(row)
    lengths = [len(r) for r in rows]
    for row in rows:
        row += [chunk(row[-1][0], row[-1][1], True, True)] * (
            max(lengths) - len(row))
    blk, hti, opens, closes, cu, ci, cv = (
        np.array([[ch[f] for ch in row] for row in rows]) for f in range(7))
    return (cu, ci, cv, MF_K.pack_chunk_meta(blk, hti, opens, closes),
            lengths)


def _check_chunk_list(entries, u_bound, ib2, u_tile, i_tile, chunk_c=512):
    """Everything the kernel's streaming rests on, for one entry set: the
    vectorised pass equals the loops; every rating sits in exactly one
    slot, in the entries' order; an entry's chunks are adjacent, opened
    once and closed once; every W block appears, as one run; the tail
    no-ops repeat the last offsets; offsets stay in bounds."""
    eu, ei, ev, ou, oi = entries
    got = insert_coverage_entries(eu, ei, ev, ou, oi, u_bound, u_tile,
                                  i_tile, chunk_c)
    *want, lengths = _chunk_list_loops(eu, ei, ev, ou, oi, u_bound, u_tile,
                                       i_tile, chunk_c)
    for g, w_ in zip(got, want):
        assert g.dtype == w_.dtype
        np.testing.assert_array_equal(g, w_)
    cu, ci, cv, meta = got
    blk, hti, opens, closes = MF_K.unpack_chunk_meta(meta)
    np.testing.assert_array_equal(
        MF_K.pack_chunk_meta(blk, hti, opens, closes), meta)   # round trip
    nblk = u_bound // u_tile
    assert cu.shape[-1] % 128 == 0           # Mosaic lane gate, any size
    assert (blk >= 0).all() and (blk < nblk).all()
    assert (hti >= 0).all() and ((hti + 1) * i_tile <= ib2).all()
    for w in range(cu.shape[0]):
        assert set(range(nblk)) <= set(blk[w].tolist())        # coverage
        change = np.flatnonzero(np.diff(blk[w]) != 0)
        assert len(set(blk[w].tolist())) == len(change) + 1    # contiguity
        # every chunk lies inside exactly one entry: opened before it or
        # by it, not yet closed — so opens and closes are set once each
        assert (np.cumsum(opens[w]) - np.cumsum(closes[w]) + closes[w]
                == 1).all()
        start = np.flatnonzero(opens[w])[np.cumsum(opens[w]) - 1]
        assert (blk[w] == blk[w, start]).all()   # and shares its offsets
        assert (hti[w] == hti[w, start]).all()
        # every rating exactly once, in the entries' order
        keep, keep0 = cu[w] < u_tile, eu[w] < u_tile
        for staged, orig in (
                ((blk[w] * u_tile)[:, None] + cu[w], ou[w][:, None] + eu[w]),
                ((hti[w] * i_tile)[:, None] + ci[w], oi[w][:, None] + ei[w]),
                (cv[w], ev[w])):
            np.testing.assert_array_equal(staged[keep], orig[keep0])
        n = lengths[w]   # the tail: all-pad one-chunk entries, offsets kept
        assert not keep[n:].any()
        assert (opens[w, n:] & closes[w, n:]).all()
        assert (blk[w, n:] == blk[w, n - 1]).all()
        assert (hti[w, n:] == hti[w, n - 1]).all()
    return got


def test_insert_coverage_entries_contract():
    rng = np.random.default_rng(3)
    nnz, n_users, n_items, u_tile, i_tile = 400, 64, 48, 8, 8
    u = rng.integers(0, 16, nnz).astype(np.int32)  # leaves blocks empty
    i = rng.integers(0, n_items, nnz).astype(np.int32)
    v = rng.normal(size=nnz).astype(np.float32)
    *entries, uo, io, ub, ib2 = MF.partition_ratings_tiles(
        u, i, v, n_users, n_items, N, u_tile, i_tile, 16)
    cu, _, _, meta = _check_chunk_list(entries, ub, ib2, u_tile, i_tile)
    # entries 16 wide: one 128-lane chunk each, and rows 2.. (users ≥ 16
    # drew no rating) are no-op chunks alone, one a W block
    assert cu.shape[-1] == 128
    assert (cu[2 * 2 * N:] == u_tile).all()


def test_insert_coverage_splits_wide_entries_into_chunks():
    """Entries 520 wide and full: two chunks of 512 each, the second
    holding the last 8 ratings — no entry is widened to 1024."""
    rng = np.random.default_rng(4)
    eu = rng.integers(0, 8, (2, 3, 520)).astype(np.int32)
    ei = rng.integers(0, 8, (2, 3, 520)).astype(np.int32)
    ev = rng.normal(size=(2, 3, 520)).astype(np.float32)
    eu[1, 2, 7:] = 8                       # but one entry holds 7 ratings
    ou = np.zeros((2, 3), np.int32)
    oi = np.zeros((2, 3), np.int32)
    cu, ci, cv, meta = _check_chunk_list((eu, ei, ev, ou, oi), 8, 8, 8, 8)
    assert cu.shape == (2, 6, 512) and meta.shape == (2, 6)
    assert ((cu < 8).sum(-1) == [[512, 8] * 3,
                                 [512, 8, 512, 8, 7, 0]]).all()


def test_chunk_meta_round_trips_and_raises_over_its_bit_budget():
    rng = np.random.default_rng(0)
    blk = rng.integers(0, 1 << MF_K._BLK_BITS, 1000)
    hti = rng.integers(0, 1 << MF_K._HT_BITS, 1000)
    blk[:2], hti[:2] = [0, (1 << MF_K._BLK_BITS) - 1], [(1 << MF_K._HT_BITS)
                                                       - 1, 0]
    opens, closes = rng.random(1000) < 0.5, rng.random(1000) < 0.5
    meta = MF_K.pack_chunk_meta(blk, hti, opens, closes)
    assert meta.dtype == np.int32 and (meta >= 0).all()
    for got, want in zip(MF_K.unpack_chunk_meta(meta),
                         (blk, hti, opens, closes)):
        np.testing.assert_array_equal(got, want)
    with pytest.raises(ValueError, match="W block index"):
        MF_K.pack_chunk_meta([1 << MF_K._BLK_BITS], [0], [True], [True])
    with pytest.raises(ValueError, match="H tile index"):
        MF_K.pack_chunk_meta([0], [1 << MF_K._HT_BITS], [True], [True])
    with pytest.raises(ValueError, match="valid slots must lead"):
        eu = np.array([[[8, 3, 8, 8]]], np.int32)   # a hole before a rating
        insert_coverage_entries(eu, eu, eu.astype(np.float32),
                                np.zeros((1, 1), np.int32),
                                np.zeros((1, 1), np.int32), 8, 8, 8)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_chunk_list_equals_the_fully_padded_list(dtype):
    """Layout equivalence: the kernel on the ragged chunk list against the
    same kernel on the list that gives every entry all C / chunk_c chunks
    (what it ran before the chunk list).  The chunks left out are all
    padding and add exact zeros: W, H, se, cnt bit-identical."""
    import jax.numpy as jnp

    rng = np.random.default_rng(2)
    u_tile = i_tile = 8
    # one worker, two half-slices of 16 items (two H tiles each), four W
    # blocks; (ratings, W block, half-slice, H tile) a tile:
    tiles = [(300, 0, 0, 0), (40, 0, 0, 1), (200, 1, 0, 1), (50, 3, 0, 0),
             (30, 1, 1, 1), (140, 3, 1, 0)]
    u = np.concatenate([b * 8 + rng.integers(0, 8, n)
                        for n, b, _, _ in tiles]).astype(np.int32)
    i = np.concatenate([s * 16 + t * 8 + rng.integers(0, 8, n)
                        for n, _, s, t in tiles]).astype(np.int32)
    v = rng.normal(size=len(u)).astype(np.float32)
    *entries, _, _, ub, ib2 = MF.partition_ratings_tiles(
        u, i, v, 32, 32, 1, u_tile, i_tile, 384)
    assert entries[0].shape == (2, 4, 304)
    ragged = _check_chunk_list(entries, ub, ib2, u_tile, i_tile, 128)
    *padded, _ = _chunk_list_loops(*entries, ub, u_tile, i_tile, 128,
                                   every_chunk=True)
    # row 0: entries of 3, 1, 2 and 1 chunks and W block 2's no-op; row 1:
    # W blocks 0 and 2 empty, entries of 1 and 2 chunks, three tail no-ops
    _, _, opens, closes = MF_K.unpack_chunk_meta(ragged[3])
    assert ragged[0].shape == (2, 8, 128) and padded[0].shape == (2, 13, 128)
    assert (np.diff(np.flatnonzero(np.append(opens[0], True)))
            == [3, 1, 2, 1, 1]).all()
    assert (np.diff(np.flatnonzero(np.append(opens[1], True)))
            == [1, 1, 1, 2, 1, 1, 1]).all()

    Wt = jnp.asarray(rng.uniform(0, 0.5, (4, ub)), jnp.float32)
    Ht = jnp.asarray(rng.uniform(0, 0.5, (4, ib2)), jnp.float32)
    for w in range(2):
        outs = [MF_K.sgd_tile_update(
            Wt, Ht, *(jnp.asarray(a[w]) for a in layout), lr=0.02, reg=0.01,
            u_tile=u_tile, i_tile=i_tile, compute_dtype=jnp.dtype(dtype),
            interpret=True) for layout in (ragged, padded)]
        assert float(outs[0][3]) == sum(n for n, _, s, _ in tiles if s == w)
        assert not np.array_equal(outs[0][0], Wt)
        for a, b_ in zip(*outs):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))


@pytest.mark.parametrize("over", ["resident_h", "chunks", "w_blocks"])
def test_pallas_rejects_shapes_over_budget(over):
    import jax
    import jax.numpy as jnp

    from harp_tpu.ops.mfsgd_kernel import sgd_tile_update

    # shapes alone decide: nothing this large is ever allocated
    R, UB, IB, NCH, match = {
        "resident_h": (8, 128, 1 << 19, 1, "VMEM budget"),   # 16 MB
        "chunks": (8, 128, 128, MF_K._MAX_CHUNKS + 1, "SMEM"),
        "w_blocks": (8, 128 * ((1 << MF_K._BLK_BITS) + 1), 128, 1,
                     "packed chunk metadata"),
    }[over]
    f32, i32 = jnp.float32, jnp.int32
    with pytest.raises(ValueError, match=match):
        jax.eval_shape(
            lambda *a: sgd_tile_update(*a, lr=0.1, reg=0.0, u_tile=128,
                                       i_tile=128, interpret=True),
            *(jax.ShapeDtypeStruct(s, d) for s, d in (
                ((R, UB), f32), ((R, IB), f32), ((NCH, 128), i32),
                ((NCH, 128), i32), ((NCH, 128), f32), ((NCH,), i32))))


@pytest.mark.parametrize("shape", [
    # (R, UB, IB, NCH, chunk, tile) — graded ML-20M tiling, the REAL smoke
    # shapes the driver bench compiles FIRST on real TPU (captured from
    # the smoke bench: C=200 is staged as one 256-wide chunk an entry by
    # insert_coverage_entries' 128-multiple rule), and the 8-worker-sim
    # smoke shape
    (64, 2048, 13440, 32, 512, 256),  # DEFAULT tiles since the
                                      # 2026-08-01 sweep (250.2M@256)
    (64, 2048, 13440, 32, 512, 512),  # explicit 512 stays supported
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

    R, UB, IB, NCH, cc, tile = shape
    f = functools.partial(MF_K.sgd_tile_update, lr=0.01, reg=0.05,
                          u_tile=tile, i_tile=tile, interpret=False)
    lowered = jax.jit(f).trace(
        jnp.zeros((R, UB)), jnp.zeros((R, IB)),
        jnp.zeros((NCH, cc), jnp.int32), jnp.zeros((NCH, cc), jnp.int32),
        jnp.zeros((NCH, cc)), jnp.zeros(NCH, jnp.int32)
    ).lower(lowering_platforms=("tpu",))
    assert "tpu_custom_call" in lowered.as_text()


def test_ml20m_pallas_epoch_lowers_for_tpu(mesh, monkeypatch):
    """The fused-kernel ML-20M epoch (138,493×26,744 grid, rank 64,
    the auto-resolved default tiles — 256×256 since the 2026-08-01
    sweep — 8-way mesh), MOSAIC-compiled, lowers for TPU on this CPU
    host — transposes, rotation scan, scalar-prefetch grids and the
    kernel itself at the true graded shapes.  (The one-chip cell's size,
    ~134k chunks a half-slice, is COMPILED for a v5e in
    tests/test_chip_compile.py, where the SMEM budget bites.)"""
    import jax
    import jax.numpy as jnp

    monkeypatch.setenv("HARP_PALLAS_FORCE_MOSAIC", "1")
    cfg = MF.MFSGDConfig(rank=64, algo="pallas")
    n, ns = 8, 16
    _, _, u_bound, ib2 = MF._dense_bounds(
        138_493, 26_744, n, ns, *MF.tiles(cfg))
    NCH, cc = 384, 512  # ~20M ratings / (n·ns) rows in 512-wide chunks
    i32, f32 = jnp.int32, jnp.float32
    shapes = [((u_bound * n, 64), f32), ((2 * ib2 * n, 64), f32),
              ((n * ns, NCH, cc), i32), ((n * ns, NCH, cc), i32),
              ((n * ns, NCH, cc), f32), ((n * ns, NCH), i32)]
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
        entry_cap=st.sampled_from([8, 16, 64, 288]),
        hot=st.booleans(),
        seed=st.integers(0, 2**31 - 1),
    )(fn))


@_property_case
def test_insert_coverage_entries_properties(nnz, n_users, n_items,
                                            u_tile, entry_cap, hot, seed):
    """The kernel's streaming correctness rests on this host prep: for
    ANY rating set — skewed ones (``hot``: nine ratings in ten on two
    users and two items, so entries run to several 128-wide chunks)
    included — everything :func:`_check_chunk_list` holds it to."""
    rng = np.random.default_rng(seed)
    u = rng.integers(0, n_users, nnz).astype(np.int32)
    i = rng.integers(0, n_items, nnz).astype(np.int32)
    if hot:
        cold = rng.random(nnz) < 0.1
        u, i = np.where(cold, u, u % 2), np.where(cold, i, i % 2)
    v = rng.normal(size=nnz).astype(np.float32)
    *entries, uo, io, ub, ib2 = MF.partition_ratings_tiles(
        u, i, v, n_users, n_items, N, u_tile, u_tile, entry_cap)
    _check_chunk_list(entries, ub, ib2, u_tile, u_tile, chunk_c=128)
