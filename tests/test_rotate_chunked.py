"""Chunked double-buffered rotation + quantized rotate/regroup wire.

The PR-2 overlap layer, pinned four ways:

1. ``rotate_pipeline(n_chunks=1)`` is bit-exact with the pre-chunking
   serial pipeline (compute-then-rotate scan, inlined here as the
   reference);
2. ``n_chunks=2`` reproduces the bespoke two-halves schedule MF-SGD/LDA
   shipped with, bit-for-bit, through an order-sensitive step function
   (the model goldens in test_mfsgd.py pin the same thing end-to-end);
3. any ``n_chunks`` covers every (worker, chunk) pair exactly once, lands
   chunks home, and agrees with ``resident_chunk_index`` — including a
   4-chunk MF-SGD epoch checked against a numpy replica of the
   generalized schedule;
4. the quantized wires round ONCE per hop with a worker-shared scale
   (ring-size-independent error — the property that makes int8 rotation
   better conditioned than int8 allreduce), and the CommLedger accounts
   them at wire width (int8 rotate bytes = ¼ of the f32 baseline).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from harp_tpu.models import lda as L
from harp_tpu.models import mfsgd as MF
from harp_tpu.parallel import collective as C
from harp_tpu.parallel.rotate import (resident_chunk_index,
                                      resident_half_index,
                                      rotate_pipeline)
from harp_tpu.utils import telemetry

N = 8  # simulated workers (conftest)


def run_spmd(mesh, fn, x, in_dim=0, out_dim=0):
    in_spec = mesh.spec(in_dim) if in_dim is not None else P()
    out_spec = mesh.spec(out_dim) if out_dim is not None else P()
    return jax.jit(mesh.shard_map(fn, in_specs=(in_spec,),
                                  out_specs=out_spec))(x)


# -- the pipeline schedule ---------------------------------------------------

def _order_sensitive_step(acc, cur, t):
    """Non-commutative in both carry and chunk: any schedule deviation
    (order, off-by-one, wrong chunk) changes the bits."""
    acc = acc * 1.0001 + cur.sum() * (t + 1).astype(jnp.float32)
    cur = cur * 1.01 + acc * 0.001
    return acc, cur


def test_n_chunks_1_bit_exact_with_serial_pipeline(mesh):
    """n_chunks=1 must be THE pre-chunking pipeline: compute on the whole
    resident slice, then rotate it — same scan, same bits."""
    slices = np.random.default_rng(0).normal(size=(N * 4, 3)).astype(
        np.float32)

    def serial(s):
        def body(state, t):
            c, cur = state
            c, cur = _order_sensitive_step(c, cur, t)
            return (c, C.rotate(cur)), None

        (c, cur), _ = lax.scan(body, (jnp.float32(0.0), s), jnp.arange(N))
        return jnp.concatenate([c[None, None].repeat(cur.shape[1], 1), cur])

    def chunked(s):
        c, cur = rotate_pipeline(_order_sensitive_step, jnp.float32(0.0), s,
                                 n_chunks=1)
        return jnp.concatenate([c[None, None].repeat(cur.shape[1], 1), cur])

    a = np.asarray(run_spmd(mesh, serial, slices))
    b = np.asarray(run_spmd(mesh, chunked, slices))
    np.testing.assert_array_equal(a, b)


def test_n_chunks_2_bit_exact_with_bespoke_two_halves(mesh):
    """The generic 2-chunk pipeline must reproduce the hand-rolled
    computing/inflight half-slice scan (the schedule mfsgd/lda shipped
    with) bit-for-bit."""
    slices = np.random.default_rng(1).normal(size=(N * 8, 3)).astype(
        np.float32)

    def bespoke(s):
        ib2 = s.shape[0] // 2
        computing, inflight = s[:ib2], s[ib2:]

        def body(carry, t):
            c, computing, inflight = carry
            received = C.rotate(inflight)
            c, computing = _order_sensitive_step(c, computing, t)
            return (c, received, computing), None

        (c, computing, inflight), _ = lax.scan(
            body, (jnp.float32(0.0), computing, inflight),
            jnp.arange(2 * N))
        out = jnp.concatenate([computing, inflight], axis=0)
        return jnp.concatenate([c[None, None].repeat(out.shape[1], 1), out])

    def chunked(s):
        c, out = rotate_pipeline(_order_sensitive_step, jnp.float32(0.0), s,
                                 n_chunks=2)
        return jnp.concatenate([c[None, None].repeat(out.shape[1], 1), out])

    a = np.asarray(run_spmd(mesh, bespoke, slices))
    b = np.asarray(run_spmd(mesh, chunked, slices))
    np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("nc", [2, 4, 8])
def test_chunked_coverage_and_home(mesh, nc):
    """Every worker computes on every one of the N·nc chunks exactly once
    per epoch, and every chunk ends back home (read-only step)."""
    slices = np.arange(N * 8, dtype=np.float32).reshape(N * 8, 1)

    def prog(s):
        def step(acc, cur, t):
            return acc + cur.sum(), cur

        acc, out = rotate_pipeline(step, jnp.float32(0.0), s, n_chunks=nc)
        return jnp.concatenate([acc[None, None], out], axis=0)

    out = np.asarray(run_spmd(mesh, prog, slices)).reshape(N, 9)
    total = slices.sum()
    np.testing.assert_allclose(out[:, 0], np.full(N, total))  # saw all
    np.testing.assert_array_equal(out[:, 1:].reshape(-1),
                                  slices.reshape(-1))  # chunks home


@pytest.mark.parametrize("nc", [2, 4])
def test_chunked_updates_travel(mesh, nc):
    """Updates made mid-rotation persist: every visitor increments the
    resident chunk, so every element ends at exactly N."""
    slices = np.zeros((N * 8, 1), np.float32)

    def prog(s):
        def step(acc, cur, t):
            return acc, cur + 1.0

        _, out = rotate_pipeline(step, jnp.float32(0.0), s, n_chunks=nc)
        return out

    out = np.asarray(run_spmd(mesh, prog, slices))
    np.testing.assert_array_equal(out, np.full((N * 8, 1), N))


@pytest.mark.parametrize("nc", [1, 2, 4])
def test_resident_chunk_index_names_the_resident_chunk(mesh, nc):
    """The index formula must agree with the pipeline's actual data
    movement: chunks carry their global id as payload, and the step
    asserts (via an error accumulator) that the id it sees equals
    resident_chunk_index(t, nc) at every step."""
    ids = np.repeat(np.arange(N * nc, dtype=np.float32), 8 // nc)[:, None]

    def prog(s):
        def step(err, cur, t):
            want = resident_chunk_index(t, nc).astype(jnp.float32)
            return err + jnp.abs(cur - want).sum(), cur

        err, _ = rotate_pipeline(step, jnp.float32(0.0), s, n_chunks=nc)
        return err[None, None]

    err = np.asarray(run_spmd(mesh, prog, ids))
    np.testing.assert_array_equal(err, np.zeros((N, 1)))


def test_resident_half_index_is_two_chunk_index(mesh):
    def prog(x):
        both = jnp.stack([
            jnp.stack([resident_half_index(jnp.int32(t)) for t in range(6)]),
            jnp.stack([resident_chunk_index(jnp.int32(t), 2)
                       for t in range(6)])])
        return both[None].astype(jnp.int32)

    out = np.asarray(run_spmd(mesh, prog, np.zeros((N, 1), np.float32)))
    out = out.reshape(N, 2, 6)
    np.testing.assert_array_equal(out[:, 0], out[:, 1])


def test_chunked_rejects_partial_coverage_shift(mesh):
    def prog(s):
        _, out = rotate_pipeline(lambda a, c, t: (a, c), jnp.zeros(()), s,
                                 n_chunks=2, shift=2)
        return out

    with pytest.raises(ValueError, match="shares a factor"):
        run_spmd(mesh, prog, np.zeros((N * 4, 1), np.float32))


def test_chunked_rejects_indivisible_slice(mesh):
    def prog(s):
        _, out = rotate_pipeline(lambda a, c, t: (a, c), jnp.zeros(()), s,
                                 n_chunks=3)
        return out

    with pytest.raises(ValueError, match="split into 3"):
        run_spmd(mesh, prog, np.zeros((N * 4, 1), np.float32))


def test_pipeline_rejects_unknown_wire(mesh):
    def prog(s):
        _, out = rotate_pipeline(lambda a, c, t: (a, c), jnp.zeros(()), s,
                                 n_chunks=2, wire="f16")
        return out

    with pytest.raises(ValueError, match="wire"):
        run_spmd(mesh, prog, np.zeros((N * 4, 1), np.float32))


# -- quantized rotate / regroup ---------------------------------------------

def test_rotate_quantized_bf16_lands_right_and_rounds_once(mesh):
    rng = np.random.default_rng(2)
    x = rng.normal(size=(N * 4, 16)).astype(np.float32)
    out = run_spmd(mesh, lambda v: C.rotate_quantized(v), x)
    expect = np.roll(x.reshape(N, 4, 16), 1, axis=0)
    got = np.asarray(out).reshape(N, 4, 16)
    assert got.dtype == np.float32
    # one bf16 rounding: rel error <= 2^-8
    np.testing.assert_allclose(got, expect, rtol=2 ** -8, atol=1e-7)


def test_rotate_quantized_int8_single_rounding_error(mesh):
    """Rotation never accumulates, so the int8 error is ONE rounding
    against the worker-shared scale — ≤ global_max/254 per element,
    independent of the ring size (the allreduce twin's bound is N× this)."""
    rng = np.random.default_rng(3)
    x = rng.normal(size=(N * 4, 32)).astype(np.float32)
    out = run_spmd(
        mesh, lambda v: C.rotate_quantized(v, wire_dtype=jnp.int8), x)
    expect = np.roll(x.reshape(N, 4, 32), 1, axis=0)
    tol = np.abs(x).max() / 127.0 / 2 + 1e-6
    assert np.abs(np.asarray(out).reshape(N, 4, 32) - expect).max() <= tol


def test_rotate_quantized_int8_per_leaf_scale(mesh):
    """Scales are per LEAF (one stacked pmax): a small-magnitude leaf must
    not inherit the big leaf's coarse scale."""
    rng = np.random.default_rng(4)
    tree = {"big": (1e3 * rng.normal(size=(N, 16))).astype(np.float32),
            "small": (1e-3 * rng.normal(size=(N, 16))).astype(np.float32)}
    fn = jax.jit(mesh.shard_map(
        lambda t: C.rotate_quantized(t, wire_dtype=jnp.int8),
        in_specs=(jax.tree.map(lambda _: mesh.spec(0), tree),),
        out_specs=jax.tree.map(lambda _: mesh.spec(0), tree)))
    out = fn(tree)
    for k in tree:
        expect = np.roll(tree[k].reshape(N, 1, 16), 1, axis=0).reshape(N, 16)
        tol = np.abs(tree[k]).max() / 127.0 / 2 + 1e-9
        assert np.abs(np.asarray(out[k]) - expect).max() <= tol, k


def test_rotate_quantized_int_leaves_exact(mesh):
    x = np.arange(N * 4, dtype=np.int32).reshape(N * 4, 1)
    out = run_spmd(mesh, lambda v: C.rotate_quantized(v, wire_dtype=jnp.int8),
                   x)
    expect = np.roll(x.reshape(N, 4, 1), 1, axis=0).reshape(N * 4, 1)
    np.testing.assert_array_equal(np.asarray(out), expect)


def test_rotate_quantized_shift_and_rejects_unknown_wire(mesh):
    x = np.arange(N, dtype=np.float32)[:, None]
    out = run_spmd(mesh,
                   lambda v: C.rotate_quantized(v, shift=-1,
                                                wire_dtype=jnp.int8), x)
    np.testing.assert_allclose(np.asarray(out).reshape(N),
                               np.roll(np.arange(N), -1), atol=0.05)
    with pytest.raises(ValueError, match="wire_dtype"):
        run_spmd(mesh,
                 lambda v: C.rotate_quantized(v, wire_dtype=jnp.float16), x)


def test_regroup_quantized_matches_exact_within_scale(mesh):
    rng = np.random.default_rng(5)
    x = rng.normal(size=(N * N, 8)).astype(np.float32)
    exact = np.asarray(run_spmd(mesh, C.regroup, x))
    for wd, tol in ((jnp.bfloat16, 2 ** -8 * np.abs(x).max() + 1e-6),
                    (jnp.int8, np.abs(x).max() / 127.0 / 2 + 1e-6)):
        out = run_spmd(mesh,
                       lambda v: C.regroup_quantized(v, wire_dtype=wd), x)
        assert np.abs(np.asarray(out) - exact).max() <= tol


def test_regroup_quantized_int_leaves_exact(mesh):
    x = np.arange(N * N, dtype=np.int32).reshape(N * N, 1)
    exact = np.asarray(run_spmd(mesh, C.regroup, x))
    out = run_spmd(mesh,
                   lambda v: C.regroup_quantized(v, wire_dtype=jnp.int8), x)
    np.testing.assert_array_equal(np.asarray(out), exact)


# -- model adoption: MF-SGD / LDA at n_chunks != 2 ---------------------------

def numpy_rotation_epoch_chunks(W, H, blocks, n, nc, chunk, lr, reg):
    """Numpy replica of a scatter-algo epoch on the GENERALIZED schedule:
    at step t worker w computes chunk-slice
    ``nc*((w - t//nc - (t%nc == nc-1)) % n) + t%nc`` — reduces to
    test_mfsgd.numpy_rotation_epoch's half formula at nc=2."""
    bu, bi, bv, bm, u_bound, ibc = blocks
    ns = nc * n
    bu = bu.reshape(n, ns, -1)
    bi = bi.reshape(n, ns, -1)
    bv = bv.reshape(n, ns, -1)
    bm = bm.reshape(n, ns, -1)
    se = cnt = 0.0
    for t in range(ns):
        for w in range(n):
            r = t % nc
            s = nc * ((w - t // nc - (1 if r == nc - 1 else 0)) % n) + r
            Wv = W[w * u_bound:(w + 1) * u_bound]
            Hv = H[s * ibc:(s + 1) * ibc]
            B = bu.shape[-1]
            for lo in range(0, B, chunk):
                sl = slice(lo, lo + chunk)
                u, i, v, m = (bu[w, s, sl], bi[w, s, sl], bv[w, s, sl],
                              bm[w, s, sl])
                wu, hi = Wv[u], Hv[i]
                err = m * (v - (wu * hi).sum(-1))
                gw = err[:, None] * hi - reg * m[:, None] * wu
                gh = err[:, None] * wu - reg * m[:, None] * hi
                np.add.at(Wv, u, lr * gw)
                np.add.at(Hv, i, lr * gh)
                se += (err ** 2).sum()
                cnt += m.sum()
    return W, H, np.sqrt(se / max(cnt, 1))


def test_mfsgd_chunked4_epoch_matches_numpy_schedule(mesh):
    """End-to-end: partitioner (n_slices = 4n), bounds, pipeline and
    index formula all line up at rotate_chunks=4 — the device epoch
    equals the numpy replica of the generalized schedule."""
    rng = np.random.default_rng(7)
    n_users, n_items, nnz, rank, chunk = 64, 48, 600, 4, 16
    u = rng.integers(0, n_users, nnz).astype(np.int32)
    i = rng.integers(0, n_items, nnz).astype(np.int32)
    v = rng.normal(size=nnz).astype(np.float32)

    cfg = MF.MFSGDConfig(rank=rank, chunk=chunk, lr=0.02, reg=0.01,
                         algo="scatter", rotate_chunks=4)
    model = MF.MFSGD(n_users, n_items, cfg, mesh, seed=3)
    W0 = np.asarray(model.W).copy()
    H0 = np.asarray(model.H).copy()
    model.set_ratings(u, i, v)
    rmse = model.train_epoch()

    blocks = MF.partition_ratings(u, i, v, n_users, n_items, N, chunk,
                                  n_slices=4 * N)
    Wr, Hr, rmse_ref = numpy_rotation_epoch_chunks(
        W0.copy(), H0.copy(), blocks, N, 4, chunk, cfg.lr, cfg.reg)
    np.testing.assert_allclose(np.asarray(model.W), Wr, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(model.H), Hr, rtol=2e-4, atol=2e-5)
    assert abs(rmse - rmse_ref) < 1e-3


@pytest.mark.parametrize("nc", [1, 4])
def test_mfsgd_chunked_factors_roundtrip_and_converge(mesh, nc):
    """Non-default chunk counts keep slices home across epochs (factors()
    correctness) and keep training: rmse must fall."""
    u, i, v = MF.synthetic_ratings(128, 96, 6_000, rank=4, noise=0.0, seed=2)
    cfg = MF.MFSGDConfig(rank=8, chunk=256, lr=0.05, reg=0.0,
                         algo="scatter", rotate_chunks=nc)
    model = MF.MFSGD(128, 96, cfg, mesh, seed=1)
    model.set_ratings(u, i, v)
    r1 = model.train_epoch()
    for _ in range(6):
        r_last = model.train_epoch()
    assert r_last < r1
    Wf, Hf = model.factors()
    assert Wf.shape == (128, 8) and Hf.shape == (96, 8)


def test_mfsgd_rotate_wire_close_to_exact(mesh):
    """One epoch per wire from identical state: the quantized wires may
    only perturb H/W within the per-hop rounding budget (and must
    actually engage — bit-identical output would mean the knob is dead)."""
    rng = np.random.default_rng(9)
    n_users, n_items, nnz = 64, 48, 600
    u = rng.integers(0, n_users, nnz).astype(np.int32)
    i = rng.integers(0, n_items, nnz).astype(np.int32)
    v = rng.normal(size=nnz).astype(np.float32)

    outs = {}
    for wire in ("exact", "bf16", "int8"):
        cfg = MF.MFSGDConfig(rank=4, chunk=64, lr=0.02, reg=0.01,
                             algo="scatter", rotate_wire=wire)
        model = MF.MFSGD(n_users, n_items, cfg, mesh, seed=3)
        model.set_ratings(u, i, v)
        model.train_epoch()
        outs[wire] = (np.asarray(model.W), np.asarray(model.H))
    for wire, atol in (("bf16", 0.02), ("int8", 0.05)):
        dw = np.abs(outs[wire][0] - outs["exact"][0]).max()
        dh = np.abs(outs[wire][1] - outs["exact"][1]).max()
        assert 0 < max(dw, dh) < atol, (wire, dw, dh)


@pytest.mark.parametrize("algo", ["scatter", "dense"])
def test_lda_chunked4_counts_invariant_and_likelihood(mesh, algo):
    """LDA at rotate_chunks=4: Gibbs count invariants survive the
    generalized schedule (token totals, Nk == column sums, non-negative)
    and the chain still improves the likelihood."""
    kw = ({"chunk": 64} if algo == "scatter"
          else {"d_tile": 8, "w_tile": 8, "entry_cap": 32})
    cfg = L.LDAConfig(n_topics=6, algo=algo, rotate_chunks=4, **kw)
    model = L.LDA(120, 64, cfg, mesh, seed=0)
    d_ids, w_ids = L.synthetic_corpus(120, 64, 3, 16, seed=1)
    model.set_tokens(d_ids, w_ids)
    ll0 = model.log_likelihood()
    for _ in range(4):
        model.sample_epoch()
    Ndk = model.doc_topic_table()
    Nwk = model.word_topic_table()
    Nk = np.asarray(model.Nk)
    assert Ndk.sum() == len(d_ids) and Nwk.sum() == len(d_ids)
    np.testing.assert_allclose(Nwk.sum(0), Nk)
    assert (Ndk >= 0).all() and (Nwk >= 0).all()
    assert model.log_likelihood() > ll0


def test_lda_rotate_wire_int8_chain_stays_sane(mesh):
    """int8 rotate wire on LDA: counts dequantize lossily, but the chain
    must stay a runnable sampler — finite likelihood, doc counts (carried,
    never rotated) still exact."""
    cfg = L.LDAConfig(n_topics=6, algo="dense", d_tile=8, w_tile=8,
                      entry_cap=32, rotate_wire="int8")
    model = L.LDA(120, 64, cfg, mesh, seed=0)
    d_ids, w_ids = L.synthetic_corpus(120, 64, 3, 16, seed=1)
    model.set_tokens(d_ids, w_ids)
    for _ in range(2):
        model.sample_epoch()
    assert np.isfinite(model.log_likelihood())
    # Ndk rides the carry, not the wire: token totals stay exact
    assert model.doc_topic_table().sum() == len(d_ids)


# -- telemetry: the wire-byte claims ----------------------------------------

def _mfsgd_rotate_site_bytes(mesh, **cfg_kwargs):
    """Per-trace ring-hop payload bytes of one MF-SGD epoch program.

    PR 11: the pipeline's ring hop is the ``reshard`` shim (same
    ppermute, same bytes — the verb name on the ledger changed, the
    wire accounting did not)."""
    u, i, v = MF.synthetic_ratings(64, 64, 500, seed=0)
    cfg = MF.MFSGDConfig(rank=8, algo="scatter", chunk=64, **cfg_kwargs)
    with telemetry.scope(True):
        model = MF.MFSGD(64, 64, cfg, mesh, seed=0)
        model.set_ratings(u, i, v)
        with telemetry.ledger.run("probe", steps=0):
            model._epoch_fn.lower(model.W, model.H, *model._blocks)
        probe = telemetry.ledger.summary()["probe"]
        return sum(s["payload_bytes"] for s in probe["sites"]
                   if s["verb"] == "reshard")


def test_ledger_int8_rotate_bytes_quarter_of_f32(mesh):
    """The acceptance claim, from the ledger itself: int8 rotate wire
    bytes are exactly ¼ of the f32 baseline for the same epoch."""
    exact = _mfsgd_rotate_site_bytes(mesh, rotate_wire="exact")
    int8 = _mfsgd_rotate_site_bytes(mesh, rotate_wire="int8")
    bf16 = _mfsgd_rotate_site_bytes(mesh, rotate_wire="bf16")
    assert exact > 0
    assert exact == 4 * int8
    assert exact == 2 * bf16


def test_ledger_records_per_chunk_wire_bytes(mesh):
    """Chunking shrinks what's on the wire PER HOP: the rotate site's
    per-trace payload at 4 chunks is half the 2-chunk payload (same
    slice, quarter-size in-flight chunks, one traced call either way)."""
    two = _mfsgd_rotate_site_bytes(mesh, rotate_chunks=2)
    four = _mfsgd_rotate_site_bytes(mesh, rotate_chunks=4)
    assert two > 0 and two == 2 * four


# -- Mosaic lowering: the chunked + quantized-wire pallas epochs -------------

def test_mfsgd_chunked_int8_pallas_epoch_lowers_for_tpu(mesh, monkeypatch):
    """kernel_equiv_check-style proof that the NEW rotation scaffolding
    (4-chunk queue, int8 wire quantize/ppermute/dequantize) composes with
    the Mosaic-compiled MF-SGD kernel — caught on CPU, not on the
    chip."""
    monkeypatch.setenv("HARP_PALLAS_FORCE_MOSAIC", "1")
    cfg = MF.MFSGDConfig(rank=8, algo="pallas", u_tile=128, i_tile=128,
                         rotate_chunks=4, rotate_wire="int8")
    n, ns = 8, 4 * 8
    _, _, u_bound, ibc = MF._dense_bounds(2048, 8192, n, ns,
                                          *MF.tiles(cfg))
    NCH, Cw = 4, 256  # the kernel's chunk list: cu / ci / cv / meta
    i32, f32 = jnp.int32, jnp.float32
    shapes = [((u_bound * n, 8), f32), ((4 * ibc * n, 8), f32),
              ((n * ns, NCH, Cw), i32), ((n * ns, NCH, Cw), i32),
              ((n * ns, NCH, Cw), f32), ((n * ns, NCH), i32)]
    sds = [jax.ShapeDtypeStruct(s, d, sharding=mesh.sharding(mesh.spec(0)))
           for s, d in shapes]
    fn = MF.make_multi_epoch_fn(mesh, cfg, epochs=2)
    text = fn.trace(*sds).lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text  # the Mosaic kernel is in the program


def test_lda_chunked_bf16_pallas_epoch_lowers_for_tpu(mesh, monkeypatch):
    """Same proof for the LDA side's distinct path: topic-major tables
    chunked along axis 1 (chunk_axis=1) with a bf16 wire, through the
    Mosaic-compiled CGS kernel + carry_db cond."""
    monkeypatch.setenv("HARP_PALLAS_FORCE_MOSAIC", "1")
    cfg = L.LDAConfig(n_topics=8, algo="pallas", d_tile=128, w_tile=128,
                      entry_cap=64, sampler="exprace", rng_impl="rbg",
                      rotate_chunks=4, rotate_wire="bf16")
    shapes = L.epoch_arg_shapes(8, 2048, 8192, cfg, n_tokens=100_000)
    sds = [jax.ShapeDtypeStruct(
        shape, dt, sharding=(mesh.replicated() if i == 2
                             else mesh.sharding(mesh.spec(0))))
        for i, (shape, dt) in enumerate(shapes)]
    fn = L.make_multi_epoch_fn(mesh, cfg, 8192, epochs=2)
    text = fn.trace(*sds).lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text
