"""reshard — the general redistribution verb (PR 11).

Contract under test:

1. Every (src_spec, dst_spec) pair over the 8-sim-worker mesh is
   BIT-identical to the naive all_gather+slice reference
   (``collective.reshard_reference``) — identity, local slice, ppermute
   rotation, all_to_all, gather, and the gather+slice fallback all take
   different fast paths and must agree exactly.
2. The quantized wires keep the one-rounding ``_quantized_move``
   contract (bf16 one cast each way; int8 error ≤ global_max/254
   against the worker-shared stacked-pmax scale; non-float leaves ride
   exact), and the chunked ppermute pipeline lowering is bit-exact with
   the one-hop rotation.
3. The equivalence-pinned shims: the rotate pipeline's ring hop and
   ``table.pull_rows`` now route through reshard and must reproduce the
   direct verbs bit-for-bit; the flagship kmeans hier-psum schedule
   reproduces the one-shot fit within float-reassociation tolerance
   (and exactly on integer payloads).
4. Flight-budget pins: each comm lowering is ONE dispatch and ZERO
   post-warmup compiles (the CLAUDE.md driver-loop traps, machine-checked).
5. The CommLedger sees every wire: verb "reshard", payload at wire
   width, chunk-sized for the chunked lowering.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from harp_tpu.parallel import collective as C
from harp_tpu.parallel.collective import ShardSpec
from harp_tpu.utils import flightrec, telemetry

S = ShardSpec

#: every layout the 2-D test array can take over the 8-worker ring —
#: the full pair matrix is 6×6 = 36 lowerings, covering every kind
SPECS = {
    "R": S.replicated(),
    "S0": S.blocked(0),
    "S0s1": S.blocked(0, 1),
    "S0s3": S.blocked(0, 3),
    "S1": S.blocked(1),
    "S1s2": S.blocked(1, 2),
}


def _global_array(nw):
    # rows 8·nw (divides by nw), cols nw (divides by nw): every spec legal
    return np.arange(nw * 8 * nw, dtype=np.float32).reshape(nw * 8, nw)


def _host_layout(x, spec, nw):
    """Pre-roll the host array so sharding dim-`spec.dim` over the mesh
    realizes the spec (worker w holds global block (w - shift) % nw)."""
    if spec.dim is None:
        return x
    if spec.shift % nw:
        bs = x.shape[spec.dim] // nw
        return np.roll(x, (spec.shift % nw) * bs, axis=spec.dim)
    return x


def _dev_spec(mesh, spec):
    return P() if spec.dim is None else mesh.spec(spec.dim, ndim=2)


def _run_pair(mesh, src, dst, **kw):
    nw = mesh.num_workers
    x = _global_array(nw)

    def prog(a):
        return (C.reshard(a, src, dst, **kw),
                C.reshard_reference(a, src, dst))

    fn = jax.jit(mesh.shard_map(
        prog, in_specs=(_dev_spec(mesh, src),),
        out_specs=(_dev_spec(mesh, dst),) * 2))
    staged = mesh.shard_array(_host_layout(x, src, nw), src.dim)
    got, ref = fn(staged)
    return np.asarray(got), np.asarray(ref)


@pytest.mark.parametrize("src_name", sorted(SPECS))
@pytest.mark.parametrize("dst_name", sorted(SPECS))
def test_every_pair_bit_exact_vs_naive_reference(mesh, src_name, dst_name):
    got, ref = _run_pair(mesh, SPECS[src_name], SPECS[dst_name])
    np.testing.assert_array_equal(got, ref)


def test_rotation_lowers_like_the_rotate_verb(mesh):
    """The ring-hop shim's pin: reshard between ring-shifted layouts is
    BIT-identical to the direct rotate verb for every shift (including
    negative and > ring size) — the lowering emits the same ppermute."""
    nw = mesh.num_workers
    x = np.random.default_rng(0).normal(size=(nw * 4, 16)).astype(np.float32)
    for shift in (1, 3, -1, nw + 2):
        def prog(a, s=shift):
            return (C.reshard(a, S.blocked(0), S.blocked(0, s)),
                    C.rotate(a, shift=s))

        fn = jax.jit(mesh.shard_map(prog, in_specs=(mesh.spec(0),),
                                    out_specs=(mesh.spec(0),) * 2))
        got, ref = fn(mesh.shard_array(x, 0))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_pipeline_ring_hop_is_the_reshard_shim(mesh):
    """rotate_pipeline's wire resolver (the mfsgd/lda/ccd ring) emits
    reshard: ledger verb 'reshard' at the pipeline site, and a 2-chunk
    epoch reproduces the pre-shim two-halves schedule bit-for-bit (the
    slice updated at t-1 lands exactly one worker on)."""
    from harp_tpu.parallel.rotate import rotate_pipeline

    nw = mesh.num_workers
    sl = np.arange(nw * 4.0, dtype=np.float32).reshape(nw * 4, 1)

    def epoch(acc, s):
        def step(c, chunk, t):
            return c + chunk.sum(), chunk * 2.0

        return rotate_pipeline(step, acc, s, n_chunks=2)

    fn = jax.jit(mesh.shard_map(
        epoch, in_specs=(P(), mesh.spec(0)), out_specs=(P(), mesh.spec(0))))
    with telemetry.scope(True):
        with telemetry.ledger.run("pipe", steps=1):
            acc, out = fn(jnp.float32(0.0), mesh.shard_array(sl, 0))
        verbs = {s["verb"]
                 for s in telemetry.ledger.summary()["pipe"]["sites"]}
    assert "reshard" in verbs
    # every chunk visited every worker once: doubled 2n times... each
    # chunk is doubled once per visit, n visits -> x * 2^n, home order
    np.testing.assert_array_equal(
        np.asarray(out), sl * 2.0 ** nw)
    assert float(acc) > 0.0


def test_wire_validation_matches_rotate_pipeline_contract(mesh):
    from harp_tpu.parallel.rotate import _wire_rotate

    with pytest.raises(ValueError, match="wire must be one of"):
        _wire_rotate("fp8", 1, "workers")
    with pytest.raises(ValueError, match="wire must be one of"):
        C.reshard(jnp.zeros(8), S.blocked(0), S.blocked(0, 1), wire="fp8")


def test_quantized_wires_round_once(mesh):
    """bf16/int8 reshard wires: single-rounding error bounds on the
    rotation AND the gather lowering; int leaves ride exact."""
    nw = mesh.num_workers
    rng = np.random.default_rng(7)
    x = rng.normal(size=(nw * 4, 8)).astype(np.float32) * 3.0
    xi = np.arange(nw * 2, dtype=np.int32).reshape(nw * 2, 1)

    def prog(a, b):
        r8 = C.reshard(a, S.blocked(0), S.blocked(0, 1), wire="int8")
        rb = C.reshard(a, S.blocked(0), S.blocked(0, 1), wire="bf16")
        g8 = C.reshard(a, S.blocked(0), S.replicated(), wire="int8")
        i8 = C.reshard(b, S.blocked(0), S.blocked(0, 1), wire="int8")
        return r8, rb, g8, i8

    fn = jax.jit(mesh.shard_map(
        prog, in_specs=(mesh.spec(0),) * 2,
        out_specs=(mesh.spec(0), mesh.spec(0), P(), mesh.spec(0))))
    r8, rb, g8, i8 = fn(mesh.shard_array(x, 0), mesh.shard_array(xi, 0))
    exact = np.roll(x, x.shape[0] // nw, axis=0)
    bound8 = np.abs(x).max() / 254 + 1e-6
    assert np.abs(np.asarray(r8) - exact).max() <= bound8
    assert np.abs(np.asarray(g8) - x).max() <= bound8
    # bf16: one cast each way
    assert np.abs(np.asarray(rb) - exact).max() <= \
        np.abs(x).max() * 2.0 ** -8 + 1e-6
    np.testing.assert_array_equal(
        np.asarray(i8), np.roll(xi, xi.shape[0] // nw, axis=0))


def test_chunked_pipeline_lowering_bit_exact_and_gated(mesh):
    """n_chunks splits the rotation into a scan of sub-chunk hops —
    bit-exact with the one-hop move; non-divisible chunk counts and
    non-rotation lowerings refuse loudly."""
    nw = mesh.num_workers
    x = np.random.default_rng(3).normal(size=(nw * 8, 4)).astype(np.float32)

    def prog(a):
        one = C.reshard(a, S.blocked(0), S.blocked(0, 1))
        four = C.reshard(a, S.blocked(0), S.blocked(0, 1), n_chunks=4)
        return one, four

    fn = jax.jit(mesh.shard_map(prog, in_specs=(mesh.spec(0),),
                                out_specs=(mesh.spec(0),) * 2))
    one, four = fn(mesh.shard_array(x, 0))
    np.testing.assert_array_equal(np.asarray(one), np.asarray(four))

    with pytest.raises(ValueError, match="does not divide"):
        jax.jit(mesh.shard_map(
            lambda a: C.reshard(a, S.blocked(0), S.blocked(0, 1),
                                n_chunks=3),
            in_specs=(mesh.spec(0),), out_specs=mesh.spec(0)))(
            mesh.shard_array(x, 0))
    with pytest.raises(ValueError, match="ring rotations only"):
        jax.jit(mesh.shard_map(
            lambda a: C.reshard(a, S.blocked(0), S.replicated(),
                                n_chunks=2),
            in_specs=(mesh.spec(0),), out_specs=P()))(
            mesh.shard_array(x, 0))


def test_spec_validation(mesh):
    with pytest.raises(ValueError, match="no ring shift"):
        S(dim=None, shift=1)
    x = np.zeros((mesh.num_workers * 2, 3), np.float32)
    # dim out of range and non-divisible sizes refuse at trace time
    with pytest.raises(ValueError, match="out of range"):
        jax.jit(mesh.shard_map(
            lambda a: C.reshard(a, S.blocked(0), S.blocked(5)),
            in_specs=(mesh.spec(0),), out_specs=mesh.spec(0)))(
            mesh.shard_array(x, 0))
    with pytest.raises(ValueError, match="does not split"):
        jax.jit(mesh.shard_map(
            lambda a: C.reshard(a, S.blocked(0), S.blocked(1)),
            in_specs=(mesh.spec(0),), out_specs=mesh.spec(1, ndim=2)))(
            mesh.shard_array(x, 0))


def test_match_reshard_rules(mesh):
    tree = {"model": {"W": np.zeros((8, 4)), "H": np.zeros((8, 4))},
            "lr": np.float32(0.1), "step": np.zeros(())}
    rules = [("model/W", S.blocked(0)), ("model/H", S.blocked(0, 1)),
             (".*", S.replicated())]
    specs = C.match_reshard_rules(rules, tree)
    assert specs["model"]["W"] == S.blocked(0)
    assert specs["model"]["H"] == S.blocked(0, 1)
    assert specs["lr"] == S.replicated()      # scalar: never partitioned
    assert specs["step"] == S.replicated()
    with pytest.raises(ValueError, match="no reshard rule"):
        C.match_reshard_rules([("W", S.blocked(0))],
                              {"other": np.zeros((4, 4))})


def test_reshard_pytree_with_per_leaf_specs(mesh):
    """A rule-matched spec tree reshards each leaf independently in one
    verb call (one ledger record, mixed lowerings)."""
    nw = mesh.num_workers
    tree = {"W": np.arange(nw * 4.0, dtype=np.float32).reshape(nw * 4, 1),
            "H": np.arange(nw * 2.0, dtype=np.float32).reshape(nw * 2, 1)}
    src = C.match_reshard_rules([("W", S.blocked(0)),
                                 ("H", S.blocked(0))], tree)
    dst = C.match_reshard_rules([("W", S.blocked(0, 1)),
                                 ("H", S.replicated())], tree)

    def prog(t):
        return C.reshard(t, src, dst)

    fn = jax.jit(mesh.shard_map(
        prog, in_specs=({"W": mesh.spec(0), "H": mesh.spec(0)},),
        out_specs={"W": mesh.spec(0), "H": P()}))
    out = fn({k: mesh.shard_array(v, 0) for k, v in tree.items()})
    np.testing.assert_array_equal(
        np.asarray(out["W"]), np.roll(tree["W"], 4, axis=0))
    np.testing.assert_array_equal(np.asarray(out["H"]), tree["H"])


# -- the shimmed call sites --------------------------------------------------

def test_pull_rows_shim_unchanged(mesh):
    """table.pull_rows rides reshard(blocked->replicated) now — same
    rows, bit-for-bit, as the raw all_gather+take reference."""
    from harp_tpu.table import pull_rows

    nw = mesh.num_workers
    tb = np.arange(nw * 4 * 3, dtype=np.float32).reshape(nw * 4, 3)
    ids = np.tile(np.arange(nw * 4, dtype=np.int32)[::-1][:4], nw)

    def prog(t, i):
        got = pull_rows(t, i)
        ref = jnp.take(jax.lax.all_gather(t, "workers", tiled=True), i,
                       axis=0)
        return got, ref

    fn = jax.jit(mesh.shard_map(prog, in_specs=(mesh.spec(0),) * 2,
                                out_specs=(mesh.spec(0),) * 2))
    got, ref = fn(mesh.shard_array(tb, 0), mesh.shard_array(ids, 0))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_kmeans_hier_psum_matches_one_shot(mesh):
    """The flagship planner schedule: psum_schedule='hier' reproduces
    the one-shot fit to float-reassociation tolerance on the same seed
    (the flip gate's 1% inertia tolerance is ~1e4x looser than this)."""
    from harp_tpu.models.kmeans import fit

    rng = np.random.default_rng(0)
    pts = rng.normal(size=(mesh.num_workers * 64, 16)).astype(np.float32)
    c1, i1 = fit(pts, k=8, iters=5, mesh=mesh, seed=3)
    c2, i2 = fit(pts, k=8, iters=5, mesh=mesh, seed=3,
                 psum_schedule="hier")
    assert abs(i1 - i2) / abs(i1) < 1e-5
    np.testing.assert_allclose(np.asarray(c1), np.asarray(c2),
                               rtol=1e-5, atol=1e-5)


def test_allreduce_hier_exact_on_ints_any_group(mesh):
    nw = mesh.num_workers
    y = np.arange(nw * 5, dtype=np.int32).reshape(nw, 5)
    for gs in (None, 1, 2, 4, nw):
        op = C.host_op(mesh, lambda t, gs=gs, **kw: C.allreduce_hier(
            t, group_size=gs, **kw), in_dim=0, out_dim=0)
        np.testing.assert_array_equal(np.asarray(op(y)),
                                      np.tile(y.sum(0), (nw, 1)))
    with pytest.raises(ValueError, match="must divide"):
        C.host_op(mesh, lambda t, **kw: C.allreduce_hier(
            t, group_size=3, **kw), in_dim=0, out_dim=0)(y)


# -- flight budgets + ledger -------------------------------------------------

def _budget_pinned(mesh, build_prog, in_specs, out_specs, args):
    """One warmup, then one invocation under the pinned budget: ONE
    dispatch, ONE stacked readback, ZERO compiles (a reshard lowering
    must never hide a re-trace or a per-leaf dispatch)."""
    fn = flightrec.track(
        jax.jit(mesh.shard_map(build_prog, in_specs=in_specs,
                               out_specs=out_specs)), "reshard.pin")
    with telemetry.scope(True):
        out = fn(*args)                      # warmup (compile here)
        jax.block_until_ready(out)
        with flightrec.budget(compiles=0, dispatches=1, readbacks=1,
                              tag="reshard.pin"):
            out = fn(*args)
            flightrec.readback(jax.tree.leaves(out)[0])


@pytest.mark.parametrize("dst_name,wire,chunks", [
    ("S0s1", "exact", 1),     # ppermute
    ("S0s1", "exact", 4),     # chunked pipeline
    ("S0s1", "int8", 1),      # quantized ring hop
    ("S1", "exact", 1),       # all_to_all
    ("R", "exact", 1),        # all_gather
    ("S1s2", "exact", 1),     # gather+slice fallback
])
def test_flight_budget_one_dispatch_zero_recompiles(mesh, dst_name, wire,
                                                    chunks):
    nw = mesh.num_workers
    x = _global_array(nw)
    dst = SPECS[dst_name]
    _budget_pinned(
        mesh,
        lambda a: C.reshard(a, S.blocked(0), dst, wire=wire,
                            n_chunks=chunks),
        (mesh.spec(0, ndim=2),), _dev_spec(mesh, dst),
        (mesh.shard_array(x, 0),))


def test_ledger_accounts_reshard_at_wire_width(mesh):
    """The CommLedger pin: exact rotation records the full payload,
    the 4-chunk pipeline records the chunk-sized hop, int8 records at
    1 B/element — the byte sheet the planner prices is the wire that
    ships (HL302's cross-check, unit-sized)."""
    nw = mesh.num_workers
    x = np.zeros((nw * 8, 4), np.float32)
    per_shard = 8 * 4 * 4  # worker's [8, 4] f32 block

    def payloads(**kw):
        with telemetry.scope(True):
            with telemetry.ledger.run("probe", steps=0):
                jax.jit(mesh.shard_map(
                    lambda a: C.reshard(a, S.blocked(0), S.blocked(0, 1),
                                        **kw),
                    in_specs=(mesh.spec(0),),
                    out_specs=mesh.spec(0))).lower(mesh.shard_array(x, 0))
            sites = telemetry.ledger.summary()["probe"]["sites"]
            return {s["verb"]: s["payload_bytes"] for s in sites}

    assert payloads()["reshard"] == per_shard
    assert payloads(n_chunks=4)["reshard"] == per_shard // 4
    assert payloads(wire="int8")["reshard"] == per_shard // 4  # 1 B/elem
    assert payloads(wire="bf16")["reshard"] == per_shard // 2
