"""harplint (harp_tpu/analysis) — golden fixtures for every layer.

One synthetic module per Layer-1 rule that must trip it, the pre-fix LDA
scan-carry gather+DUS pattern pinned as a Layer-2 positive (and the
fixed tile-local form as a negative), a 3-seed-word ``prng_seed`` toy
kernel the Mosaic audit must flag WITHOUT hardware, the Layer-4
CommGraph fixtures (kmeans' hand-computed byte sheet as the HL302
cross-check, an unledgered psum for HL301, a sabotaged donated-buffer
re-read for HL303, a loop-invariant allgather for HL304), the Layer-5
thread-root fixtures (one sabotaged synthetic plane per HL401–HL405
plus its clean twin, driven through ``threadgraph.analyze_sources``),
and the repo-wide tier-1 gate: zero unallowlisted violations at HEAD.
"""

import contextlib
import json
import os
import sys
import textwrap

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "scripts"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

import harp_tpu.utils.telemetry as T  # noqa: E402
from harp_tpu.analysis import commgraph  # noqa: E402
from harp_tpu.analysis import rule_ids  # noqa: E402
from harp_tpu.analysis import allowlist as allowlist_mod  # noqa: E402
from harp_tpu.analysis.astlints import lint_source  # noqa: E402
from harp_tpu.analysis.jaxpr_checks import (  # noqa: E402
    find_large_constants, find_scan_copy_traps)
from harp_tpu.analysis.mosaic_audit import (  # noqa: E402
    audit_kernel, check_kernel_jaxpr)
from harp_tpu.analysis import cli  # noqa: E402


def _rules(violations):
    return sorted({v.rule for v in violations})


# ---------------------------------------------------------------------------
# Layer 1 — one synthetic module per rule
# ---------------------------------------------------------------------------

def test_hl001_raw_collective_trips():
    src = textwrap.dedent("""
        from jax import lax
        def step(x):
            return lax.psum(x, "workers")
    """)
    vs = lint_source("harp_tpu/models/fake.py", src)
    assert _rules(vs) == ["HL001"]


def test_hl001_exempt_inside_verb_layer():
    src = "from jax import lax\ndef f(x):\n    return lax.psum(x, 'w')\n"
    assert lint_source("harp_tpu/parallel/collective.py", src) == []
    assert lint_source("harp_tpu/parallel/rotate.py", src) == []


def test_hl001_axis_queries_stay_legal():
    src = ("from jax import lax\n"
           "def f():\n"
           "    return lax.axis_index('w') + lax.axis_size('w')\n")
    assert lint_source("harp_tpu/models/fake.py", src) == []


def test_hl002_prngkey_trips():
    src = ("import jax\n"
           "def seed_me(s):\n"
           "    return jax.random.PRNGKey(s)\n")
    vs = lint_source("harp_tpu/models/fake.py", src)
    assert _rules(vs) == ["HL002"]
    # the helper that wraps the trap is exempt
    assert lint_source("harp_tpu/utils/prng.py", src) == []


def test_hl003_asarray_on_numpy_trips():
    src = ("import jax.numpy as jnp, numpy as np\n"
           "def ingest(x):\n"
           "    return jnp.asarray(np.asarray(x, np.float32))\n")
    vs = lint_source("harp_tpu/models/fake.py", src)
    assert _rules(vs) == ["HL003"]


def test_hl003_device_put_wrapper_is_clean():
    src = ("import jax, jax.numpy as jnp, numpy as np\n"
           "def ingest(x):\n"
           "    return jax.device_put(jnp.asarray(np.asarray(x)))\n")
    assert lint_source("harp_tpu/models/fake.py", src) == []


def test_hl004_untracked_jit_trips_only_in_models():
    src = ("import jax\n"
           "def driver():\n"
           "    step = jax.jit(lambda x: x)\n"
           "    return step\n")
    assert _rules(lint_source("harp_tpu/models/fake.py", src)) == ["HL004"]
    assert lint_source("harp_tpu/utils/fake.py", src) == []


def test_hl004_factory_return_and_track_are_clean():
    src = ("import jax\n"
           "from harp_tpu.utils import flightrec\n"
           "def make_step_fn():\n"
           "    return jax.jit(lambda x: x)\n"
           "def driver():\n"
           "    return flightrec.track(jax.jit(lambda x: x), 'd.step')\n")
    assert lint_source("harp_tpu/models/fake.py", src) == []


def test_hl005_undated_perf_claim_trips():
    src = ('def fast():\n'
           '    """Runs at 246.5M ups/s on the graded shape."""\n')
    vs = lint_source("harp_tpu/models/fake.py", src)
    assert _rules(vs) == ["HL005"]
    # date + chip in the documented form passes
    src_ok = ('def fast():\n'
              '    """246.5M ups/s (2026-08-01, 1x v5e)."""\n')
    assert lint_source("harp_tpu/models/fake.py", src_ok) == []


def test_hl000_syntax_error_is_loud():
    assert _rules(lint_source("harp_tpu/models/fake.py",
                              "def broken(:\n")) == ["HL000"]


# ---------------------------------------------------------------------------
# Layer 2 — the LDA copy-trap regression, pinned
# ---------------------------------------------------------------------------

def _prefix_lda_pattern(table, idxs, upds):
    """The PRE-FIX shape of the LDA epoch: the scan body gathers from the
    carried table AND dynamic_update_slice's it (cost 20 s of a 29 s
    epoch before the tile-local fix)."""

    def body(tbl, x):
        i, u = x
        vals = jnp.take(tbl, i, axis=0)              # gather from carry
        tbl = lax.dynamic_update_slice(tbl, u, (i[0], 0))
        return tbl, vals.sum()

    return lax.scan(body, table, (idxs, upds))


def _fixed_lda_pattern(table, idxs, upds):
    """The FIXED form: dynamic_slice the tile first, gather tile-locally
    — the gather operand is the slice result, not the carry."""

    def body(tbl, x):
        i, u = x
        tile = lax.dynamic_slice(tbl, (0, 0), (4, tbl.shape[1]))
        vals = jnp.take(tile, i % 4, axis=0)
        tbl = lax.dynamic_update_slice(tbl, u, (i[0], 0))
        return tbl, vals.sum()

    return lax.scan(body, table, (idxs, upds))


_SCAN_ARGS = (jnp.zeros((16, 8)), jnp.zeros((3, 2), jnp.int32),
              jnp.zeros((3, 1, 8)))


def test_scan_copy_trap_positive():
    closed = jax.jit(_prefix_lda_pattern).trace(*_SCAN_ARGS).jaxpr
    vs = find_scan_copy_traps(closed, "fixture")
    assert _rules(vs) == ["HL101"]
    assert "copy the whole" in vs[0].message.lower()


def test_scan_copy_trap_fixed_form_negative():
    closed = jax.jit(_fixed_lda_pattern).trace(*_SCAN_ARGS).jaxpr
    assert find_scan_copy_traps(closed, "fixture") == []


def test_scan_copy_trap_sees_fori_loop():
    def bad_fori(table, idxs, upds):
        def body(t, tbl):
            vals = jnp.take(tbl, idxs[t], axis=0)
            return lax.dynamic_update_slice(
                tbl, upds[t] + vals.sum(), (idxs[t][0], 0))
        return lax.fori_loop(0, 3, body, table)

    closed = jax.jit(bad_fori).trace(*_SCAN_ARGS).jaxpr
    assert _rules(find_scan_copy_traps(closed, "f")) == ["HL101"]


def test_large_constant_detector():
    big = np.ones((1 << 18,), np.float32)            # 1 MiB exactly

    def closes_over(x):
        return x + jnp.asarray(big)

    closed = jax.jit(closes_over).trace(jnp.zeros(1 << 18)).jaxpr
    # over a small threshold: flagged; at the default 1 MiB: exactly at
    # the boundary (not >), so clean
    assert _rules(find_large_constants(closed, "f", 1 << 16)) == ["HL102"]
    assert find_large_constants(closed, "f", 1 << 20) == []


def test_driver_registry_is_clean():
    """The registered flagship driver programs (kmeans fit, ring
    attention, mfsgd epoch) carry no copy trap and no oversized
    literal."""
    from harp_tpu.analysis.drivers import DRIVERS
    from harp_tpu.analysis.jaxpr_checks import analyze_program

    assert set(DRIVERS) >= {"kmeans.fit", "ring_attention", "mfsgd.epoch"}
    for name, build in DRIVERS.items():
        fn, args = build()
        assert analyze_program(fn, args, f"driver:{name}") == []


# ---------------------------------------------------------------------------
# Layer 3 — Mosaic audit, no hardware
# ---------------------------------------------------------------------------

def _toy_seed_kernel(n_words: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kern(seed_ref, o_ref):
        pltpu.prng_seed(*(seed_ref[i] for i in range(n_words)))
        bits = pltpu.prng_random_bits(o_ref.shape)
        o_ref[...] = lax.shift_right_logical(bits, 8).astype(jnp.float32)

    def f(seed):
        # seed words ride SMEM so seed_ref[i] reads scalars, as the real
        # lda kernel's scalar-prefetch grid does
        return pl.pallas_call(
            kern,
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)],
            out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32)
        )(seed)

    return f, (jnp.zeros(max(n_words, 1), jnp.int32),)


def test_mosaic_audit_flags_3_seed_words():
    """The 2026-08-01 in-window silicon failure, caught on CPU: a 3-word
    prng_seed must trip HL202 from the jaxpr alone."""
    fn, args = _toy_seed_kernel(3)
    closed = jax.jit(fn).trace(*args).jaxpr
    vs = check_kernel_jaxpr(closed, "kernel:toy3")
    assert "HL202" in _rules(vs)
    assert "2 " in vs[0].message or "TWO" in vs[0].message


def test_mosaic_audit_2_seed_words_clean():
    fn, args = _toy_seed_kernel(2)
    vs = audit_kernel("toy2", fn, args)
    assert vs == [], [v.message for v in vs]


def test_mosaic_audit_flags_uint32_float_cast():
    from jax.experimental import pallas as pl

    def kern(x_ref, o_ref):
        o_ref[...] = x_ref[...].astype(jnp.float32)

    def f(x):
        return pl.pallas_call(
            kern, out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32)
        )(x)

    vs = audit_kernel("toyu32", f, (jnp.zeros((8, 128), jnp.uint32),))
    # the silicon limit local lowering does NOT enforce: HL203 must fire
    # even though the local Mosaic pass stays green
    assert "HL203" in _rules(vs)


def test_mosaic_audit_flags_unaligned_block_dim():
    from jax.experimental import pallas as pl

    def kern(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def f(x):
        return pl.pallas_call(
            kern, grid=(4,),
            in_specs=[pl.BlockSpec((4, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((4, 128), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((16, 128), jnp.float32))(x)

    vs = audit_kernel("toyblk", f, (jnp.zeros((16, 128), jnp.float32),))
    assert "HL204" in _rules(vs)


def test_kernel_registry_audit_is_clean():
    """Every registered ops/ kernel lowers for TPU on this CPU host and
    passes the silicon-limit checks (the audit that caught
    flash_attention's is_finite, which had only ever run in interpret
    mode)."""
    from harp_tpu.analysis.mosaic_audit import audit_registry, \
        registered_kernels

    assert set(registered_kernels()) >= {
        "kmeans.partials", "kmeans.partials_int8", "lda.cgs_entry_update",
        "mfsgd.sgd_tile_update", "flash_attention"}
    vs = audit_registry()
    assert vs == [], [v.format() for v in vs]


# ---------------------------------------------------------------------------
# Layer 4 — CommGraph (static communication audit)
# ---------------------------------------------------------------------------

AX = "workers"


def _wmesh():
    from harp_tpu.parallel.mesh import WorkerMesh

    return WorkerMesh()


def _sharded(mesh, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=mesh.sharding(mesh.spec(0)))


def test_commgraph_kmeans_sheet_matches_hand_computed():
    """THE acceptance fixture: the static byte sheet for kmeans.fit
    equals the hand-computed (k·d·4 + k·4 + 4) per-iteration allreduce
    sheet (sums + counts + inertia — the same sheet
    tests/test_telemetry.py pins at runtime), amplified by the fori trip
    count, and matches the CommLedger's trace-time bytes EXACTLY."""
    from harp_tpu.analysis.drivers import DRIVERS

    fn, args = DRIVERS["kmeans.fit"]()
    vs, graph = commgraph.analyze_program("kmeans.fit", fn, args)
    assert vs == [], [v.format() for v in vs]
    (site,) = graph.sites
    k, d, iters = 8, 32, 2  # the registry's driver shapes
    per_iter = k * d * 4 + k * 4 + 4
    assert site.primitive == "psum" and site.verb == "allreduce"
    assert site.site.startswith("kmeans.py:")
    assert site.calls_per_trace == 3          # sums, counts, inertia
    assert site.per_shard_bytes == per_iter
    assert site.amplification == iters and not site.dynamic
    sheet = graph.sheet()
    assert sheet["bytes_per_trace"] == per_iter
    assert sheet["amplified_bytes"] == per_iter * iters
    # static == ledger, to the byte (the HL302 contract)
    ledger_total = sum(r["payload_bytes"]
                       for recs in graph.ledger_sites.values()
                       for r in recs)
    assert ledger_total == per_iter


def test_hl301_unledgered_collective_fires():
    """A raw lax.psum inside shard_map leaves no CommLedger record —
    the untracked wire HL301 exists for."""
    mesh = _wmesh()

    def raw(x):
        return lax.psum(x, AX)

    fn = jax.jit(mesh.shard_map(raw, in_specs=(mesh.spec(0),),
                                out_specs=P()))
    vs, graph = commgraph.analyze_program(
        "fix301", fn, (_sharded(mesh, (8, 4)),))
    assert _rules(vs) == ["HL301"]
    assert "untracked wire" in vs[0].message
    assert graph.sites and graph.sites[0].verb is None


def test_hl302_lying_byte_sheet_fires():
    """A verb that records a SMALLER tree than it reduces (record_comm
    and the psum share one source line, so both sides key the same call
    site) must trip the static-vs-ledger byte cross-check."""
    mesh = _wmesh()

    def lying(x):
        return T.record_comm("allreduce", x[0, 0], axis=AX) or lax.psum(x, AX)  # noqa: E501

    fn = jax.jit(mesh.shard_map(lying, in_specs=(mesh.spec(0),),
                                out_specs=P()))
    vs, _ = commgraph.analyze_program("fix302", fn,
                                      (_sharded(mesh, (8, 4)),))
    assert _rules(vs) == ["HL302"]
    assert "disagrees" in vs[0].message


def test_hl302_quantized_wire_is_exempt():
    """The int8 wire accounts 1 B/elem logically while the lowering
    accumulates in int32 — a documented divergence the byte cross-check
    must NOT flag (and the extra stacked-scale pmax at the same site
    must not read as an untracked wire either)."""
    from harp_tpu.parallel import collective as C

    mesh = _wmesh()

    def q(x):
        return C.allreduce_quantized(x, wire_dtype=jnp.int8)

    fn = jax.jit(mesh.shard_map(q, in_specs=(mesh.spec(0),),
                                out_specs=P()))
    vs, graph = commgraph.analyze_program("fixq", fn,
                                          (_sharded(mesh, (8, 4)),))
    assert vs == [], [v.format() for v in vs]
    assert any(s.ledger_wire == "int8" for s in graph.sites)


def test_hl304_loop_invariant_collective_fires():
    """An allgather of a scan CONST re-ships identical bytes every
    iteration — hoistable, and the sheet must show the wasted
    amplification."""
    from harp_tpu.parallel import collective as C

    mesh = _wmesh()

    def prog(x):
        def body(c, _):
            return c + C.allgather(x).sum(), None

        out, _ = lax.scan(body, jnp.float32(0.0), None, length=4)
        return out

    fn = jax.jit(mesh.shard_map(prog, in_specs=(mesh.spec(0),),
                                out_specs=P()))
    vs, graph = commgraph.analyze_program("fix304", fn,
                                          (_sharded(mesh, (8, 4)),))
    assert _rules(vs) == ["HL304"]
    assert "hoist" in vs[0].message
    (site,) = graph.sites
    assert site.amplification == 4 and site.loop_invariant


def test_hl304_carry_dependent_collective_is_clean():
    """The same allgather on the CARRY is real per-iteration traffic —
    no hoist finding (ring attention / rotate_pipeline shape)."""
    from harp_tpu.parallel import collective as C

    mesh = _wmesh()

    def prog(x):
        def body(c, _):
            return C.allgather(c)[: c.shape[0]] * 0.5 + c, None

        out, _ = lax.scan(body, x, None, length=4)
        return out

    fn = jax.jit(mesh.shard_map(prog, in_specs=(mesh.spec(0),),
                                out_specs=mesh.spec(0)))
    vs, _ = commgraph.analyze_program("fix304n", fn,
                                      (_sharded(mesh, (8, 4)),))
    assert vs == [], [v.format() for v in vs]


def test_hl303_sabotaged_donated_reread_and_redispatch_fire():
    """The violation fixture: a buffer donated to a dispatch is read
    back AND re-dispatched.  On this CPU backend the re-use may also
    raise jax's own 'Array has been deleted' — the audit must have
    recorded the violation BEFORE the crash (on TPU there is no crash,
    just garbage — which is the whole point of the lint)."""
    from harp_tpu.utils import flightrec

    exe = jax.jit(lambda s, b: s + b, donate_argnums=(1,))
    s = jax.device_put(np.ones((4,), np.float32))
    audit = commgraph.DonationAudit("protocol:sabotage")
    with audit:
        w = audit.wrap(exe, (1,), "toy.step")
        buf = jax.device_put(np.ones((4,), np.float32))
        w(s, buf)
        with contextlib.suppress(RuntimeError):
            flightrec.readback(buf)        # use-after-donate: host read
        with contextlib.suppress(RuntimeError, ValueError):
            w(s, buf)                      # use-after-donate: re-dispatch
        fresh = jax.device_put(np.ones((4,), np.float32))
        w(s, fresh)                        # correct discipline: clean
    assert [v.rule for v in audit.violations] == ["HL303", "HL303"]
    assert "host read" in audit.violations[0].message
    assert "re-dispatched" in audit.violations[1].message


def test_hl303_continuous_runner_discipline_is_clean():
    """The clean fixture: the REAL serve ContinuousRunner depth-2
    in-flight pipeline (fresh staged buffer per batch, donated exactly
    once) passes the donation audit — the registered lint-time
    protocols drive exactly this."""
    from harp_tpu.analysis.drivers import PROTOCOLS

    assert set(PROTOCOLS) >= {"serve.kmeans_continuous",
                              "serve.mfsgd_continuous"}
    drive = PROTOCOLS["serve.kmeans_continuous"]()
    vs = commgraph.audit_protocol("serve.kmeans_continuous", drive)
    assert vs == [], [v.format() for v in vs]


def test_hl303_retry_restage_protocol_is_clean_and_non_vacuous():
    """The PR-10 retry protocol: an injector-killed dispatch retried
    through a FRESHLY staged buffer passes the donation audit — and the
    drive itself asserts the fault fired, so the protocol can never go
    vacuously green."""
    from harp_tpu.analysis.drivers import PROTOCOLS

    assert "serve.retry_restage" in PROTOCOLS
    drive = PROTOCOLS["serve.retry_restage"]()
    vs = commgraph.audit_protocol("serve.retry_restage", drive)
    assert vs == [], [v.format() for v in vs]


def test_hl303_sabotaged_retry_redispatching_donated_buffer_fires(mesh):
    """The sabotaged twin of serve.retry_restage: a retry loop that
    re-dispatches the SAME staged buffer after the failed attempt (the
    'obvious' retry) is exactly the use-after-donate HL303 exists for —
    the CPU sim would pass it silently."""
    from harp_tpu.serve.engines import ENGINES
    from harp_tpu.serve.server import Server
    from harp_tpu.utils.fault import FaultInjector, InjectedFault

    rng = np.random.default_rng(0)
    srv = Server("kmeans",
                 state=ENGINES["kmeans"].synthetic_state(rng, k=4, d=8),
                 mesh=mesh, ladder=(1, 4))
    srv.startup()
    n_state = len(srv.engine.state_args())
    audit = commgraph.DonationAudit("protocol:sabotaged_retry")
    with audit:
        srv.wrap_executables(
            lambda rung, exe: audit.wrap(exe, (n_state,), f"b{rung}"))
        staged = srv.engine.put_input(
            srv.engine.make_input(
                rng.normal(size=(2, 8)).astype(np.float32), 4))
        inj = FaultInjector(fail={"dispatch": (1,)})
        with inj.arm():
            with contextlib.suppress(InjectedFault):
                srv._exec[4](*srv.engine.state_args(), staged)
            # the sabotage: retry WITHOUT restaging
            with contextlib.suppress(RuntimeError, ValueError):
                srv._exec[4](*srv.engine.state_args(), staged)
    assert any(v.rule == "HL303" and "re-dispatched" in v.message
               for v in audit.violations)


def test_hl303_elastic_rebalance_restage_protocol_is_clean():
    """The PR-15 elastic survival protocol: a permanent worker loss
    mid-loop, shrink to survivors, every post-shrink dispatch through a
    FRESHLY restaged buffer — clean under the donation audit, and the
    drive asserts the loss fired (never vacuously green)."""
    from harp_tpu.analysis.drivers import PROTOCOLS

    assert "elastic.rebalance_restage" in PROTOCOLS
    drive = PROTOCOLS["elastic.rebalance_restage"]()
    vs = commgraph.audit_protocol("elastic.rebalance_restage", drive)
    assert vs == [], [v.format() for v in vs]


def test_hl303_sabotaged_shrink_reusing_preloss_buffer_fires(mesh):
    """The sabotaged twin of elastic.rebalance_restage: after the
    permanent loss, the 'obvious' continuation re-dispatches the
    PRE-SHRINK staged buffer on the survivor mesh — but that buffer was
    already donated to the dead dispatch (and lives on a mesh that no
    longer exists).  The CPU sim passes it silently; HL303 must not."""
    import jax
    import jax.numpy as jnp

    from harp_tpu.parallel.mesh import WorkerMesh
    from harp_tpu.utils import flightrec
    from harp_tpu.utils.fault import FaultInjector, PermanentWorkerLoss

    audit = commgraph.DonationAudit("protocol:sabotaged_shrink")

    def build(m, tag):
        fn = jax.jit(lambda c, x: (c + x.sum(), x * 2.0),
                     donate_argnums=(1,))
        return audit.wrap(flightrec.track(fn, tag), (1,), tag)

    rng = np.random.default_rng(0)
    exe = build(mesh, "b_full")
    carry = jax.device_put(jnp.float32(0.0), mesh.replicated())
    inj = FaultInjector(seed=0, permanent={"dispatch": (1,)},
                        lost_worker=mesh.num_workers - 1)
    with audit, inj.arm():
        staged = mesh.shard_array(
            rng.normal(size=(56, 4)).astype(np.float32), 0)
        with contextlib.suppress(PermanentWorkerLoss):
            exe(carry, staged)  # donated here, then the loss fires
        surv = WorkerMesh(mesh.devices[:-1])
        exe2 = build(surv, "b_surv")
        carry2 = jax.device_put(jnp.float32(0.0), surv.replicated())
        # the sabotage: continue on the survivors WITHOUT restaging
        with contextlib.suppress(Exception):
            exe2(carry2, staged)
    assert any(v.rule == "HL303" and "already donated" in v.message
               for v in audit.violations), \
        [v.format() for v in audit.violations]


def test_commgraph_registry_is_clean_and_covers_the_surface():
    """Every registered driver extracts a clean CommGraph (no untracked
    wire, no lying sheet, no hoistable collective), the registry covers
    >= 10 programs (all six serve engines + rotate pipeline + ingest
    pair), and the serve engines' donated batch arg is visible in the
    aliasing info."""
    from harp_tpu.analysis.drivers import DRIVERS

    assert len(DRIVERS) >= 10
    assert {"serve.kmeans_assign", "serve.mfsgd_topk", "serve.lda_infer",
            "serve.mlp_logits", "serve.rf_vote", "serve.svm_scores",
            "rotate.pipeline_chunked", "ingest.accum_chunk",
            "ingest.finish_epoch"} <= set(DRIVERS)
    for name, build in DRIVERS.items():
        fn, args = build()
        vs, graph = commgraph.analyze_program(name, fn, args)
        assert vs == [], (name, [v.format() for v in vs])
        if name.startswith("serve."):
            assert graph.donated_args, name  # the batch buffer donates
    # the chunked rotate pipeline's ring traffic carries the full
    # n_chunks * ring-size amplification
    fn, args = DRIVERS["rotate.pipeline_chunked"]()
    _, graph = commgraph.analyze_program("rotate.pipeline_chunked", fn,
                                         args)
    (site,) = graph.sites
    assert site.primitive == "ppermute" and site.amplification == 16


def test_check_jsonl_commgraph_sets_in_sync():
    """check_jsonl freezes the byte-sheet vocabulary (standalone
    script); drift from the live registries fails here."""
    import check_jsonl

    from harp_tpu.analysis.drivers import DRIVERS
    from harp_tpu.parallel.collective import PRIMITIVE_VERBS

    assert tuple(sorted(DRIVERS)) == check_jsonl.KNOWN_LINT_PROGRAMS
    assert tuple(sorted(PRIMITIVE_VERBS)) == \
        check_jsonl.KNOWN_COMM_PRIMITIVES
    all_verbs = set().union(*PRIMITIVE_VERBS.values())
    assert tuple(sorted(all_verbs)) == check_jsonl.KNOWN_COMM_VERBS


# ---------------------------------------------------------------------------
# Allowlist + registry + CLI
# ---------------------------------------------------------------------------

def test_allowlist_requires_reason(tmp_path):
    p = tmp_path / "allow.toml"
    p.write_text('[[allow]]\nrule = "HL001"\npath = "x.py"\n')
    with pytest.raises(allowlist_mod.AllowlistError):
        allowlist_mod.load(str(p))


def test_allowlist_suppresses_and_reports_stale(tmp_path):
    from harp_tpu.analysis import Violation

    p = tmp_path / "allow.toml"
    p.write_text(textwrap.dedent("""
        [[allow]]
        rule = "HL001"
        path = "a.py"
        reason = "legit"
        [[allow]]
        rule = "HL002"
        path = "never.py"
        reason = "stale"
    """))
    entries = allowlist_mod.load(str(p))
    vs = [Violation("HL001", "a.py", 1, "m"),
          Violation("HL001", "b.py", 1, "m")]
    kept, suppressed, stale = allowlist_mod.apply(vs, entries)
    assert [v.path for v in kept] == ["b.py"]
    assert [v.path for v in suppressed] == ["a.py"]
    assert [e["path"] for e in stale] == ["never.py"]


def test_check_jsonl_rule_set_in_sync():
    """scripts/check_jsonl.py invariant 6 hardcodes the rule ids (the
    script stays standalone); drift from the registry fails here."""
    import check_jsonl

    assert tuple(rule_ids()) == check_jsonl.KNOWN_LINT_RULES


def test_cli_fixture_path_exits_nonzero(tmp_path, capsys):
    bad = tmp_path / "bad_module.py"
    bad.write_text("import jax\n"
                   "def f(s):\n"
                   "    return jax.random.PRNGKey(s)\n")
    rc = cli.main([str(bad), "--json"])
    out = capsys.readouterr().out.strip().splitlines()[-1]
    row = json.loads(out)
    assert rc == 1
    assert row["kind"] == "lint" and row["violations"] == 1
    assert row["per_rule"] == {"HL002": 1}
    # provenance stamp rides the line (check_jsonl invariant 6)
    assert all(k in row for k in ("backend", "date", "commit"))


def test_cli_audit_module_trips_jaxpr_and_mosaic_layers(tmp_path, capsys):
    fixture = tmp_path / "fixture_mod.py"
    fixture.write_text(textwrap.dedent("""
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def _bad_scan():
            def f(table, idxs, upds):
                def body(tbl, x):
                    i, u = x
                    vals = jnp.take(tbl, i, axis=0)
                    tbl = lax.dynamic_update_slice(tbl, u, (i[0], 0))
                    return tbl, vals.sum()
                return lax.scan(body, table, (idxs, upds))
            return f, (jnp.zeros((16, 8)), jnp.zeros((3, 2), jnp.int32),
                       jnp.zeros((3, 1, 8)))

        def _bad_kernel():
            def kern(seed_ref, o_ref):
                pltpu.prng_seed(seed_ref[0], seed_ref[1], seed_ref[2])
                bits = pltpu.prng_random_bits(o_ref.shape)
                o_ref[...] = lax.shift_right_logical(
                    bits, 8).astype(jnp.float32)
            def f(seed):
                return pl.pallas_call(kern, out_shape=jax.ShapeDtypeStruct(
                    (8, 128), jnp.float32))(seed)
            return f, (jnp.zeros(3, jnp.int32),)

        HARPLINT_DRIVERS = {"bad_scan": _bad_scan}
        HARPLINT_KERNELS = {"bad_seed": _bad_kernel}
    """))
    rc = cli.main(["--audit-module", str(fixture), "--json"])
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 1
    assert "HL101" in row["per_rule"] and "HL202" in row["per_rule"]


def test_cli_audit_module_trips_commgraph_layer(tmp_path, capsys):
    """Layer-4 exit codes through the CLI: an unledgered psum (HL301),
    a loop-invariant allgather (HL304), and a sabotaged donation
    protocol (HL303) in one fixture module must all land in per_rule
    and flip the exit code."""
    fixture = tmp_path / "fixture_cg.py"
    fixture.write_text(textwrap.dedent("""
        import contextlib

        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from harp_tpu.parallel import collective as C
        from harp_tpu.parallel.mesh import WorkerMesh
        from harp_tpu.utils import flightrec


        def _mesh():
            return WorkerMesh()


        def _x(mesh):
            return jax.ShapeDtypeStruct(
                (8, 4), jnp.float32,
                sharding=mesh.sharding(mesh.spec(0)))


        def _raw_psum():
            mesh = _mesh()
            fn = jax.jit(mesh.shard_map(
                lambda x: lax.psum(x, "workers"),
                in_specs=(mesh.spec(0),), out_specs=P()))
            return fn, (_x(mesh),)


        def _hoistable():
            mesh = _mesh()

            def prog(x):
                def body(c, _):
                    return c + C.allgather(x).sum(), None
                out, _ = lax.scan(body, jnp.float32(0.0), None,
                                  length=4)
                return out

            fn = jax.jit(mesh.shard_map(
                prog, in_specs=(mesh.spec(0),), out_specs=P()))
            return fn, (_x(mesh),)


        def _sabotage():
            def drive(audit):
                exe = jax.jit(lambda s, b: s + b, donate_argnums=(1,))
                w = audit.wrap(exe, (1,), "toy.step")
                s = jax.device_put(np.ones((4,), np.float32))
                buf = jax.device_put(np.ones((4,), np.float32))
                w(s, buf)
                with contextlib.suppress(RuntimeError):
                    flightrec.readback(buf)
            return drive


        HARPLINT_DRIVERS = {"raw_psum": _raw_psum,
                            "hoistable": _hoistable}
        HARPLINT_PROTOCOLS = {"sabotage": _sabotage}
    """))
    rc = cli.main(["--audit-module", str(fixture), "--json"])
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 1
    assert {"HL301", "HL303", "HL304"} <= set(row["per_rule"])
    # fixture rows never ship byte sheets: sheet program names are
    # pinned to the drivers registry by check_jsonl invariant 6
    assert "byte_sheets" not in row


def test_cli_stale_allowlist_entry_fails(tmp_path, capsys):
    """Satellite: a stale allowlist entry is a HARD failure, not a
    report line — same exit as an unallowlisted violation (AST-layer
    full-repo run; the committed entries are all AST-rule entries, so
    the control run stays green)."""
    committed = open(os.path.join(ROOT, "harp_tpu", "analysis",
                                  "allowlist.toml")).read()
    ok = tmp_path / "ok.toml"
    ok.write_text(committed)
    rc = cli.main(["--json", "--layer", "ast", "--allowlist", str(ok)])
    capsys.readouterr()
    assert rc == 0
    stale = tmp_path / "stale.toml"
    stale.write_text(committed + textwrap.dedent("""
        [[allow]]
        rule = "HL002"
        path = "harp_tpu/models/never_existed.py"
        reason = "synthetic stale entry for the hard-fail test"
    """))
    rc = cli.main(["--json", "--layer", "ast", "--allowlist",
                   str(stale)])
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 1
    assert row["stale_allowlist"] == 1
    assert row["clean"] is True  # no violations — the ENTRY is the rot


def test_cli_changed_mode_scopes_the_ast_layer(monkeypatch, capsys):
    """--changed lints only the git-changed files in the AST layer (the
    ~2 s dev loop); staleness reporting is disabled because an unswept
    file cannot prove an entry dead."""
    monkeypatch.setattr(cli, "_changed_paths",
                        lambda repo: ["harp_tpu/utils/timing.py"])
    rc = cli.main(["--changed", "--json", "--layer", "ast"])
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0, row
    assert row["files_scanned"] == 1
    assert row["stale_allowlist"] == 0


def test_changed_paths_subset_of_sweep():
    """_changed_paths returns repo-relative paths drawn from the same
    set the full sweep lints (deleted files never error)."""
    from harp_tpu.analysis.astlints import iter_python_files

    repo = cli.repo_root()
    changed = cli._changed_paths(repo)
    assert isinstance(changed, list)
    assert set(changed) <= set(iter_python_files(repo))


def test_cli_repo_run_is_clean(capsys):
    """THE tier-1 gate: zero unallowlisted violations at HEAD, all five
    layers, and the machine line passes check_jsonl invariant 6 — with
    the Layer-4 byte sheets riding the row (>= 10 programs; kmeans.fit
    matching the hand-computed sheet exactly)."""
    import check_jsonl

    rc = cli.main(["--json"])
    out = capsys.readouterr().out.strip().splitlines()[-1]
    row = json.loads(out)
    assert rc == 0, row
    assert row["clean"] is True and row["violations"] == 0
    assert row["stale_allowlist"] == 0
    assert check_jsonl._check_lint_row("stdout", 1, row) == []
    sheets = row["byte_sheets"]
    assert len(sheets) >= 10
    km = sheets["kmeans.fit"]
    assert km["bytes_per_trace"] == 8 * 32 * 4 + 8 * 4 + 4
    assert km["amplified_bytes"] == 2 * km["bytes_per_trace"]
    assert km["collectives"][0]["verb"] == "allreduce"


# ---------------------------------------------------------------------------
# Layer 5 — thread-root graph (HL4xx): one sabotaged plane per rule
# ---------------------------------------------------------------------------

from harp_tpu.analysis import threadgraph  # noqa: E402


def _plane(owners=("main",), name="fix"):
    return threadgraph.PlaneSpec(name, ("fix.py",), tuple(owners))


def _analyze(src, owners=("main",), spine_locked=None):
    return threadgraph.analyze_sources(
        _plane(owners), {"fix.py": textwrap.dedent(src)},
        spine_locked=spine_locked)


_HL401_SRC = """
    import threading

    import jax.numpy as jnp

    class Worker:
        def start(self):
            t = threading.Thread(target=self._work, daemon=True,
                                 name="fix-worker")
            t.start()

        def _work(self):
            return jnp.zeros((4,))
"""


def test_hl401_jax_from_non_owner_thread_fires():
    """The sabotaged twin: a named worker thread whose entry reaches a
    jax call on a plane where only main owns jax."""
    vs = _analyze(_HL401_SRC)
    assert _rules(vs) == ["HL401"]
    assert "thread:_work" in vs[0].message
    assert "jnp.zeros" in vs[0].source


def test_hl401_designated_owner_is_clean():
    """The transport-dispatcher shape: the SAME source is clean once the
    plane declares the thread root a jax owner (serve's
    thread:_dispatch_loop is the pinned real case)."""
    assert _analyze(_HL401_SRC, owners=("main", "thread:_work")) == []


_HL402_SRC = """
    import time

    class FrontEnd:
        async def _run(self):
            while True:
                self._drain()

        def _drain(self):
            time.sleep(0.1)
            self._done.wait()
"""


def test_hl402_blocking_call_in_event_loop_fires():
    """time.sleep and an unbounded Event.wait both reachable from the
    coroutine root freeze every socket the loop owns."""
    vs = _analyze(_HL402_SRC)
    assert _rules(vs) == ["HL402"] and len(vs) == 2
    assert any("time.sleep" in v.message for v in vs)
    assert any("wait" in v.source for v in vs)


def test_hl402_bounded_and_awaited_are_clean():
    vs = _analyze("""
        import asyncio

        class FrontEnd:
            async def _run(self):
                await asyncio.sleep(0.1)
                self._done.wait(0.5)
    """)
    assert vs == []


_HL403_SPINE_SRC = """
    import threading

    from harp_tpu.utils import reqtrace

    class Pump:
        def start(self):
            t = threading.Thread(target=self._pump, daemon=True,
                                 name="fix-pump")
            t.start()

        def serve_one(self):
            rid = reqtrace.tracer.begin(0.0)

        def _pump(self):
            reqtrace.tracer.event("r1", "deliver")
"""


def test_hl403_spine_written_from_two_roots_unlocked_fires():
    """The single-writer contract: main and a pump thread both hit the
    reqtrace spine, whose mutators are NOT verified locked."""
    vs = _analyze(_HL403_SPINE_SRC, spine_locked={"reqtrace": False})
    assert _rules(vs) == ["HL403"]
    assert "reqtrace" in vs[0].message
    assert "single-writer" in vs[0].message


def test_hl403_verified_locked_spine_is_clean():
    """Same two-root writes, but the spine's own mutators verified as
    internally locked (the PR-20 reqtrace RLock) — no violation."""
    assert _analyze(_HL403_SPINE_SRC,
                    spine_locked={"reqtrace": True}) == []


_HL403_ATTR_TMPL = """
    import threading

    class Counter:
        def __init__(self):
            self.n = 0
            self._lock = threading.Lock()

        def start(self):
            t = threading.Thread(target=self._bump, daemon=True,
                                 name="fix-bump")
            t.start()

        def bump_from_main(self):
            {main_write}

        def _bump(self):
            {thread_write}
"""


def test_hl403_shared_attr_two_roots_no_lock_fires():
    vs = _analyze(_HL403_ATTR_TMPL.format(
        main_write="self.n += 1", thread_write="self.n += 1"))
    assert _rules(vs) == ["HL403"]
    assert "'n'" in vs[0].message and "no common lock" in vs[0].message


def test_hl403_shared_attr_common_lock_is_clean():
    """Both write paths under self._lock: the lock sets intersect, and
    __init__ writes are exempt (construction happens-before start)."""
    vs = _analyze(_HL403_ATTR_TMPL.format(
        main_write="with self._lock:\n                self.n += 1",
        thread_write="with self._lock:\n                self.n += 1"))
    assert vs == []


def test_hl404_dispatch_under_lock_fires():
    """A tracked-executable dispatch AND a jax call inside a with-lock
    body: device round trips while holding the lock."""
    vs = _analyze("""
        class Runner:
            def flush(self, batch):
                with self._lock:
                    out = self._exec[0](batch)
                return out

            def stage(self, a, b):
                import jax.numpy as jnp
                with self._lock:
                    return jnp.dot(a, b)
    """)
    assert _rules(vs) == ["HL404"] and len(vs) == 2
    assert all("holding" in v.message for v in vs)


def test_hl404_dispatch_after_lock_release_is_clean():
    vs = _analyze("""
        class Runner:
            def flush(self):
                with self._lock:
                    batch = self._q.popleft()
                return self._exec[0](batch)
    """)
    assert vs == []


def test_hl405_unjoinable_thread_fires():
    vs = _analyze("""
        import threading

        def spawn(fn):
            t = threading.Thread(target=fn, name="fix-zombie")
            t.start()
            return t
    """)
    assert _rules(vs) == ["HL405"]
    assert "daemon" in vs[0].message


def test_hl405_daemon_or_bounded_join_is_clean():
    assert _analyze("""
        import threading

        def spawn_daemon(fn):
            t = threading.Thread(target=fn, daemon=True)
            t.start()

        def spawn_joined(fn):
            t = threading.Thread(target=fn)
            t.start()
            t.join(5.0)
    """) == []


def test_threads_layer_repo_at_head_only_allowlisted_findings():
    """The Layer-5 HEAD gate at the API level: every finding over the
    real planes is HL403 and matched by a committed allowlist entry
    (with its reviewed reason) — nothing unallowlisted, nothing stale
    among the HL4xx entries."""
    vs = threadgraph.analyze_repo(ROOT)
    assert vs, "the four reviewed HL403 findings should exist at HEAD"
    assert _rules(vs) == ["HL403"]
    entries = allowlist_mod.load()
    kept, suppressed, stale = allowlist_mod.apply(vs, entries)
    assert kept == []
    assert len(suppressed) == len(vs)
    assert not any(e["rule"].startswith("HL4") for e in stale)


def test_cli_threads_layer_scoped_run_is_clean(capsys):
    """`lint --layer threads` (the scoped run `--changed` uses): exit 0,
    every finding allowlisted, and staleness judged ONLY against
    threads-layer entries (an AST entry can't be proven dead here)."""
    rc = cli.main(["--json", "--layer", "threads"])
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0, row
    assert row["clean"] is True and row["violations"] == 0
    assert row["allowlisted"] >= 4
    assert row["stale_allowlist"] == 0


def test_planes_for_paths_scopes_changed_runs():
    """--changed scoping: a plane module maps to its plane; a spine
    module re-runs every plane (lock verdicts feed all of them); an
    unrelated file runs none."""
    assert threadgraph.planes_for_paths(["harp_tpu/ingest.py"]) == \
        ["ingest"]
    allp = [p.name for p in threadgraph.PLANES]
    assert threadgraph.planes_for_paths(
        ["harp_tpu/utils/reqtrace.py"]) == allp
    assert threadgraph.planes_for_paths(["harp_tpu/models/kmeans.py"]) \
        == []


def test_spine_lock_verification_reads_the_mutator_bodies():
    """The verdict is derived from the spine SOURCE, not asserted: a
    twin ReqTracer with one unlocked mutator flips to False."""
    spec = next(s for s in threadgraph.SPINES if s.name == "reqtrace")
    locked = textwrap.dedent("""
        class ReqTracer:
            def begin(self, t):
                with self._lock:
                    return 1
            def event(self, rid, name):
                with self._lock:
                    pass
            def end(self, rid, outcome, t):
                with self._lock:
                    pass
            def mark(self, name):
                with self._lock:
                    pass
    """)
    assert threadgraph._spine_locked_from_source(spec, locked) is True
    sabotaged = locked.replace(
        "def mark(self, name):\n        with self._lock:\n            pass",
        "def mark(self, name):\n        self.rows.append(name)")
    assert threadgraph._spine_locked_from_source(spec, sabotaged) is False
    # the REAL reqtrace at HEAD carries the PR-20 RLock
    verdicts = threadgraph.spine_lock_verdicts(ROOT)
    assert verdicts["reqtrace"] is True


def test_ownership_map_is_generated_from_the_static_graph():
    """The runtime twin's contract: forbidden patterns are exactly the
    named non-owner roots the graph discovered (watchdog, scheduler
    workers, the TCP accept loop) — and the serve dispatcher, a
    designated owner, is NOT forbidden."""
    import fnmatch

    omap = threadgraph.ownership_map(ROOT)
    pats = omap["forbidden_thread_patterns"]
    assert "harp-watchdog" in pats
    assert "harp-serve-tcp" in pats
    assert any(p.startswith("harp-sched-static-") for p in pats)
    assert any(p.startswith("harp-sched-dyn-") for p in pats)
    assert not any(fnmatch.fnmatch("harp-serve-dispatch", p)
                   for p in pats)
    assert set(omap["spines"]) == {sp.name for sp in threadgraph.SPINES}
    assert omap["spines"]["reqtrace"]["locked"] is True
    for name, plane in omap["planes"].items():
        assert set(plane["forbidden_thread_patterns"]) <= set(pats)
        assert "main" in plane["jax_owners"]
