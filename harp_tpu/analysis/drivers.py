"""Registered driver programs for the Layer-2 jaxpr and Layer-4
CommGraph sweeps.

The copy-trap / literal detectors (:mod:`harp_tpu.analysis.jaxpr_checks`)
and the communication auditor (:mod:`harp_tpu.analysis.commgraph`) need
*traced programs* to walk.  This registry builds the flagship driver
programs at small proven shapes on the active (CPU-forced) backend —
mirroring how the lowering tests pin them — so ``python -m harp_tpu
lint`` sweeps real epoch programs, not just synthetic fixtures:

- ``kmeans.fit`` — the full T-iteration Lloyd program (fori_loop body:
  the dense one-hot pattern, no gathers; its hand-computed allreduce
  byte sheet is the Layer-4 HL302 cross-check fixture);
- ``ring_attention`` — the rotate-scan K/V pipeline (a scan that carries
  and *reads* buffers every step: the structural cousin of the LDA trap
  that must stay clean);
- ``mfsgd.epoch`` — the rotation epoch with dynamic_update_slice'd
  factor tables: the closest in-tree relative of the pre-fix LDA
  copy-trap, pinned clean;
- ``serve.*`` — every serving engine's batched step at one ladder rung
  (the steady-state programs the budget guard pins);
- ``rotate.pipeline_chunked`` — PR 2's generic software double buffer
  (n_chunks=2, the former bespoke two-halves schedule);
- ``ingest.accum_chunk`` / ``ingest.finish_epoch`` — the program pair
  every IngestPipeline-shipped kmeans chunk rides: per-chunk accumulate
  (deliberately collective-free — registering it pins that emptiness in
  the byte sheet) and the epoch-end allreduce;
- ``elastic.regather`` — PR 15's mid-run state move (one all_gather
  over the reshard verb + a wire-free local gather), so an elastic
  rebalance's cost stays on the byte sheet.

Builders return ``(traced_fn_or_fn, args)``; args may be concrete arrays
or sharded ``ShapeDtypeStruct``s.  Each runs in a couple hundred ms on
the 8-sim-worker CPU mesh.

``PROTOCOLS`` registers *host-protocol* drives for the Layer-4 donation
audit (HL303): each builder returns ``drive(audit)`` which wraps its
donating executables via ``audit.wrap`` and runs the real pipeline — the
serve ``ContinuousRunner`` depth-2 in-flight loop is the motivating
case, pinned here in its correct discipline (the sabotaged twin lives in
tests/test_lint.py).
"""

from __future__ import annotations

from typing import Any, Callable

DRIVERS: dict[str, Callable[[], tuple[Callable, tuple[Any, ...]]]] = {}

#: host-protocol drives for the donation audit: name -> builder,
#: builder() -> drive, drive(commgraph.DonationAudit) -> None
PROTOCOLS: dict[str, Callable[[], Callable]] = {}


def register_driver(name: str):
    def deco(build):
        DRIVERS[name] = build
        return build
    return deco


def register_protocol(name: str):
    def deco(build):
        PROTOCOLS[name] = build
        return build
    return deco


def _mesh():
    from harp_tpu.parallel.mesh import WorkerMesh

    return WorkerMesh()


@register_driver("kmeans.fit")
def _kmeans_fit():
    import jax
    import jax.numpy as jnp

    from harp_tpu.models.kmeans import KMeansConfig, make_fit_fn

    mesh = _mesh()
    nw = mesh.num_workers
    fn = make_fit_fn(mesh, KMeansConfig(k=8, iters=2))
    pts = jax.ShapeDtypeStruct((16 * nw, 32), jnp.float32,
                               sharding=mesh.sharding(mesh.spec(0)))
    cents = jax.ShapeDtypeStruct((8, 32), jnp.float32,
                                 sharding=mesh.replicated())
    return fn, (pts, cents)


@register_driver("ring_attention")
def _ring_attention():
    import jax
    import jax.numpy as jnp

    from harp_tpu.ops.ring_attention import make_ring_attention_fn

    mesh = _mesh()
    nw = mesh.num_workers
    fn = make_ring_attention_fn(mesh, causal=True)
    qkv = jax.ShapeDtypeStruct((2, 8 * nw, 4, 16), jnp.float32,
                               sharding=mesh.sharding(mesh.spec(1, ndim=4)))
    return fn, (qkv, qkv, qkv)


@register_driver("serve.kmeans_assign")
def _serve_kmeans_assign():
    """The serving step for kmeans at one ladder rung — the steady-state
    program the budget guard pins; registered so HL101/HL102 sweep the
    serve path like every other driver."""
    import numpy as np

    from harp_tpu.serve.engines import KMeansAssign

    mesh = _mesh()
    rng = np.random.default_rng(0)
    eng = KMeansAssign(KMeansAssign.synthetic_state(rng, k=8, d=32), mesh)
    return eng.jitted(), eng.trace_args(8)


@register_driver("serve.mfsgd_topk")
def _serve_mfsgd_topk():
    """The sharded-H top-k recommendation step (local top-k + one pull
    merge) — the serve path's model-parallel program."""
    import numpy as np

    from harp_tpu.serve.engines import MFSGDTopK

    mesh = _mesh()
    nw = mesh.num_workers
    rng = np.random.default_rng(0)
    eng = MFSGDTopK(
        MFSGDTopK.synthetic_state(rng, n_users=16 * nw,
                                  n_items=8 * nw, rank=8),
        mesh, topk=4)
    return eng.jitted(), eng.trace_args(8)


@register_driver("serve.lda_infer")
def _serve_lda_infer():
    """The LDA fold-in step (fixed-iteration EM over phi): the only
    serve engine with a device-side loop, so its byte sheet pins that
    fold-in stays collective-free at every trip count."""
    import numpy as np

    from harp_tpu.serve.engines import LDAInfer

    mesh = _mesh()
    rng = np.random.default_rng(0)
    eng = LDAInfer(LDAInfer.synthetic_state(rng, vocab_size=64,
                                            n_topics=8),
                   mesh, em_iters=4)
    return eng.jitted(), eng.trace_args(8)


@register_driver("serve.mlp_logits")
def _serve_mlp_logits():
    """The MLP forward pass through models/mlp.forward — the serve
    engine that calls back into trainer code, so the sweep sees the
    shared forward program."""
    import numpy as np

    from harp_tpu.serve.engines import MLPPredict

    mesh = _mesh()
    rng = np.random.default_rng(0)
    eng = MLPPredict(MLPPredict.synthetic_state(rng, sizes=(32, 16, 4)),
                     mesh)
    return eng.jitted(), eng.trace_args(8)


@register_driver("serve.rf_vote")
def _serve_rf_vote():
    """Majority-vote forest routing (host binize feeds device routing)."""
    import numpy as np

    from harp_tpu.serve.engines import RFPredict

    mesh = _mesh()
    rng = np.random.default_rng(0)
    eng = RFPredict(RFPredict.synthetic_state(rng, n_trees=4,
                                              max_depth=3, n_features=8),
                    mesh)
    return eng.jitted(), eng.trace_args(8)


@register_driver("serve.svm_scores")
def _serve_svm_scores():
    """The linear decision function — smallest serve program, pinned so
    the sweep covers the whole engine table."""
    import numpy as np

    from harp_tpu.serve.engines import SVMPredict

    mesh = _mesh()
    rng = np.random.default_rng(0)
    eng = SVMPredict(SVMPredict.synthetic_state(rng, d=32), mesh)
    return eng.jitted(), eng.trace_args(8)


@register_driver("rotate.pipeline_chunked")
def _rotate_pipeline_chunked():
    """PR 2's generic chunked rotation epoch (n_chunks=2 — the former
    bespoke two-halves schedule) with a slice-updating step, so the
    ppermute rides a scan whose carry the step mutates: the byte sheet
    must show the ring traffic amplified by n_chunks * ring size and the
    hoist detector (HL304) must stay quiet (the payload is the updated
    carry)."""
    import jax
    import jax.numpy as jnp

    from harp_tpu.parallel.rotate import rotate_pipeline

    mesh = _mesh()
    nw = mesh.num_workers

    def epoch(acc, sl):
        def step(c, chunk, t):
            return c + chunk.sum(), chunk * 1.01

        return rotate_pipeline(step, acc, sl, n_chunks=2)

    fn = jax.jit(mesh.shard_map(
        epoch, in_specs=(mesh.spec(0), mesh.spec(0)),
        out_specs=(mesh.spec(0), mesh.spec(0))))
    acc = jax.ShapeDtypeStruct((nw,), jnp.float32,
                               sharding=mesh.sharding(mesh.spec(0)))
    sl = jax.ShapeDtypeStruct((8 * nw, 16), jnp.float32,
                              sharding=mesh.sharding(mesh.spec(0)))
    return fn, (acc, sl)


def _ingest_shapes(mesh):
    import jax
    import jax.numpy as jnp

    nw = mesh.num_workers
    k, d, chunk = 8, 16, 8 * nw
    sh0 = mesh.sharding(mesh.spec(0))
    return {
        "pts": jax.ShapeDtypeStruct((chunk, d), jnp.float32, sharding=sh0),
        "mask": jax.ShapeDtypeStruct((chunk,), jnp.float32, sharding=sh0),
        "cents": jax.ShapeDtypeStruct((k, d), jnp.float32,
                                      sharding=mesh.replicated()),
        "sums": jax.ShapeDtypeStruct((nw, k, d), jnp.float32, sharding=sh0),
        "counts": jax.ShapeDtypeStruct((nw, k), jnp.float32, sharding=sh0),
        "inertia": jax.ShapeDtypeStruct((nw,), jnp.float32, sharding=sh0),
    }


@register_driver("ingest.accum_chunk")
def _ingest_accum_chunk():
    """The per-chunk accumulate every IngestPipeline-shipped kmeans chunk
    rides (kmeans_stream._make_accum_fn) — deliberately collective-free
    (partials land in the per-worker accumulator; the epoch-end finish
    carries the ONE allreduce).  Registering it pins that emptiness: a
    collective leaking into the per-chunk path would multiply by the
    whole chunk count and show up in this byte sheet first."""
    from harp_tpu.models.kmeans_stream import StreamConfig, _make_accum_fn

    mesh = _mesh()
    s = _ingest_shapes(mesh)
    fn = _make_accum_fn(mesh, StreamConfig(k=8))
    return fn, (s["pts"], s["mask"], s["cents"], s["sums"], s["counts"],
                s["inertia"])


@register_driver("ingest.finish_epoch")
def _ingest_finish_epoch():
    """The streaming epoch tail: the one allreduce the whole chunk loop
    amortizes (kmeans_stream._make_finish_fn)."""
    from harp_tpu.models.kmeans_stream import _make_finish_fn

    mesh = _mesh()
    s = _ingest_shapes(mesh)
    fn = _make_finish_fn(mesh)
    return fn, (s["sums"], s["counts"], s["inertia"], s["cents"])


@register_driver("mfsgd.epoch")
def _mfsgd_epoch():
    from harp_tpu.models.mfsgd import MFSGD, MFSGDConfig, synthetic_ratings

    mesh = _mesh()
    nw = mesh.num_workers
    users, items, vals = synthetic_ratings(8 * nw, 16 * nw, 64 * nw,
                                           rank=4)
    model = MFSGD(8 * nw, 16 * nw, MFSGDConfig(rank=4, algo="dense"),
                  mesh=mesh)
    model.set_ratings(users, items, vals)
    # the tracked epoch program + the device operands set_ratings staged
    return model._epoch_fn, (model.W, model.H) + model._blocks


@register_driver("lda.epoch")
def _lda_epoch():
    """The third flagship rotation epoch (PR 11): Gibbs sweep on the
    dense tiled algo, word-topic slices riding the reshard-shimmed ring
    — registering it closes the flagship set (kmeans/mfsgd/lda all
    byte-sheeted) and gives the planner its lda_planner_wire /
    lda_rotate_int8 candidate site."""
    from harp_tpu.models.lda import LDA, LDAConfig, synthetic_corpus

    mesh = _mesh()
    nw = mesh.num_workers
    d_ids, w_ids = synthetic_corpus(n_docs=6 * nw, vocab_size=8 * nw,
                                    n_topics_true=3, tokens_per_doc=16,
                                    seed=0)
    model = LDA(6 * nw, 8 * nw,
                LDAConfig(n_topics=4, algo="dense", d_tile=8, w_tile=8,
                          entry_cap=32), mesh, seed=0)
    model.set_tokens(d_ids, w_ids)
    return model._epoch_fn, model._epoch_args()


@register_driver("kmeans.fit_hier")
def _kmeans_fit_hier():
    """The planner's hierarchical two-stage psum schedule on the kmeans
    fit program (flip candidate kmeans_hier_psum) — registered so
    HL301/HL302 byte-exact cross-checking covers the alternative
    schedule the planner can emit, not just the incumbent: the
    allreduce_hier site's sheet must show BOTH psum stages and agree
    with the ledger to the byte."""
    import jax
    import jax.numpy as jnp

    from harp_tpu.models.kmeans import KMeansConfig, make_fit_fn

    mesh = _mesh()
    nw = mesh.num_workers
    fn = make_fit_fn(mesh, KMeansConfig(k=8, iters=2,
                                        psum_schedule="hier"))
    pts = jax.ShapeDtypeStruct((16 * nw, 32), jnp.float32,
                               sharding=mesh.sharding(mesh.spec(0)))
    cents = jax.ShapeDtypeStruct((8, 32), jnp.float32,
                                 sharding=mesh.replicated())
    return fn, (pts, cents)


@register_driver("collective.reshard")
def _collective_reshard():
    """The reshard verb's exact lowerings in one traced program (PR 11):
    ring rotation (ppermute), dim change (all_to_all), replication
    (all_gather), and the local slice (deliberately wire-free — its
    absence from the sheet pins that a replicated→blocked move costs
    nothing).  One program, four sites, each HL301/HL302-checked."""
    import jax
    import jax.numpy as jnp

    from harp_tpu.parallel.collective import ShardSpec, reshard
    from jax.sharding import PartitionSpec as P

    mesh = _mesh()
    nw = mesh.num_workers

    def prog(x):
        rot = reshard(x, ShardSpec.blocked(0), ShardSpec.blocked(0, 1))
        swap = reshard(x, ShardSpec.blocked(0), ShardSpec.blocked(1))
        full = reshard(x, ShardSpec.blocked(0), ShardSpec.replicated())
        back = reshard(full, ShardSpec.replicated(), ShardSpec.blocked(0))
        return rot, swap, full.sum(), back

    fn = jax.jit(mesh.shard_map(
        prog, in_specs=(mesh.spec(0, ndim=2),),
        out_specs=(mesh.spec(0, ndim=2), mesh.spec(1, ndim=2), P(),
                   mesh.spec(0, ndim=2))))
    x = jax.ShapeDtypeStruct((8 * nw, nw), jnp.float32,
                             sharding=mesh.sharding(mesh.spec(0, ndim=2)))
    return fn, (x,)


@register_driver("collective.reshard_wire")
def _collective_reshard_wire():
    """The planner's non-default reshard schedules (PR 11): the chunked
    ppermute pipeline (n_chunks=2 — the sheet must show the hop at
    chunk size with 2x amplification) and the int8 quantized wire (the
    stacked-pmax scale exchange plus the narrow hop; ledger wire_dtype
    exempts it from the exact-byte cross-check, exactly like the
    *_quantized verbs)."""
    import jax
    import jax.numpy as jnp

    from harp_tpu.parallel.collective import ShardSpec, reshard

    mesh = _mesh()
    nw = mesh.num_workers

    def prog(x):
        chunked = reshard(x, ShardSpec.blocked(0), ShardSpec.blocked(0, 1),
                          n_chunks=2)
        narrow = reshard(x, ShardSpec.blocked(0), ShardSpec.blocked(0, 2),
                         wire="int8")
        return chunked, narrow

    fn = jax.jit(mesh.shard_map(
        prog, in_specs=(mesh.spec(0, ndim=2),),
        out_specs=(mesh.spec(0, ndim=2),) * 2))
    x = jax.ShapeDtypeStruct((8 * nw, 16), jnp.float32,
                             sharding=mesh.sharding(mesh.spec(0, ndim=2)))
    return fn, (x,)


@register_driver("elastic.regather")
def _elastic_regather():
    """The PR-15 elastic row move: rebalanced model-state rows ride the
    reshard verb's always-legal split — ONE all_gather (blocked →
    replicated) then a purely local gather of each worker's new rows.
    Registering it keeps the mid-run move on the CommGraph byte sheet:
    the sheet must show exactly the replication hop (no second
    collective — the local gather is wire-free), HL301/HL302-checked on
    every full lint like the other reshard programs."""
    import jax
    import jax.numpy as jnp

    from harp_tpu.elastic.move import make_regather_fn

    mesh = _mesh()
    nw = mesh.num_workers
    fn = make_regather_fn(mesh, ndim=2)
    x = jax.ShapeDtypeStruct((8 * nw, 16), jnp.float32,
                             sharding=mesh.sharding(mesh.spec(0, ndim=2)))
    rows = jax.ShapeDtypeStruct((8 * nw,), jnp.int32,
                                sharding=mesh.sharding(mesh.spec(0)))
    return fn, (x, rows)


@register_driver("svm.train")
def _svm_train():
    """The SVM outer loop (PR 12): per-round SV exchange riding
    ``reshard`` blocked→replicated (SVMConfig.sv_wire's site — the
    planner's svm_sv_bf16/_int8 candidates price it) amplified by
    ``outer_rounds``, plus the final model-average allreduce pair.  One
    of the two per-app wires that had no byte sheet (ROADMAP planner
    item, with wdamds.smacof)."""
    import jax
    import jax.numpy as jnp

    from harp_tpu.models.svm import SVMConfig, make_train_fn

    mesh = _mesh()
    nw = mesh.num_workers
    n_loc = 8
    fn = make_train_fn(mesh, SVMConfig(inner_steps=4, outer_rounds=2,
                                       sv_per_worker=4),
                       d=16, n_loc=n_loc)
    sh0 = mesh.sharding(mesh.spec(0))
    x = jax.ShapeDtypeStruct((n_loc * nw, 16), jnp.float32, sharding=sh0)
    y = jax.ShapeDtypeStruct((n_loc * nw,), jnp.float32, sharding=sh0)
    sw = jax.ShapeDtypeStruct((n_loc * nw,), jnp.float32, sharding=sh0)
    return fn, (x, y, sw)


@register_driver("svm.train_pallas")
def _svm_train_pallas():
    """The PR-17 kernelized inner solve (SVMConfig.algo='pallas' —
    ops/svm_kernel.py, flip candidate svm_kernel_pallas): same outer
    wires as svm.train, but the per-round Pegasos scan dispatches the
    fused hinge-gradient pallas_call instead of the two-pass XLA dots.
    Registered so the jaxpr sweep and the Layer-4 byte sheet cover the
    kernel arm's program — the sheet must match svm.train's (the kernel
    changes the memory schedule, not the wires)."""
    import jax
    import jax.numpy as jnp

    from harp_tpu.models.svm import SVMConfig, make_train_fn

    mesh = _mesh()
    nw = mesh.num_workers
    n_loc = 8
    fn = make_train_fn(mesh, SVMConfig(algo="pallas", inner_steps=4,
                                       outer_rounds=2, sv_per_worker=4),
                       d=16, n_loc=n_loc)
    sh0 = mesh.sharding(mesh.spec(0))
    x = jax.ShapeDtypeStruct((n_loc * nw, 16), jnp.float32, sharding=sh0)
    y = jax.ShapeDtypeStruct((n_loc * nw,), jnp.float32, sharding=sh0)
    sw = jax.ShapeDtypeStruct((n_loc * nw,), jnp.float32, sharding=sh0)
    return fn, (x, y, sw)


@register_driver("wdamds.smacof")
def _wdamds_smacof():
    """The unweighted SMACOF run (PR 12): the per-iteration coordinate
    exchange riding ``reshard`` blocked→replicated
    (MDSConfig.coord_wire's site — wdamds_coord_bf16/_int8 candidates)
    amplified by ``iters``, plus the final stress allreduce.  Closes
    the per-app wire coverage (ROADMAP planner item, with svm.train)."""
    import jax
    import jax.numpy as jnp

    from harp_tpu.models.wdamds import MDSConfig, make_smacof_fn

    mesh = _mesh()
    nw = mesh.num_workers
    n_pad = 4 * nw
    fn = make_smacof_fn(mesh, MDSConfig(dim=2, iters=2), n_pad)
    sh0 = mesh.sharding(mesh.spec(0))
    delta = jax.ShapeDtypeStruct((n_pad, n_pad), jnp.float32,
                                 sharding=sh0)
    mask = jax.ShapeDtypeStruct((n_pad,), jnp.float32, sharding=sh0)
    x0 = jax.ShapeDtypeStruct((n_pad, 2), jnp.float32,
                              sharding=mesh.replicated())
    n_real = jax.ShapeDtypeStruct((), jnp.float32,
                                  sharding=mesh.replicated())
    return fn, (delta, mask, x0, n_real)


@register_driver("wdamds.smacof_pallas")
def _wdamds_smacof_pallas():
    """The PR-17 fused Guttman step (MDSConfig.algo='pallas' —
    ops/wdamds_kernel.py, flip candidate wdamds_dist_pallas).  n_pad is
    16·nw = 128 here, NOT the xla driver's 4·nw: the pallas branch
    engages only on 128-multiple N (smaller shapes fall back to XLA and
    the sweep would silently re-trace the incumbent program)."""
    import jax
    import jax.numpy as jnp

    from harp_tpu.models.wdamds import MDSConfig, make_smacof_fn

    mesh = _mesh()
    nw = mesh.num_workers
    n_pad = 16 * nw
    fn = make_smacof_fn(mesh, MDSConfig(algo="pallas", dim=2, iters=2),
                        n_pad)
    sh0 = mesh.sharding(mesh.spec(0))
    delta = jax.ShapeDtypeStruct((n_pad, n_pad), jnp.float32,
                                 sharding=sh0)
    mask = jax.ShapeDtypeStruct((n_pad,), jnp.float32, sharding=sh0)
    x0 = jax.ShapeDtypeStruct((n_pad, 2), jnp.float32,
                              sharding=mesh.replicated())
    n_real = jax.ShapeDtypeStruct((), jnp.float32,
                                  sharding=mesh.replicated())
    return fn, (delta, mask, x0, n_real)


@register_driver("rf.grow")
def _rf_grow():
    """Per-worker forest growth + the tree allgather (PR 16): the
    level-wise one-hot histogram matmuls (the dense MXU formulation the
    perfmodel's rf term prices against the 25 GB/s scatter wall,
    measured 2026-07-30 on 1x v5e) and the forest allgather wire.
    Gives rf a Layer-2/Layer-4 byte sheet and the wall-attribution
    observatory a capture target."""
    import jax
    import jax.numpy as jnp

    from harp_tpu.models.rf import RFConfig, make_train_fn

    mesh = _mesh()
    nw = mesh.num_workers
    fn = make_train_fn(mesh, RFConfig(n_trees=2 * nw, max_depth=2,
                                      n_bins=8, seed=0), n_features=8)
    sh0 = mesh.sharding(mesh.spec(0))
    bins = jax.ShapeDtypeStruct((16 * nw, 8), jnp.int32, sharding=sh0)
    y = jax.ShapeDtypeStruct((16 * nw,), jnp.int32, sharding=sh0)
    keys = jax.ShapeDtypeStruct((nw, 2, 2), jnp.uint32, sharding=sh0)
    return fn, (bins, y, keys)


@register_driver("rf.grow_pallas")
def _rf_grow_pallas():
    """The PR-17 on-chip histogram arm (RFConfig.hist_algo='pallas' —
    ops/rf_kernel.py, flip candidate rf_hist_pallas).  n_features=16 at
    n_bins=8 gives fB = 128: the pallas branch engages only on
    128-multiple f·B (odd widths fall through to dense and the sweep
    would silently re-trace the incumbent program).  Counts are
    bit-identical to rf.grow's dense arm, so the byte sheet must match
    it too — only the memory schedule differs."""
    import jax
    import jax.numpy as jnp

    from harp_tpu.models.rf import RFConfig, make_train_fn

    mesh = _mesh()
    nw = mesh.num_workers
    fn = make_train_fn(mesh, RFConfig(hist_algo="pallas", n_trees=2 * nw,
                                      max_depth=2, n_bins=8, seed=0),
                       n_features=16)
    sh0 = mesh.sharding(mesh.spec(0))
    bins = jax.ShapeDtypeStruct((16 * nw, 16), jnp.int32, sharding=sh0)
    y = jax.ShapeDtypeStruct((16 * nw,), jnp.int32, sharding=sh0)
    keys = jax.ShapeDtypeStruct((nw, 2, 2), jnp.uint32, sharding=sh0)
    return fn, (bins, y, keys)


@register_driver("subgraph.count")
def _subgraph_count():
    """One color-coding DP chunk over the padded CSR + exact segment
    overflow tail, ending in the counts allreduce (PR 16).  The fn
    comes back flightrec-tracked (tag "subgraph.count"), matching the
    real driver loop; colors ride spec(1), everything else spec(0) —
    the traversal gather pattern the perfmodel's subgraph term prices.

    One lint-facing constraint: the model's `_FN_CACHE` is cleared so
    every analysis layer re-traces (a cache hit skips the Python body
    and the CommLedger never records — HL301 fires on a wire that IS
    verb-routed).  The trial chunk is 1; since PR 38 a chunk's trials
    are the minor index of the tables (no `jax.vmap`), so the ledger
    and the static sheet agree at any chunk."""
    import jax
    import jax.numpy as jnp

    from harp_tpu.models import subgraph as SG
    from harp_tpu.models.subgraph import TEMPLATES, make_colorful_count_fn

    mesh = _mesh()
    nw = mesh.num_workers
    n_pad, deg = 8 * nw, 4
    SG._FN_CACHE.clear()
    fn = make_colorful_count_fn(TEMPLATES["u3-path"], 3, mesh, "segment")
    sh0 = mesh.sharding(mesh.spec(0))
    nbr = jax.ShapeDtypeStruct((n_pad, deg), jnp.int32, sharding=sh0)
    msk = jax.ShapeDtypeStruct((n_pad, deg), jnp.float32, sharding=sh0)
    o_nbr = jax.ShapeDtypeStruct((nw,), jnp.int32, sharding=sh0)
    o_row = jax.ShapeDtypeStruct((nw,), jnp.int32, sharding=sh0)
    o_msk = jax.ShapeDtypeStruct((nw,), jnp.float32, sharding=sh0)
    colors = jax.ShapeDtypeStruct(
        (1, n_pad), jnp.int32, sharding=mesh.sharding(mesh.spec(1)))
    return fn, (nbr, msk, o_nbr, o_row, o_msk, colors)


# ---------------------------------------------------------------------------
# Donation-audit protocols (Layer 4, HL303)
# ---------------------------------------------------------------------------

def _serve_continuous_drive(app: str, engine_cls, state_kw: dict,
                            req_rows: int):
    """Build+drive the real ContinuousRunner depth-2 pipeline for one
    app under a DonationAudit: synthetic state, two-rung ladder, six
    requests interleaved with steps so batches genuinely overlap in
    flight — the correct staging discipline (a FRESH buffer per batch,
    donated exactly once, never touched after) must come out clean."""

    def drive(audit):
        import numpy as np

        from harp_tpu.serve.server import Server

        rng = np.random.default_rng(0)
        srv = Server(app, state=engine_cls.synthetic_state(rng, **state_kw),
                     mesh=_mesh(), ladder=(1, 8))
        srv.startup()
        n_state = len(srv.engine.state_args())
        srv.wrap_executables(
            lambda rung, exe: audit.wrap(exe, (n_state,),
                                         f"serve.{app}.b{rung}"))
        runner = srv.make_runner(depth=2)
        for i in range(6):
            runner.submit(i, srv.engine.synthetic_request(rng, req_rows))
            runner.step()
        runner.drain()

    return drive


@register_protocol("serve.kmeans_continuous")
def _serve_kmeans_protocol():
    from harp_tpu.serve.engines import KMeansAssign

    return _serve_continuous_drive("kmeans", KMeansAssign,
                                   {"k": 8, "d": 32}, req_rows=3)


@register_protocol("serve.mfsgd_continuous")
def _serve_mfsgd_protocol():
    """The model-parallel engine (sharded H, donated user-id batch) —
    the depth-2 pipeline the HL303 rule exists for."""
    from harp_tpu.serve.engines import MFSGDTopK

    return _serve_continuous_drive(
        "mfsgd", MFSGDTopK,
        {"n_users": 64, "n_items": 32, "rank": 8}, req_rows=3)


@register_protocol("serve.retry_restage")
def _serve_retry_restage_protocol():
    """The fault plane's retry path (PR 10): a seeded FaultInjector kills
    dispatches mid-pipeline and the ContinuousRunner retries each failed
    batch — ALWAYS through a freshly staged input buffer, because the
    failed attempt's buffer was already donated to the dead dispatch.
    Driving the retry loop here proves that discipline under the HL303
    audit on every full lint run (the sabotaged twin — re-dispatching
    the donated buffer on retry — lives in tests/test_lint.py); the
    drive also asserts the faults actually fired, so a refactor that
    silently unhooks the injector fails the lint instead of passing
    vacuously."""

    def drive(audit):
        import numpy as np

        from harp_tpu.serve.engines import KMeansAssign
        from harp_tpu.serve.server import Server
        from harp_tpu.utils.fault import FaultInjector

        rng = np.random.default_rng(0)
        srv = Server("kmeans",
                     state=KMeansAssign.synthetic_state(rng, k=8, d=32),
                     mesh=_mesh(), ladder=(1, 8))
        srv.startup()
        n_state = len(srv.engine.state_args())
        srv.wrap_executables(
            lambda rung, exe: audit.wrap(exe, (n_state,),
                                         f"serve.kmeans.b{rung}"))
        runner = srv.make_runner(depth=2, max_retries=2)
        inj = FaultInjector(seed=0, fail={"dispatch": (2,)})
        with inj.arm():
            for i in range(6):
                runner.submit(i, srv.engine.synthetic_request(rng, 3))
                runner.step()
            runner.drain()
        assert inj.injected["dispatch"] == 1, "no fault fired: vacuous"
        assert runner.fault_retries == 1, "fault fired but no retry ran"
        assert runner.completed == 6, "retry path lost responses"

    return drive


@register_protocol("elastic.rebalance_restage")
def _elastic_rebalance_restage_protocol():
    """The PR-15 restage-after-shrink path (HL303): a host loop donates
    a freshly staged batch per dispatch; an injected PERMANENT worker
    loss kills a dispatch mid-run, the loop shrinks to the survivor
    mesh, rebuilds its executable there, and must RESTAGE every
    post-shrink input from host data — the pre-shrink buffer was
    already donated to the dead dispatch (and lives on a mesh that no
    longer exists).  Driving it here proves the discipline under the
    donation audit on every full lint; the sabotaged twin
    (re-dispatching the pre-shrink donated buffer on the survivors)
    lives in tests/test_lint.py.  The drive asserts the loss actually
    fired, so a refactor that unhooks the injector fails the lint
    instead of passing vacuously."""

    def drive(audit):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from harp_tpu.parallel.mesh import WorkerMesh
        from harp_tpu.utils import flightrec
        from harp_tpu.utils.fault import (FaultInjector,
                                          PermanentWorkerLoss)

        def build(mesh, tag):
            fn = jax.jit(lambda c, x: (c + x.sum(), x * 2.0),
                         donate_argnums=(1,))
            return audit.wrap(flightrec.track(fn, tag), (1,), tag)

        mesh = WorkerMesh()
        exe = build(mesh, "elastic.step_full")
        carry = jax.device_put(jnp.float32(0.0), mesh.replicated())
        rng = np.random.default_rng(0)
        # 56 rows: divisible by the 8-worker mesh AND any 7-survivor one
        batches = [rng.normal(size=(56, 4)).astype(np.float32)
                   for _ in range(4)]
        inj = FaultInjector(seed=0, permanent={"dispatch": (2,)},
                            lost_worker=mesh.num_workers - 1)
        survived = False
        with inj.arm():
            try:
                for b in batches[:2]:
                    staged = mesh.shard_array(b, 0)  # fresh per dispatch
                    carry, _ = exe(carry, staged)
            except PermanentWorkerLoss as e:
                surv = WorkerMesh([d for i, d in enumerate(mesh.devices)
                                   if i != e.worker])
                exe2 = build(surv, "elastic.step_surv")
                carry = jax.device_put(
                    jnp.float32(float(np.asarray(carry))),
                    surv.replicated())
                for b in batches[2:]:
                    staged = surv.shard_array(b, 0)  # RESTAGE on survivors
                    carry, _ = exe2(carry, staged)
                survived = True
        assert inj.permanent_fired, "no permanent loss fired: vacuous"
        assert survived, "loss fired but the survivor loop never ran"

    return drive
