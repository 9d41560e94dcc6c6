"""Registry of every Pallas kernel in ``ops/`` — the Mosaic audit's input
AND the cost model's kernel work sheet.

Reference parity note (SURVEY.md §3.2): Harp's native compute kernels
lived behind DAAL's JNI boundary with no enumeration — auditing them
meant reading C++.  Here each kernel registers a **builder** returning
``(fn, args)`` at a small proven shape with ``interpret=False``, so
:mod:`harp_tpu.analysis.mosaic_audit` can (a) run the full Pallas→Mosaic
lowering via ``.trace(...).lower(lowering_platforms=("tpu",))`` on the
CPU backend and (b) walk the traced jaxpr for the silicon limits local
lowering does NOT enforce (≤2 ``prng_seed`` words, sublane-aligned block
dims, no uint32→f32 cast).  Shapes mirror the smallest cases already
pinned by the kernel test files, so an audit failure means the kernel
changed, not the harness.

PR 13 (perfmodel): registration now REQUIRES a declared work model —
``flops`` (arithmetic at the registered shape), ``min_hbm_bytes`` (the
roofline-style lower-bound HBM traffic: inputs read once, outputs
written once), and ``vmem_bytes`` (the kernel's own scoped-VMEM budget
estimate at the registered shape, the same byte algebra its dispatch
gate enforces — e.g. ``kmeans_kernel._tile_rows_int8``'s OOM-calibrated
model).  The Mosaic audit and :mod:`harp_tpu.perfmodel` read ONE source
of truth: a new kernel registered without its work model raises HERE,
at import/lint time, not twenty minutes into a predict run
(tests/test_perfmodel.py pins that every entry prices without a
fallback and fits the 16 MiB VMEM ceiling).

Builders are lazy (imports inside) — registering costs nothing until an
audit actually runs, and the registry module itself imports without jax.
"""

from __future__ import annotations

from typing import Any, Callable

# name -> zero-arg builder returning (fn, args_tuple)
KERNELS: dict[str, Callable[[], tuple[Callable, tuple[Any, ...]]]] = {}

#: name -> {"flops", "min_hbm_bytes", "vmem_bytes"} at the builder's
#: registered shape (ints; every field required and positive)
KERNEL_WORK: dict[str, dict] = {}

_WORK_FIELDS = ("flops", "min_hbm_bytes", "vmem_bytes")


def register_kernel(name: str, *, flops: int, min_hbm_bytes: int,
                    vmem_bytes: int):
    """Register a kernel builder WITH its declared work model.

    The keyword fields are mandatory by signature: a kernel that cannot
    state its FLOPs, HBM floor, and VMEM footprint at its own registered
    shape is not auditable or priceable, and the failure happens at
    import time (``python -m harp_tpu lint`` imports this module) —
    loudly, before any chip time is spent discovering it.
    """
    work = {"flops": flops, "min_hbm_bytes": min_hbm_bytes,
            "vmem_bytes": vmem_bytes}
    for k in _WORK_FIELDS:
        v = work[k]
        if isinstance(v, bool) or not isinstance(v, int) or v <= 0:
            raise ValueError(
                f"kernel {name!r}: work field {k}={v!r} must be a "
                "positive int — declare the kernel's work model at its "
                "registered shape (see module docstring)")

    def deco(build):
        KERNELS[name] = build
        KERNEL_WORK[name] = work
        return build
    return deco


# kmeans.partials at (n=128, d=256, k=8, kp=128): one Lloyd partial pass.
# flops = 4ndk (distance matmul 2ndk + one-hot sums matmul 2ndk);
# min bytes = points once (f32) + centroid operand + sums/counts out;
# vmem = point tile (tn=128, double-buffered) + padded centroid operand
# + [kp, d] sums + [tn, kp] score/one-hot temporaries, all f32.
@register_kernel("kmeans.partials",
                 flops=4 * 128 * 256 * 8,
                 min_hbm_bytes=4 * (128 * 256 + 128 * 256 + 8 * 256 + 8 + 1),
                 vmem_bytes=4 * (2 * 128 * 256 + 2 * 128 * 256
                                 + 2 * 128 * 128))
def _kmeans_f32():
    import functools

    import jax.numpy as jnp

    from harp_tpu.ops.kmeans_kernel import kmeans_partials

    fn = functools.partial(kmeans_partials, interpret=False)
    return fn, (jnp.zeros((128, 256), jnp.float32),
                jnp.zeros((8, 256), jnp.float32))


# kmeans.partials_int8 at (n=128, d=256, k=8, kp=128): int8 OPs on the
# MXU (same 4ndk count), int8 points read once; vmem = the kernel's own
# OOM-calibrated byte model (kmeans_kernel._tile_rows_int8, measured
# 2026-08-01): tn·(2d + 8kp) + 5·kp·d + 64 KiB at tn=128.
@register_kernel("kmeans.partials_int8",
                 flops=4 * 128 * 256 * 8,
                 min_hbm_bytes=(128 * 256 + 128 * 256
                                + 4 * (8 * 256 + 8 + 1)),
                 vmem_bytes=128 * (2 * 256 + 8 * 128) + 5 * 128 * 256
                 + (64 << 10))
def _kmeans_int8():
    import functools

    import jax.numpy as jnp

    from harp_tpu.ops.kmeans_kernel import kmeans_partials_int8

    fn = functools.partial(kmeans_partials_int8, interpret=False)
    return fn, (jnp.zeros((128, 256), jnp.int8),
                jnp.zeros((8, 256), jnp.int8),
                jnp.zeros(8, jnp.float32),
                jnp.zeros(8, jnp.float32),
                jnp.ones(256, jnp.float32))


# lda.cgs_entry_update at (K=64, DR=WR=128, C=256): the one-entry face of
# cgs_run_update (one run, one word tile, 256-slot chunks).  Per token
# ~14K flops (posterior + draw + delta matmuls) over C tokens; min bytes
# = both table tiles in/out + token streams; vmem = the kernel's own
# lda_kernel.vmem_bytes(64, 128, 128, 256, 4, 2, 3): both tiles in and
# out, double-buffered, + live [K, cc] temporaries + gather planes.
@register_kernel("lda.cgs_entry_update",
                 flops=14 * 64 * 256,
                 min_hbm_bytes=(2 * 4 * (64 * 128 + 64 * 128) + 4 * 64
                                + 3 * 4 * 256),
                 vmem_bytes=4 * 4 * 64 * 128 + 4 * 4 * 64 * 128
                 + 6 * 4 * 64 * 256 + 6 * 64 * 128)
def _lda_cgs():
    import functools

    import jax.numpy as jnp

    from harp_tpu.ops.lda_kernel import cgs_entry_update

    # compiled path (interpret=False): exercises the REAL pltpu.prng_seed
    # / prng_random_bits lowering the silicon checks exist for
    fn = functools.partial(cgs_entry_update, alpha=0.5, beta=0.1,
                           vbeta=12.8, interpret=False)
    K, DR, WR, C = 64, 128, 128, 256
    return fn, (jnp.zeros((K, DR), jnp.float32),
                jnp.zeros((K, WR), jnp.float32),
                jnp.zeros(K, jnp.float32),
                jnp.zeros(C, jnp.int32),
                jnp.full((C,), DR, jnp.int32),
                jnp.full((C,), WR, jnp.int32),
                jnp.zeros(2, jnp.int32))


# mfsgd.sgd_tile_update at the 8-worker-sim smoke tiling (R=64,
# UB=2048, IB=13440, NCH=32 chunks of 512, tile=256): 6·R flops per
# rating over NCH·512 rating slots; min bytes = W/H blocks in+out (f32)
# + chunk streams; vmem = the kernel's own budget algebra: TWO resident
# H copies (h_in + h_out) + four [R, tile] scratch tiles + chunk streams.
@register_kernel("mfsgd.sgd_tile_update",
                 flops=6 * 64 * 32 * 512,
                 min_hbm_bytes=(2 * 4 * (64 * 2048 + 64 * 13440)
                                + 3 * 4 * 32 * 512),
                 vmem_bytes=2 * 13440 * 64 * 4 + 4 * 64 * 256 * 4
                 + 3 * 4 * 512)
def _mfsgd_tile():
    import functools

    import jax.numpy as jnp

    from harp_tpu.ops.mfsgd_kernel import sgd_tile_update

    # the 8-worker-sim smoke tiling pinned in tests/test_mfsgd_kernel.py
    R, UB, IB, NCH, cc, tile = 64, 2048, 13440, 32, 512, 256
    fn = functools.partial(sgd_tile_update, lr=0.01, reg=0.05,
                           u_tile=tile, i_tile=tile, interpret=False)
    return fn, (jnp.zeros((R, UB), jnp.float32),
                jnp.zeros((R, IB), jnp.float32),
                jnp.zeros((NCH, cc), jnp.int32),
                jnp.zeros((NCH, cc), jnp.int32),
                jnp.zeros((NCH, cc), jnp.float32),
                jnp.zeros(NCH, jnp.int32))


# flash_attention at (batch=2, T=256, d=128), causal: 4·T²·d flops per
# batch row (QK^T + PV, halved by causality, ×2 ops per MAC cancels);
# min bytes = Q/K/V read + O written (f32); vmem = Q block + K/V blocks
# + online-softmax scratch (m, l, acc) at the kernel's default blocks.
@register_kernel("flash_attention",
                 flops=2 * 4 * 256 * 256 * 128 // 2,
                 min_hbm_bytes=4 * 4 * 2 * 256 * 128,
                 vmem_bytes=4 * (3 * 256 * 128 + 256 * 128 + 2 * 256))
def _flash():
    import functools

    import jax.numpy as jnp

    from harp_tpu.ops.flash_attention import flash_attention

    fn = functools.partial(flash_attention, causal=True, interpret=False)
    q = jnp.zeros((2, 256, 128), jnp.float32)
    return fn, (q, q, q)


# svm.kernel_row at (dp=128, n_pad=512, tn=128): fused Pegasos hinge
# gradient — two MXU dots (score + gradient contraction) = 4·dp·n
# flops; min bytes = x^T read once (the fusion's whole point: ONE pass,
# not SVM_X_PASSES_PER_STEP=2) + w/b/y/sw streams + gw/gs out; vmem =
# the kernel's own byte model (svm_kernel.vmem_bytes) at tn=128.
@register_kernel("svm.kernel_row",
                 flops=4 * 128 * 512,
                 min_hbm_bytes=4 * (128 * 512 + 2 * 512 + 2 * 128 + 2),
                 vmem_bytes=2 * 128 * 128 * 4 + 6 * 128 * 4
                 + 2 * 128 * 4 + (64 << 10))
def _svm_kernel_row():
    import functools

    import jax.numpy as jnp

    from harp_tpu.ops.svm_kernel import pegasos_grad

    # the small proven shape pinned in tests/test_svm_kernel.py
    fn = functools.partial(pegasos_grad, tn=128, interpret=False)
    return fn, (jnp.zeros((128,), jnp.float32),
                jnp.float32(0.0),
                jnp.zeros((128, 512), jnp.float32),
                jnp.zeros((512,), jnp.float32),
                jnp.zeros((512,), jnp.float32))


# wdamds.smacof_dist at (N=256, n_loc=32, tn=32, dim=2): fused distance
# + Guttman B·X row block — two MXU matmuls (cross + ratio·X) =
# 4·n_loc·N·dimp flops at the padded dimp=128; min bytes = δ rows + the
# real (unpadded) X/Xl/out coordinates (D and ratio never touch HBM —
# the fusion's point); vmem = the kernel's own byte model
# (wdamds_kernel.vmem_bytes) at tn=32.
@register_kernel("wdamds.smacof_dist",
                 flops=4 * 32 * 256 * 128,
                 min_hbm_bytes=4 * (32 * 256 + 256 * 2 + 2 * 32 * 2),
                 vmem_bytes=128 * 256 * 4 + 2 * 32 * 256 * 4
                 + 3 * 32 * 256 * 4 + 4 * 32 * 128 * 4 + (64 << 10))
def _wdamds_smacof_dist():
    import functools

    import jax.numpy as jnp

    from harp_tpu.ops.wdamds_kernel import smacof_bx

    # the small proven shape pinned in tests/test_wdamds_kernel.py
    fn = functools.partial(smacof_bx, eps=1e-9, tn=32, interpret=False)
    return fn, (jnp.zeros((32, 256), jnp.float32),
                jnp.zeros((32,), jnp.float32),
                jnp.zeros((32, 2), jnp.float32),
                jnp.zeros((256, 2), jnp.float32),
                jnp.float32(256.0))


# rf.hist_bins at (n=512, fB=512, tn=128, nodeC=8): on-chip one-hot
# histogram — one int8 MXU dot per tile = 2·n·nodeCp·fB OPs (the
# transposed one-hot build is VPU); min bytes = int8 BO read once +
# row-code/weight streams + int32 histogram out (the [nodeCp, tn]
# one-hot never touches HBM — the fusion's point); vmem = the kernel's
# own byte model (rf_kernel.vmem_bytes) at tn=128.
@register_kernel("rf.hist_bins",
                 flops=2 * 512 * 8 * 512,
                 min_hbm_bytes=512 * 512 + 2 * 4 * 512 + 4 * 8 * 512,
                 vmem_bytes=2 * 128 * 512 + 4 * 128 * 4 + 8 * 128
                 + 8 * 128 * 4 + 8 * 512 * 4 + (64 << 10))
def _rf_hist_bins():
    import functools

    import jax.numpy as jnp

    from harp_tpu.ops.rf_kernel import hist_bins

    # the small proven shape pinned in tests/test_rf_kernel.py
    fn = functools.partial(hist_bins, n_node_classes=8, tn=128,
                           interpret=False)
    return fn, (jnp.zeros((512, 512), jnp.int8),
                jnp.zeros((512,), jnp.int32),
                jnp.zeros((512,), jnp.int32))
