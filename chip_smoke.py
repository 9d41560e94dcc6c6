#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the north-star pair of BASELINE.json once, at full width, through
the functions the launcher calls (``models/kmeans.benchmark``
and ``.fit``, ``models/mfsgd.benchmark``), then compiles and executes every
Pallas kernel in ``ops/kernel_registry.KERNELS`` against the reference its
own test file uses, then (on more than one device) checks each base verb of
``harp_tpu/benchmark.VERBS`` and ``collective.barrier`` against numpy.

One process, every device ``jax.devices()`` returns.  Exit status is
non-zero unless the backend is a TPU and every phase passed: no phase's
exception, mismatch or non-finite value is caught.  The last stdout line
is ``{"ok": true, "device": {"platform", "kind", "count"}}``.

    python chip_smoke.py            # no options: nothing lets it pass on a CPU

Tests reach the phases by import at toy shapes (tests/test_chip_smoke.py).
Not a benchmark: weights and data are random from fixed seeds, the rates
it prints are from one cold run each.
"""

from __future__ import annotations

import contextlib
import functools
import importlib.metadata
import json
import math
import sys
import time

import numpy as np

MOSAIC_CALL = "tpu_custom_call"  # analysis/mosaic_audit.py's marker

# The fused int8 kernel against the XLA int8 arm at 1M×300 k=100.  Sums
# and counts are exact integers on both arms, so one Lloyd step differs
# only where two programs round a near-tied score differently, and Lloyd
# then amplifies it: measured on the chip (2026-09-26, random-normal
# points) the centroids are bit-identical after 2 iterations and differ by
# up to 0.024 after 10, with the inertia 5e-6 apart.  So centroids are
# compared after 2 iterations (scripts/kernel_equiv_check.py uses rtol
# 1e-5 at toy size; 2e-3 absolute here admits a handful of near-tie
# flips, each worth |x|/count ≈ 4e-4, while a dropped 5000-row tile moves
# the inertia by 5e-3 relative) and only the inertia after 10.
KMEANS_FIT_ITERS = 2
KMEANS_CENTROID_ATOL = 2e-3
KMEANS_INERTIA_RTOL = 1e-5      # after KMEANS_FIT_ITERS iterations
KMEANS_INERTIA_RTOL_10 = 1e-4   # after the benchmark's 10
MFSGD_RMSE_RTOL = 0.01  # pallas vs dense at the same seed (both 0.366, 2026-08-01)

# graded config #1 (BASELINE.json), >= 10 Lloyd iterations
KMEANS_FULL = {"n": 1_000_000, "d": 300, "k": 100, "iters": 10}


class Meter:
    """Compile seconds, persistent-cache hits and the Mosaic calls of
    every tracked program, read off flightrec's observer hooks."""

    def __init__(self):
        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.programs: list[tuple[str, int]] = []  # (label, mosaic calls)
        self._seen: dict[int, object] = {}

    def on_compile(self, kind: str, seconds: float) -> None:
        if kind == "cache_hit":
            self.cache_hits += 1
        else:
            self.compiles += 1
            self.compile_s += seconds

    def on_program(self, label, fn, args, kw) -> None:
        if id(fn) in self._seen:
            return
        self._seen[id(fn)] = fn  # held, so the id cannot be reused
        # an AOT executable (mfsgd.compile_epochs) prints itself; a jitted
        # callable is lowered with the very arguments it is about to get
        text = (fn.as_text() if hasattr(fn, "as_text")
                else fn.lower(*args, **kw).as_text())
        self.programs.append((label, text.count(MOSAIC_CALL)))

    @contextlib.contextmanager
    def watching(self):
        from harp_tpu.utils import flightrec

        with flightrec.observe_compiles(self.on_compile), \
                flightrec.observe_programs(self.on_program):
            yield self

    def mosaic_calls(self, since: int, label: str) -> int:
        """Mosaic calls in the ``label`` programs first run since
        ``len(self.programs)`` was ``since``."""
        hits = [n for lb, n in self.programs[since:] if lb == label]
        if not hits:
            raise AssertionError(f"no tracked program {label!r} ran")
        return sum(hits)


def _require_arm(meter, since, label, *, mosaic: bool, on_tpu: bool):
    """The program really is the arm the phase claims: a Pallas phase's
    compiled program holds a Mosaic call (neither interpret mode nor an
    XLA fallback passes for the kernel), an XLA phase's holds none."""
    calls = meter.mosaic_calls(since, label)
    if mosaic and on_tpu and calls < 1:
        raise AssertionError(
            f"{label}: no {MOSAIC_CALL} in the lowered program — the "
            "Pallas kernel fell out of the compiled path")
    if not mosaic and calls:
        raise AssertionError(
            f"{label}: {calls} {MOSAIC_CALL} in what should be the XLA arm")
    return calls


def _finite(name: str, value) -> None:
    """Every number a phase reports must be finite."""
    if isinstance(value, dict):
        for k, v in value.items():
            _finite(f"{name}.{k}", v)
    elif isinstance(value, (list, tuple)):
        for i, v in enumerate(value):
            _finite(f"{name}[{i}]", v)
    elif isinstance(value, (float, np.floating)) and not math.isfinite(value):
        raise AssertionError(f"{name} is not finite: {value!r}")


# ---------------------------------------------------------------------------
# Phase 1 — KMeans, graded config #1
# ---------------------------------------------------------------------------

def phase_kmeans(mesh, meter, *, n, d, k, iters, on_tpu) -> dict:
    """f32 XLA arm + int8 fused Pallas arm through ``kmeans.benchmark``
    (the launcher's two kmeans arms), and the XLA int8 arm as the fused
    kernel's reference: equal inertia after ``iters`` Lloyd iterations."""
    from harp_tpu.models import kmeans

    out = {}
    for name, arm, kw in (
            ("f32_xla", "xla_f32", {"use_pallas": False}),
            ("int8_fused", "pallas_int8",
             {"quantize": "int8", "use_pallas": True}),
            ("int8_xla", "xla_int8",
             {"quantize": "int8", "use_pallas": False})):
        since = len(meter.programs)
        r = kmeans.benchmark(n=n, d=d, k=k, iters=iters, warmup=2,
                             mesh=mesh, **kw)
        if r["arm"] != arm:
            raise AssertionError(f"kmeans {name}: ran {r['arm']}, not {arm}")
        calls = _require_arm(meter, since, "kmeans.benchmark",
                             mosaic=arm.startswith("pallas"), on_tpu=on_tpu)
        out[name] = {"arm": r["arm"], "mosaic_calls": calls,
                     "inertia": r["inertia"],
                     "sec_per_iter": r["sec_per_iter"]}
    np.testing.assert_allclose(
        out["int8_fused"]["inertia"], out["int8_xla"]["inertia"],
        rtol=KMEANS_INERTIA_RTOL_10,
        err_msg="fused int8 kernel vs XLA int8 arm: inertia")
    return {"n": n, "d": d, "k": k, "iters": iters,
            "num_workers": mesh.num_workers, **out}


def phase_kmeans_fit(mesh, meter, *, n, d, k, on_tpu) -> dict:
    """The launcher's non-bench path (``kmeans.fit``: host points staged
    through ``mesh.shard_array``, centroids read back) on both int8 arms
    — the centroid-level twin of scripts/kernel_equiv_check.py's check 3
    at the graded shape (tolerances: see KMEANS_FIT_ITERS above)."""
    from harp_tpu.models import kmeans

    pts = np.random.default_rng(0).standard_normal((n, d), dtype=np.float32)
    got = {}
    for name, use_pallas in (("xla", False), ("fused", True)):
        since = len(meter.programs)
        got[name] = kmeans.fit(pts, k=k, iters=KMEANS_FIT_ITERS, mesh=mesh,
                               seed=5, quantize="int8",
                               use_pallas=use_pallas)
        _require_arm(meter, since, "kmeans.fit", mosaic=use_pallas,
                     on_tpu=on_tpu)
    (ca, ia), (cb, ib) = got["xla"], got["fused"]
    np.testing.assert_allclose(
        cb, ca, rtol=0, atol=KMEANS_CENTROID_ATOL,
        err_msg="fused int8 kernel vs XLA int8 arm: centroids")
    np.testing.assert_allclose(ib, ia, rtol=KMEANS_INERTIA_RTOL)
    return {"n": n, "d": d, "k": k, "iters": KMEANS_FIT_ITERS,
            "inertia": ib,
            "centroid_max_abs_diff": float(np.abs(cb - ca).max()),
            "centroid_atol": KMEANS_CENTROID_ATOL}


# ---------------------------------------------------------------------------
# Phase 2 — MF-SGD, graded config #2
# ---------------------------------------------------------------------------

def phase_mfsgd(mesh, meter, *, on_tpu, **shape) -> dict:
    """``algo="pallas"`` (the default, auto 256×256 tiles unless ``shape``
    pins toy ones) and ``algo="dense"`` through ``mfsgd.benchmark``:
    RMSE finite, falling, and equal across the two within 1%."""
    from harp_tpu.models import mfsgd

    out = {}
    for algo in ("pallas", "dense"):
        since = len(meter.programs)
        r = mfsgd.benchmark(mesh=mesh, algo=algo, **shape)
        calls = sum(_require_arm(meter, since, label,
                                 mosaic=algo == "pallas", on_tpu=on_tpu)
                    for label in ("mfsgd.epoch", "mfsgd.epochs"))
        if not r["rmse_final"] < r["rmse_first_epoch"]:
            raise AssertionError(
                f"mfsgd {algo}: RMSE did not fall "
                f"({r['rmse_first_epoch']} -> {r['rmse_final']})")
        out[algo] = {"algo": r["algo"], "mosaic_calls": calls,
                     "rmse_first_epoch": r["rmse_first_epoch"],
                     "rmse_final": r["rmse_final"],
                     "sec_per_epoch": r["sec_per_epoch"],
                     "prep_sec": r["prep_sec"]}
    np.testing.assert_allclose(
        out["pallas"]["rmse_final"], out["dense"]["rmse_final"],
        rtol=MFSGD_RMSE_RTOL, err_msg="mfsgd pallas vs dense rmse_final")
    return {"nnz": r["nnz"], "rank": r["rank"],
            "num_workers": r["num_workers"], **out}


# ---------------------------------------------------------------------------
# Phase 3 — every registered kernel, compiled, against its test reference
# ---------------------------------------------------------------------------
# One check per kernel, at the registry builder's shape.  Each takes the
# kernel callable (the registry's, ``interpret=False``; tests rebind it to
# interpret mode) and returns (reference outputs..., kernel outputs...)
# already compared.  Tolerances follow the kernel's test file; where the
# chip's arithmetic licenses a looser one the reason is on the line.

def _blobs(n, d, k, seed=0):
    # well-separated clusters: assignment is unambiguous under the bf16
    # scoring both arms use (tests/test_kmeans_kernel.py:_blobs)
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(k, d)).astype(np.float32) * 8.0
    assign = rng.integers(0, k, n)
    pts = centers[assign] + rng.normal(size=(n, d)).astype(np.float32) * 0.1
    return pts.astype(np.float32), centers


def _check_kmeans_f32(run):
    import jax.numpy as jnp

    from harp_tpu.models.kmeans import _partials_block

    pts, centers = _blobs(128, 256, 8)
    c = jnp.asarray(centers)
    s1, n1, i1 = run(jnp.asarray(pts), c)
    s2, n2, i2 = _partials_block(jnp.asarray(pts), c, (c ** 2).sum(-1))
    np.testing.assert_array_equal(np.asarray(n1), np.asarray(n2))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=2e-2, atol=2e-2)
    # bf16 scoring: inertia error scales with Σ‖x‖² (kernel docstring)
    x2 = float((pts.astype(np.float64) ** 2).sum())
    assert abs(float(i1) - float(i2)) < 4e-3 * x2, (float(i1), float(i2))


def _check_kmeans_int8(run):
    import jax.numpy as jnp

    from harp_tpu.models.kmeans import (_partials_block_int8,
                                        _quantize_centroids,
                                        quantize_points_int8)

    pts, centers = _blobs(128, 256, 8)
    q, scale = (jnp.asarray(a) for a in quantize_points_int8(pts))
    c = jnp.asarray(centers)
    c_q, c_scale, c2 = _quantize_centroids(c, scale)
    s1, n1, best = run(q, c_q, c_scale, c2, scale)
    i1 = best + ((q.astype(jnp.float32) * scale[None, :]) ** 2).sum()
    s2, n2, i2 = _partials_block_int8(q, scale, c, c2)
    # exact integer matmuls on both sides: BITWISE sums and counts
    np.testing.assert_array_equal(np.asarray(n1), np.asarray(n2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    np.testing.assert_allclose(float(i1), float(i2), rtol=1e-5)


def _check_lda(run):
    """tests/test_lda_kernel.py::test_kernel_draws_from_posterior at the
    registry shape: topic frequencies over fresh seeds must match
    p ∝ (ndk+α)(nwk+β)/(nk+Vβ).  On the chip the bits come from the
    hardware PRNG, which no CPU test exercises — a stuck or correlated
    stream shows up as one topic taking every draw."""
    import jax.numpy as jnp

    K, DR, WR, C = 64, 128, 128, 256
    rng = np.random.default_rng(0)
    av = rng.integers(1, 5, K).astype(np.float64) * 10_000
    bv = rng.integers(1, 5, K).astype(np.float64) * 10_000
    DbT = jnp.zeros((K, DR), jnp.float32).at[:, 0].set(jnp.asarray(av, jnp.float32))
    WbT = jnp.zeros((K, WR), jnp.float32).at[:, 0].set(jnp.asarray(bv, jnp.float32))
    nk = jnp.full((K,), 1e6, jnp.float32)
    z = jnp.zeros(C, jnp.int32)   # current topic 0 (consistent: av[0] ≫ C)
    cd = jnp.zeros(C, jnp.int32)
    cw = jnp.zeros(C, jnp.int32)
    # remove-current: topic 0 scores (a0−1)(b0−1)/(c0−1); the registry
    # builder fixes alpha=0.5, beta=0.1, vbeta=12.8
    a, b, c = av + 0.5, bv + 0.1, np.full(K, 1e6) + 12.8
    a[0] -= 1
    b[0] -= 1
    c[0] -= 1
    p = a * b / c
    p /= p.sum()
    reps, counts = 24, np.zeros(K)
    for r in range(reps):
        Db2, Wb2, z_new, dnk = run(DbT, WbT, nk, z, cd, cw,
                                   jnp.array([3, 100 + r], jnp.int32))
        hist = np.bincount(np.asarray(z_new), minlength=K)
        counts += hist
        # count bookkeeping, every call: dnk ≡ assignment histogram delta,
        # and the tiles moved by exactly that
        delta = hist - np.array([C] + [0] * (K - 1))
        np.testing.assert_array_equal(np.asarray(dnk), delta)
        np.testing.assert_array_equal(
            np.asarray(Db2)[:, 0] - np.asarray(DbT)[:, 0], delta)
        np.testing.assert_array_equal(
            np.asarray(Wb2)[:, 0] - np.asarray(WbT)[:, 0], delta)
    freq = counts / (reps * C)
    se = np.sqrt(p * (1 - p) / (reps * C)).max()
    np.testing.assert_allclose(freq, p, atol=5 * se + 0.005)


def _check_mfsgd(run):
    """The kernel replays ``mfsgd._tile_block_update`` entry for entry
    (tests/test_mfsgd_kernel.py pins it through whole epochs).  bf16
    operands on both sides (the registry's default compute dtype, and the
    only one whose XLA twin is defined the same way on a TPU, where an
    f32 dot runs as one bf16 pass); what may differ is accumulation
    order, and a gradient that lands on a bf16 rounding boundary."""
    import jax
    import jax.numpy as jnp

    from harp_tpu.models import mfsgd
    from harp_tpu.ops.mfsgd_kernel import insert_coverage_entries

    R, UB, IB, NE, C, tile = 64, 2048, 13440, 8, 2048, 256
    rng = np.random.default_rng(1)
    W = rng.uniform(0, R ** -0.5, (UB, R)).astype(np.float32)
    H = rng.uniform(0, R ** -0.5, (IB, R)).astype(np.float32)
    eu = rng.integers(0, tile, (NE, C)).astype(np.int32)
    eu[:, -200:] = tile                          # pad slots drop out
    ei = rng.integers(0, tile, (NE, C)).astype(np.int32)
    ev = rng.normal(size=(NE, C)).astype(np.float32)
    ou = (np.arange(NE) * tile).astype(np.int32)  # u-major, full coverage
    oi = (rng.integers(0, IB // tile, NE) * tile).astype(np.int32)
    block = tuple(jnp.asarray(a) for a in (eu, ei, ev, ou, oi))
    # the kernel's layout of the same entries: four 512-wide chunks each
    chunks = insert_coverage_entries(
        *(a[None] for a in (eu, ei, ev, ou, oi)), UB, tile, tile)
    assert chunks[0].shape == (1, 4 * NE, 512)
    Wt, Ht, se, cnt = run(jnp.asarray(W.T), jnp.asarray(H.T),
                          *(jnp.asarray(a[0]) for a in chunks))
    cfg = mfsgd.MFSGDConfig(rank=R, algo="dense", u_tile=tile, i_tile=tile,
                            lr=0.01, reg=0.05)
    W2, H2, se2, cnt2 = jax.jit(
        lambda w, h, blk: mfsgd._tile_block_update(w, h, blk, cfg))(
        jnp.asarray(W), jnp.asarray(H), block)
    np.testing.assert_allclose(np.asarray(Wt).T, np.asarray(W2),
                               rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(np.asarray(Ht).T, np.asarray(H2),
                               rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(float(se), float(se2), rtol=1e-3)
    assert float(cnt) == float(cnt2) == NE * (C - 200)


def _check_flash(run):
    import jax
    import jax.numpy as jnp

    from harp_tpu.ops.flash_attention import reference_attention

    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 256, 128)), jnp.float32)
               for _ in range(3))
    out = run(q, k, v)
    # the reference's einsums would run as single bf16 passes on a TPU
    with jax.default_matmul_precision("highest"):
        ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)


def _bf16_exact(a):
    """Round to the nearest bfloat16-representable float32."""
    import ml_dtypes

    return np.asarray(a, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


def _check_svm(run):
    """tests/test_svm_kernel.py's numpy golden on bf16-exact operands.
    An f32 dot runs as one bf16 MXU pass on the chip (Mosaic and XLA
    alike; measured here: 0.17 absolute on |gw| ≈ 40 over 512 random
    samples), so operands the pass cannot round keep the comparison as
    tight as the CPU test's — tight enough to see a dropped tile."""
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    dp, n = 128, 512
    x = _bf16_exact(rng.normal(size=(n, dp)))
    y = np.sign(rng.normal(size=n)).astype(np.float32)
    sw = _bf16_exact(rng.uniform(0.5, 2.0, n))
    w = _bf16_exact(rng.normal(size=dp) / np.sqrt(dp))
    b = np.float32(0.3)
    margin = y * (x.astype(np.float64) @ w + b)
    # a sample within accumulation rounding of the hinge would flip sides
    # between the kernel and numpy; weight 0 takes it out of both sums
    sw[np.abs(margin - 1.0) < 1e-3] = 0.0
    gw, gs = run(jnp.asarray(w), jnp.asarray(b), jnp.asarray(x.T),
                 jnp.asarray(y), jnp.asarray(sw))
    coef = np.where(margin < 1.0, sw, 0.0) * y
    np.testing.assert_allclose(np.asarray(gw), coef @ x.astype(np.float64),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(float(gs), coef.sum(), rtol=1e-5, atol=1e-5)


def _check_wdamds(run):
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    N, n_loc, dim, eps = 256, 32, 2, 1e-9
    pts = rng.normal(size=(N, dim)).astype(np.float32)
    delta = np.sqrt(((pts[:n_loc, None] - pts[None]) ** 2).sum(-1))
    X = rng.normal(size=(N, dim)).astype(np.float32)
    rm = np.ones(n_loc, np.float32)
    out = run(jnp.asarray(delta), jnp.asarray(rm), jnp.asarray(X[:n_loc]),
              jnp.asarray(X), jnp.float32(N))
    # the XLA body's math (models/wdamds.py) in numpy
    Xl = X[:n_loc].astype(np.float64)
    Xd = X.astype(np.float64)
    D = np.sqrt(np.maximum((Xl ** 2).sum(-1)[:, None] - 2.0 * Xl @ Xd.T
                           + (Xd ** 2).sum(-1)[None, :], 0.0))
    ratio = np.where(D > eps, delta / np.maximum(D, eps), 0.0)
    exp = (-ratio @ Xd + ratio.sum(1)[:, None] * Xl) / N
    np.testing.assert_allclose(np.asarray(out), exp, rtol=2e-2, atol=2e-2)


def _check_rf(run):
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    n, f, B, nodeC = 512, 64, 8, 8               # fB = 512
    bins = rng.integers(0, B, (n, f))
    rowcode = rng.integers(0, nodeC, n).astype(np.int32)
    w = rng.poisson(1.0, n).astype(np.int32)
    BO = np.zeros((n, f * B), np.int8)
    BO[np.arange(n)[:, None], np.arange(f)[None, :] * B + bins] = 1
    hist = run(jnp.asarray(BO), jnp.asarray(rowcode), jnp.asarray(w))
    exp = np.zeros((nodeC, f * B), np.int64)
    np.add.at(exp, (rowcode[:, None], np.arange(f)[None, :] * B + bins),
              w[:, None])
    # integer counts: a single off-by-one can change a Gini argmin
    np.testing.assert_array_equal(np.asarray(hist), exp)


KERNEL_CHECKS = {
    "kmeans.partials": _check_kmeans_f32,
    "kmeans.partials_int8": _check_kmeans_int8,
    "lda.cgs_entry_update": _check_lda,
    "mfsgd.sgd_tile_update": _check_mfsgd,
    "flash_attention": _check_flash,
    "svm.kernel_row": _check_svm,
    "wdamds.smacof_dist": _check_wdamds,
    "rf.hist_bins": _check_rf,
}


def phase_kernels(meter, *, on_tpu) -> dict:
    """Compile (``interpret=False`` on the chip) and execute every
    registered kernel; each must hold a Mosaic call and agree with its
    reference.  A kernel registered without a check here fails the
    smoke, so a new kernel cannot skip its first run on silicon."""
    import jax

    from harp_tpu.ops.kernel_registry import KERNELS

    if set(KERNEL_CHECKS) != set(KERNELS):
        raise AssertionError(
            "chip_smoke.KERNEL_CHECKS and ops/kernel_registry.KERNELS "
            f"differ: {sorted(set(KERNEL_CHECKS) ^ set(KERNELS))}")
    out = {}
    for name in sorted(KERNELS):
        fn, _ = KERNELS[name]()
        if not on_tpu:  # tests: the same builder, interpreted
            fn = functools.partial(fn.func, *fn.args,
                                   **{**fn.keywords, "interpret": True})
        jitted = jax.jit(fn)
        mosaic = []

        def run(*args):
            lowered = jitted.lower(*args)
            mosaic.append(lowered.as_text().count(MOSAIC_CALL))
            return jax.block_until_ready(lowered.compile()(*args))

        t0 = time.perf_counter()
        KERNEL_CHECKS[name](run)
        if on_tpu and not all(mosaic):
            raise AssertionError(
                f"kernel {name}: no {MOSAIC_CALL} in the lowered program")
        out[name] = {"mosaic_calls": mosaic[0], "verdict": "ok",
                     "sec": round(time.perf_counter() - t0, 3)}
    return out


# ---------------------------------------------------------------------------
# Phase 4 — the base verbs across real devices
# ---------------------------------------------------------------------------

BASE_VERBS = ("allreduce", "allgather", "broadcast", "reduce", "regroup",
              "rotate", "push", "pull")


def phase_verbs(mesh) -> dict:
    """Each base verb of ``harp_tpu/benchmark.VERBS`` once, against a
    straight-line numpy model of Harp's documented semantics
    (tests/test_collective.py), plus ``collective.barrier``."""
    import jax

    from harp_tpu.benchmark import VERBS
    from harp_tpu.parallel import collective as C

    nw = mesh.num_workers
    b, c = 8, 128
    x = np.random.default_rng(0).normal(size=(nw * nw * b, c)).astype(np.float32)
    sh = x.reshape(nw, nw * b, c)                  # [worker, rows, c]
    blocks = x.reshape(nw, nw, b, c)               # [src, dst, b, c]
    zeros = np.zeros_like(sh[1:])
    expect = {
        "allreduce": sh.sum(0),
        "allgather": x,
        "broadcast": sh[0],
        "reduce": np.concatenate([sh.sum(0)[None], zeros]).reshape(-1, c),
        "regroup": blocks.transpose(1, 0, 2, 3).reshape(-1, c),
        "rotate": np.roll(sh, 1, axis=0).reshape(-1, c),
        "push": blocks.sum(0).reshape(-1, c),
        "pull": x,
    }
    for name in BASE_VERBS:
        fn, kwargs, out_dim, _ = VERBS[name]
        got = np.asarray(C.host_op(mesh, fn, in_dim=0, out_dim=out_dim,
                                   **kwargs)(x))
        np.testing.assert_allclose(got, expect[name], rtol=1e-5, atol=1e-5,
                                   err_msg=f"verb {name} on {nw} workers")
    bar = jax.jit(mesh.shard_map(
        lambda v: v + C.barrier().astype(v.dtype),
        in_specs=(mesh.spec(0),), out_specs=mesh.spec(0)))(
        np.ones((nw, 1), np.float32))
    np.testing.assert_array_equal(np.asarray(bar), np.ones((nw, 1)))
    return {"num_workers": nw, "verbs": list(BASE_VERBS) + ["barrier"]}


# ---------------------------------------------------------------------------

def run(phases, meter) -> list[dict]:
    """Run ``(name, thunk)`` phases in order; nothing a phase raises is
    caught.  One JSON line per phase: its result, wall seconds, and the
    compile seconds / persistent-cache hits inside it."""
    rows = []
    with meter.watching():
        for name, thunk in phases:
            t0 = time.perf_counter()
            c0, n0, h0 = meter.compile_s, meter.compiles, meter.cache_hits
            result = thunk()
            _finite(name, result)
            row = {"phase": name,
                   "wall_s": round(time.perf_counter() - t0, 2),
                   "compile_s": round(meter.compile_s - c0, 2),
                   "compiles": meter.compiles - n0,
                   "cache_hits": meter.cache_hits - h0,
                   "result": result}
            print(json.dumps(row), flush=True)
            rows.append(row)
    return rows


def full_phases(mesh, meter):
    """The chip run: graded config #1 and #2 at full width."""
    km = KMEANS_FULL
    phases = [
        ("kmeans", lambda: phase_kmeans(mesh, meter, on_tpu=True, **km)),
        ("kmeans_fit", lambda: phase_kmeans_fit(
            mesh, meter, n=km["n"], d=km["d"], k=km["k"], on_tpu=True)),
        # benchmark()'s defaults ARE the MovieLens-20M shape: 138,493 ×
        # 26,744, 20M ratings, rank 64, 3 epochs
        ("mfsgd", lambda: phase_mfsgd(mesh, meter, on_tpu=True)),
        ("kernels", lambda: phase_kernels(meter, on_tpu=True)),
    ]
    if mesh.num_workers > 1:
        phases.append(("verbs", lambda: phase_verbs(mesh)))
    return phases


def main() -> int:
    from harp_tpu.utils import chip

    cache_dir = chip.setup_compile_cache()
    info = chip.require_tpu("chip_smoke.py")  # exits non-zero off-chip

    import jax
    import jaxlib

    from harp_tpu.parallel.mesh import WorkerMesh, set_mesh

    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    print(json.dumps({"chip_smoke": "start", **info, "jax": jax.__version__,
                      "jaxlib": jaxlib.__version__, "libtpu": libtpu,
                      "compile_cache_dir": cache_dir}), flush=True)
    mesh = WorkerMesh()  # every device jax.devices() returns, one process
    set_mesh(mesh)
    meter = Meter()
    t0 = time.perf_counter()
    run(full_phases(mesh, meter), meter)
    print(json.dumps({"chip_smoke": "done",
                      "wall_s": round(time.perf_counter() - t0, 1),
                      "compile_s": round(meter.compile_s, 1),
                      "compiles": meter.compiles,
                      "cache_hits": meter.cache_hits,
                      "compile_cache_dir": cache_dir}), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": info["platform"], "kind": info["device_kind"],
        "count": info["n_devices"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
