"""Fused Pegasos hinge-gradient — Pallas TPU kernel for the SVM inner loop.

Reference parity: Harp's ``edu.iu.svm`` local solve (SURVEY.md §3.4),
in-tree as the XLA path (`models/svm.py:_pegasos`).  The PR-16 wall
attribution priced svm on exactly two big dots per Pegasos step —
f(x) = x·w and g = (viol·y)ᵀx — which the XLA schedule runs as TWO
separate passes over the [n, d] feature block (the perfmodel's
``SVM_X_PASSES_PER_STEP = 2``).  This kernel fuses both dots into ONE
pass: each [dp, tn] feature tile is read once, scored against the
resident (w, b), and immediately contracted back into the gradient
accumulator, so the margin/violator intermediates never touch HBM.

Layout (the hard-won `ops/kmeans_kernel.py` rules): features ride
TRANSPOSED as x^T [dp, n_pad] so both matmuls contract over the legal
Mosaic patterns —

    fx [1, tn]  = w [1, dp] @ xT [dp, tn]        (A-lanes × B-sublanes)
    gw [1, dp] += coef [1, tn] · xT [dp, tn]     (lanes of BOTH)

Grid/memory plan (1-D sequential grid over sample tiles): w/b ride
whole in VMEM with constant index maps; xT/y/sw stream tn-wide; the
gw/gs outputs zero-init at step 0 and accumulate across the sequential
grid (`ops/mfsgd_kernel.py` precedent).  The bf16 arm composes with
``SVMConfig.x_dtype``: a bf16-staged x streams half the HBM bytes and
both dots run bf16×bf16→f32 (accumulation stays f32 via
``preferred_element_type``).

Expected headroom (analytic, 2026-08-06 — NOT yet a measurement; the
tile comes from ``perfmodel.presize("svm.kernel_row", ...)`` and the
kernel is Mosaic-proven via HL201 only): one feature pass per step
instead of two at the graded 500k×128 shape.  First ran on a chip 2026-09-26
(chip_smoke.py: compiles, agrees with its reference; TPU v5 lite) —
correctness only.  A TPU speed measurement goes in BASELINE.md when flip
candidate ``svm_kernel_pallas`` is measured — until then prefer ``algo="xla"``, whose
numbers are real.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

_LANE = 128
# xT tile + vector streams + residents must fit beside Mosaic's own
# buffers; 14 MB leaves ~2 MB slack under the 16 MB/core ceiling the
# registry test pins (same headroom rule as ops/wdamds_kernel.py).
VMEM_BUDGET = 14 << 20
TILE_CANDIDATES = (8192, 4096, 2048, 1024, 512, 256, 128)


def vmem_bytes(dp: int, tn: int, xsize: int) -> int:
    """Analytic VMEM byte model (also what ``perfmodel.presize``
    consults): double-buffered xT tile + streamed y/sw tiles and the
    fx/margin/coef intermediates + resident w/gw rows + fixed slack."""
    return 2 * dp * tn * xsize + 6 * tn * 4 + 2 * dp * 4 + (64 << 10)


def fit_tiles(d: int, xsize: int, budget: int = VMEM_BUDGET) -> list[int]:
    """Sample-tile candidates whose working set fits the VMEM budget."""
    dp = _LANE * -(-d // _LANE)
    return [t for t in TILE_CANDIDATES if vmem_bytes(dp, t, xsize) <= budget]


def pick_tile(n: int, d: int, xsize: int) -> int:
    """Largest fitting tile no wider than the (padded) sample count —
    the same "largest fits" rule ``perfmodel.presize`` reproduces from
    the price model (per-grid-program overhead is monotone in 1/tn)."""
    fits = fit_tiles(d, xsize)
    if not fits:
        dp = _LANE * -(-d // _LANE)
        raise ValueError(
            f"pallas svm: no sample tile fits dp={dp} (xsize={xsize}) under "
            f"the {VMEM_BUDGET >> 20} MB VMEM budget; use algo='xla'")
    cap = _LANE * -(-max(n, 1) // _LANE)
    small = [t for t in fits if t <= cap]
    return max(small) if small else min(fits)


def _kernel(w_ref, b_ref, xT_ref, y_ref, sw_ref, gw_ref, gs_ref, *,
            compute_dtype):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        gw_ref[...] = jnp.zeros_like(gw_ref)
        gs_ref[...] = jnp.zeros_like(gs_ref)

    cd = compute_dtype
    dot = functools.partial(lax.dot_general,
                            preferred_element_type=jnp.float32)
    xT = xT_ref[...].astype(cd)                         # [dp, tn]
    fx = dot(w_ref[...].astype(cd), xT,
             (((1,), (0,)), ((), ())))                  # [1, tn] f32
    margin = y_ref[...] * (fx + b_ref[...])
    # pad samples carry sw = 0, so they drop out of both sums here
    coef = jnp.where(margin < 1.0, sw_ref[...], 0.0) * y_ref[...]
    gw_ref[...] += dot(coef.astype(cd), xT,
                       (((1,), (1,)), ((), ())))        # [1, dp]
    gs_ref[...] += coef.sum().reshape(1, 1)


def pegasos_grad(w, b, xT, y, sw, *, tn: int,
                 compute_dtype=jnp.float32, interpret: bool = False):
    """One fused hinge-gradient pass over all samples.

    ``w`` [dp] f32, ``b`` scalar, ``xT`` [dp, n_pad] f32/bf16
    (transposed features; pad samples MUST carry ``sw = 0``),
    ``y``/``sw`` [n_pad] f32.  Returns ``(gw [dp], gs scalar)`` with
    gw = Σ coef·x and gs = Σ coef for coef = 1[y·(x·w+b) < 1]·sw·y —
    exactly the per-step sums of `models/svm.py:_pegasos` (whose update
    is w' = w − lr·(l2·w − gw/Σsw), b' = b + lr·gs/Σsw).
    """
    dp, n_pad = xT.shape
    if not interpret:
        for name, v, m in (("feature pad dp", dp, _LANE),
                           ("sample tile tn", tn, _LANE)):
            if v % m:
                raise ValueError(
                    f"pallas svm: {name}={v} must be a multiple of {m} on "
                    f"TPU (use algo='xla' for odd shapes)")
    if n_pad % tn:
        raise ValueError(
            f"pallas svm: n_pad={n_pad} not a multiple of tn={tn}; pad "
            f"samples (with sw=0) to a tile multiple first")
    xsize = jnp.dtype(xT.dtype).itemsize
    if vmem_bytes(dp, tn, xsize) > VMEM_BUDGET:
        raise ValueError(
            f"pallas svm: tile ({dp}, {tn}) needs "
            f"{vmem_bytes(dp, tn, xsize) / 2**20:.1f} MB > "
            f"{VMEM_BUDGET >> 20} MB VMEM budget; shrink tn "
            f"(perfmodel.presize picks a fitting tile)")
    gw, gs = pl.pallas_call(
        functools.partial(_kernel, compute_dtype=compute_dtype),
        grid=(n_pad // tn,),
        in_specs=[
            pl.BlockSpec((1, dp), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((dp, tn), lambda i: (0, i)),
            pl.BlockSpec((1, tn), lambda i: (0, i)),
            pl.BlockSpec((1, tn), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, dp), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, dp), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(w.reshape(1, dp).astype(jnp.float32),
      jnp.asarray(b, jnp.float32).reshape(1, 1),
      xT,
      y.reshape(1, n_pad).astype(jnp.float32),
      sw.reshape(1, n_pad).astype(jnp.float32))
    return gw.reshape(dp), gs[0, 0]
