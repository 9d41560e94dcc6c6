"""Fused SMACOF distance + B·X kernel (ops/wdamds_kernel.py) vs the XLA body.

The kernel promises the SAME Guttman row-block update as
`models/wdamds.py:make_smacof_fn`'s XLA ``body`` (D and ratio never
leaving VMEM is a schedule change, not a math change) — these tests pin
it against a numpy golden of that body, the live-masking contract for
padded rows/columns, the bf16 δ arm, the full model under the 8-worker
mesh, and the offline guarantees (VMEM rejection + Mosaic lowering).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from harp_tpu.models import wdamds as MDS
from harp_tpu.ops import wdamds_kernel as K

EPS = 1e-7


def _golden(delta_rows, row_mask, Xl, X, n_real, eps=EPS):
    """The XLA body's math (models/wdamds.py) in numpy, f32."""
    x2 = (Xl ** 2).sum(-1)[:, None]
    y2 = (X ** 2).sum(-1)[None, :]
    D = np.sqrt(np.maximum(x2 - 2.0 * (Xl @ X.T) + y2, 0.0))
    live = row_mask[:, None] * (np.arange(X.shape[0])[None, :]
                                < n_real).astype(np.float32)
    ratio = np.where(D > eps, delta_rows / np.maximum(D, eps), 0.0) * live
    bx = -ratio @ X + ratio.sum(1)[:, None] * Xl
    return bx / max(n_real, 1.0)


def test_fused_block_matches_numpy():
    rng = np.random.default_rng(0)
    N, n_loc, dim = 64, 24, 3           # pads rows → tn, dim → 128
    pts = rng.normal(size=(N, dim)).astype(np.float32)
    delta = np.sqrt(((pts[:, None] - pts[None]) ** 2).sum(-1))
    X = rng.normal(size=(N, dim)).astype(np.float32)
    out = K.smacof_bx(jnp.asarray(delta[:n_loc]), jnp.ones(n_loc),
                      jnp.asarray(X[:n_loc]), jnp.asarray(X),
                      jnp.float32(N), eps=EPS, tn=8, interpret=True)
    exp = _golden(delta[:n_loc], np.ones(n_loc, np.float32),
                  X[:n_loc], X, N)
    np.testing.assert_allclose(np.asarray(out), exp, rtol=1e-4, atol=1e-5)


def test_masked_rows_and_columns_drop_out():
    """Padded rows (row_mask 0) must come out zero and padded columns
    (index ≥ n_real) must not contribute — junk in the pad coordinates
    must be invisible, exactly as in the XLA body's ``live`` mask."""
    rng = np.random.default_rng(1)
    N, n_real, n_loc, dim = 48, 41, 48, 2
    X = rng.normal(size=(N, dim)).astype(np.float32)
    X[n_real:] = 1e6                    # junk pad coordinates
    delta = np.abs(rng.normal(size=(n_loc, N))).astype(np.float32)
    rm = np.zeros(n_loc, np.float32)
    rm[:n_real] = 1.0
    out = np.asarray(K.smacof_bx(
        jnp.asarray(delta), jnp.asarray(rm), jnp.asarray(X),
        jnp.asarray(X), jnp.float32(n_real), eps=EPS, tn=8,
        interpret=True))
    exp = _golden(delta, rm, X, X, n_real)
    np.testing.assert_allclose(out, exp, rtol=1e-4, atol=1e-5)
    assert (out[n_real:] == 0.0).all()  # masked rows exactly zero


def test_bf16_delta_arm_matches_bf16_golden():
    """The delta_dtype="bf16" composition: a bf16-staged δ promotes to
    f32 in-kernel, so the result matches the golden computed on the
    SAME bf16-rounded δ (rounding is the only difference)."""
    rng = np.random.default_rng(2)
    N, n_loc, dim = 32, 16, 3
    pts = rng.normal(size=(N, dim)).astype(np.float32)
    delta = np.sqrt(((pts[:, None] - pts[None]) ** 2).sum(-1))[:n_loc]
    d_bf = np.asarray(jnp.asarray(delta).astype(jnp.bfloat16))
    X = rng.normal(size=(N, dim)).astype(np.float32)
    out = K.smacof_bx(jnp.asarray(d_bf), jnp.ones(n_loc),
                      jnp.asarray(X[:n_loc]), jnp.asarray(X),
                      jnp.float32(N), eps=EPS, tn=8, interpret=True)
    exp = _golden(d_bf.astype(np.float32), np.ones(n_loc, np.float32),
                  X[:n_loc], X, N)
    np.testing.assert_allclose(np.asarray(out), exp, rtol=1e-4, atol=1e-5)


def test_model_pallas_matches_xla(mesh):
    """End-to-end mds() under the 8-worker mesh at a 128-multiple n_pad
    (n=250 → n_pad=256, so pad rows AND pad columns are live in the
    masking path): same geometry recovery and matching stress."""
    rng = np.random.default_rng(3)
    n = 250
    pts = rng.normal(size=(n, 2)).astype(np.float32)
    delta = np.sqrt(((pts[:, None] - pts[None]) ** 2).sum(-1))
    out = {}
    for algo in ("xla", "pallas"):
        cfg = MDS.MDSConfig(dim=2, iters=60, algo=algo)
        out[algo] = MDS.mds(delta, cfg, mesh, seed=0)
    Xp, sp = out["pallas"]
    Xx, sx = out["xla"]
    np.testing.assert_allclose(sp, sx, rtol=1e-3)
    demb = np.sqrt(((Xp[:, None] - Xp[None]) ** 2).sum(-1))
    rel = np.abs(demb - delta)[np.triu_indices(n, 1)].mean() / delta.mean()
    assert rel < 0.1, rel


def test_odd_n_pad_falls_back_to_xla(mesh):
    """algo="pallas" at an n_pad that is not a 128 multiple must fall
    back to the XLA body (not error): n=60 → n_pad=64 on 8 workers."""
    rng = np.random.default_rng(4)
    pts = rng.normal(size=(60, 2)).astype(np.float32)
    delta = np.sqrt(((pts[:, None] - pts[None]) ** 2).sum(-1))
    X, stress = MDS.mds(delta, MDS.MDSConfig(dim=2, iters=30,
                                             algo="pallas"), mesh, seed=0)
    assert np.isfinite(stress) and X.shape == (60, 2)


def test_pick_tile_is_largest_fitting():
    assert K.pick_tile(512, 4096, 4) == 128       # the presize pin
    assert K.pick_tile(16, 4096, 4) == 16         # capped by n_loc
    with pytest.raises(ValueError, match="VMEM budget"):
        K.pick_tile(512, 1 << 20, 4)              # no tile fits


def test_rejects_tile_over_vmem_budget():
    N, tn = 2048, 512                   # ~21 MB working set
    with pytest.raises(ValueError, match="VMEM budget"):
        K.smacof_bx(jnp.zeros((tn, N)), jnp.ones(tn), jnp.zeros((tn, 2)),
                    jnp.zeros((N, 2)), jnp.float32(N), eps=EPS, tn=tn,
                    interpret=True)


def test_rejects_unaligned_n_for_tpu():
    with pytest.raises(ValueError, match="multiple of 128"):
        K.smacof_bx(jnp.zeros((8, 96)), jnp.ones(8), jnp.zeros((8, 2)),
                    jnp.zeros((96, 2)), jnp.float32(96), eps=EPS, tn=8,
                    interpret=False)


@pytest.mark.parametrize("N,n_loc,tn,dim,dtype", [
    (256, 32, 32, 2, jnp.float32),     # the registry-proven shape
    (4096, 512, 128, 3, jnp.float32),  # the graded presized tile
    (4096, 512, 128, 3, jnp.bfloat16),  # the delta_dtype-composed arm
])
def test_kernel_lowers_for_tpu(N, n_loc, tn, dim, dtype):
    """Cross-platform lowering runs the Pallas->Mosaic verification
    without hardware (HL201 idiom) — this caught the 0-d scalar
    arith.maximumf mix before any chip time was spent."""
    import functools

    f = functools.partial(K.smacof_bx, eps=EPS, tn=tn, interpret=False)
    lowered = jax.jit(f).trace(
        jnp.zeros((n_loc, N), dtype), jnp.zeros(n_loc),
        jnp.zeros((n_loc, dim)), jnp.zeros((N, dim)),
        jnp.float32(N)).lower(lowering_platforms=("tpu",))
    assert "tpu_custom_call" in lowered.as_text()
