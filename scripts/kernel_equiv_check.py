#!/usr/bin/env python
"""Pallas-kernel ≡ reference equivalence on the CURRENT backend.

ADVICE r3 (ops/mfsgd_kernel.py:101): kernel correctness on real TPU
hinges on Mosaic buffer-revision behavior that interpret mode + lowering
cannot prove — so before any pallas number is recorded, the equivalence
checks execute on silicon.  chip_smoke.py runs the per-kernel half of
this on every chip visit; this script is the app-level half (whole
epochs through the public drivers, the carry variants' bit-identity,
the >256-count LDA gathers).

Small shapes, TPU-legal tiles, one process.  Exit 0 = all kernels
equivalent; nonzero = do not record pallas rows.

Usage: python scripts/kernel_equiv_check.py
The backend is the environment's: the chip through the chip tool, or
JAX_PLATFORMS=cpu (with --xla_force_host_platform_device_count=8 in
XLA_FLAGS for the 8-worker simulation) for local validation.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    import jax
    import jax.numpy as jnp

    from harp_tpu import WorkerMesh
    from harp_tpu.models.kmeans import fit as kfit
    from harp_tpu.models.lda import LDA, LDAConfig, synthetic_corpus
    from harp_tpu.models.mfsgd import MFSGD, MFSGDConfig, synthetic_ratings
    from harp_tpu.parallel.mesh import set_mesh

    mesh = WorkerMesh()
    set_mesh(mesh)
    on_tpu = jax.default_backend() != "cpu"
    tile = 128 if on_tpu else 8  # kernels gate 128-multiples on TPU
    rng = np.random.default_rng(0)

    if not on_tpu:
        # TPU shape pre-pass (round 5, review finding): a CPU run uses
        # CPU tiles, so the kernels' TPU-only validation branches
        # (n_topics/tile multiple-of rules) never execute — the
        # n_topics=4 hot-count shape cost chip time that way.
        # Trace-lower each LDA pallas config THIS SCRIPT runs on TPU, at
        # the TPU-mode tiles, through the same Mosaic pin the kernel
        # tests use (CLAUDE.md: catches what the chip would refuse,
        # hardware-free).  Any future shape edit here fails on the CPU,
        # not on the chip.
        import harp_tpu.models.lda as Lm

        os.environ["HARP_PALLAS_FORCE_MOSAIC"] = "1"
        try:
            for n_topics, n_docs, vocab, n_tok, exact in (
                    (8, 64, 32, 64 * 40, True),        # check 2's config
                    (8, 64, 128, 64 * 320, True),      # check 5, exact
                    (8, 64, 128, 64 * 320, False)):    # check 5, approx
                pcfg = Lm.LDAConfig(
                    n_topics=n_topics, algo="pallas", d_tile=128,
                    w_tile=128, entry_cap=64, alpha=0.5, beta=0.1,
                    sampler="exprace", rng_impl="rbg",
                    pallas_exact_gathers=exact)
                shapes = Lm.epoch_arg_shapes(mesh.num_workers, n_docs,
                                             vocab, pcfg, n_tokens=n_tok)
                sds = [jax.ShapeDtypeStruct(
                    shape, dt,
                    sharding=(mesh.replicated() if i == 2
                              else mesh.sharding(mesh.spec(0))))
                    for i, (shape, dt) in enumerate(shapes)]
                fn = Lm.make_multi_epoch_fn(mesh, pcfg, vocab, epochs=1)
                text = fn.trace(*sds).lower(
                    lowering_platforms=("tpu",)).as_text()
                assert "tpu_custom_call" in text
        finally:
            del os.environ["HARP_PALLAS_FORCE_MOSAIC"]
        print("tpu shape pre-pass: every TPU-mode LDA config "
              "traces + Mosaic-lowers")

    # 1. MF-SGD: pallas kernel replays dense's exact update order
    u, i, v = synthetic_ratings(96, 64, 3000, rank=4, noise=0.05, seed=2)
    factors = {}
    for algo in ("dense", "pallas"):
        cfg = MFSGDConfig(rank=8, algo=algo, u_tile=tile, i_tile=tile,
                          entry_cap=32, compute_dtype=jnp.float32,
                          lr=0.03, reg=0.01)
        m = MFSGD(96, 64, cfg, mesh, seed=4)
        m.set_ratings(u, i, v)
        rm = [m.train_epoch() for _ in range(2)]
        factors[algo] = (m.factors(), rm)
    np.testing.assert_allclose(factors["pallas"][0][0],
                               factors["dense"][0][0], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(factors["pallas"][0][1],
                               factors["dense"][0][1], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(factors["pallas"][1], factors["dense"][1],
                               rtol=1e-5)
    print(f"mfsgd pallas == dense (rmse {factors['pallas'][1][-1]:.4f})")

    # 2. LDA: pallas chain ascends, counts exact, quality matches gumbel
    d, w = synthetic_corpus(n_docs=64, vocab_size=32, n_topics_true=4,
                            tokens_per_doc=40, seed=3)
    lt = 128 if on_tpu else 16
    lls = {}
    for algo in ("dense", "pallas"):
        # the pallas kernel fuses the exprace draw over hardware bits —
        # its required sampler stack; dense keeps the gumbel default so
        # this doubles as the sampler-stack quality A/B
        extra = ({"sampler": "exprace", "rng_impl": "rbg"}
                 if algo == "pallas" else {})
        lcfg = LDAConfig(n_topics=8, algo=algo, d_tile=lt, w_tile=lt,
                         entry_cap=64, alpha=0.5, beta=0.1, **extra)
        lm = LDA(64, 32, lcfg, mesh, seed=1)
        lm.set_tokens(d, w)
        for _ in range(6):
            lm.sample_epoch()
        ndk, nwk = np.asarray(lm.Ndk), np.asarray(lm.Nwk)
        assert ndk.sum() == lm.n_tokens and (ndk >= 0).all()
        assert (nwk == np.round(nwk)).all(), "counts must stay integers"
        lls[algo] = lm.log_likelihood()
    # different streams on a tiny corpus: ~10% spread; gate with margin
    assert abs(lls["pallas"] - lls["dense"]) / abs(lls["dense"]) < 0.25, lls
    print(f"lda pallas chain quality == dense ({lls})")

    # 3. KMeans: fused int8 kernel == XLA int8 formulation
    pts = rng.normal(size=(1024, 16)).astype(np.float32) * 3
    # use_pallas=False EXPLICIT: since the int8 auto default flipped to
    # the kernel (2026-08-01), an unset arm would make this check
    # kernel-vs-kernel — vacuously green (review finding, round 5)
    ca, ia = kfit(pts, k=4, iters=4, mesh=mesh, seed=5, quantize="int8",
                  use_pallas=False)
    cb, ib = kfit(pts, k=4, iters=4, mesh=mesh, seed=5, quantize="int8",
                  use_pallas=True)
    np.testing.assert_allclose(ca, cb, rtol=1e-5, atol=1e-5)
    print(f"kmeans fused int8 == XLA int8 (inertia {ib:.1f})")

    # 4. carry variants: the run-carried tiles must be bit-identical to
    # the slice-per-entry chains ON THIS BACKEND (the cond+DUS-on-carry
    # interaction is exactly where an XLA:TPU buffer decision could
    # diverge from the CPU sim — gate it before lda_carry / mfsgd_carry
    # rows record)
    chains = {}
    for carry in (False, True):
        cm = LDA(64, 32, LDAConfig(n_topics=8, algo="dense", d_tile=lt,
                                   w_tile=lt, entry_cap=64, alpha=0.5,
                                   beta=0.1, carry_db=carry), mesh, seed=3)
        cm.set_tokens(d, w)
        for _ in range(3):
            cm.sample_epoch()
        chains[carry] = (np.asarray(cm.Ndk), np.asarray(cm.Nwk),
                         np.asarray(cm.z_grid))
    for a, b in zip(chains[False], chains[True]):
        np.testing.assert_array_equal(a, b)
    print("lda carry_db == slice-per-entry (bit-identical)")

    mf_chains = {}
    for carry in (False, True):
        mc = MFSGD(96, 64, MFSGDConfig(rank=8, algo="dense", u_tile=tile,
                                       i_tile=tile, entry_cap=32,
                                       compute_dtype=jnp.float32, lr=0.03,
                                       reg=0.01, carry_w=carry),
                   mesh, seed=4)
        mc.set_ratings(u, i, v)
        rm = [mc.train_epoch() for _ in range(2)]
        mf_chains[carry] = (mc.factors(), rm)
    np.testing.assert_array_equal(mf_chains[True][0][0],
                                  mf_chains[False][0][0])
    np.testing.assert_array_equal(mf_chains[True][0][1],
                                  mf_chains[False][0][1])
    np.testing.assert_array_equal(mf_chains[True][1], mf_chains[False][1])
    print("mfsgd carry_w == slice-per-entry (bit-identical)")

    # 5. hot counts (round 5): the lda_pallas_hot/_approx_hot sweep pair
    # runs where per-cell counts exceed 256, engaging the SECOND base-256
    # digit plane in the exact gathers — a plane-count bug on silicon
    # would only show here, so gate it before those rows record.  Corpus:
    # 20480 tokens over 8 distinct words (count bound 2560 >> 256), and
    # n_topics=8 is the kernel's TPU minimum (the first in-window run
    # failed the kernel's own multiple-of-8 check at n_topics=4, which
    # interpret-mode rehearsal cannot catch); max(Nwk) >= 2560/8 = 320
    # keeps the >256 hot condition true by construction.
    dh = np.repeat(np.arange(64, dtype=np.int32), 320)
    wh = (np.arange(64 * 320, dtype=np.int32) % 8)
    hot_lls = {}
    for algo, exact in (("dense", None), ("pallas", True),
                        ("pallas", False)):
        extra = ({"sampler": "exprace", "rng_impl": "rbg",
                  "pallas_exact_gathers": exact}
                 if algo == "pallas" else {})
        hm = LDA(64, 128, LDAConfig(n_topics=8, algo=algo, d_tile=lt,
                                    w_tile=lt, entry_cap=64, alpha=0.5,
                                    beta=0.1, **extra), mesh, seed=7)
        hm.set_tokens(dh, wh)
        for _ in range(3):
            hm.sample_epoch()
        ndk = np.asarray(hm.Ndk)
        assert ndk.sum() == hm.n_tokens and (ndk >= 0).all()
        nwk = np.asarray(hm.Nwk)
        assert (nwk == np.round(nwk)).all(), (algo, exact,
                                              "counts must stay integers")
        assert nwk.max() > 256, "shape failed to reach hot counts"
        hot_lls[(algo, exact)] = hm.log_likelihood()
    ref = hot_lls[("dense", None)]
    assert abs(hot_lls[("pallas", True)] - ref) / abs(ref) < 0.25, hot_lls
    # the approx variant gets only a GARBAGE bound (2x the exact
    # tolerance): its fine-grained quality question is a likelihood A/B's
    # to answer — but a gather path that zeroes (not rounds) the high
    # plane must be caught here
    assert abs(hot_lls[("pallas", False)] - ref) / abs(ref) < 0.5, hot_lls
    print(f"lda pallas hot-count (>256) exact gathers == dense ({hot_lls})")

    print(f"KERNEL EQUIV OK ({jax.default_backend()})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
