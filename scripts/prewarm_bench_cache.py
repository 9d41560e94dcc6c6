#!/usr/bin/env python
"""Prewarm .bench_data/ so chip time is spent on the chip, not on prep.

The benchmark's two big host costs are pure CPU work with no TPU
dependency: the LDA corpus packs (~675 s at enwiki-1M, ~30-320 s for
the others, identical bytes whatever backend later installs them) and
the 12 GB ingest npy.  Run this on the host, on the CPU backend with one
device (matching the 1-chip mesh, which the pack key includes), and the
next full-shape run hits warm caches for every lda config and the
ingest file.

Usage: JAX_PLATFORMS=cpu python scripts/prewarm_bench_cache.py [--skip-ingest]
Idempotent: existing cache files are kept.
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

if os.environ.get("JAX_PLATFORMS") != "cpu":
    sys.exit("prewarm_bench_cache.py is host-only prep: run it with "
             "JAX_PLATFORMS=cpu")

from measure_all import BENCH_DATA  # the one shared artifacts dir

# every FULL-mode lda config in measure_all, by distinct pack layout:
# dense covers lda/lda_carry/lda_exprace/lda_fast; pallas covers
# lda_pallas/_approx/_carry (sampler/rng/carry knobs don't touch layout)
PACKS = [
    dict(algo="dense"),
    dict(algo="pallas", sampler="exprace", rng_impl="rbg"),
    dict(algo="scatter"),
    dict(algo="dense", n_docs=500_000, ndk_dtype="int16"),
    dict(algo="dense", n_docs=1_000_000, ndk_dtype="int16"),
    # round 5: the hot-count LL A/B pair (lda_pallas_hot/_approx_hot) —
    # exact_gathers is not layout-relevant, one pack serves both
    dict(algo="pallas", sampler="exprace", rng_impl="rbg", n_docs=20_000,
         vocab_size=256, n_topics=32, tokens_per_doc=200, d_tile=128,
         w_tile=128),
]


def prewarm_pack(n_docs=100_000, vocab_size=50_000, n_topics=1000,
                 tokens_per_doc=100, seed=0, algo="dense", sampler=None,
                 rng_impl=None, ndk_dtype="float32", d_tile=None,
                 w_tile=None):
    from harp_tpu import WorkerMesh
    from harp_tpu.models import lda as L

    mesh = WorkerMesh()  # 1 CPU device == the 1-chip mesh
    assert mesh.num_workers == 1, mesh.num_workers
    cfg = L._make_cfg(n_topics, algo, sampler=sampler, rng_impl=rng_impl,
                      ndk_dtype=ndk_dtype, d_tile=d_tile, w_tile=w_tile)
    path = L._pack_cache_path(BENCH_DATA, cfg, mesh.num_workers, n_docs,
                              vocab_size, n_topics, tokens_per_doc, seed)
    label = f"{algo} n_docs={n_docs} ndk={cfg.ndk_dtype}"
    if os.path.exists(path):
        print(f"pack ok (cached): {label} -> {os.path.basename(path)}")
        return
    t0 = time.time()
    # the SAME corpus constructor benchmark uses — a second construction
    # here would let the cached bytes drift from the key's promise
    d_ids, w_ids = L.benchmark_corpus(n_docs, vocab_size, tokens_per_doc,
                                      seed)
    model = L.LDA(n_docs, vocab_size, cfg, mesh, seed)
    pack = model.pack_tokens(d_ids, w_ids)
    L._save_pack(path, pack)
    print(f"pack built: {label} -> {os.path.basename(path)} "
          f"({time.time() - t0:.0f}s, {os.path.getsize(path) / 2**30:.2f} GiB)")


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--skip-ingest", action="store_true")
    args = p.parse_args()
    for kw in PACKS:
        prewarm_pack(**kw)
    if not args.skip_ingest:
        # same preset bench.py uses (bench_ingest --ensure-only)
        import bench_ingest

        bench_ingest.main(["--rows", "20000000", "--ensure-only"])
    print("prewarm done")


if __name__ == "__main__":
    main()
