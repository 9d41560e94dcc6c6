#!/usr/bin/env python
"""Real-ingest benchmark for streaming KMeans — the disk-resident half of
the 1B-point north-star (SURVEY.md §1, §4.2 "load points shard").

``benchmark_streaming`` proves the compute formulation; THIS measures the
ingest-bound reality: a .npy memmap (or CSV via the native streaming
parser) on local disk, streamed through ``fit_streaming`` with device
compute double-buffered behind the host read/parse/transfer pipeline.
Prints one JSON line (same fields as
``kmeans_stream.benchmark_ingest``).

Usage:
    python scripts/bench_ingest.py                       # 100M×300 f16 npy
    python scripts/bench_ingest.py --format csv --rows 2000000
    python scripts/bench_ingest.py --smoke --platform cpu
    python scripts/bench_ingest.py --rows 1000000000 ... # if disk allows

Dataset notes (measured constraints, 2026-07-30, this host):
- 100M×300 f32 = 120 GB > the 79 GB free on /; the default disk dtype is
  float16 (60 GB) so the TRUE 100M-row count runs — GB/s is computed on
  actual on-disk bytes, so the rate is honest for the format streamed.
  Pass ``--disk-dtype float32 --rows 40000000`` for a pure-f32 run.
- CSV text is ~2.4 GB per 1M rows at 300 cols; the CSV default is 2M
  rows (parse rate is row-width-independent enough to project).
- The file lands in ``.bench_data/`` (gitignored) and is DELETED after
  the run unless ``--keep`` — it is most of the disk.
- With 125 GB RAM the OS page cache holds the whole default file after
  generation, so ``host_gb_per_sec`` measures the warm-cache pipeline
  (parse+pad+dispatch), not cold spindle reads; ``--drop-caches`` echoes
  3 > /proc/sys/vm/drop_caches first (needs root) for the cold number.
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

DATA_DIR = os.path.join(REPO, ".bench_data")


def gen_points_npy(path: str, rows: int, cols: int, dtype="float16",
                   seed=0, chunk_rows=1 << 20) -> None:
    """Write a [rows, cols] standard-normal .npy in bounded memory."""
    import numpy as np

    os.makedirs(os.path.dirname(path), exist_ok=True)
    out = np.lib.format.open_memmap(path, mode="w+", dtype=np.dtype(dtype),
                                    shape=(rows, cols))
    rng = np.random.default_rng(seed)
    for lo in range(0, rows, chunk_rows):
        hi = min(lo + chunk_rows, rows)
        out[lo:hi] = rng.standard_normal((hi - lo, cols),
                                         dtype=np.float32).astype(out.dtype)
    out.flush()
    del out


def gen_points_csv(path: str, rows: int, cols: int, seed=0,
                   chunk_rows=1 << 16) -> None:
    """Write a [rows, cols] CSV in bounded memory (%.4f ≈ 7 B/value)."""
    import numpy as np

    os.makedirs(os.path.dirname(path), exist_ok=True)
    rng = np.random.default_rng(seed)
    with open(path, "w") as f:
        for lo in range(0, rows, chunk_rows):
            hi = min(lo + chunk_rows, rows)
            blk = rng.standard_normal((hi - lo, cols), dtype=np.float32)
            np.savetxt(f, blk, fmt="%.4f", delimiter=",")


def ensure_dataset(fmt: str, rows: int, cols: int, disk_dtype: str,
                   verbose=True) -> tuple[str, bool]:
    """Generate (or reuse) the benchmark file → (path, generated_now).

    ``generated_now`` lets run() clean up only files THIS invocation
    created — a cached file another run kept (a reusable 12 GB
    dataset) must survive a no-``--keep`` run that merely
    reused it."""
    name = (f"pts_{rows}x{cols}_{disk_dtype}.npy" if fmt == "npy"
            else f"pts_{rows}x{cols}.csv")
    path = os.path.join(DATA_DIR, name)
    if os.path.exists(path):
        return path, False
    t0 = time.perf_counter()
    if verbose:
        print(f"generating {path} ...", file=sys.stderr, flush=True)
    if fmt == "npy":
        gen_points_npy(path, rows, cols, disk_dtype)
    else:
        gen_points_csv(path, rows, cols)
    if verbose:
        gb = os.path.getsize(path) / 1e9
        print(f"  {gb:.1f} GB in {time.perf_counter() - t0:.0f}s",
              file=sys.stderr, flush=True)
    return path, True


def run(fmt="npy", rows=100_000_000, cols=300, disk_dtype="float16",
        k=1000, iters=2, chunk_points=262_144, keep=False,
        compare_synthetic=False, drop_caches=False, verbose=True,
        quantize=None, prefetch=2) -> dict:
    import numpy as np

    from harp_tpu.models.kmeans_stream import benchmark_ingest

    path, generated = ensure_dataset(fmt, rows, cols, disk_dtype,
                                     verbose=verbose)
    cold = False
    try:
        if drop_caches:
            # record cold_cache only if the drop actually happened — a
            # non-root failure must not label a warm-cache rate as cold
            cold = os.system(
                "sync; echo 3 > /proc/sys/vm/drop_caches") == 0
            if not cold:
                print("drop_caches failed (need root) — measuring warm "
                      "cache", file=sys.stderr)
        if fmt == "npy":
            pts = np.load(path, mmap_mode="r")
        else:
            from harp_tpu.native.datasource import CSVPoints

            pts = CSVPoints(path, chunk_rows=chunk_points)
        res = benchmark_ingest(pts, k=k, iters=iters,
                               chunk_points=chunk_points,
                               disk_bytes=os.path.getsize(path),
                               compare_synthetic=compare_synthetic,
                               quantize=quantize, prefetch=prefetch)
        res.update({"format": fmt, "disk_dtype":
                    (disk_dtype if fmt == "npy" else "text"),
                    "cold_cache": cold})
        return res
    finally:
        # delete only what this run created: a cached file another run
        # kept must survive a no-keep rerun that merely reused it
        if not keep and generated and os.path.exists(path):
            os.remove(path)


# the A/B smoke shape: big enough that the host chain, not thread/jit
# overhead, dominates (51 MB f16 over 25 chunks × 4 epochs) yet seconds
# on the CPU sim; the tiny run_smoke shape (2.6 MB) reads ~1.0x at any
# truth.  f16 disk + the auto f16 wire is the north-star disk format,
# and the shape where the staged chain's work elimination (memmap view
# straight into device_put, masks shipped once instead of per chunk) is
# cleanly measurable.  prefetch=1 deliberately: the staged chain is
# bit-exact at every depth, but on a 1-core host the thread-prefetch
# modes only add scheduler preemption noise to the measurement (depth-2
# reruns spread 0.94-1.35x while depth-1 repeats at ~1.9x, measured
# 2026-08-04 CPU host) — CPU-bound stages cannot overlap on one core
# (see harp_tpu/ingest.py module doc), so the A/B grades the chain, and
# the measurement run's multi-core kmeans_ingest config grades the depth
AB_SMOKE = dict(fmt="npy", rows=400_000, cols=64, disk_dtype="float16",
                k=8, iters=4, chunk_points=16_384, prefetch=1)


def run_ab(fmt="npy", rows=200_000, cols=64, disk_dtype="float32",
           k=16, iters=2, chunk_points=32_768, keep=True, quantize=None,
           prefetch=2, verbose=True) -> dict:
    """The pipelined-vs-serial host-path A/B at ONE config (PR 8
    acceptance row): arm A is ``prefetch=0`` — the pre-pipeline serial
    chain kept verbatim in ``kmeans_stream._legacy_put_chunk`` — arm B
    the prefetch pipeline.  Both arms stream the same (page-cache-warm)
    file, so ``pipeline_speedup`` is host-chain work, not disk luck.
    Emits ONE merged ``kind:"ingest"`` dict (checked by check_jsonl
    invariant 8): pipelined fields canonical, serial arm suffixed."""
    import numpy as np

    path, generated = ensure_dataset(fmt, rows, cols, disk_dtype,
                                     verbose=verbose)
    common = dict(fmt=fmt, rows=rows, cols=cols, disk_dtype=disk_dtype,
                  k=k, iters=iters, chunk_points=chunk_points, keep=True,
                  quantize=quantize, verbose=verbose)
    try:
        if fmt == "npy":
            # warm the page cache for BOTH arms: a freshly generated
            # file's dirty pages flush during arm A otherwise, charging
            # writeback to whichever arm runs first
            float(np.asarray(np.load(path, mmap_mode="r")).max())
        serial = run(prefetch=0, **common)
        piped = run(prefetch=prefetch, **common)
    finally:
        # both arms ran keep=True so arm B reuses arm A's (warm) file;
        # clean up here instead, only what THIS call generated
        if not keep and generated and os.path.exists(path):
            os.remove(path)
    piped.update({
        "mode": "ab",
        "host_gb_per_sec_serial": serial["host_gb_per_sec"],
        "host_sec_per_epoch_serial": serial["host_sec_per_epoch"],
        "points_per_sec_serial": serial["points_per_sec"],
        "pipeline_speedup": (piped["host_gb_per_sec"]
                             / serial["host_gb_per_sec"]),
    })
    return piped


def run_smoke(quantize=None) -> dict:
    """The smoke preset — tiny npy, CPU-safe, regenerated per run."""
    return run("npy", 20_000, 32, "float32", k=16, iters=2,
               chunk_points=4096, verbose=False, quantize=quantize)


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--format", choices=["npy", "csv"], default="npy")
    p.add_argument("--rows", type=int, default=None,
                   help="default: 100M npy / 2M csv (smoke: 20k)")
    p.add_argument("--cols", type=int, default=300)
    p.add_argument("--disk-dtype", choices=["float16", "float32"],
                   default="float16",
                   help="npy on-disk dtype (f16 default: 100M×300 must "
                        "fit the 79 GB free on this host)")
    p.add_argument("--k", type=int, default=1000)
    p.add_argument("--iters", type=int, default=2)
    p.add_argument("--chunk", type=int, default=262_144)
    p.add_argument("--keep", action="store_true",
                   help="keep the generated file (it is most of the disk)")
    p.add_argument("--compare-synthetic", action="store_true",
                   help="also time the device-regenerated formulation at "
                        "the same shapes (second compile + run)")
    p.add_argument("--drop-caches", action="store_true")
    p.add_argument("--prefetch", type=int, default=2,
                   help="ingest pipeline work-ahead depth (0 = the "
                        "pre-pipeline serial loop, the A/B incumbent)")
    p.add_argument("--ensure-only", action="store_true",
                   help="generate (or reuse) the dataset file and exit — "
                        "run this OUTSIDE any benchmark watchdog: on this "
                        "1-core host generation alone can eat most of a "
                        "1200 s window (12 GB took 864 s on 2026-07-31)")
    p.add_argument("--smoke", action="store_true")
    args = p.parse_args(argv)

    if args.smoke:
        # --smoke IS the pipelined-vs-serial A/B (PR 8 acceptance): one
        # provenance-stamped kind:"ingest" line, ready to tee into
        # BENCH_local.jsonl and graded by check_jsonl invariant 8
        from harp_tpu.utils.metrics import benchmark_json

        res = run_ab(keep=False, **AB_SMOKE)
        print(benchmark_json("kmeans_ingest_ab_smoke", res))
        return
    rows = args.rows or (100_000_000 if args.format == "npy"
                         else 2_000_000)
    cols, k, chunk = args.cols, args.k, args.chunk
    if args.ensure_only:
        path, generated = ensure_dataset(args.format, rows, cols,
                                         args.disk_dtype)
        print(json.dumps({"ensured": path, "generated_now": generated}))
        return
    res = run(args.format, rows, cols, args.disk_dtype, k, args.iters,
              chunk, keep=args.keep,
              compare_synthetic=args.compare_synthetic,
              drop_caches=args.drop_caches, prefetch=args.prefetch)
    print(json.dumps({k2: (round(v, 4) if isinstance(v, float) else v)
                      for k2, v in res.items()}))


if __name__ == "__main__":
    main()
