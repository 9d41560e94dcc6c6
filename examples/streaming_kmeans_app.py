"""Runnable beyond-HBM KMeans app — the 1B-point pattern, end to end.

Shows the round-2 streaming stack on a dataset the device never holds:
a CSV written to disk, streamed through the native double-buffered
reader (``harp_tpu.native.CSVPoints``), clustered by the blocked-epoch
Lloyd (``kmeans_stream.fit_streaming``) with checkpoint/resume, and
verified against the device-resident ``kmeans.fit`` on the same data.
The production north-star config swaps the toy shapes for
``--n 1000000000 --d 300 --k 1000`` and a real corpus.

Run:  python examples/streaming_kmeans_app.py [--n 20000]

Runs on whatever devices JAX finds; to simulate 8 workers on the host:
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8
"""

import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--n", type=int, default=20_000)
    p.add_argument("--d", type=int, default=16)
    p.add_argument("--k", type=int, default=8)
    p.add_argument("--iters", type=int, default=6)
    p.add_argument("--chunk", type=int, default=4096)
    args = p.parse_args()

    import numpy as np

    from harp_tpu.models import kmeans, kmeans_stream
    from harp_tpu.native import CSVPoints
    from harp_tpu.parallel.mesh import WorkerMesh, set_mesh

    mesh = WorkerMesh()
    set_mesh(mesh)
    print(f"mesh: {mesh}")

    rng = np.random.default_rng(0)
    pts = (rng.normal(size=(args.n, args.d))
           + rng.integers(0, args.k, size=(args.n, 1)) * 6).astype(np.float32)

    with tempfile.TemporaryDirectory() as tmp:
        # "HDFS split" stand-in: the dataset lives on disk as text
        csv = os.path.join(tmp, "points.csv")
        with open(csv, "w") as f:
            f.write("# synthetic blobs\n")
            for row in pts:
                f.write(",".join(f"{v:.9e}" for v in row) + "\n")  # f32 round-trips at 9 sig digits

        src = CSVPoints(csv, chunk_rows=args.chunk)
        print(f"source: {src.shape[0]} rows x {src.shape[1]} cols "
              f"(streamed, chunk={args.chunk})")

        ck = os.path.join(tmp, "ckpt")
        c_stream, inertia, hist = kmeans_stream.fit_streaming(
            src, k=args.k, iters=args.iters, chunk_points=args.chunk,
            mesh=mesh, seed=1, return_history=True,
            ckpt_dir=ck, ckpt_every=2)
        src.close()
        print("streamed inertia per epoch:",
              [round(float(h), 1) for h in hist])

        # ground truth: the device-resident fit on the same data/init
        c_res, inertia_res = kmeans.fit(pts, k=args.k, iters=args.iters,
                                        mesh=mesh, seed=1)
        rel = abs(inertia - inertia_res) / max(abs(inertia_res), 1e-9)
        print(f"resident inertia {inertia_res:.1f} vs streamed "
              f"{inertia:.1f}  (rel diff {rel:.2e})")
        assert rel < 1e-3, "streamed != resident Lloyd"
        print("OK: beyond-HBM streaming == device-resident KMeans")


if __name__ == "__main__":
    main()
