#!/usr/bin/env python
"""Measure raw host->device transfer bandwidth over the current backend.

Times the placement ingest actually uses — ``WorkerMesh.shard_array``,
rows split over every device ``jax.devices()`` returns (one or four) —
and the ``np.asarray`` readback, at a few sizes; prints one JSON line.
What a streaming ingest can reach is bounded by the first number.
"""

import json
import sys
import time

import numpy as np


def main():
    import os

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import jax

    from harp_tpu.parallel.mesh import WorkerMesh
    from harp_tpu.utils import chip

    mesh = WorkerMesh()
    nw = mesh.num_workers
    out = {"config": "probe_h2d", **chip.device_info(), "probes": []}
    for mb in (1, 16, 64, 157):
        arr = np.random.default_rng(0).standard_normal(
            ((mb << 20) // 2 // nw * nw,)).astype(np.float16).reshape(nw, -1)
        # warm one tiny transfer to exclude set-up from the first row
        jax.block_until_ready(mesh.shard_array(np.ones((nw, 8), np.float16)))
        t0 = time.perf_counter()
        x = jax.block_until_ready(mesh.shard_array(arr, 0))
        h2d = time.perf_counter() - t0
        t0 = time.perf_counter()
        back = np.asarray(x)
        d2h = time.perf_counter() - t0
        assert back[0, 0] == arr[0, 0]
        out["probes"].append({"mb": mb, "h2d_s": round(h2d, 3),
                              "h2d_mb_s": round(mb / h2d, 1),
                              "d2h_s": round(d2h, 3),
                              "d2h_mb_s": round(mb / d2h, 1)})
        print(json.dumps(out["probes"][-1]), file=sys.stderr, flush=True)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
