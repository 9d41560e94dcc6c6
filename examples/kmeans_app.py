"""Runnable Harp-style KMeans app — the MIGRATING.md side-by-side, complete.

Shows the ``CollectiveApp`` / ``mapCollective`` programming model (Harp L4)
on synthetic data; the production implementation with the fused MXU path
and on-device iteration loop is ``harp_tpu.models.kmeans``.

Run:  python examples/kmeans_app.py [--n 4096] [--k 8] [--iters 10]

Runs on whatever devices JAX finds; to simulate 8 workers on the host:
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--n", type=int, default=4096)
    p.add_argument("--d", type=int, default=16)
    p.add_argument("--k", type=int, default=8)
    p.add_argument("--iters", type=int, default=10)
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from harp_tpu import CollectiveApp, Combiner, run_app
    from harp_tpu.parallel import collective as C

    class KMeansApp(CollectiveApp):
        def load_shard(self):
            rng = np.random.default_rng(0)
            n = args.n // self.num_workers * self.num_workers
            pts = rng.normal(size=(n, args.d)).astype(np.float32)
            return self.mesh.shard_array(pts, 0), pts

        def map_collective(self):
            pts_sharded, pts_host = self.load_shard()
            cents = jax.device_put(
                jnp.asarray(pts_host[: args.k]), self.mesh.replicated()
            )

            def step(pts, cents):  # one SPMD program per iteration
                d2 = ((pts[:, None] - cents[None]) ** 2).sum(-1)
                one_hot = jax.nn.one_hot(d2.argmin(1), cents.shape[0],
                                         dtype=pts.dtype)
                sums = one_hot.T @ pts
                counts = one_hot.sum(0)
                sums, counts = C.allreduce((sums, counts), Combiner.ADD)
                return sums / jnp.maximum(counts[:, None], 1.0)

            fit = jax.jit(self.mesh.shard_map(
                step, in_specs=(self.mesh.spec(0), P()), out_specs=P()))
            for i in range(args.iters):
                cents = fit(pts_sharded, cents)
                self.metrics.log(step=i)
            return np.asarray(cents)

    cents = run_app(KMeansApp, config=vars(args))
    print({"k": args.k, "iters": args.iters,
           "centroid_norm": float(np.linalg.norm(cents))})


if __name__ == "__main__":
    main()
