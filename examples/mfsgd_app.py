"""Runnable Harp-style MF-SGD app — the model-rotation pattern, complete.

Shows the signature Harp pattern (``edu.iu.sgd``): item factors travel the
worker ring while each worker trains on its resident slice.  The production
implementation (dense one-hot MXU updates, multi-epoch single-dispatch,
checkpoint/resume) is ``harp_tpu.models.mfsgd``; this example drives it
through the ``CollectiveApp`` lifecycle the way a Harp ``mapCollective``
program would.

Run:  python examples/mfsgd_app.py [--users 600] [--items 400]
      [--nnz 20000] [--epochs 10]

Runs on whatever devices JAX finds; to simulate 8 workers on the host:
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--users", type=int, default=600)
    p.add_argument("--items", type=int, default=400)
    p.add_argument("--nnz", type=int, default=20_000)
    p.add_argument("--rank", type=int, default=16)
    p.add_argument("--epochs", type=int, default=10)
    args = p.parse_args()
    if args.epochs < 1:
        p.error("--epochs must be >= 1")

    from harp_tpu import CollectiveApp, run_app
    from harp_tpu.models.mfsgd import MFSGD, MFSGDConfig, synthetic_ratings

    class MFSGDApp(CollectiveApp):
        def map_collective(self):
            # load this job's ratings (a real app would read file splits
            # through self.reader; see `python -m harp_tpu mfsgd --input`)
            u, i, v = synthetic_ratings(args.users, args.items, args.nnz,
                                        rank=4, noise=0.05, seed=0)
            # algo="dense" explicitly: the demo's 64-row tiles are below
            # the default pallas kernel's 128-multiple TPU minimum
            cfg = MFSGDConfig(rank=args.rank, lr=0.05, algo="dense",
                              u_tile=64, i_tile=64, entry_cap=256)
            model = MFSGD(args.users, args.items, cfg, self.mesh, seed=0)
            model.set_ratings(u, i, v)

            # every epoch is a full ring rotation of the item factors; all
            # epochs run as ONE device program (no per-epoch dispatches)
            rmses = model.train_epochs(args.epochs)
            for e, r in enumerate(rmses):
                self.metrics.log(epoch=e, rmse=round(r, 4))
            return {"rmse_first": round(rmses[0], 4),
                    "rmse_final": round(rmses[-1], 4),
                    "workers": self.num_workers}

    print(run_app(MFSGDApp))


if __name__ == "__main__":
    main()
