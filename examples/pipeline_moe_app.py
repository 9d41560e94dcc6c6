"""Runnable pipeline-parallel + expert-parallel training demo.

Composes the two parallelism strategies Harp lacked (SURVEY.md §3.5
marks PP and EP ❌ upstream; `parallel/pipeline.py` and `ops/moe.py`
carry the design notes) the way a Harp app composes verbs:

1. GPipe pipeline: each worker owns ONE stage of a deep tanh-MLP;
   microbatches enter at stage 0 and activations hop the worker ring
   (`rotate`/ppermute) — `pipeline_loss_and_grads` differentiates
   through the hops, so plain SGD on each worker's stage trains the
   whole stack.  The loss must visibly descend.
2. Switch MoE layer: the same mesh, one expert per worker, tokens
   routed by a gating argmax through ONE `regroup` (all-to-all) each
   way — checked against the dense host reference.

Run:  python examples/pipeline_moe_app.py [--steps 20]

Runs on whatever devices JAX finds; to simulate 8 workers on the host:
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--width", type=int, default=16)
    p.add_argument("--microbatches", type=int, default=4)
    p.add_argument("--lr", type=float, default=0.2)
    args = p.parse_args()
    if args.steps < 2:
        p.error("--steps must be >= 2 (the descent check compares "
                "first and last step)")

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from harp_tpu import WorkerMesh
    from harp_tpu.ops.moe import moe_ffn, reference_moe
    from harp_tpu.parallel.pipeline import pipeline_loss_and_grads

    mesh = WorkerMesh()
    nw = mesh.num_workers
    w = args.width
    rng = np.random.default_rng(0)

    # --- 1. GPipe pipeline training over the worker ring ---
    def stage_fn(params, h):
        return jax.nn.tanh(h @ params["w"] + params["b"])

    params = {
        "w": (rng.normal(size=(nw, w, w)) * 0.5).astype(np.float32),
        "b": np.zeros((nw, w), np.float32),
    }
    # teacher-student: targets from the same stack under other weights,
    # so the regression is realizable and the loss visibly descends
    teacher = {
        "w": (rng.normal(size=(nw, w, w)) * 0.5).astype(np.float32),
        "b": (rng.normal(size=(nw, w)) * 0.1).astype(np.float32),
    }
    x = rng.normal(size=(args.microbatches, 8, w)).astype(np.float32)
    tgt = np.asarray(x)
    for s in range(nw):
        tgt = np.tanh(tgt @ teacher["w"][s] + teacher["b"][s])

    def loss_fn(outs, targets):
        return ((outs - targets) ** 2).mean()

    spec = {"w": mesh.spec(0), "b": mesh.spec(0)}

    @jax.jit
    def sgd_step(params, x, tgt):
        def device(p, xx, tt):
            loss, grads = pipeline_loss_and_grads(
                stage_fn, loss_fn, jax.tree_util.tree_map(
                    lambda a: a[0], p), xx, tt)
            # each worker updates ITS stage; re-add the leading stage dim
            new = jax.tree_util.tree_map(
                lambda a, g: a - args.lr * g[None],
                jax.tree_util.tree_map(lambda a: a[0], p), grads)
            return loss, new

        return mesh.shard_map(
            device, in_specs=(spec, P(), P()), out_specs=(P(), spec))(
            params, x, tgt)

    losses = []
    for _ in range(args.steps):
        loss, params = sgd_step(params, x, tgt)
        losses.append(float(jax.device_get(loss)))
    print(f"pipeline[{nw} stages x {args.microbatches} microbatches] "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    assert losses[-1] < losses[0], "pipeline training must descend"

    # --- 2. Switch MoE layer through the regroup dispatch ---
    d, hdim, cap = w, 2 * w, 8
    moe_w = {
        "gate": rng.normal(size=(d, nw)).astype(np.float32),
        "w1": (rng.normal(size=(nw, d, hdim)) * 0.5).astype(np.float32),
        "b1": np.zeros((nw, hdim), np.float32),
        "w2": (rng.normal(size=(nw, hdim, d)) * 0.5).astype(np.float32),
        "b2": np.zeros((nw, d), np.float32),
    }
    tokens = rng.normal(size=(nw * cap, d)).astype(np.float32)
    y, dropped = jax.jit(mesh.shard_map(
        lambda xx, wt: moe_ffn(xx, wt["gate"], wt["w1"][0], wt["b1"][0],
                               wt["w2"][0], wt["b2"][0], capacity=cap),
        in_specs=(mesh.spec(0),
                  {"gate": P(), "w1": mesh.spec(0), "b1": mesh.spec(0),
                   "w2": mesh.spec(0), "b2": mesh.spec(0)}),
        out_specs=(mesh.spec(0), P())))(tokens, moe_w)
    ref = reference_moe(tokens, moe_w["gate"], moe_w["w1"], moe_w["b1"],
                        moe_w["w2"], moe_w["b2"], cap, nw)
    np.testing.assert_allclose(np.asarray(y), ref, rtol=2e-4, atol=2e-5)
    print(f"moe[{nw} experts, capacity {cap}] == dense reference "
          f"(dropped={int(jax.device_get(dropped))})")


if __name__ == "__main__":
    main()
