"""Runnable long-context transformer layer — the sequence-parallel stack.

Long-context composition demo (SURVEY.md §6 "long-context / sequence
parallelism"; the attention/rope modules carry the per-piece parity notes).
Composes the long-context toolkit end to end the way a Harp app composes
collective verbs: sequence-sharded activations, shard-local RoPE
(`harp_tpu.ops.rope`), windowed causal GQA ring attention
(`harp_tpu.ops.ring_attention`), and a data-parallel gradient allreduce
through the same `collective.allreduce` verb every app uses — one training
step of a transformer layer whose sequence never fits on one chip.

Run:  python examples/longctx_layer.py [--seq 512] [--window 64]

Runs on whatever devices JAX finds; to simulate 8 workers on the host:
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--seq", type=int, default=512)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--kv-heads", type=int, default=2)
    p.add_argument("--dim", type=int, default=16)
    p.add_argument("--window", type=int, default=64)
    p.add_argument("--steps", type=int, default=10)
    args = p.parse_args()
    if args.steps < 1:
        p.error("--steps must be >= 1")

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from harp_tpu import WorkerMesh, Combiner, collective as C
    from harp_tpu.ops import apply_rope, ring_attention

    mesh = WorkerMesh()
    h, g, d = args.heads, args.kv_heads, args.dim
    model_d = h * d
    rng = np.random.default_rng(0)

    params = {
        "wq": rng.normal(size=(model_d, h * d)).astype(np.float32) * 0.05,
        "wk": rng.normal(size=(model_d, g * d)).astype(np.float32) * 0.05,
        "wv": rng.normal(size=(model_d, g * d)).astype(np.float32) * 0.05,
        "wo": rng.normal(size=(h * d, model_d)).astype(np.float32) * 0.05,
    }
    x = rng.normal(size=(1, args.seq, model_d)).astype(np.float32)

    def layer(params, x):
        b, s, _ = x.shape
        q = apply_rope((x @ params["wq"]).reshape(b, s, h, d))
        k = apply_rope((x @ params["wk"]).reshape(b, s, g, d))
        v = (x @ params["wv"]).reshape(b, s, g, d)
        o = ring_attention(q, k, v, causal=True, window=args.window)
        return o.reshape(b, s, h * d) @ params["wo"]

    # teacher-student: the target is the same layer under different weights,
    # so the regression is realizable and the loss visibly descends
    teacher = {k2: rng.normal(size=v2.shape).astype(np.float32) * 0.05
               for k2, v2 in params.items()}

    def step(params, x, y):
        def loss_fn(p):
            return ((layer(p, x) - y) ** 2).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        # the Harp verb: sequence shards each see part of the loss surface;
        # one allreduce makes the update identical everywhere
        grads, loss = C.allreduce((grads, loss), Combiner.AVG)
        return jax.tree.map(lambda p, g: p - 2.0 * g, params, grads), loss

    spec = mesh.spec(1, ndim=3)  # shard the sequence dim
    fit = jax.jit(mesh.shard_map(
        step, in_specs=(P(), spec, spec), out_specs=(P(), P())))
    target = np.asarray(jax.jit(mesh.shard_map(
        layer, in_specs=(P(), spec), out_specs=spec))(teacher, x))

    losses = []
    for _ in range(args.steps):
        params, loss = fit(params, x, target)
        losses.append(float(np.asarray(loss)))
    print({"workers": mesh.num_workers, "seq": args.seq,
           "heads": f"{h}q/{g}kv", "window": args.window,
           "loss_first": round(losses[0], 5), "loss_final": round(losses[-1], 5)})


if __name__ == "__main__":
    main()
