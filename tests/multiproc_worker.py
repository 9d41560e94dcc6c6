"""Worker script for the multi-process jax.distributed tests (not a pytest module).

Launched by tests/test_multiprocess.py as ``python multiproc_worker.py
<process_id> <port> [num_processes] [local_devices]``.  Validates the
multi-host code paths without TPU hardware: ``init_distributed``
bootstrap, a mesh spanning processes, and EVERY collective family
crossing a real process boundary (Gloo on CPU — the DCN stand-in):
allreduce, regroup / all_to_all, dense push/pull, the sparse
request/serve pull/push, the host-side ``kv_allreduce`` union, full
MF-SGD / LDA epochs, ZeRO-1 optimizer steps (sharded state asserted per
process, trajectory == replicated adam), and a tensor-parallel MLP step
on a 2-D mesh whose model axis crosses the process link.

``local_devices > 1`` is the POD-SHAPED topology (VERDICT r2 item 6): a
v4-32 is N processes × M chips, where intra-process (ICI stand-in) and
inter-process (DCN stand-in) links coexist in ONE mesh — the launcher
sets ``--xla_force_host_platform_device_count=M`` per process, and every
check below validates each process's M addressable shards against the
globally-expected array, so block layouts that happen to be right only
at one-device-per-process cannot pass silently.
"""

import os
import sys

proc_id = int(sys.argv[1])
port = sys.argv[2]
n_procs = int(sys.argv[3]) if len(sys.argv) > 3 else 2
local_devices = int(sys.argv[4]) if len(sys.argv) > 4 else 1

if local_devices > 1:
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               f" --xla_force_host_platform_device_count={local_devices}")

import jax  # the backend is the parent's JAX_PLATFORMS=cpu

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harp_tpu import Int2IntKVTable, WorkerMesh, init_distributed, kv_allreduce
from harp_tpu.parallel import collective as C

init_distributed(f"127.0.0.1:{port}", num_processes=n_procs,
                 process_id=proc_id)
assert jax.process_count() == n_procs, jax.process_count()
assert jax.local_device_count() == local_devices, jax.local_device_count()

import numpy as np

mesh = WorkerMesh()
nw = mesh.num_workers
assert nw == n_procs * local_devices, (nw, n_procs, local_devices)


def check_global(arr, expected, rtol=1e-7, atol=0.0):
    """Validate every shard THIS process can address against the expected
    global array — works for any sharding and any devices-per-process."""
    expected = np.asarray(expected)
    for sh in arr.addressable_shards:
        np.testing.assert_allclose(np.asarray(sh.data), expected[sh.index],
                                   rtol=rtol, atol=atol)


# device collective across the process boundary
op = C.host_op(mesh, C.allreduce, in_dim=0, out_dim=0)
x = np.arange(2 * nw, dtype=np.float32).reshape(nw, 2)
check_global(op(x), np.tile(x.sum(0), (nw, 1)))

# regroup / all_to_all across the boundary: worker w sends block j of
# its [nw] vector to worker j; worker w ends holding every peer's block w
rg = C.host_op(mesh, C.regroup, in_dim=0, out_dim=0)
xr = (np.arange(nw)[:, None] * 10 + np.arange(nw)[None, :]).astype(
    np.float32).reshape(-1)  # worker w holds [10w+0 .. 10w+(nw-1)]
check_global(rg(xr),
             (np.arange(nw)[None, :] * 10
              + np.arange(nw)[:, None]).astype(np.float32).reshape(-1))

# dense push (psum_scatter: combined owner shards) and pull (all_gather)
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pushpull_prog(contrib):
    mine = C.push(contrib)          # [rows/nw, d] owner block, summed
    full = C.pull(mine)             # re-materialized [rows, d]
    return mine, full


pp = jax.jit(mesh.shard_map(
    pushpull_prog, in_specs=(P(),), out_specs=(mesh.spec(0), P())))
contrib = np.arange(nw * 3, dtype=np.float32).reshape(nw, 3)
mine, full = pp(contrib)
check_global(mine, contrib * nw)
check_global(full, contrib * nw)

# sparse request/serve pull + push: two all_to_alls cross the boundary
from harp_tpu.table import pull_rows_sparse, push_rows_sparse


def sparse_prog(shard, ids):
    rows, ok, dropped = pull_rows_sparse(shard, ids, capacity=2)
    new_shard, pdrop = push_rows_sparse(
        shard, ids, jnp.ones((ids.shape[0],) + shard.shape[1:],
                             shard.dtype), capacity=2)
    return rows, ok, dropped, new_shard, pdrop


sp = jax.jit(mesh.shard_map(
    sparse_prog, in_specs=(mesh.spec(0), mesh.spec(0)),
    out_specs=(mesh.spec(0), mesh.spec(0), P(), mesh.spec(0), P())))
table = np.arange(nw * 2 * 3, dtype=np.float32).reshape(nw * 2, 3)
# every worker asks for row 0 (owner 0) and its right neighbor's first row
ids = np.stack([np.zeros(nw, np.int64),
                ((np.arange(nw) + 1) % nw) * 2], 1).reshape(-1)
rows, ok, dropped, new_tab, pdrop = sp(table, ids.astype(np.int32))
assert int(np.asarray(dropped)) == 0 and int(np.asarray(pdrop)) == 0
check_global(rows, table[ids])
check_global(ok, np.ones(2 * nw, bool))
exp = table.copy()
np.add.at(exp, ids, 1.0)
check_global(new_tab, exp)

# host-side KV union across processes
t = Int2IntKVTable()
t.add(proc_id, 1)        # unique key per process
t.add(100, proc_id + 1)  # shared: combined 1+2
u = kv_allreduce(t)
assert u.keys() == list(range(n_procs)) + [100], u.keys()
assert int(u.get(100)) == sum(range(1, n_procs + 1)), u.get(100)

# a full dense MF-SGD rotation epoch spanning the process boundary: the
# ring ppermute of H half-slices and the loss allreduce cross the
# process link (and, pod-shaped, the intra-process segments too)
from harp_tpu.models import mfsgd as MF

u_ids, i_ids, vals = MF.synthetic_ratings(32, 24, 400, rank=3, seed=0)
model = MF.MFSGD(32, 24, MF.MFSGDConfig(rank=4, u_tile=8, i_tile=8,
                                        entry_cap=32, lr=0.05),
                 mesh, seed=0)
model.set_ratings(u_ids, i_ids, vals)
r1 = model.train_epoch()
rs = model.train_epochs(3)
assert np.isfinite(r1) and rs[-1] < r1, (r1, rs)

# the fused-kernel algo (interpret-mode pallas off-TPU) through the same
# cross-process rotation: scalar-prefetch grids + scratch under
# shard_map with a process-boundary mesh must match the dense result
model_p = MF.MFSGD(32, 24, MF.MFSGDConfig(rank=4, algo="pallas", u_tile=8,
                                          i_tile=8, entry_cap=32, lr=0.05,
                                          compute_dtype=jnp.float32),
                   mesh, seed=0)
model_p.set_ratings(u_ids, i_ids, vals)
rp = model_p.train_epoch()
model_d = MF.MFSGD(32, 24, MF.MFSGDConfig(rank=4, u_tile=8, i_tile=8,
                                          entry_cap=32, lr=0.05,
                                          compute_dtype=jnp.float32),
                   mesh, seed=0)
model_d.set_ratings(u_ids, i_ids, vals)
rd = model_d.train_epoch()
assert abs(rp - rd) < 1e-5, (rp, rd)

# LDA pull/push epoch across the boundary: the word-topic table is
# row-sharded over the WHOLE mesh, so chunk pull/push request/serve
# round trips cross both intra- and inter-process links
from harp_tpu.models.lda import LDA, LDAConfig, synthetic_corpus

dl, wl = synthetic_corpus(n_docs=8 * nw, vocab_size=8 * nw,
                          n_topics_true=2, tokens_per_doc=8, seed=0)
lda = LDA(8 * nw, 8 * nw, LDAConfig(n_topics=4, algo="pushpull", chunk=16),
          mesh, seed=0)
lda.set_tokens(dl, wl)
for _ in range(3):
    lda.sample_epoch()
assert lda.last_dropped == 0  # default pull_cap: zero drops guaranteed
# multi-host: a process can only read its own shards — check the
# replicated Nk (global topic totals must still equal the token count)
Nk = np.asarray(lda.Nk.addressable_shards[0].data)
np.testing.assert_allclose(Nk.sum(), lda.n_tokens)
local_Nwk = np.asarray(lda.Nwk.addressable_shards[0].data)
assert (local_Nwk >= 0).all() and np.isfinite(local_Nwk).all()

# sharded ingest: each process streams ONLY its own split
# (fit_streaming_local — Harp's HDFS-split model); the result must match
# a straight-line numpy Lloyd on the concatenated dataset
from harp_tpu.models.kmeans_stream import fit_streaming_local

rng = np.random.RandomState(7)
full = (rng.randn(64 * n_procs, 6).astype(np.float32)
        + (np.arange(64 * n_procs)[:, None] % 4) * 5.0)
mine_slice = full[proc_id * 64:(proc_id + 1) * 64]   # THIS process's split
c0 = full[:4].copy()
c_got, inertia_got = fit_streaming_local(mine_slice, k=4, iters=4,
                                         chunk_points=40, mesh=mesh,
                                         init=c0)


def np_lloyd(pts, c, iters):
    c = c.copy()
    for _ in range(iters):
        d2 = ((pts[:, None, :] - c[None]) ** 2).sum(-1)
        a = d2.argmin(1)
        last_inertia = float(d2[np.arange(len(pts)), a].sum())
        for j in range(len(c)):
            if (a == j).any():
                c[j] = pts[a == j].mean(0)
    return c, last_inertia


c_ref, inertia_ref = np_lloyd(full, c0, 4)
np.testing.assert_allclose(c_got, c_ref, rtol=1e-3, atol=1e-3)
assert abs(inertia_got - inertia_ref) < 1e-3 * abs(inertia_ref)

# pod-shaped only: one rotate step around the mixed ICI/DCN ring —
# worker w's block must land on worker (w+1) % nw regardless of which
# segments are intra- vs inter-process
rot = C.host_op(mesh, C.rotate, in_dim=0, out_dim=0)
xrot = np.arange(nw, dtype=np.float32).reshape(nw, 1)
check_global(rot(xrot), np.roll(xrot, 1, axis=0))

# ZeRO-1 optimizer steps across the process boundary (VERDICT r3 item 7):
# the gradient push (psum_scatter) + param pull (all_gather) cross the
# process link, each process holds ONLY its 1/nw optimizer-state shards,
# and the loss trajectory must equal the replicated-adam trainer's
from harp_tpu.models.mlp import MLPConfig, MLPTrainer, synthetic_mnist

xz, yz = synthetic_mnist(n=4 * nw, d=8, classes=4, seed=1)
zcfg = dict(sizes=(8, 16, 4), optimizer="adam")
tr_z = MLPTrainer(MLPConfig(zero1=True, **zcfg), mesh, seed=0)
tr_r = MLPTrainer(MLPConfig(**zcfg), mesh, seed=0)
losses_z = [tr_z.train_batch(xz, yz)[0] for _ in range(3)]
losses_r = [tr_r.train_batch(xz, yz)[0] for _ in range(3)]
np.testing.assert_allclose(losses_z, losses_r, rtol=1e-5, atol=1e-6)
import jax.tree_util as jtu

vec_leaves = [lf for lf in jtu.tree_leaves(tr_z.opt_state) if lf.ndim > 0]
assert vec_leaves, "adam zero1 state must have vector leaves"
for lf in vec_leaves:
    # TRUE sharding per process: local_devices shards of 1/nw each, at
    # distinct offsets — a silently replicated state fails here
    shards = lf.addressable_shards
    assert len(shards) == local_devices, (len(shards), local_devices)
    starts = set()
    for sh in shards:
        assert sh.data.shape[0] == lf.shape[0] // nw, (
            sh.data.shape, lf.shape, nw)
        starts.add(sh.index[0].start or 0)
    assert len(starts) == local_devices, starts
# adam's first moment is nonzero after real steps — the sharded state is
# actually being updated, not dead weight
mu_max = max(float(np.abs(np.asarray(sh.data)).max())
             for sh in vec_leaves[0].addressable_shards)
assert mu_max > 0.0

# tensor parallel across the boundary: a 2-D (data x model) mesh whose
# model axis spans real process links; first-step loss must match the
# data-parallel trainer (GSPMD numerics == explicit-verb numerics)
from harp_tpu.models.mlp import TPMLPTrainer
from harp_tpu.parallel.mesh import mesh_2d

n_model = next(d for d in (4, 2, 1) if nw % d == 0)
tp = TPMLPTrainer(MLPConfig(sizes=(8, 16, 4)),
                  mesh_2d(nw // n_model, n_model), seed=0)
dp = MLPTrainer(MLPConfig(sizes=(8, 16, 4)), mesh, seed=0)
tp_loss, tp_acc = tp.train_batch(xz, yz)
dp_loss, dp_acc = dp.train_batch(xz, yz)
assert abs(tp_loss - dp_loss) < 1e-4, (tp_loss, dp_loss)
assert abs(tp_acc - dp_acc) < 1e-6, (tp_acc, dp_acc)

# --- VERDICT r4 item 6: the remaining parallelism strategies cross the
# same real process boundary the verbs/ZeRO-1/TP already do ---

# pipeline parallelism: one GPipe loss+grad step — activations hop the
# stage ring via rotate/ppermute, so every microbatch crosses the
# process link (and intra-process segments, pod-shaped) S+M-1 times;
# loss AND per-stage grads must match the serial host chain rule
from harp_tpu.parallel.pipeline import pipeline_loss_and_grads

PW, PMB, PM = 8, 2, 3  # width, microbatch, n_microbatches
pp_rng = np.random.default_rng(40)
pp_params = {"w": (pp_rng.normal(size=(nw, PW, PW)) * 0.5).astype(np.float32),
             "b": (pp_rng.normal(size=(nw, PW)) * 0.1).astype(np.float32)}
px = pp_rng.normal(size=(PM, PMB, PW)).astype(np.float32)
pt = pp_rng.normal(size=(PM, PMB, PW)).astype(np.float32)


def pp_stage(params, h):
    return jax.nn.tanh(h @ params["w"] + params["b"])


def pp_loss(outs, targets):
    return ((outs - targets) ** 2).mean()


pp_fn = jax.jit(mesh.shard_map(
    lambda p, xx, tt: pipeline_loss_and_grads(
        pp_stage, pp_loss, jax.tree_util.tree_map(lambda a: a[0], p),
        xx, tt),
    in_specs=({"w": mesh.spec(0), "b": mesh.spec(0)}, P(), P()),
    out_specs=(P(), {"w": mesh.spec(0), "b": mesh.spec(0)})))
pp_l, pp_g = pp_fn(pp_params, px, pt)


def pp_serial_loss(p):
    outs = []
    for i in range(PM):
        h = jnp.asarray(px[i])
        for s in range(nw):
            h = pp_stage({"w": p["w"][s], "b": p["b"][s]}, h)
        outs.append(h)
    return pp_loss(jnp.stack(outs), jnp.asarray(pt))


pp_ref_l, pp_ref_g = jax.value_and_grad(pp_serial_loss)(
    jax.tree_util.tree_map(jnp.asarray, pp_params))
lz = np.asarray(pp_l.addressable_shards[0].data)
assert abs(float(lz) - float(pp_ref_l)) < 1e-5, (lz, pp_ref_l)
# shard_map concatenated per-stage grads along dim 0 (see test_pipeline)
check_global(pp_g["w"], np.asarray(pp_ref_g["w"]).reshape(nw * PW, PW),
             rtol=1e-4, atol=1e-6)
check_global(pp_g["b"], np.asarray(pp_ref_g["b"]).reshape(nw * PW),
             rtol=1e-4, atol=1e-6)

# expert-parallel MoE: the regroup (all_to_all) dispatch + inverse
# exchange cross the process link; capacity sized so nothing drops
from harp_tpu.ops.moe import moe_ffn, reference_moe

MD, MH = 8, 16
moe_rng = np.random.default_rng(41)
moe_w = {"gate": moe_rng.normal(size=(MD, nw)).astype(np.float32),
         "w1": (moe_rng.normal(size=(nw, MD, MH)) * 0.5).astype(np.float32),
         "b1": (moe_rng.normal(size=(nw, MH)) * 0.1).astype(np.float32),
         "w2": (moe_rng.normal(size=(nw, MH, MD)) * 0.5).astype(np.float32),
         "b2": (moe_rng.normal(size=(nw, MD)) * 0.1).astype(np.float32)}
mx = moe_rng.normal(size=(nw * 8, MD)).astype(np.float32)
moe_fn = jax.jit(mesh.shard_map(
    lambda xx, wt: moe_ffn(xx, wt["gate"], wt["w1"][0], wt["b1"][0],
                           wt["w2"][0], wt["b2"][0], capacity=8),
    in_specs=(mesh.spec(0),
              {"gate": P(), "w1": mesh.spec(0), "b1": mesh.spec(0),
               "w2": mesh.spec(0), "b2": mesh.spec(0)}),
    out_specs=(mesh.spec(0), P())))
my, mdrop = moe_fn(mx, moe_w)
assert int(np.asarray(mdrop.addressable_shards[0].data)) == 0
moe_ref = reference_moe(mx, moe_w["gate"], moe_w["w1"], moe_w["b1"],
                        moe_w["w2"], moe_w["b2"], 8, nw)
check_global(my, np.asarray(moe_ref), rtol=2e-4, atol=2e-5)

# ring attention (causal): the K/V ring ppermute crosses the process
# link every block step; online-softmax result must match full attention
from harp_tpu.ops.flash_attention import reference_attention
from harp_tpu.ops.ring_attention import make_ring_attention_fn

ab, ah, ad = 2, 2, 8
an = 8 * nw  # sequence sharded over the whole mesh
at_rng = np.random.default_rng(42)
aq, ak, av = (at_rng.normal(size=(ab, an, ah, ad)).astype(np.float32)
              for _ in range(3))
a_out = make_ring_attention_fn(mesh, causal=True)(aq, ak, av)
qf = jnp.asarray(aq).transpose(0, 2, 1, 3).reshape(ab * ah, an, ad)
kf = jnp.asarray(ak).transpose(0, 2, 1, 3).reshape(ab * ah, an, ad)
vf = jnp.asarray(av).transpose(0, 2, 1, 3).reshape(ab * ah, an, ad)
a_ref = np.asarray(reference_attention(qf, kf, vf, causal=True))
a_ref = a_ref.reshape(ab, ah, an, ad).transpose(0, 2, 1, 3)
for sh in a_out.addressable_shards:
    np.testing.assert_allclose(np.asarray(sh.data), a_ref[sh.index],
                               rtol=2e-4, atol=2e-5)

print(f"proc {proc_id}: MULTIPROC OK", flush=True)
