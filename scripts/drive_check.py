"""End-to-end drive of the PUBLIC harp_tpu API, checked against numpy.

The standing verification recipe (see .claude/skills/verify/SKILL.md):
imports only the package surface, runs every major subsystem — the
collective verbs with edge-case shifts/dtypes, Zipf LDA pushpull with
exact capacity sizing, the real-ingest harness, the sparse capacity
sweep, power-law subgraph with both overflow tails, the enwiki-1M and
million-token lowering pins, sharded/file-split/int8 ingest — and
checks results against straight-line numpy.  Grows a section per round;
every "DRIVE OK round-N" line must print.

Usage: JAX_PLATFORMS=cpu python scripts/drive_check.py

A CPU drive: 8 simulated workers in this process, and every child it
starts inherits JAX_PLATFORMS=cpu from the environment — so it can run
beside a chip without touching it.  The chip check is chip_smoke.py.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if os.environ.get("JAX_PLATFORMS") != "cpu":
    sys.exit("drive_check.py is a CPU drive: run it with JAX_PLATFORMS=cpu "
             "(the chip check is chip_smoke.py)")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")

import jax

import numpy as np
import jax.numpy as jnp

from harp_tpu import WorkerMesh
from harp_tpu.parallel import collective as C
from harp_tpu.parallel.mesh import set_mesh

mesh = WorkerMesh()
set_mesh(mesh)
nw = mesh.num_workers
print(f"backend={jax.default_backend()} workers={nw}")

# 1. iterative program through shard_map + verbs vs numpy straight-line
x = np.arange(nw * 4, dtype=np.float32).reshape(nw, 4)
op = C.host_op(mesh, C.allreduce, in_dim=0, out_dim=0)
got = np.asarray(op(x))
np.testing.assert_allclose(got, np.tile(x.sum(0), (nw, 1)))

# rotate shift 0 / negative / > nw
for shift in (0, -1, nw + 1):
    rot = C.host_op(mesh, lambda t, s=shift, **kw: C.rotate(t, s, **kw),
                    in_dim=0, out_dim=0)
    np.testing.assert_allclose(np.asarray(rot(x)),
                               np.roll(x, shift % nw, axis=0),
                               err_msg=f"shift={shift}")

# bool through broadcast/reduce (psum promotes bool)
b = np.zeros(nw, bool)
b[0] = True
bc = C.host_op(mesh, C.broadcast, in_dim=0, out_dim=0)
assert np.asarray(bc(b)).any()

# regroup divisibility: rows % nw != 0 must raise, not corrupt
try:
    bad = C.host_op(mesh, C.regroup, in_dim=0, out_dim=0)
    bad(np.zeros((nw, 3), np.float32)) if nw > 3 else None
    if nw > 3:
        raise SystemExit("regroup divisibility did not raise")
except ValueError:
    pass
except Exception as e:  # XLA's own divisibility error is fine too
    assert "divisible" in str(e) or "divide" in str(e), e

# 2. round-3: LDA pushpull dedup + exact cap sizing on a Zipf corpus
from harp_tpu.models.lda import LDA, LDAConfig

rng = np.random.default_rng(0)
n_docs, vocab, tpd = 8 * nw, 128, 16
d_ids = np.repeat(np.arange(n_docs, dtype=np.int32), tpd)
w_ids = ((rng.zipf(1.1, size=n_docs * tpd) - 1) % vocab).astype(np.int32)
model = LDA(n_docs, vocab, LDAConfig(n_topics=4, algo="pushpull", chunk=32),
            mesh, seed=0)
model.set_tokens(d_ids, w_ids)
cap = model.suggest_pull_cap(apply=True)
assert 1 <= cap <= 32, cap
model.sample_epoch()
assert model.last_dropped == 0, model.last_dropped
assert np.asarray(model.Ndk).sum() == model.n_tokens
print(f"lda pushpull dedup: cap={cap}, 0 drops, counts exact")

# 3. round-3: real-ingest harness on a disk npy
import tempfile

from harp_tpu.models.kmeans_stream import benchmark_ingest

tmp = tempfile.mkdtemp()
pts = rng.normal(size=(4096, 16)).astype(np.float16)
np.save(os.path.join(tmp, "p.npy"), pts)
mm = np.load(os.path.join(tmp, "p.npy"), mmap_mode="r")
r = benchmark_ingest(mm, k=8, iters=2, chunk_points=1024, mesh=mesh,
                     disk_bytes=os.path.getsize(os.path.join(tmp, "p.npy")))
assert r["points_per_sec"] > 0 and 0 < r["overlap_efficiency"] <= 1
assert r["host_sec_per_epoch"] <= r["epoch_sec"]
print(f"ingest: {r['points_per_sec']:.0f} pts/s, "
      f"host {r['host_gb_per_sec']:.2f} GB/s, "
      f"overlap {r['overlap_efficiency']:.2f}")

# 4. round-3: capacity sweep contract under skew
from harp_tpu import benchmark as B

recs = list(B.sweep_sparse_capacity(mesh, m=256, d=8, reps=1,
                                    caps=(1 / 4, 1.0)))
by = {}
for rec in recs:
    by.setdefault(rec["dist"], []).append(rec)
assert by["zipf_dedup"][0]["drop_rate"] <= by["zipf"][0]["drop_rate"]
assert all(rows[-1]["drop_rate"] == 0.0 for rows in by.values())
print("sparse capacity sweep: dedup<=raw, full cap never drops")

# 5. round-3: subgraph power-law graph, exact overflow
from harp_tpu.models.subgraph import benchmark as sg_bench

sg = sg_bench(n_vertices=1000, avg_degree=4, template="u3-path",
              max_degree=4, graph="powerlaw", mesh=mesh)
assert sg["dropped_edges"] == 0 and sg["overflow_share"] > 0
print(f"subgraph powerlaw: overflow {sg['overflow_share']:.0%}, 0 dropped")

# 6. round-3: enwiki shape model + lowering of the true-shape program
from harp_tpu.models import lda as L

cfg = L.LDAConfig(n_topics=64, algo="pushpull", ndk_dtype="int16")
shapes = L.epoch_arg_shapes(nw, 10_000, 2_000, cfg, n_tokens=200_000)
sds = [jax.ShapeDtypeStruct(s, dt, sharding=(mesh.replicated() if i == 2
                                             else mesh.sharding(mesh.spec(0))))
       for i, (s, dt) in enumerate(shapes)]
text = L.make_multi_epoch_fn(mesh, cfg, 2_000, epochs=2).lower(*sds).as_text()
assert "while" in text and "xi16" in text
print("epoch_arg_shapes lowering: ok")

print("DRIVE OK")

# 7. public dedup verbs: one slot per distinct id, contract parity
from harp_tpu.table import pull_rows_sparse_dedup, push_rows_sparse_dedup

tb = np.arange(nw * 4 * 2, dtype=np.float32).reshape(nw * 4, 2)
hot = np.zeros(nw * 6, np.int32)  # every worker: 6 copies of row 0

def ddprog(t, i):
    rows, ok, drop = pull_rows_sparse_dedup(t, i, capacity=1)
    t2, pdrop = push_rows_sparse_dedup(
        t, i, jnp.ones((i.shape[0], 2), jnp.float32), capacity=1)
    return rows, ok, drop, t2, pdrop

dd = jax.jit(mesh.shard_map(
    ddprog, in_specs=(mesh.spec(0),) * 2,
    out_specs=(mesh.spec(0), mesh.spec(0), None, mesh.spec(0), None)))
try:
    rows, ok, drop, t2, pdrop = dd(tb, hot)
except Exception:
    from jax.sharding import PartitionSpec as PS
    dd = jax.jit(mesh.shard_map(
        ddprog, in_specs=(mesh.spec(0),) * 2,
        out_specs=(mesh.spec(0), mesh.spec(0), PS(), mesh.spec(0), PS())))
    rows, ok, drop, t2, pdrop = dd(tb, hot)
assert int(drop) == 0 and int(pdrop) == 0 and np.asarray(ok).all()
np.testing.assert_allclose(np.asarray(rows), np.tile(tb[0], (nw * 6, 1)))
exp = tb.copy(); exp[0] += 6 * nw  # 6 dups pre-summed, pushed by nw workers
np.testing.assert_allclose(np.asarray(t2), exp)
print("dedup verbs: cap=1 serves the hot row, push pre-sum exact")
print("DRIVE OK round-2")

# 8. sharded ingest: fit_streaming_local ≡ fit_streaming (explicit init)
from harp_tpu.models.kmeans_stream import fit_streaming, fit_streaming_local

pl = rng.normal(size=(3000, 12)).astype(np.float32) \
    + (np.arange(3000)[:, None] % 3) * 6
c0 = pl[:6].copy()
cg, ig = fit_streaming(pl, k=6, iters=4, chunk_points=400, mesh=mesh, init=c0)
cl_, il_ = fit_streaming_local(pl, k=6, iters=4, chunk_points=400,
                               mesh=mesh, init=c0)
# the two paths sum partial stats in different orders, so f32 roundoff
# can flip one boundary point's assignment (moves a centroid by
# ~point_scale/cluster_size; seen at 0.009 on jax 0.4.37) — the invariant
# is inertia parity plus boundary-flip-sized centroid agreement
assert abs(ig - il_) / max(abs(ig), 1.0) < 1e-3, (ig, il_)
assert np.allclose(cg, cl_, rtol=1e-4, atol=0.05)
print(f"sharded ingest: local≡global, inertia {ig:.1f} vs {il_:.1f}")
print("DRIVE OK round-3")

# 9. file-split ingest: directory of splits, per-worker file streams
import glob as _glob

from harp_tpu.models.kmeans_stream import fit_streaming_files

sdir = tempfile.mkdtemp()
fpts = rng.normal(size=(900, 10)).astype(np.float32) \
    + (np.arange(900)[:, None] % 3) * 7
for i in range(4):
    np.savetxt(os.path.join(sdir, f"part_{i}.csv"),
               fpts[i * 225:(i + 1) * 225], fmt="%.5f", delimiter=",")
c0f = fpts[:5].copy()
cf, inf = fit_streaming_files(sorted(_glob.glob(os.path.join(sdir, "*.csv"))),
                              k=5, iters=3, chunk_points=200, mesh=mesh,
                              init=c0f)
cg2, ig2 = fit_streaming(fpts, k=5, iters=3, chunk_points=200, mesh=mesh,
                         init=c0f)
assert np.allclose(cf, cg2, rtol=1e-3, atol=1e-3)
print(f"file-split ingest: 4 csv splits ≡ single source ({inf:.1f})")
print("DRIVE OK round-4")

# 10. subgraph overflow: both exact tails agree through the public API
from harp_tpu.models import subgraph as SG

hub_edges = [(0, i) for i in range(1, 48)] + \
    [(int(a), int(b)) for a, b in zip(rng.integers(0, 48, 80),
                                      rng.integers(0, 48, 80))]
trials = {}
for algo in ("segment", "onehot"):
    cfgs = SG.SubgraphConfig(template="u3-path", n_trials=3, seed=2,
                             max_degree=4, overflow_algo=algo,
                             overflow_row_tile=8, overflow_entry_tile=16)
    est, tr, ovf = SG.count_template(hub_edges, 48, cfgs, mesh)
    assert ovf > 0
    trials[algo] = tr
np.testing.assert_allclose(trials["onehot"], trials["segment"], rtol=1e-5)
print("subgraph overflow: onehot ≡ segment on a hub graph")
print("DRIVE OK round-5")

# 11. int8 sharded ingest + million-token attention lowering
from harp_tpu.models.kmeans_stream import fit_streaming_local as fsl

cq, iq = fsl(pl, k=6, iters=3, chunk_points=400, mesh=mesh, init=c0,
             quantize="int8")
assert np.isfinite(iq)
from harp_tpu.ops.ring_attention import make_ring_attention_fn as mra

sh_att = mesh.sharding(mesh.spec(1, ndim=4))
sds_att = [jax.ShapeDtypeStruct((1, 1_048_576, 8, 128), jnp.bfloat16,
                                sharding=sh_att) for _ in range(3)]
t_att = mra(mesh, causal=True).lower(*sds_att).as_text()
assert "collective_permute" in t_att and "131072" in t_att
print("int8 sharded ingest + 1M-token ring attention lowering: ok")
print("DRIVE OK round-6")

# 12. int8 file-split ingest through the CLI surface
from harp_tpu.models.kmeans_stream import fit_streaming_files as fsf

cq2, iq2 = fsf(sorted(_glob.glob(os.path.join(sdir, "*.csv"))), k=5,
               iters=2, chunk_points=200, mesh=mesh, init=c0f,
               quantize="int8")
assert np.isfinite(iq2)
print(f"int8 file-split ingest: ok ({iq2:.1f})")
print("DRIVE OK round-7")

# 13. roofline annotation math (this session: default-precision bf16 peak
# + fused-kernel kmeans byte model, driven against hand-computed numpy)
from harp_tpu.utils.roofline import V5E, V5E_PEAKS, annotate

rec = {"n": 1_000_000, "d": 300, "k": 100, "iters_per_sec": 400.0,
       "quantize": None, "num_workers": 1}
ann = annotate("kmeans", rec, V5E)
flops_s = 4.0 * rec["n"] * rec["d"] * rec["k"] * rec["iters_per_sec"]
bytes_s = (rec["n"] * rec["d"] * 4 + 4.0 * rec["n"]) * rec["iters_per_sec"]
np.testing.assert_allclose(ann["achieved_tflops"], round(flops_s / 1e12, 3))
np.testing.assert_allclose(ann["achieved_gbs"], round(bytes_s / 1e9, 2))
assert ann["roofline_peak"] == "bf16_flops"  # default-precision matmuls
np.testing.assert_allclose(
    ann["pct_peak_flops"],
    round(100.0 * flops_s / V5E_PEAKS["bf16_flops"], 2))
# the silicon fact that forced the fix: 131 TF/s measured ex-gen on
# kmeans_stream must be REPRESENTABLE (< 100% of the chosen peak)
fast = annotate("kmeans_stream", {"n": 99_876_864, "d": 300, "k": 1000,
                                  "iters_per_sec": 0.53,
                                  "iters_per_sec_ex_gen": 1.0934,
                                  "quantize": None, "num_workers": 1}, V5E)
assert fast["pct_peak_flops"] < 100.0, fast
assert fast["bound"] == "compute"
print("roofline: bf16 peak + fused byte model vs numpy: ok")
print("DRIVE OK round-8")

# 14. wire-dtype streaming + fused int8 kernel (this session)
import tempfile as _tf

_wd = _tf.mkdtemp(prefix="drive_wire_")
_pts16 = (rng.normal(size=(1500, 16)).astype(np.float32) * 3).astype(np.float16)
_npy = os.path.join(_wd, "pts16.npy")
np.save(_npy, _pts16)
_mm = np.load(_npy, mmap_mode="r")
from harp_tpu.models.kmeans_stream import fit_streaming as _fstr

_c_auto, _i_auto = _fstr(_mm, k=6, iters=3, chunk_points=512, mesh=mesh,
                         seed=11)
_c_leg, _i_leg = _fstr(_mm, k=6, iters=3, chunk_points=512, mesh=mesh,
                       seed=11, wire_dtype=None)
np.testing.assert_array_equal(_c_auto, _c_leg)  # f16 wire is exact
from harp_tpu.models.kmeans import fit as _kfit

_pts_i8 = np.asarray(_pts16, np.float32)[:1024]
_ca, _ia = _kfit(_pts_i8, k=4, iters=4, mesh=mesh, seed=5, quantize="int8")
_cb, _ib = _kfit(_pts_i8, k=4, iters=4, mesh=mesh, seed=5, quantize="int8",
                 use_pallas=True)
np.testing.assert_allclose(_ca, _cb, rtol=1e-5, atol=1e-5)
print(f"wire dtype exact + fused int8 kernel ≡ XLA int8 ({_ib:.1f})")
print("DRIVE OK round-9")

# 15. fused Pallas MF-SGD (this session): algo="pallas" through the public
# MFSGD driver must reproduce algo="dense" (same entries, same order) and
# leave ratings-free W blocks untouched.
from harp_tpu.models.mfsgd import MFSGD, MFSGDConfig, synthetic_ratings

_u, _i, _v = synthetic_ratings(96, 64, 3000, rank=4, noise=0.05, seed=2)
_factors = {}
_mt = 8
for _algo in ("dense", "pallas"):
    _cfg = MFSGDConfig(rank=8, algo=_algo, u_tile=_mt, i_tile=_mt,
                       entry_cap=32, compute_dtype=jnp.float32,
                       lr=0.03, reg=0.01)
    _m = MFSGD(96, 64, _cfg, mesh, seed=4)
    _m.set_ratings(_u, _i, _v)
    _rm = [_m.train_epoch() for _ in range(2)]
    _factors[_algo] = (_m.factors(), _rm)
np.testing.assert_allclose(_factors["pallas"][0][0], _factors["dense"][0][0],
                           rtol=1e-4, atol=1e-5)
np.testing.assert_allclose(_factors["pallas"][0][1], _factors["dense"][0][1],
                           rtol=1e-4, atol=1e-5)
np.testing.assert_allclose(_factors["pallas"][1], _factors["dense"][1],
                           rtol=1e-5)
assert _factors["pallas"][1][1] < _factors["pallas"][1][0]  # converging
print(f"pallas MF-SGD ≡ dense through public driver "
      f"(rmse {_factors['pallas'][1][-1]:.4f})")
print("DRIVE OK round-10")

# 16. self-time op_breakdown (this session): trace a real jitted run and
# check the table is flame-graph-consistent — parent/aggregate spans must
# not outweigh the whole capture (they triple-counted before the fix).
import tempfile as _tf2

from harp_tpu.utils.profiling import op_breakdown, trace

_x = jnp.ones((256, 256))
_g = jax.jit(lambda a: (a @ a).sum())
float(_g(_x))  # compile outside
with trace(_tf2.mkdtemp(prefix="drive_prof_")) as _td:
    float(_g(_x))
_rows = op_breakdown(_td, top=50)
assert _rows and all(s >= 0 for _, s in _rows)
_raw = op_breakdown(_td, top=50, self_time=False)
# self-time never exceeds raw for any op, and the self-time total is ≤ raw
assert sum(s for _, s in _rows) <= sum(s for _, s in _raw) + 1e-9
print(f"self-time op_breakdown: {len(_rows)} ops, "
      f"{sum(s for _, s in _rows) * 1e3:.2f} ms traced")
print("DRIVE OK round-11")

# 17. exprace topic sampler (this session): the exponential-race draw
# through the public LDA driver — frequencies must match the posterior
# (identical distribution to gumbel, ~5× fewer transcendentals).
from harp_tpu.models.lda import LDA, LDAConfig, synthetic_corpus

_d, _w = synthetic_corpus(n_docs=64, vocab_size=32, n_topics_true=4,
                          tokens_per_doc=40, seed=3)
_lls = {}
for _sm, _ri in (("gumbel", "threefry"), ("exprace", "threefry"),
                 ("exprace", "rbg")):
    _lcfg = LDAConfig(n_topics=8, algo="dense", d_tile=16, w_tile=16,
                      entry_cap=64, alpha=0.5, beta=0.1, sampler=_sm,
                      rng_impl=_ri)
    _lm = LDA(64, 32, _lcfg, mesh, seed=1)
    _lm.set_tokens(_d, _w)
    for _ in range(8):
        _lm.sample_epoch()
    _lls[f"{_sm}/{_ri}"] = _lm.log_likelihood()
    _ndk = np.asarray(_lm.Ndk)
    assert _ndk.sum() == _lm.n_tokens and (_ndk >= 0).all()
# both chains must reach the same likelihood ballpark on this corpus
# (different random streams on a tiny corpus: ~10% run-to-run spread,
# so the gate needs real margin over it)
_base = _lls["gumbel/threefry"]
for _k, _v in _lls.items():
    assert abs(_v - _base) / abs(_base) < 0.25, _lls
print(f"sampler/rng variants ≡ gumbel chain quality ({_lls})")
print("DRIVE OK round-12")

# 18. fused Pallas LDA entry resample (this session): algo="pallas"
# through the public driver — chain ascends, counts stay exact integers.
_pt = 16
_pcfg = LDAConfig(n_topics=8, algo="pallas", d_tile=_pt, w_tile=_pt,
                  entry_cap=64, alpha=0.5, beta=0.1,
                  sampler="exprace", rng_impl="rbg")
_pm = LDA(64, 32, _pcfg, mesh, seed=1)
_pm.set_tokens(_d, _w)
_pll0 = _pm.log_likelihood()
for _ in range(6):
    _pm.sample_epoch()
_pndk = np.asarray(_pm.Ndk)
_pnwk = np.asarray(_pm.Nwk)
assert _pndk.sum() == _pm.n_tokens and (_pndk >= 0).all()
assert (_pnwk == np.round(_pnwk)).all()  # integer counts survive bf16 gathers
np.testing.assert_allclose(_pnwk.sum(0), np.asarray(_pm.Nk))
assert _pm.log_likelihood() > _pll0
_pbase = _lls["gumbel/threefry"]
assert abs(_pm.log_likelihood() - _pbase) / abs(_pbase) < 0.25
print(f"pallas LDA chain ok (ll {_pll0:.2f} -> {_pm.log_likelihood():.2f})")
print("DRIVE OK round-13")

# 19. int8 synthetic streaming formulation (this session): the north-star
# compute twin on the int8 MXU — same keys as f32, inertia within the
# quantization tolerance and descending.
from harp_tpu.models.kmeans_stream import benchmark_streaming as _bstr

_bkw = dict(n=32768, d=16, k=8, chunk_points=4096, mesh=mesh, warmup=1)
_bf = _bstr(iters=2, **_bkw)
_bq = _bstr(iters=2, quantize="int8", **_bkw)
assert _bq["quantize"] == "int8"
assert abs(_bq["inertia"] - _bf["inertia"]) / _bf["inertia"] < 0.05
print(f"int8 streaming formulation ≡ f32 within tolerance "
      f"({_bq['inertia']:.0f} vs {_bf['inertia']:.0f})")
print("DRIVE OK round-14")

# 20. ZeRO-1 sharded optimizer (this session): the optax update through
# push/pull must equal the replicated step, and the state must actually
# shard.
from harp_tpu.models.mlp import MLPConfig, MLPTrainer, synthetic_mnist

_zx, _zy = synthetic_mnist(n=256, d=32, classes=4, seed=0)
_zout = {}
for _z in (False, True):
    _zt = MLPTrainer(MLPConfig(sizes=(32, 48, 4), optimizer="adam",
                               zero1=_z), mesh, seed=0)
    _zl = [_zt.train_batch(_zx, _zy)[0] for _ in range(3)]
    _zout[_z] = (_zl, np.concatenate(
        [np.asarray(p).ravel() for p in jax.tree.leaves(_zt.params)]))
np.testing.assert_allclose(_zout[True][0], _zout[False][0], rtol=1e-5)
np.testing.assert_allclose(_zout[True][1], _zout[False][1],
                           rtol=2e-5, atol=2e-6)
print(f"zero1 ≡ replicated adam over 3 steps (loss {_zout[True][0][-1]:.4f})")
print("DRIVE OK round-15")

# 21. round 4 (this session): carry_db through the public LDA driver —
# the od-run-carried doc tile must be BIT-identical to the
# slice-per-entry chain on the dense algo; under pallas the carry is the
# kernel's (PR 32): None and True are one chain and False raises; the
# exact-gather kernel default keeps integer tables; and the flip gate
# refuses a degraded candidate.
from harp_tpu.models.lda import LDA as _R4L
from harp_tpu.models.lda import LDAConfig as _R4C
from harp_tpu.models.lda import synthetic_corpus as _r4corpus

_r4d, _r4w = _r4corpus(n_docs=48, vocab_size=24, n_topics_true=3,
                       tokens_per_doc=24, seed=9)
for _r4algo in ("dense", "pallas"):
    _r4extra = ({"sampler": "exprace", "rng_impl": "rbg"}
                if _r4algo == "pallas" else {})
    _r4chains = {}
    _r4base = None if _r4algo == "pallas" else False
    for _r4carry in (_r4base, True):
        _r4m = _R4L(48, 24, _R4C(n_topics=4, algo=_r4algo, d_tile=8,
                                 w_tile=8, entry_cap=32,
                                 carry_db=_r4carry, **_r4extra),
                    mesh, seed=2)
        _r4m.set_tokens(_r4d, _r4w)
        for _ in range(3):
            _r4m.sample_epoch()
        _r4chains[_r4carry] = (np.asarray(_r4m.Ndk), np.asarray(_r4m.Nwk),
                               np.asarray(_r4m.z_grid))
    for _a, _b in zip(_r4chains[_r4base], _r4chains[True]):
        np.testing.assert_array_equal(_a, _b)
    print(f"carry_db ≡ {_r4base} ({_r4algo}, bit-identical)")
try:
    _R4C(n_topics=4, algo="pallas", sampler="exprace", rng_impl="rbg",
         carry_db=False)
    raise AssertionError("pallas carry_db=False must raise")
except ValueError:
    print("pallas carry_db=False refused (the carry is the kernel's)")

import os as _r4os

print("DRIVE OK round-16")

# 22. round 5 (this session): ADVICE r4 fixes through the public surface.
# (a) the shared carry_tile_switch stays exact for OVERLAPPING
# (non-tile-aligned) offsets — carry vs slice-per-entry bit-identical on
# a hand-built block whose u-runs overlap (0 -> 4 -> 0 with u_tile=8);
from harp_tpu.models import mfsgd as _R5M

_r5rng = np.random.default_rng(11)
_r5blk = (jnp.asarray(_r5rng.integers(0, 8, (5, 4)).astype(np.int32)),
          jnp.asarray(_r5rng.integers(0, 8, (5, 4)).astype(np.int32)),
          jnp.asarray(_r5rng.normal(size=(5, 4)).astype(np.float32)),
          jnp.asarray(np.array([0, 0, 4, 4, 0], np.int32)),
          jnp.asarray(np.array([0, 8, 0, 8, 0], np.int32)))
_r5W0 = _r5rng.normal(size=(24, 3)).astype(np.float32)
_r5H0 = _r5rng.normal(size=(16, 3)).astype(np.float32)
_r5out = {}
for _r5c in (False, True):
    _r5cfg = _R5M.MFSGDConfig(rank=3, algo="dense", u_tile=8, i_tile=8,
                              entry_cap=4, compute_dtype=jnp.float32,
                              lr=0.05, reg=0.01, carry_w=_r5c)
    _r5out[_r5c] = jax.jit(
        lambda W, H, b, c=_r5cfg: _R5M._tile_block_update(W, H, b, c))(
        jnp.asarray(_r5W0), jnp.asarray(_r5H0), _r5blk)
for _a, _b in zip(_r5out[False], _r5out[True]):
    np.testing.assert_array_equal(np.asarray(_a), np.asarray(_b))
print("carry_tile_switch exact for overlapping offsets (bit-identical)")

# (d) the mlp fit CLI emits one parseable JSON line (ADVICE r4 #5).
import contextlib as _r5ctx
import io as _r5io
import json as _r5json

from harp_tpu.models import mlp as _R5mlp

_r5buf = _r5io.StringIO()
with _r5ctx.redirect_stdout(_r5buf):
    _R5mlp.main(["--train", "--batch", "256"])
_r5rows = [_r5json.loads(ln) for ln in _r5buf.getvalue().splitlines()
           if ln.strip()]
assert any(r.get("config") == "mlp_fit_cli" and "train_acc" in r
           for r in _r5rows)
print("mlp --train CLI emits parseable mlp_fit_cli JSON")
print("DRIVE OK round-17")

# 24. round 5 session 2: the two-word prng_seed invariant.  The real TPU
# compiler rejects pltpu.prng_seed with >2 seed words ("Setting seed
# with more than 2 values is not supported" — silicon 2026-08-01, which
# cost the sprint its pallas rows until the mid-window fix); the local
# Mosaic lowering pass does NOT enforce it and the kernel MLIR is
# serialized inside the lowered module (not text-greppable), so the pin
# records the call arity AT TRACE TIME: wrap pltpu.prng_seed, lower the
# noise-free (compiled-mode) kernel for TPU, assert every call passed
# <= 2 words.
import functools as _r5f2

from harp_tpu.ops import lda_kernel as _r5lk

_r5arities = []
_r5orig_seed = _r5lk.pltpu.prng_seed


def _r5rec_seed(*a):
    # count seed WORDS, not positional args — prng_seed accepts array
    # args, so a [3]-shaped single argument is still 3 words to the
    # compiler (review finding, round 5)
    _r5arities.append(sum(int(np.size(x)) for x in a))
    return _r5orig_seed(*a)


_r5lk.pltpu.prng_seed = _r5rec_seed
try:
    _r5kf = _r5f2.partial(_r5lk.cgs_entry_update,
                          alpha=0.1, beta=0.01, vbeta=1.28)
    _r5kargs = (jnp.zeros((128, 128), jnp.float32),
                jnp.zeros((128, 128), jnp.float32),
                jnp.zeros((128,), jnp.float32),
                jnp.zeros((256,), jnp.int32), jnp.zeros((256,), jnp.int32),
                jnp.zeros((256,), jnp.int32), jnp.zeros((2,), jnp.int32))
    jax.jit(_r5kf).trace(*_r5kargs).lower(lowering_platforms=("tpu",))
finally:
    _r5lk.pltpu.prng_seed = _r5orig_seed
assert _r5arities, "noise-free kernel never seeded the PRNG"
assert max(_r5arities) <= 2, (
    f"prng_seed called with {max(_r5arities)} words — the real TPU "
    "compiler takes at most 2 (silicon 2026-08-01)")
print(f"prng_seed arity <= 2 across {len(_r5arities)} trace-time calls")
print("DRIVE OK round-19")

# 25. round 6 (this session): the telemetry spine through the public
# surface.  (a) CommLedger counts per EXECUTION, not per trace: a jitted
# allreduce invoked 3 times (1 trace) must report 3x the hand-computed
# per-shard sheet; (b) kmeans.fit's allreduce row is exactly
# (k*d*4 + k*4 + 4) per iteration; (c) spans nest and export; (d) the
# report CLI round-trips the exported JSONL; (e) disabled telemetry
# records nothing.
from harp_tpu.utils import telemetry as _r6T
from harp_tpu.parallel import collective as _r6C

_r6T.ledger.reset(); _r6T.tracer.reset()
_r6op = _r6C.host_op(mesh, _r6C.allreduce)
_r6x = np.ones((nw * 8, 128), np.float32)
with _r6T.scope():
    for _ in range(3):
        with _r6T.ledger.run("drive.ar", steps=1):
            _r6op(_r6x)
    _r6per = 8 * 128 * 4  # per-shard: [8, 128] f32
    assert _r6T.ledger.bytes_per_execution("drive.ar") == _r6per
    assert _r6T.ledger.volume("drive.ar") == 3 * _r6per

    from harp_tpu.models import kmeans as _r6KM
    _r6k, _r6d, _r6it = 8, 16, 3
    _r6pts = np.random.default_rng(6).normal(
        size=(nw * 32, _r6d)).astype(np.float32)
    _r6KM.fit(_r6pts, k=_r6k, iters=_r6it, mesh=mesh)
    _r6tag = _r6T.ledger.summary()["kmeans.fit"]
    _r6sheet = _r6k * _r6d * 4 + _r6k * 4 + 4  # sums + counts + inertia
    assert _r6tag["bytes_per_execution"] == _r6sheet, _r6tag
    assert _r6tag["executions"] == _r6it
    assert _r6tag["total_bytes"] == _r6sheet * _r6it

    with _r6T.span("drive.outer"):
        with _r6T.span("drive.inner"):
            pass
    _r6recs = {r["span"]: r for r in _r6T.tracer.records}
    assert _r6recs["drive.inner"]["path"] == "drive.outer/drive.inner"

    _r6path = os.path.join(tempfile.mkdtemp(), "run.jsonl")
    _r6T.export(_r6path)

import json as _r6json
import subprocess as _r6sp

_r6rep = _r6sp.run(
    [sys.executable, "-m", "harp_tpu", "report", "--telemetry", _r6path],
    capture_output=True, text=True, timeout=300,
    cwd=_r4os.path.dirname(_r4os.path.dirname(_r4os.path.abspath(__file__))))
assert _r6rep.returncode == 0, _r6rep.stderr[-500:]
assert "== harp-tpu run report ==" in _r6rep.stdout
_r6row = _r6json.loads(_r6rep.stdout.strip().splitlines()[-1])
assert _r6row["comm_tags"]["kmeans.fit"]["total_bytes"] == _r6sheet * _r6it
assert all(f in _r6row for f in ("backend", "date", "commit"))

# disabled => zero records (the stay-on-for-sprints guarantee)
assert not _r6T.enabled()
_r6T.ledger.reset(); _r6T.tracer.reset()
with _r6T.ledger.run("off", steps=1):
    _r6op(np.ones((nw, 128), np.float32))
with _r6T.span("off"):
    pass
assert _r6T.ledger.summary() == {} and _r6T.tracer.records == []
print(f"telemetry: exec-counted ledger, kmeans sheet {_r6sheet} B/iter, "
      "report round-trip, zero-cost off")
print("DRIVE OK round-20")

# 25. PR 2 (this session): overlap-first rotation through the public
# surface.  (a) the chunked pipeline at n_chunks=4: the resident-chunk
# index formula, coverage, and home-placement against a numpy model of
# the queue schedule;
from harp_tpu.parallel import resident_chunk_index, rotate_pipeline
from jax.sharding import PartitionSpec as _P2

_p2nc = 4
_p2rows = 8  # per worker, divisible by 4
_p2ids = np.repeat(np.arange(nw * _p2nc, dtype=np.float32),
                   _p2rows // _p2nc)[:, None]


def _p2prog(s):
    def step(st, cur, t):
        err, acc = st
        want = resident_chunk_index(t, _p2nc).astype(jnp.float32)
        return (err + jnp.abs(cur - want).sum(), acc + cur.sum()), cur

    (err, acc), out = rotate_pipeline(
        step, (jnp.float32(0.0), jnp.float32(0.0)), s, n_chunks=_p2nc)
    return jnp.concatenate([err[None, None], acc[None, None], out], 0)


_p2out = np.asarray(jax.jit(mesh.shard_map(
    _p2prog, in_specs=(mesh.spec(0),), out_specs=mesh.spec(0)))(_p2ids))
_p2out = _p2out.reshape(nw, _p2rows + 2)
assert (_p2out[:, 0] == 0).all()          # schedule == index formula
np.testing.assert_allclose(                # every worker saw every chunk
    _p2out[:, 1], np.full(nw, _p2ids.sum()))
np.testing.assert_array_equal(             # chunks land home
    _p2out[:, 2:].reshape(-1), _p2ids.reshape(-1))
print(f"chunked rotate_pipeline(n_chunks={_p2nc}): schedule, coverage, home")

# (b) quantized data movement: one rounding against the worker-shared
# scale (vs numpy roll / exact regroup), int leaves exact
_p2x = np.random.default_rng(21).normal(size=(nw * 4, 16)).astype(np.float32)
_p2rot = C.host_op(mesh, C.rotate_quantized, in_dim=0, out_dim=0,
                   wire_dtype=jnp.int8)
_p2got = np.asarray(_p2rot(_p2x)).reshape(nw, 4, 16)
_p2exp = np.roll(_p2x.reshape(nw, 4, 16), 1, axis=0)
assert np.abs(_p2got - _p2exp).max() <= np.abs(_p2x).max() / 254 + 1e-6
_p2xi = np.arange(nw * nw, dtype=np.int32).reshape(nw * nw, 1)
_p2rg = C.host_op(mesh, C.regroup_quantized, in_dim=0, out_dim=0,
                  wire_dtype=jnp.int8)
_p2rge = C.host_op(mesh, C.regroup, in_dim=0, out_dim=0)
np.testing.assert_array_equal(np.asarray(_p2rg(_p2xi)),
                              np.asarray(_p2rge(_p2xi)))
print("rotate/regroup_quantized int8: single-rounding bound, int exact")

# (c) MF-SGD at rotate_chunks=4 through the public driver vs the numpy
# replica of the generalized schedule
from harp_tpu.models import mfsgd as _P2M

_p2rng = np.random.default_rng(23)
_p2u = _p2rng.integers(0, 8 * nw, 400).astype(np.int32)
_p2i = _p2rng.integers(0, 6 * nw, 400).astype(np.int32)
_p2v = _p2rng.normal(size=400).astype(np.float32)
_p2cfg = _P2M.MFSGDConfig(rank=4, chunk=16, lr=0.02, reg=0.01,
                          algo="scatter", rotate_chunks=4)
_p2m = _P2M.MFSGD(8 * nw, 6 * nw, _p2cfg, mesh, seed=3)
_p2W0, _p2H0 = np.asarray(_p2m.W).copy(), np.asarray(_p2m.H).copy()
_p2m.set_ratings(_p2u, _p2i, _p2v)
_p2m.train_epoch()
_p2bu, _p2bi, _p2bv, _p2bm, _p2ub, _p2ib = _P2M.partition_ratings(
    _p2u, _p2i, _p2v, 8 * nw, 6 * nw, nw, 16, n_slices=4 * nw)
_p2ns = 4 * nw
_p2W, _p2H = _p2W0.copy(), _p2H0.copy()
_p2bu2 = _p2bu.reshape(nw, _p2ns, -1)
_p2bi2 = _p2bi.reshape(nw, _p2ns, -1)
_p2bv2 = _p2bv.reshape(nw, _p2ns, -1)
_p2bm2 = _p2bm.reshape(nw, _p2ns, -1)
for _t in range(_p2ns):
    for _w in range(nw):
        _r = _t % 4
        _s = 4 * ((_w - _t // 4 - (1 if _r == 3 else 0)) % nw) + _r
        _Wv = _p2W[_w * _p2ub:(_w + 1) * _p2ub]
        _Hv = _p2H[_s * _p2ib:(_s + 1) * _p2ib]
        _B = _p2bu2.shape[-1]
        for _lo in range(0, _B, 16):
            _sl = slice(_lo, _lo + 16)
            _uu, _ii, _vv, _mm = (_p2bu2[_w, _s, _sl], _p2bi2[_w, _s, _sl],
                                  _p2bv2[_w, _s, _sl], _p2bm2[_w, _s, _sl])
            _wu, _hi = _Wv[_uu], _Hv[_ii]
            _err = _mm * (_vv - (_wu * _hi).sum(-1))
            _gw = _err[:, None] * _hi - 0.01 * _mm[:, None] * _wu
            _gh = _err[:, None] * _wu - 0.01 * _mm[:, None] * _hi
            np.add.at(_Wv, _uu, 0.02 * _gw)
            np.add.at(_Hv, _ii, 0.02 * _gh)
np.testing.assert_allclose(np.asarray(_p2m.W), _p2W, rtol=2e-4, atol=2e-5)
np.testing.assert_allclose(np.asarray(_p2m.H), _p2H, rtol=2e-4, atol=2e-5)
print("mfsgd rotate_chunks=4 epoch == numpy generalized schedule")

# (d) LDA at rotate_chunks=4: Gibbs count invariants survive the
# generalized schedule; the CommLedger accounts the int8 rotate wire at
# exactly 1/4 of the f32 baseline (the report's bytes-on-wire claim)
from harp_tpu.models.lda import LDA as _P2L
from harp_tpu.models.lda import LDAConfig as _P2LC
from harp_tpu.models.lda import synthetic_corpus as _p2corpus
from harp_tpu.utils import telemetry as _P2T

_p2d, _p2w = _p2corpus(6 * nw, 64, 3, 16, seed=5)
_p2lm = _P2L(6 * nw, 64, _P2LC(n_topics=6, algo="dense", d_tile=8,
                               w_tile=8, entry_cap=32, rotate_chunks=4),
             mesh, seed=0)
_p2lm.set_tokens(_p2d, _p2w)
for _ in range(2):
    _p2lm.sample_epoch()
assert _p2lm.doc_topic_table().sum() == len(_p2d)
assert _p2lm.word_topic_table().sum() == len(_p2d)
np.testing.assert_allclose(_p2lm.word_topic_table().sum(0),
                           np.asarray(_p2lm.Nk))
assert np.isfinite(_p2lm.log_likelihood())


def _p2rot_bytes(wire):
    with _P2T.scope(True):
        _m = _P2M.MFSGD(64, 64, _P2M.MFSGDConfig(
            rank=8, algo="scatter", chunk=64, rotate_wire=wire), mesh,
            seed=0)
        _m.set_ratings(*_P2M.synthetic_ratings(64, 64, 500, seed=0))
        with _P2T.ledger.run("probe", steps=0):
            _m._epoch_fn.lower(_m.W, _m.H, *_m._blocks)
        return sum(s["payload_bytes"]
                   for s in _P2T.ledger.summary()["probe"]["sites"]
                   # PR 11: the ring hop is the reshard shim now
                   if s["verb"] in ("rotate", "rotate_quantized",
                                    "reshard"))


assert _p2rot_bytes("exact") == 4 * _p2rot_bytes("int8") > 0
print("lda rotate_chunks=4 invariants; ledger: int8 rotate = 1/4 f32 bytes")

print("DRIVE OK round-21")

# --- round 22: execution flight recorder ----------------------------------
# CompileWatch counts real XLA backend compiles with span attribution, the
# TransferLedger's counters reproduce hand-computed byte sheets for a real
# kmeans fit, the budget guard catches the documented driver-loop traps and the
# shipped loop passes its pinned budget, report + export + checker round-trip.
import json as _fr_json
import tempfile as _fr_tmp

from harp_tpu import report as _FRrep
from harp_tpu.models import kmeans as _FRKM
from harp_tpu.utils import flightrec as _FR
from harp_tpu.utils import prng as _FRprng
from harp_tpu.utils import telemetry as _FRT

# (a) collectors against hand-computed values on a real fit
_fr_pts = np.random.default_rng(0).normal(size=(32 * nw, 8)).astype(np.float32)
_FRKM.fit(_fr_pts, k=4, iters=3, mesh=mesh, seed=0)  # warm shared ops
with _FRT.scope(True):
    with _FRT.span("fit"):
        with _FR.budget(compiles=1, dispatches=1, readbacks=2,
                        h2d_bytes=_fr_pts.nbytes, tag="drive.kmeans"):
            _fr_c, _fr_inertia = _FRKM.fit(_fr_pts, k=4, iters=3, mesh=mesh,
                                           seed=0)
    _fr_row, _fr_spans = _FRrep.live_report()
    assert _FR.transfers.h2d_bytes == _fr_pts.nbytes      # points, ONCE
    assert _FR.transfers.dispatches == 1                  # one tracked fit
    assert _FR.transfers.readbacks == 2                   # stats + centroids
    # PR 4: the inertia readback became the [nw, 2] per-worker stats
    # array (rows + inertia — the skew counter rides the same fetch)
    assert _FR.transfers.d2h_bytes == 2 * 4 * nw + _fr_c.nbytes
    assert _FR.compile_watch.count == 1                   # one fresh seed jit
    assert _FR.compile_watch.summary()["by_span"] == {
        "fit/kmeans.fit": {"count": 1,
                           "total_s": _FR.compile_watch.summary()["total_s"]}}
    assert np.isfinite(_fr_inertia)
    # the report row carries the same numbers
    assert _fr_row["compile"]["count"] == 1
    assert _fr_row["transfer"]["h2d_bytes"] == _fr_pts.nbytes
    _fr_text = _FRrep.render(_fr_row, _fr_spans)
    assert "compiles (XLA backend): 1" in _fr_text
    assert "transfers (host<->device):" in _fr_text
    # (b) export -> CLI report -> checker, all from one file
    with _fr_tmp.NamedTemporaryFile("r", suffix=".jsonl") as _fr_fh:
        _FRT.export(_fr_fh.name)
        _fr_kinds = _FRT.load_rows(_fr_fh.name)
        assert _fr_kinds["compile"] and _fr_kinds["transfer"]
        for _fr_r in _fr_kinds["compile"] + _fr_kinds["transfer"]:
            assert {"backend", "date", "commit"} <= set(_fr_r)
        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.abspath(__file__))))
        import check_jsonl as _fr_cj

        assert _fr_cj.check_file(_fr_fh.name) == []
        _fr_row2 = _FRrep.build_row(
            _FRrep.comm_summary_from_rows(_fr_kinds["comm"]),
            _FRrep.span_summary_from_rows(_fr_kinds["span"]),
            compile_info=_FRrep.compile_summary_from_rows(
                _fr_kinds["compile"]),
            transfer_info=_FRrep.transfer_summary_from_rows(
                _fr_kinds["transfer"]))
        assert _fr_row2["compile"]["count"] == _fr_row["compile"]["count"]
        assert _fr_row2["transfer"]["h2d_bytes"] == _fr_pts.nbytes

# (c) the budget guard CATCHES the driver-loop traps (raise mode)
with _FRT.scope(True):
    _fr_f = jax.jit(lambda x: x * 1.01)
    _fr_x = _fr_f(jnp.ones(8))
    from harp_tpu.utils.timing import device_sync as _fr_sync
    try:
        with _FR.budget(readbacks=1, tag="trap"):
            for _ in range(3):
                _fr_x = _fr_f(_fr_x)
                _fr_sync(_fr_x)  # per-epoch readback loop
        raise AssertionError("readbacks budget failed to trip")
    except _FR.BudgetExceeded as _fr_e:
        assert "readbacks used 3 > budget 1" in str(_fr_e)

# (d) prng.key_bits: bit-exact vs PRNGKey and compile-free across seeds
for _fr_seed in (0, 7, -3, 2**40 + 1):
    assert np.array_equal(_FRprng.key_bits(_fr_seed),
                          np.asarray(jax.random.PRNGKey(_fr_seed)))
with _FRT.scope(True):
    _FRprng.split_keys(1, nw)  # warm the shape-keyed split program
    _fr_n = _FR.compile_watch.count
    for _fr_seed in range(50, 60):
        _FRprng.split_keys(_fr_seed, nw)
    assert _FR.compile_watch.count == _fr_n  # zero per-seed compiles

# (e) zero-cost when off: no counter moves, result identical
with _FRT.scope(False):
    _fr_c2, _fr_i2 = _FRKM.fit(_fr_pts, k=4, iters=3, mesh=mesh, seed=0)
    assert _FR.compile_watch.count == 0 and _FR.transfers.dispatches == 0
    assert _FR.transfers.h2d_bytes == 0 and _FR.transfers.readbacks == 0
np.testing.assert_array_equal(_fr_c2, _fr_c)
print("flight recorder: counters == hand sheet, budget trips trap, "
      "export/report/checker round-trip, prng compile-free, zero-cost off")
print("DRIVE OK round-22")

# --- round 23: superstep skew profiler -------------------------------------
# SkewLedger per-worker counts == numpy bincount by the partitioners'
# ownership rule, the execution counters ride the EXISTING stacked
# readbacks (flagship budgets hold), the imbalance model and roofline
# composition match hand math, suggest_rebalance closes the loop through
# schedule.apply_rebalance on REAL files, and export rows pass checker
# invariant 5 (while a forged bad row fails it).
import tempfile as _sk_tmp

from harp_tpu import schedule as _SKsched
from harp_tpu.fileformat import multi_file_splits as _sk_splits
from harp_tpu.models import lda as _SKL
from harp_tpu.models import mfsgd as _SKMF
from harp_tpu.utils import skew as _SK
from harp_tpu.utils import telemetry as _SKT

# (a) skewed LDA: ingest == execution == numpy bincount; budget holds
_sk_d = np.concatenate([np.repeat(np.arange(8), 40),
                        np.repeat(np.arange(8, 64), 4)]).astype(np.int32)
_sk_w = np.random.default_rng(0).integers(0, 48, len(_sk_d)).astype(np.int32)
with _SKT.scope(True):
    _sk_lda = _SKL.LDA(64, 48, _SKL.LDAConfig(
        n_topics=8, algo="dense", d_tile=16, w_tile=16, entry_cap=64),
        mesh, seed=0)
    _sk_lda.set_tokens(_sk_d, _sk_w)
    _sk_lda.sample_epoch()  # warmup compile
    _sk_lda.compile_epochs(2)
    with _FR.budget(compiles=0, dispatches=1, readbacks=1,
                    h2d_bytes=nw * 8, tag="drive.skew.lda"):
        _sk_lda.sample_epochs(2)
    _sk_expect = np.bincount(_sk_d // _sk_lda.d_own, minlength=nw)
    for _sk_phase in ("lda.partition", "lda.epochs"):
        _sk_s = _SK.ledger.summary()[_sk_phase]
        np.testing.assert_allclose(_sk_s["work"], _sk_expect)
        assert _sk_s["total"] == len(_sk_d)
    assert _sk_s["max_mean_ratio"] == round(
        float(_sk_expect.max() / _sk_expect.mean()), 4)
    assert _sk_s["wasted_chip_s"] > 0  # wall measured, waste priced
    # report section renders with per-worker bars and sums
    _sk_row, _sk_spans = _FRrep.live_report()
    _sk_text = _FRrep.render(_sk_row, _sk_spans)
    assert "skew (per-worker load" in _sk_text and "max/mean" in _sk_text
    assert sum(_sk_row["skew"]["lda.epochs"]["work"]) == \
        _sk_row["skew"]["lda.epochs"]["total"]
    # (b) export -> checker invariant 5: real rows clean, forged row loud
    with _sk_tmp.NamedTemporaryFile("r+", suffix=".jsonl") as _sk_fh:
        _SKT.export(_sk_fh.name)
        # lda.partition, lda.kernel_slots (since PR 29), lda.epochs
        assert len(_SKT.load_rows(_sk_fh.name)["skew"]) == 3
        assert _fr_cj.check_file(_sk_fh.name) == []
        _sk_fh.seek(0, 2)
        _sk_fh.write(_fr_json.dumps(
            {"kind": "skew", "phase": "forged", "work": [2, 2],
             "total": 5, "padding_frac": 1.5, "backend": "cpu",
             "date": "2026-08-04", "commit": "x"}) + "\n")
        _sk_fh.flush()
        _sk_errs = _fr_cj.check_file(_sk_fh.name)
        assert len(_sk_errs) == 2  # bad sum AND bad padding_frac
        assert any("sum" in e for e in _sk_errs)
        assert any("padding_frac" in e for e in _sk_errs)

# (c) mfsgd execution counter rides the stacked readback, == bincount
_sk_u = np.concatenate([np.random.default_rng(1).integers(0, 8, 700),
                        np.random.default_rng(2).integers(8, 64, 300)]
                       ).astype(np.int32)
_sk_i = np.random.default_rng(3).integers(0, 48, 1000).astype(np.int32)
_sk_v = np.random.default_rng(4).normal(size=1000).astype(np.float32)
with _SKT.scope(True):
    _sk_m = _SKMF.MFSGD(64, 48, _SKMF.MFSGDConfig(
        rank=4, algo="dense", u_tile=8, i_tile=8, entry_cap=32), mesh, 0)
    _sk_m.set_ratings(_sk_u, _sk_i, _sk_v)
    _sk_m.train_epoch()
    with _FR.budget(dispatches=1, readbacks=1, tag="drive.skew.mf"):
        _sk_m.train_epochs(2)
    np.testing.assert_allclose(
        _SK.ledger.summary()["mfsgd.epochs"]["work"],
        np.bincount(_sk_u // _sk_m.u_own, minlength=nw))

# (d) imbalance model + roofline composition, hand math
with _SKT.scope(True):
    _SK.record_execution("p", [10, 2, 2, 2], unit="u", wall_s=2.0)
    _sk_p = _SK.ledger.summary()["p"]
    assert (_sk_p["max_mean_ratio"], _sk_p["wasted_frac"]) == (2.5, 0.6)
    assert abs(_sk_p["wasted_chip_s"] - 4.8) < 1e-9  # 4 chips x 2 s x 0.6
    _sk_pct = _SK.wasted_pct_of_peak(
        "lda", {"n_topics": 100, "tokens_per_sec_per_chip": 1e9}, "p", V5E)
    # 1e9 tok/s x 1400 flop/tok / 197e12 peak = 0.7107 %-of-peak, 60% lost
    assert abs(_sk_pct - round(100 * 1e9 * 1400 / 197e12 * 0.6, 3)) < 2e-3
    # (e) rebalance loop on REAL files: measured loads -> whole-file plan
    with _sk_tmp.TemporaryDirectory() as _sk_dir:
        _sk_paths = []
        for _sk_j, _sk_kb in enumerate((48, 40, 2, 1, 1, 1)):
            _sk_p2 = os.path.join(_sk_dir, f"f{_sk_j}.csv")
            open(_sk_p2, "wb").write(b"x" * (_sk_kb * 1024))
            _sk_paths.append(_sk_p2)
        _sk_sp = _sk_splits(_sk_paths, 2)  # records units + byte loads
        _sk_plan = _SK.suggest_rebalance("fileformat.multi_file_splits")
        assert _sk_plan["ratio_after"] <= _sk_plan["ratio_before"]
        _sk_new = _SKsched.apply_rebalance(_sk_sp, _sk_plan)
        _sk_loads = [sum(os.path.getsize(p) for p in s) for s in _sk_new]
        np.testing.assert_allclose(_sk_loads, _sk_plan["work_after"])

# (f) zero-cost off: ledger untouched, LDA chain identical on/off
with _SKT.scope(False):
    _SK.record_execution("off", [1, 2], unit="u")
    assert _SK.ledger.summary() == {}
print("skew: ingest==execution==bincount, budgets hold, waste priced, "
      "roofline composed, file rebalance loop closed, invariant 5 loud")
print("DRIVE OK round-23")

# ===========================================================================
# Round 24 — harplint: static analysis (PR 5).
# Drives the linter as a CONSUMER: seeded violations in every layer must
# exit non-zero, the repo at HEAD must be clean, the rerouted table verbs
# must match numpy AND become visible to the CommLedger (the point of
# HL001), and the flash_attention is_finite fix must keep numerics.
# ===========================================================================
import json as _hl_json
import tempfile as _hl_tmp

from harp_tpu.analysis import cli as _HLC
from harp_tpu.analysis import rule_ids as _hl_rule_ids
from harp_tpu.analysis.astlints import lint_source as _hl_lint
from harp_tpu.analysis.jaxpr_checks import find_scan_copy_traps as _hl_scan
from harp_tpu.analysis.mosaic_audit import (audit_registry as _hl_audit,
                                            check_kernel_jaxpr as _hl_kchk)
from jax import lax as _hl_lax

# (a) one seeded Layer-1 violation per rule id, via the public lint_source
for _hl_src, _hl_want in (
        ("from jax import lax\ndef f(x): return lax.psum(x, 'w')\n",
         "HL001"),
        ("import jax\ndef f(s): return jax.random.PRNGKey(s)\n", "HL002"),
        ("import jax.numpy as jnp, numpy as np\n"
         "def f(x): return jnp.asarray(np.asarray(x))\n", "HL003"),
        ("import jax\ndef f():\n    s = jax.jit(lambda x: x)\n"
         "    return s\n", "HL004"),
        ('def f():\n    """Hits 9.9M tok/s."""\n', "HL005")):
    _hl_got = {v.rule for v in _hl_lint("harp_tpu/models/fake.py", _hl_src)}
    assert _hl_got == {_hl_want}, (_hl_want, _hl_got)

# (b) the pre-fix LDA copy trap flags; the tile-local fixed form is clean
def _hl_bad(tbl, i, u):
    def body(t, x):
        vals = jnp.take(t, x[0], axis=0)
        return _hl_lax.dynamic_update_slice(t, x[1], (x[0][0], 0)), vals.sum()
    return _hl_lax.scan(body, tbl, (i, u))

def _hl_good(tbl, i, u):
    def body(t, x):
        tile = _hl_lax.dynamic_slice(t, (0, 0), (4, t.shape[1]))
        vals = jnp.take(tile, x[0] % 4, axis=0)
        return _hl_lax.dynamic_update_slice(t, x[1], (x[0][0], 0)), vals.sum()
    return _hl_lax.scan(body, tbl, (i, u))

_hl_args = (jnp.zeros((16, 8)), jnp.zeros((3, 2), jnp.int32),
            jnp.zeros((3, 1, 8)))
assert [v.rule for v in _hl_scan(
    jax.jit(_hl_bad).trace(*_hl_args).jaxpr, "d")] == ["HL101"]
assert _hl_scan(jax.jit(_hl_good).trace(*_hl_args).jaxpr, "d") == []

# (c) Mosaic: the 2026-08-01 3-seed-word silicon failure flags from the
# jaxpr alone (no hardware), and the whole ops/ registry audits clean —
# including flash_attention, whose is_finite this audit caught
from jax.experimental import pallas as _hl_pl
from jax.experimental.pallas import tpu as _hl_pltpu

def _hl_seed3(seed):
    def kern(seed_ref, o_ref):
        _hl_pltpu.prng_seed(seed_ref[0], seed_ref[1], seed_ref[2])
        bits = _hl_pltpu.prng_random_bits(o_ref.shape)
        o_ref[...] = _hl_lax.shift_right_logical(bits, 8).astype(jnp.float32)
    return _hl_pl.pallas_call(
        kern, in_specs=[_hl_pl.BlockSpec(memory_space=_hl_pltpu.SMEM)],
        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32))(seed)

_hl_vs = _hl_kchk(jax.jit(_hl_seed3).trace(jnp.zeros(3, jnp.int32)).jaxpr,
                  "toy")
assert "HL202" in {v.rule for v in _hl_vs}
assert _hl_audit() == []

# flash_attention numerics after the > -inf fix: == reference, causal+window
from harp_tpu.ops.flash_attention import (flash_attention as _hl_fa,
                                          reference_attention as _hl_ref)
_hl_rng = np.random.default_rng(24)
_hl_q, _hl_k, _hl_v = (jnp.asarray(
    _hl_rng.normal(size=(2, 64, 16)).astype(np.float32)) for _ in range(3))
for _hl_kw in ({"causal": True}, {"causal": True, "window": 8}):
    np.testing.assert_allclose(
        np.asarray(_hl_fa(_hl_q, _hl_k, _hl_v, block_q=32, block_k=32,
                          interpret=True, **_hl_kw)),
        np.asarray(_hl_ref(_hl_q, _hl_k, _hl_v, **_hl_kw)),
        rtol=2e-5, atol=2e-5)

# (d) rerouted table verbs: == numpy golden AND now on the CommLedger
from harp_tpu import table as _hl_table
from harp_tpu.utils import telemetry as _HLT

_hl_shard = _hl_rng.normal(size=(16, 4)).astype(np.float32)   # 2 rows/worker
_hl_ids = np.array([0, 5, 11, 3], np.int32)
_hl_deltas = _hl_rng.normal(size=(4, 4)).astype(np.float32)
with _HLT.scope(True):
    _hl_pull = jax.jit(mesh.shard_map(
        lambda g: _hl_table.pull_rows(g, jnp.asarray(_hl_ids)),
        in_specs=(mesh.spec(0),), out_specs=mesh.spec(0)))
    _hl_got = np.asarray(_hl_pull(mesh.shard_array(_hl_shard, 0)))
    np.testing.assert_allclose(_hl_got[:4], _hl_shard[_hl_ids], rtol=1e-6)
    _hl_push = jax.jit(mesh.shard_map(
        lambda g, d: _hl_table.push_rows(g, jnp.asarray(_hl_ids), d),
        in_specs=(mesh.spec(0), None), out_specs=mesh.spec(0)))
    _hl_after = np.asarray(_hl_push(mesh.shard_array(_hl_shard, 0),
                                    jax.device_put(_hl_deltas)))
    _hl_gold = _hl_shard.copy()
    np.add.at(_hl_gold, _hl_ids, _hl_deltas)   # every worker pushes once...
    _hl_gold = _hl_shard + (_hl_gold - _hl_shard) * mesh.num_workers
    np.testing.assert_allclose(_hl_after, _hl_gold, rtol=1e-5)
    _hl_verbs = {s["verb"] for t in _HLT.ledger.summary().values()
                 for s in t["sites"]}
    # HL001's whole point: the row exchange is on the ledger (PR 11:
    # pull_rows's replication rides the reshard shim)
    assert {"reshard", "push"} <= _hl_verbs, _hl_verbs

# (e) the lint CLI at HEAD: exit 0, clean, stamped line that satisfies
# check_jsonl invariant 6; a seeded file exits 1
import io as _hl_io
import contextlib as _hl_ctx
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "scripts"))
import check_jsonl as _hl_cj

_hl_buf = _hl_io.StringIO()
with _hl_ctx.redirect_stdout(_hl_buf):
    _hl_rc = _HLC.main(["--json"])
_hl_row = _hl_json.loads(_hl_buf.getvalue().strip().splitlines()[-1])
assert _hl_rc == 0 and _hl_row["clean"] is True
assert _hl_cj._check_lint_row("drive", 1, _hl_row) == []
assert tuple(_hl_rule_ids()) == _hl_cj.KNOWN_LINT_RULES
with _hl_tmp.TemporaryDirectory() as _hl_dir:
    _hl_bad_py = os.path.join(_hl_dir, "bad.py")
    open(_hl_bad_py, "w").write(
        "import jax\ndef f(s): return jax.random.PRNGKey(s)\n")
    with _hl_ctx.redirect_stdout(_hl_io.StringIO()):
        assert _HLC.main([_hl_bad_py, "--json"]) == 1

print("harplint: 5 AST rules seeded+tripped, copy trap pinned both ways, "
      "3-word prng_seed flagged sans hardware, registry+repo clean at "
      "HEAD, rerouted pull/push == numpy and on the ledger, CLI exit "
      "codes + invariant 6 round-trip")
print("DRIVE OK round-24")

# ---------------------------------------------------------------------------
# Round 25 — harp serve: persistent-mesh inference (PR 6)
# Drives the PUBLIC serve surface end to end: checkpoint →
# restore_latest → Server startup (AOT cache cold, then warm with ZERO
# compiles after jax.clear_caches), ladder batching, the steady-state
# budget's exact dispatch/readback accounting, kmeans/mfsgd answers vs
# straight-line numpy, the stdio JSONL protocol, and a bench row through
# check_jsonl invariant 7.
# ---------------------------------------------------------------------------
import tempfile as _sv_tmp
import io as _sv_io
import json as _sv_json

from harp_tpu.serve import Server as _SvServer
from harp_tpu.serve.bench import benchmark as _sv_bench
from harp_tpu.utils import flightrec as _sv_fr, telemetry as _sv_tel
from harp_tpu.utils.checkpoint import CheckpointManager as _SvCkpt
from harp_tpu.utils.metrics import benchmark_json as _sv_bjson
import check_jsonl as _sv_cj

_sv_rng = np.random.default_rng(25)
with _sv_tmp.TemporaryDirectory() as _sv_dir:
    # checkpoint → newest step wins through restore_latest
    _sv_mgr = _SvCkpt(os.path.join(_sv_dir, "ckpt"))
    _sv_c_old = _sv_rng.normal(size=(6, 12)).astype(np.float32)
    _sv_c = _sv_rng.normal(size=(6, 12)).astype(np.float32)
    _sv_mgr.save(1, {"centroids": _sv_c_old})
    _sv_mgr.save(4, {"centroids": _sv_c})
    assert _sv_mgr.restore_latest()[0] == 4

    _sv_cache = os.path.join(_sv_dir, "aot")
    with _sv_tel.scope(True):
        _sv_srv = _SvServer("kmeans", ckpt=os.path.join(_sv_dir, "ckpt"),
                            mesh=mesh, ladder=(1, 8, 32),
                            cache_dir=_sv_cache)
        _sv_cold = _sv_srv.startup()
        assert _sv_cold["cache_misses"] == 3 and _sv_cold["compiles"] >= 3
        # steady state: 70 rows over a (1,8,32) ladder → 32+32+8-pad
        _sv_x = _sv_rng.normal(size=(70, 12)).astype(np.float32)
        _sv_base = _sv_fr.snapshot()
        (_sv_resp,) = _sv_srv.process([{"id": 0, "x": _sv_x.tolist()}])
        _sv_spent = _sv_fr.delta_since(_sv_base)
        assert _sv_srv.steady.batches == 3 and _sv_srv.steady.violations == 0
        assert (_sv_spent["compiles"], _sv_spent["dispatches"],
                _sv_spent["readbacks"]) == (0, 3, 3)
        _sv_ref = np.argmin(
            ((_sv_x[:, None, :] - _sv_c[None]) ** 2).sum(-1), axis=1)
        assert _sv_resp["result"] == _sv_ref.tolist()

    # warm restart: in-memory jit caches dropped, disk cache must serve
    jax.clear_caches()
    with _sv_tel.scope(True):
        _sv_srv2 = _SvServer("kmeans", ckpt=os.path.join(_sv_dir, "ckpt"),
                             mesh=mesh, ladder=(1, 8, 32),
                             cache_dir=_sv_cache)
        _sv_warm = _sv_srv2.startup()
        assert _sv_warm == {"rungs": [1, 8, 32], "cache_hits": 3,
                            "cache_misses": 0, "compiles": 0}, _sv_warm
        # stdio protocol round trip on the warm server
        _sv_in = _sv_io.StringIO(
            _sv_json.dumps({"id": "q", "x": _sv_x[:3].tolist()}) + "\n"
            + _sv_json.dumps({"cmd": "quit"}) + "\n")
        _sv_out = _sv_io.StringIO()
        _sv_srv2.serve_stdio(_sv_in, _sv_out)
        (_sv_line,) = _sv_out.getvalue().splitlines()
        assert _sv_json.loads(_sv_line)["result"] == _sv_ref[:3].tolist()
        assert _sv_fr.compile_watch.count == 0  # still zero post-serve

# mfsgd top-k: sharded H + pull merge == numpy argsort (49 items ⇒ the
# worker padding must not leak phantom items)
from harp_tpu.serve.engines import ENGINES as _SvEngines
_sv_st = _SvEngines["mfsgd"].synthetic_state(_sv_rng, n_users=40,
                                             n_items=49, rank=8)
with _sv_tmp.TemporaryDirectory() as _sv_dir2:
    _sv_m = _SvServer("mfsgd", state=_sv_st, mesh=mesh, ladder=(1, 8),
                      cache_dir=_sv_dir2, engine_opts={"topk": 5})
    _sv_m.startup()
    (_sv_r,) = _sv_m.process([{"id": 1, "users": [0, 17, 39]}])
    for _sv_row, _sv_u in zip(_sv_r["result"], [0, 17, 39]):
        _sv_sc = _sv_st["W"][_sv_u] @ _sv_st["H"].T
        assert _sv_row["items"] == np.argsort(-_sv_sc)[:5].tolist()

# bench row → provenance stamp → invariant 7 clean
_sv_res = _sv_bench(app="kmeans", n_requests=12, rows_per_request=1,
                    burst=4, ladder=(1, 8), mesh=mesh,
                    state_shape={"k": 4, "d": 8})
assert _sv_res["steady_compiles"] == 0 and _sv_res["qps"] > 0
_sv_rowd = _sv_json.loads(_sv_bjson("serve_kmeans", _sv_res))
assert _sv_cj._check_serve_row("drive", 1, _sv_rowd) == []
# and the checker is LOUD on a row that compiled in steady state
assert _sv_cj._check_serve_row("drive", 1,
                               {**_sv_rowd, "steady_compiles": 2})

print("serve: restore_latest → cold AOT cache → warm restart 0 compiles, "
      "steady batches exact (0 compiles / 1 dispatch / 1 readback each), "
      "kmeans+sharded-topk == numpy, stdio round trip, bench row through "
      "invariant 7 both ways")
print("DRIVE OK round-25")

# ---------------------------------------------------------------------------
# Round 26 — serve review fixes (PR 6 follow-up): option-keyed AOT cache,
# any-exception cache fallback, parallel sources in the fingerprint, and
# raw-fd burst reads that see past TextIOWrapper buffering.
# ---------------------------------------------------------------------------
import hashlib as _rv_hash
import warnings as _rv_warn

from harp_tpu.serve.cache import code_fingerprint as _rv_fp

# (a) engine options are program constants, not avals: a restart with a
# different --topk must MISS and answer with the new k (numpy-checked)
with _sv_tmp.TemporaryDirectory() as _rv_dir:
    _rv_st = _SvEngines["mfsgd"].synthetic_state(_sv_rng, n_users=40,
                                                 n_items=49, rank=8)
    _rv_a = _SvServer("mfsgd", state=_rv_st, mesh=mesh, ladder=(4,),
                      cache_dir=_rv_dir, engine_opts={"topk": 5})
    _rv_a.startup()
    _rv_b = _SvServer("mfsgd", state=_rv_st, mesh=mesh, ladder=(4,),
                      cache_dir=_rv_dir, engine_opts={"topk": 7})
    _rv_info = _rv_b.startup()
    assert (_rv_info["cache_hits"], _rv_info["cache_misses"]) == (0, 1)
    (_rv_r7,) = _rv_b.process([{"id": 0, "users": [3, 21]}])
    for _rv_row, _rv_u in zip(_rv_r7["result"], [3, 21]):
        _rv_sc = _rv_st["W"][_rv_u] @ _rv_st["H"].T
        assert _rv_row["items"] == np.argsort(-_rv_sc)[:7].tolist()
    _rv_c = _SvServer("mfsgd", state=_rv_st, mesh=mesh, ladder=(4,),
                      cache_dir=_rv_dir, engine_opts={"topk": 5})
    assert _rv_c.startup()["cache_hits"] == 1  # tag keys, doesn't disable

    # (b) ANY deserialize exception degrades to a fresh compile
    from jax.experimental import serialize_executable as _rv_se
    _rv_orig = _rv_se.deserialize_and_load

    def _rv_boom(*a, **k):
        raise RuntimeError("xla rejected the payload")

    _rv_se.deserialize_and_load = _rv_boom
    try:
        with _rv_warn.catch_warnings(record=True) as _rv_caught:
            _rv_warn.simplefilter("always")
            _rv_d = _SvServer("mfsgd", state=_rv_st, mesh=mesh,
                              ladder=(4,), cache_dir=_rv_dir,
                              engine_opts={"topk": 5})
            _rv_dinfo = _rv_d.startup()
    finally:
        _rv_se.deserialize_and_load = _rv_orig
    assert _rv_dinfo["cache_misses"] == 1
    assert any("unreadable" in str(w.message) for w in _rv_caught)
print("serve cache: --topk restart misses + answers new k, same-opts "
      "hits, arbitrary deserialize error recompiles")

# (c) the fingerprint hashes the parallel layer too (shard_map +
# collective verbs compile into the mfsgd program) — replicate the sha1
# by hand to prove which sources participate
import harp_tpu.parallel.collective as _rv_coll
import harp_tpu.parallel.mesh as _rv_mesh
import harp_tpu.serve as _rv_pkg

_rv_h = _rv_hash.sha1()
_rv_pdir = os.path.dirname(os.path.abspath(_rv_pkg.__file__))
_rv_paths = [os.path.join(_rv_pdir, f) for f in sorted(os.listdir(_rv_pdir))
             if f.endswith(".py")]
_rv_paths += [_rv_coll.__file__, _rv_mesh.__file__]
for _rv_p in _rv_paths:
    _rv_h.update(open(_rv_p, "rb").read())
assert _rv_fp() == _rv_h.hexdigest()[:16]
print("serve fingerprint covers serve/* + parallel/collective + mesh")

# (d) burst reader: lines a TextIOWrapper would hold internally (fd not
# selectable) land in the CURRENT burst; partial lines carry over
from harp_tpu.serve.server import _BurstReader as _RvBurst

_rv_r, _rv_w = os.pipe()
_rv_stdin = os.fdopen(_rv_r, "r")
try:
    os.write(_rv_w, b'{"id": 1}\n{"id": 2}\n{"id": 3')
    _rv_reader = _RvBurst(_rv_stdin)
    assert [_sv_json.loads(x)["id"]
            for x in _rv_reader.read_burst()] == [1, 2]
    os.write(_rv_w, b'}\n')
    assert [_sv_json.loads(x)["id"]
            for x in _rv_reader.read_burst()] == [3]
    os.close(_rv_w)
    assert _rv_reader.read_burst() == []
finally:
    _rv_stdin.close()
print("burst reader: queued lines in-burst, partial line carries, EOF")
print("DRIVE OK round-26")

# ---------------------------------------------------------------------------
# Round 27 — continuous serving (PR 7): the asyncio TCP front end over a
# REAL socket (concurrent connections, per-connection order, interleaved
# clients, stats/quit/shutdown), the admit-while-in-flight scheduler's
# exact steady accounting, and the sustained-load A/B row through the
# extended invariant 7 — all without a chip.
# ---------------------------------------------------------------------------
import socket as _ct_socket
import threading as _ct_threading

from harp_tpu.serve.bench import benchmark_sustained as _ct_sus
from harp_tpu.serve.transport import TCPFrontEnd as _CtFE

_ct_rng = np.random.default_rng(27)
_ct_state = _SvEngines["kmeans"].synthetic_state(_ct_rng, k=8, d=16)
with _sv_tmp.TemporaryDirectory() as _ct_dir:
    _ct_srv = _SvServer("kmeans", state=_ct_state, mesh=mesh,
                        ladder=(1, 8, 32), cache_dir=_ct_dir,
                        budget_action="warn")
    _ct_srv.startup()
    _ct_fe = _CtFE(_ct_srv, port=0,
                   max_queue_delay_s=0.002).start_in_thread()
    _ct_cent = _ct_state["centroids"]

    def _ct_client(nm, out):
        s = _ct_socket.create_connection(("127.0.0.1", _ct_fe.port),
                                         timeout=120)
        f = s.makefile("rw")
        xs = [_ct_rng.normal(size=(1 + i % 4, 16)).astype(np.float32)
              for i in range(16)]
        for i, x in enumerate(xs):  # all 16 in flight at once
            f.write(_sv_json.dumps({"id": f"{nm}-{i}",
                                    "x": x.tolist()}) + "\n")
        f.flush()
        got = [_sv_json.loads(f.readline()) for _ in xs]
        f.write(_sv_json.dumps({"cmd": "stats"}) + "\n")
        f.flush()
        st = _sv_json.loads(f.readline())
        assert st["kind"] == "serve_stats" and "continuous" in st
        f.write(_sv_json.dumps({"cmd": "quit"}) + "\n")
        f.flush()
        assert f.readline() == ""  # server closed after the drain
        s.close()
        out[nm] = (xs, got)

    _ct_out = {}
    _ct_threads = [_ct_threading.Thread(target=_ct_client,
                                        args=(nm, _ct_out))
                   for nm in ("c1", "c2", "c3")]
    for _t in _ct_threads:
        _t.start()
    for _t in _ct_threads:
        _t.join(240)
    assert set(_ct_out) == {"c1", "c2", "c3"}
    for _nm, (_xs, _got) in _ct_out.items():
        assert [r["id"] for r in _got] == [f"{_nm}-{i}"
                                           for i in range(16)]
        for _r, _x in zip(_got, _xs):  # routed to the right conn, exact
            _ref = np.argmin(((_x[:, None, :] - _ct_cent[None]) ** 2
                              ).sum(-1), 1)
            assert _r["result"] == _ref.tolist()
    # runner totals are EXACT: one dispatch + one readback per batch
    _ct_fe.runner.verify_exact()
    _ct_fe.shutdown()
    _ct_fe.join(120)
print("tcp front end: 3 interleaved clients x 16 requests routed + "
      "ordered per connection, stats/quit/shutdown, exact accounting")

# sustained A/B: one seeded trace, both planes, extended invariant 7
_ct_res = _ct_sus(app="kmeans", n_requests=96, rows_per_request=1,
                  burst_admit=8, ladder=(1, 8, 32), mesh=mesh,
                  state_shape={"k": 8, "d": 16})
assert _ct_res["offered_qps"] >= _ct_res["achieved_qps"] > 0
assert _ct_res["steady_compiles"] == 0
assert _ct_res["steady_dispatches"] == _ct_res["batches"] == \
    _ct_res["steady_readbacks"]
_ct_row = _sv_json.loads(_sv_bjson("serve_kmeans_sustained", _ct_res))
assert _sv_cj._check_serve_row("drive", 1, _ct_row) == []
assert _sv_cj._check_serve_row(  # forged: queue evidence stripped
    "drive", 1, {k: v for k, v in _ct_row.items()
                 if k != "qdepth_p95"})
assert _sv_cj._check_serve_row(  # forged: achieved above offered
    "drive", 1, {**_ct_row, "achieved_qps": _ct_row["offered_qps"] + 1})
print(f"sustained A/B: {_ct_res['qps_ratio_vs_burst']}x vs burst at "
      f"p99 {_ct_res['p99_ms']:.1f} vs {_ct_res['burst_p99_ms']:.1f} ms, "
      "row passes extended invariant 7, forgeries loud")
print("DRIVE OK round-27")

# ---------------------------------------------------------------------------
# Round 28 — prefetch-pipelined ingest (PR 8): the bench_ingest --smoke A/B
# through a real subprocess (the new staged chain vs the pre-PR serial loop
# on one page-cache-warm file), depth bit-exactness through the public
# fit_streaming surface, and the kind:"ingest" row through invariant 8
# both ways.
# ---------------------------------------------------------------------------
import subprocess as _ig_sp

_ig_run = _ig_sp.run(
    [sys.executable, "scripts/bench_ingest.py", "--smoke"],
    capture_output=True, text=True, timeout=600,
    cwd=_r4os.path.dirname(_r4os.path.dirname(_r4os.path.abspath(__file__))))
assert _ig_run.returncode == 0, _ig_run.stderr[-800:]
_ig_row = _r5json.loads(_ig_run.stdout.strip().splitlines()[-1])
assert _ig_row["kind"] == "ingest" and _ig_row["mode"] == "ab"
assert _ig_row["host_gb_per_sec"] > 0 and _ig_row["points_per_sec"] > 0
assert 0.0 <= _ig_row["overlap_efficiency"] <= 1.0
# a loaded driver box adds scheduler noise, so this smoke pass gates the
# A/B DIRECTION only; the graded >= 1.25x number is the committed
# BENCH_local kmeans_ingest_ab_smoke row (2026-08-04: 1.7-1.9x)
assert _ig_row["pipeline_speedup"] > 1.0, _ig_row["pipeline_speedup"]
assert _ig_row["host_gb_per_sec_serial"] > 0
assert _sv_cj._check_ingest_row("drive", 1, _ig_row) == []
assert _sv_cj._check_ingest_row(  # forged: impossible overlap score
    "drive", 1, {**_ig_row, "overlap_efficiency": 1.7})
assert _sv_cj._check_ingest_row(  # forged: stamp stripped
    "drive", 1, {k: v for k, v in _ig_row.items() if k != "backend"})
assert _sv_cj._check_ingest_row(  # forged: the loop never ran
    "drive", 1, {**_ig_row, "points_per_sec": 0})

# depth is invisible to the math: legacy chain (0) == pipelined (2)
_ig_pts = rng.normal(size=(2000, 12)).astype(np.float32)
_ig_outs = [fit_streaming(_ig_pts, k=5, iters=3, chunk_points=512,
                          mesh=mesh, seed=4, prefetch=_p)
            for _p in (0, 2)]
np.testing.assert_array_equal(_ig_outs[0][0], _ig_outs[1][0])
assert _ig_outs[0][1] == _ig_outs[1][1]
print(f"ingest A/B: pipelined {_ig_row['host_gb_per_sec']:.2f} GB/s = "
      f"{_ig_row['pipeline_speedup']:.2f}x serial "
      f"{_ig_row['host_gb_per_sec_serial']:.2f} GB/s, overlap "
      f"{_ig_row['overlap_efficiency']:.2f}, depths bit-exact, "
      "row through invariant 8 both ways")
print("DRIVE OK round-28")

# --- round 29: harplint Layer 4 — CommGraph static communication audit -----
# The static collective schedule extractor cross-checked against the
# CommLedger (HL301/HL302) and numpy byte math, the hoistable-collective
# detector's per-leaf granularity (HL304), the use-after-donate audit
# over the REAL serve ContinuousRunner depth-2 pipeline (HL303, clean)
# and a sabotaged twin (flags), the full registry sweep, and the CLI
# round trip: byte_sheets through check_jsonl invariant 6 both ways.
# ---------------------------------------------------------------------------
import contextlib as _cg_ctx
import json as _cg_json
import subprocess as _cg_sp
import tempfile as _cg_tmp

from jax import lax as _cg_lax
from jax.sharding import PartitionSpec as _cg_P

import harp_tpu.utils.telemetry as _cg_T
from harp_tpu.analysis import cli as _cg_cli
from harp_tpu.analysis import commgraph as _cg
from harp_tpu.analysis.drivers import DRIVERS as _cg_DRIVERS
from harp_tpu.analysis.drivers import PROTOCOLS as _cg_PROTOCOLS
from harp_tpu.utils import flightrec as _cg_fr

_cg_repo = _r4os.path.dirname(_r4os.path.dirname(_r4os.path.abspath(__file__)))

# (a) hand-built iterative program: allreduce of a two-leaf tree inside
# a 3-iter fori.  Static sheet == numpy byte math == ledger payload,
# amplified by the trip count; both leaves depend on the carry -> clean.
_cg_rows, _cg_d, _cg_iters = 2 * nw, 8, 3
_cg_r = _cg_rows // nw  # per-shard rows
_cg_x = jax.ShapeDtypeStruct((_cg_rows, _cg_d), jnp.float32,
                             sharding=mesh.sharding(mesh.spec(0)))


def _cg_clean_epoch(x):
    def body(i, c):
        s, n = C.allreduce((x * c.sum(), x[:, 0] + c[0, 0]))
        return c + s[:1, :1] + n.sum()

    return _cg_lax.fori_loop(0, _cg_iters, body,
                             jnp.zeros((1, 1), jnp.float32))


_cg_fn = jax.jit(mesh.shard_map(_cg_clean_epoch, in_specs=(mesh.spec(0),),
                                out_specs=_cg_P(), check_vma=False))
_cg_vs, _cg_g = _cg.analyze_program("drive.clean", _cg_fn, (_cg_x,))
assert _cg_vs == [], [v.format() for v in _cg_vs]
_cg_expect = _cg_r * _cg_d * 4 + _cg_r * 4  # leaf bytes, per shard
assert _cg_g.bytes_per_trace() == _cg_expect, _cg_g.sheet()
assert _cg_g.amplified_bytes() == _cg_expect * _cg_iters
(_cg_site,) = _cg_g.sites
assert _cg_site.verb == "allreduce" and _cg_site.amplification == _cg_iters
_cg_ledger = sum(r["payload_bytes"] for recs in _cg_g.ledger_sites.values()
                 for r in recs)
assert _cg_ledger == _cg_expect  # static == ledger, to the byte

# (b) per-leaf hoist granularity: make the SECOND leaf loop-invariant
# (drops the carry term) -> exactly one HL304, naming the psum site
def _cg_hoist_epoch(x):
    def body(i, c):
        s, n = C.allreduce((x * c.sum(), x[:, 0]))
        return c + s[:1, :1] + n.sum()

    return _cg_lax.fori_loop(0, _cg_iters, body,
                             jnp.zeros((1, 1), jnp.float32))


_cg_fn = jax.jit(mesh.shard_map(_cg_hoist_epoch, in_specs=(mesh.spec(0),),
                                out_specs=_cg_P(), check_vma=False))
_cg_vs, _ = _cg.analyze_program("drive.hoist", _cg_fn, (_cg_x,))
assert [v.rule for v in _cg_vs] == ["HL304"], [v.format() for v in _cg_vs]
assert "hoist" in _cg_vs[0].message

# (c) untracked wire: the raw-lax twin leaves no ledger record -> HL301
def _cg_raw(x):
    return _cg_lax.psum(x, "workers")


_cg_fn = jax.jit(mesh.shard_map(_cg_raw, in_specs=(mesh.spec(0),),
                                out_specs=_cg_P()))
_cg_vs, _ = _cg.analyze_program("drive.raw", _cg_fn, (_cg_x,))
assert [v.rule for v in _cg_vs] == ["HL301"]

# (d) lying byte sheet: record a scalar, psum the full array (one source
# line, so both sides key the same call site) -> HL302
def _cg_lying(x):
    return _cg_T.record_comm("allreduce", x[0, 0], axis="workers") or _cg_lax.psum(x, "workers")  # noqa: E501


_cg_fn = jax.jit(mesh.shard_map(_cg_lying, in_specs=(mesh.spec(0),),
                                out_specs=_cg_P()))
_cg_vs, _ = _cg.analyze_program("drive.lying", _cg_fn, (_cg_x,))
assert [v.rule for v in _cg_vs] == ["HL302"]

# (e) the full registry sweeps clean, covers >= 10 programs, and the
# serve engines' donated batch buffer is visible in the aliasing info
assert len(_cg_DRIVERS) >= 10
for _cg_name, _cg_build in sorted(_cg_DRIVERS.items()):
    _cg_f, _cg_a = _cg_build()
    _cg_vs, _cg_g = _cg.analyze_program(_cg_name, _cg_f, _cg_a)
    assert _cg_vs == [], (_cg_name, [v.format() for v in _cg_vs])
    if _cg_name.startswith("serve."):
        assert _cg_g.donated_args, _cg_name

# (f) HL303: the REAL ContinuousRunner depth-2 protocol is clean; a
# sabotaged re-read + re-dispatch of a donated buffer flags twice (the
# audit records BEFORE jax's own deletion error, which only this CPU
# path even raises — silicon silently reads garbage, hence the lint)
_cg_vs = _cg.audit_protocol("serve.kmeans_continuous",
                            _cg_PROTOCOLS["serve.kmeans_continuous"]())
assert _cg_vs == [], [v.format() for v in _cg_vs]
_cg_audit = _cg.DonationAudit("protocol:drive-sabotage")
with _cg_audit:
    _cg_exe = _cg_audit.wrap(jax.jit(lambda s, b: s + b,
                                     donate_argnums=(1,)), (1,), "toy")
    _cg_s = jax.device_put(np.ones((4,), np.float32))
    _cg_b = jax.device_put(np.ones((4,), np.float32))
    _cg_exe(_cg_s, _cg_b)
    with _cg_ctx.suppress(RuntimeError):
        _cg_fr.readback(_cg_b)
    with _cg_ctx.suppress(RuntimeError, ValueError):
        _cg_exe(_cg_s, _cg_b)
assert [v.rule for v in _cg_audit.violations] == ["HL303", "HL303"]

# (g) the CLI round trip: one full four-layer run prints a clean row
# whose byte_sheets block carries every registered program, kmeans.fit
# matching the hand-computed sheet exactly; the row passes invariant 6
# and forged sheets fail it
_cg_run = _cg_sp.run([sys.executable, "-m", "harp_tpu", "lint", "--json"],
                     capture_output=True, text=True, timeout=900,
                     cwd=_cg_repo)
assert _cg_run.returncode == 0, _cg_run.stdout[-800:] + _cg_run.stderr[-800:]
_cg_row = _cg_json.loads(_cg_run.stdout.strip().splitlines()[-1])
assert _cg_row["clean"] is True and _cg_row["stale_allowlist"] == 0
assert set(_cg_row["byte_sheets"]) == set(_cg_DRIVERS)
_cg_km = _cg_row["byte_sheets"]["kmeans.fit"]
assert _cg_km["bytes_per_trace"] == 8 * 32 * 4 + 8 * 4 + 4
assert _cg_km["amplified_bytes"] == 2 * _cg_km["bytes_per_trace"]
assert _sv_cj._check_lint_row("drive", 1, _cg_row) == []
assert _sv_cj._check_lint_row(  # forged: unregistered program name
    "drive", 1, {**_cg_row, "byte_sheets": {"madeup.prog": _cg_km}})
assert _sv_cj._check_lint_row(  # forged: negative byte count
    "drive", 1, {**_cg_row, "byte_sheets": {
        "kmeans.fit": {**_cg_km, "bytes_per_trace": -1}}})

# (h) stale allowlist entries hard-fail (AST layer is enough to prove
# the exit-code contract), and --changed draws from the sweep set
with _cg_tmp.TemporaryDirectory() as _cg_dir:
    _cg_toml = _r4os.path.join(_cg_dir, "stale.toml")
    with open(_r4os.path.join(_cg_repo, "harp_tpu", "analysis",
                              "allowlist.toml")) as _cg_fh:
        _cg_committed = _cg_fh.read()
    with open(_cg_toml, "w") as _cg_fh:
        _cg_fh.write(_cg_committed + '\n[[allow]]\nrule = "HL002"\n'
                     'path = "harp_tpu/never.py"\nreason = "stale"\n')
    _cg_run = _cg_sp.run(
        [sys.executable, "-m", "harp_tpu", "lint", "--json",
         "--layer", "ast", "--allowlist", _cg_toml],
        capture_output=True, text=True, timeout=300, cwd=_cg_repo)
    assert _cg_run.returncode == 1, _cg_run.stdout[-400:]
    _cg_row = _cg_json.loads(_cg_run.stdout.strip().splitlines()[-1])
    assert _cg_row["stale_allowlist"] == 1 and _cg_row["clean"] is True
from harp_tpu.analysis.astlints import iter_python_files as _cg_iter

assert set(_cg_cli._changed_paths(_cg_repo)) <= set(_cg_iter(_cg_repo))

print(f"commgraph: clean epoch sheet {_cg_expect} B/shard x{_cg_iters} "
      f"== ledger; HL301/302/303/304 all fire on their fixtures; "
      f"{len(_cg_DRIVERS)} driver sheets clean through the CLI + "
      "invariant 6 both ways")
print("DRIVE OK round-29")

# 30. the fault plane (PR 10): deterministic chaos + kill/resume +
# degraded serving, end to end over the public surface
import tempfile as _fp_tmp

from harp_tpu.models import mfsgd as _fp_MF
from harp_tpu.serve.bench import benchmark_sustained as _fp_sustained
from harp_tpu.utils.checkpoint import CheckpointManager as _fp_CM
from harp_tpu.utils.fault import FaultInjector as _fp_FI
from harp_tpu.utils.fault import InjectedFault as _fp_IF

with _fp_tmp.TemporaryDirectory() as _fp_dir:
    _fp_rng = np.random.default_rng(0)
    _fp_u = _fp_rng.integers(0, 32, 400).astype(np.int32)
    _fp_i = _fp_rng.integers(0, 24, 400).astype(np.int32)
    _fp_v = _fp_rng.normal(size=400).astype(np.float32)

    def _fp_model():
        m = _fp_MF.MFSGD(32, 24, _fp_MF.MFSGDConfig(
            rank=4, algo="dense", u_tile=8, i_tile=8, entry_cap=32),
            mesh=mesh)
        m.set_ratings(_fp_u, _fp_i, _fp_v)
        return m

    _fp_clean = _fp_model()
    _fp_clean.fit(6)
    _fp_ck = os.path.join(_fp_dir, "kill")
    _fp_crash = _fp_model()
    _fp_inj = _fp_FI(seed=7, fail={"dispatch": (4,)})
    try:
        with _fp_inj.arm():
            _fp_crash.fit(6, _fp_ck, ckpt_every=2, max_restarts=0)
        raise AssertionError("injector never fired")
    except _fp_IF:
        pass
    assert _fp_CM(_fp_ck).latest_step() == 1
    _fp_res = _fp_model()
    _fp_res.fit(6, _fp_ck, ckpt_every=2)
    np.testing.assert_array_equal(np.asarray(_fp_res.W),
                                  np.asarray(_fp_clean.W))
    np.testing.assert_array_equal(np.asarray(_fp_res.H),
                                  np.asarray(_fp_clean.H))

# degraded sustained serving under seeded ~1% dispatch chaos: books
# balance, row passes invariants 7 + 9 both ways
import check_jsonl as _fp_cj  # scripts/ already on sys.path for round 22

_fp_row = _fp_sustained(
    app="kmeans", n_requests=96, rows_per_request=1, burst_admit=8,
    ladder=(1, 8, 32), state_shape={"k": 8, "d": 16},
    fault_rate=0.01, fault_seed=34, deadline_ms=10_000.0,
    max_queue_rows=4096, max_retries=3)
assert _fp_row["faults_injected"] >= 1 and _fp_row["fault_retries"] >= 1
assert (_fp_row["served_requests"] + _fp_row["shed_requests"]
        + _fp_row["failed_requests"]) == _fp_row["offered_requests"] == 96
assert _fp_row["steady_compiles"] == 0
_fp_stamped = {**_fp_row, "backend": "cpu", "date": "2026-08-04",
               "commit": "drive"}
assert _fp_cj._check_serve_row("drive", 1, _fp_stamped) == []
assert any("exactly one of the three" in e for e in _fp_cj._check_serve_row(
    "drive", 1, {**_fp_stamped,
                 "shed_requests": _fp_stamped["shed_requests"] + 1}))

print(f"fault plane: injector-killed mfsgd resumed bit-identical from "
      f"step 1; degraded sustained row balanced "
      f"({_fp_row['served_requests']} served / {_fp_row['shed_requests']} "
      f"shed / {_fp_row['failed_requests']} failed of 96, "
      f"{_fp_row['fault_retries']} retries) through invariant 9 both ways")
print("DRIVE OK round-30")

# --- round 31: the collective planner end-to-end (PR 11) -------------------
# One registered program, subprocess-free: CommGraph byte sheet -> Plan ->
# the executed schedule -> ledger agreement BOTH ways (every planned site
# has a trace-time record; every recorded wire is a planned site), plus
# the reshard verb executing the planner's alternative schedules
# bit-identically to "keep".
from harp_tpu.analysis import commgraph as _plC
from harp_tpu.analysis.drivers import DRIVERS as _plD
from harp_tpu.parallel.collective import ShardSpec as _plS
from harp_tpu.plan import planner as _plP
from harp_tpu.plan import topology as _plT
from harp_tpu.utils import telemetry as _plTel

_pl_topo = _plT.detect(mesh)
assert _pl_topo.name == "sim_ring_8"

# byte sheet -> Plan (fail closed, predictions == sheet, exactly)
_pl_fn, _pl_args = _plD["mfsgd.epoch"]()
_pl_graph = _plC.extract("mfsgd.epoch", _pl_fn, _pl_args)
_pl_plan = _plP.plan_sheet(
    "mfsgd.epoch", {"collectives": [s.row() for s in _pl_graph.sites]},
    _pl_topo)
assert all(d.schedule == "keep" for d in _pl_plan.sites)
assert _pl_plan.predicted_bytes_total() == _pl_graph.amplified_bytes() > 0

# ledger agreement both ways: the extraction traced under the ledger, so
# every static site must have a record (HL301's direction) AND every
# recorded comm site must be a planned site (the planner misses nothing)
_pl_static_sites = {d.site for d in _pl_plan.sites}
_pl_ledger_sites = set(_pl_graph.ledger_sites)
assert _pl_static_sites <= _pl_ledger_sites, (
    _pl_static_sites - _pl_ledger_sites)
assert _pl_ledger_sites <= _pl_static_sites, (
    _pl_ledger_sites - _pl_static_sites)
# and byte-exactness site by site: sheet bytes == ledger payload *
# amplification for every exact-wire site (HL302's direction, from the
# planner's own rows)
_pl_amp = {d.site: d for d in _pl_plan.sites}
for _pl_site, _pl_recs in _pl_graph.ledger_sites.items():
    if all(r["wire_dtype"] is None for r in _pl_recs):
        _pl_led = sum(r["payload_bytes"] for r in _pl_recs)
        _pl_sheet = sum(s.per_shard_bytes for s in _pl_graph.sites
                        if s.site == _pl_site)
        assert _pl_led == _pl_sheet, (_pl_site, _pl_led, _pl_sheet)

# the planner's alternative schedules EXECUTE and agree with "keep":
# chunked pipeline bit-identical, int8 wire within its rounding bound
_pl_x = np.arange(nw * 8 * 4, dtype=np.float32).reshape(nw * 8, 4)


def _pl_prog(a):
    keep = C.reshard(a, _plS.blocked(0), _plS.blocked(0, 1))
    chunked = C.reshard(a, _plS.blocked(0), _plS.blocked(0, 1), n_chunks=4)
    narrow = C.reshard(a, _plS.blocked(0), _plS.blocked(0, 1), wire="int8")
    return keep, chunked, narrow


_pl_keep, _pl_chunk, _pl_n8 = jax.jit(mesh.shard_map(
    _pl_prog, in_specs=(mesh.spec(0),),
    out_specs=(mesh.spec(0),) * 3))(mesh.shard_array(_pl_x, 0))
np.testing.assert_array_equal(np.asarray(_pl_keep), np.asarray(_pl_chunk))
assert np.abs(np.asarray(_pl_n8) - np.asarray(_pl_keep)).max() <= \
    np.abs(_pl_x).max() / 254 + 1e-6

# a topology where the alternatives win names ONLY measurable flip
# candidates and still chooses "keep" everywhere (fail closed under
# temptation); kmeans's hier candidate appears exactly on the
# multi-host price list
_pl_flat = _plP.plan_program("kmeans.fit", _plT.sim_ring(8))
_pl_multi = _plP.plan_program("kmeans.fit", _plT.v4_32())
assert _pl_flat.flip_candidates() == []
assert _pl_multi.flip_candidates() == ["kmeans_hier_psum"]
assert all(d.schedule == "keep" for d in _pl_multi.sites)
print(f"planner: mfsgd.epoch sheet {_pl_plan.predicted_bytes_total()} B "
      "== ledger both ways; alt schedules execute bit-identical; "
      "hier candidate only on v4_32")
print("DRIVE OK round-31")

# --- round 32: request-level tracing (PR 12) -------------------------------
# One causal timeline across the serve plane: a continuous run under
# seeded chaos yields complete span trees that reconcile EXACTLY with
# the runner's own counters, the merged timeline passes check_jsonl
# invariant 11 next to its ledger row, the trace CLI and the Perfetto
# exporter both load it, and the new svm/wdamds wire knobs execute
# with their exact arm unchanged.
from harp_tpu.serve.engines import ENGINES as _rtE
from harp_tpu.serve.server import Server as _rtServer
from harp_tpu.utils import reqtrace as _rt
from harp_tpu.utils import telemetry as _rtT
from harp_tpu.utils.fault import FaultInjector as _rtFI

import json as _rt_json
import subprocess as _rt_sp
import tempfile as _rt_tmp

with _rtT.scope(True):
    _rt_rng = np.random.default_rng(32)
    _rt_srv = _rtServer(
        "kmeans", state=_rtE["kmeans"].synthetic_state(_rt_rng, k=4, d=8),
        mesh=mesh, ladder=(1, 8))
    _rt_srv.startup()
    _rt_srv.steady.reset()
    _rt_r = _rt_srv.make_runner(depth=2, max_queue_rows=8, max_retries=1)
    _rt_inj = _rtFI(seed=0, fail={"dispatch": (2,)})
    _rt_t = 0.0
    with _rt_inj.arm():
        for _rt_i in range(8):
            _rt_r.submit(_rt_i, {"id": _rt_i, "x": _rt_rng.normal(
                size=(2, 8)).tolist()}, now=_rt_t)
            _rt_t += 0.001
            _rt_r.step(_rt_t)
        _rt_r.drain(_rt_t + 0.1)
    # chaos fired, the retry absorbed it, and EVERY offered request has
    # exactly one terminated span whose counts match the runner's books
    assert _rt_inj.injected["dispatch"] == 1
    assert _rt_r.fault_retries == 1
    _rt_tr = _rt.tracer
    assert _rt_tr.counts["served"] == _rt_r.completed
    assert _rt_tr.counts["shed"] == _rt_r.shed
    assert _rt_tr.counts["failed"] == _rt_r.failed
    assert sum(_rt_tr.counts.values()) == 8
    assert _rt_tr.summary()["open"] == 0
    assert _rt_tr.batch_event_count("retry") == 1
    assert any(m["source"] == "fault" for m in _rt_tr.marks)
    _rt_r.verify_exact()  # flagship budgets hold with tracing armed
    # streaming window percentiles agree with the exact samples they saw
    _rt_win = _rt_r.win.snapshot(_rt_t + 0.1)
    _rt_lat = sorted(_rt_r.latencies_ms)
    import math as _rt_math
    _rt_exact99 = _rt_lat[max(1, _rt_math.ceil(0.99 * len(_rt_lat))) - 1]
    assert abs(_rt_win["p99_ms"] - _rt_exact99) <= \
        _rt.QUANTILE_REL_ERR * _rt_exact99 + 1e-9
    with _rt_tmp.TemporaryDirectory() as _rt_d:
        _rt_p = os.path.join(_rt_d, "timeline.jsonl")
        _rtT.export_timeline(_rt_p)
        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.abspath(__file__))))
        import check_jsonl as _rt_cj
        assert _rt_cj.check_file(_rt_p) == []
        _rt_rows = _rtT.load_rows(_rt_p)["trace"]
        _rt_perf = _rt.perfetto(_rt_rows)
        _rt_json.dumps(_rt_perf)
        assert any(e.get("ph") == "X" for e in _rt_perf["traceEvents"])
        # the CLI validates the same file (exit 0, machine row)
        _rt_out = _rt_sp.run(
            [sys.executable, "-m", "harp_tpu", "trace", _rt_p, "--json"],
            capture_output=True, text=True, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert _rt_out.returncode == 0, _rt_out.stderr[-500:]
        _rt_row = _rt_json.loads(_rt_out.stdout.strip().splitlines()[-1])
        assert _rt_row["unterminated"] == []
        assert _rt_row["served"] == _rt_tr.counts["served"]
print(f"reqtrace: 8 requests -> {_rt_tr.counts} reconciled, 1 injected "
      "fault absorbed, timeline invariant-11 clean, CLI + Perfetto load")

# svm/wdamds wires: the exact arm still trains/embeds (the reshard shim
# is bit-identical to the old allgather), bf16 stays close, and the
# planner names exactly the new measurable candidates
from harp_tpu.models.svm import SVM as _rtSVM, SVMConfig as _rtSVMC
_rt_x = _rt_rng.normal(size=(128, 8)).astype(np.float32)
_rt_y = np.sign(_rt_x @ _rt_rng.normal(size=8) + 1e-3).astype(np.float32)
_rt_cfg = dict(inner_steps=40, outer_rounds=2, sv_per_worker=8)
_rt_exact = _rtSVM(_rtSVMC(**_rt_cfg), mesh).fit(_rt_x, _rt_y)
_rt_bf16 = _rtSVM(_rtSVMC(sv_wire="bf16", **_rt_cfg), mesh).fit(_rt_x, _rt_y)
assert _rt_exact.accuracy(_rt_x, _rt_y) > 0.9
assert abs(_rt_bf16.accuracy(_rt_x, _rt_y)
           - _rt_exact.accuracy(_rt_x, _rt_y)) < 0.05
from harp_tpu.models.wdamds import MDSConfig as _rtMDSC, mds as _rt_mds
_rt_pts = _rt_rng.normal(size=(64, 4)).astype(np.float32)
_rt_delta = np.sqrt(((_rt_pts[:, None] - _rt_pts[None]) ** 2).sum(-1))
_rt_X, _rt_s = _rt_mds(_rt_delta, _rtMDSC(dim=3, iters=10), mesh, seed=0)
_rt_Xb, _rt_sb = _rt_mds(_rt_delta, _rtMDSC(dim=3, iters=10,
                                            coord_wire="bf16"), mesh,
                         seed=0)
assert np.isfinite(_rt_s) and _rt_s > 0
assert abs(_rt_sb - _rt_s) / _rt_s < 0.05
from harp_tpu.plan import planner as _rt_plan, topology as _rt_topo
assert set(_rt_plan.plan_program(
    "svm.train", _rt_topo.sim_ring(8)).flip_candidates()) == \
    {"svm_sv_bf16", "svm_sv_int8"}
assert set(_rt_plan.plan_program(
    "wdamds.smacof", _rt_topo.sim_ring(8)).flip_candidates()) == \
    {"wdamds_coord_bf16", "wdamds_coord_int8"}
print("svm/wdamds wires: exact arm trains/embeds, bf16 within bounds, "
      "planner names the four new candidates")
print("DRIVE OK round-32")

# --- round 33: the predictive performance observatory (PR 13) --------------
# Byte sheets -> model rows, end-to-end through the CLI subprocess,
# CPU-only: (a) the predict CLI prices every byte-sheeted program AND
# every modeled config as invariant-12-clean rows; (b) self-grading
# against the committed evidence exits 0; (d) the shared wire oracle
# prices the planner's sites identically; (e) the pre-sizer reproduces
# the OOM-calibrated tiles.
import json as _pm_json
import subprocess as _pm_sp
import tempfile as _pm_tmp

from harp_tpu import perfmodel as _pm
from harp_tpu.perfmodel import grade as _pm_g

_pm_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_pm_env = {**os.environ, "JAX_PLATFORMS": "cpu"}

# (a) predict CLI: one row per program with a byte sheet (18+) + one per
# modeled config, every row invariant-12-clean
_pm_out = _pm_sp.run(
    [sys.executable, "-m", "harp_tpu", "predict", "--json",
     "--topology", "v4_32"],
    capture_output=True, text=True, timeout=600, env=_pm_env,
    cwd=_pm_root)
assert _pm_out.returncode == 0, _pm_out.stderr[-800:]
_pm_rows = [_pm_json.loads(ln)
            for ln in _pm_out.stdout.strip().splitlines()]
assert sum(1 for r in _pm_rows if r.get("program")) >= 18
assert sum(1 for r in _pm_rows if r.get("config")) >= 25
import check_jsonl as _pm_cj
with _pm_tmp.TemporaryDirectory() as _pm_d:
    _pm_p = os.path.join(_pm_d, "model.jsonl")
    with open(_pm_p, "w") as _pm_f:
        _pm_f.write(_pm_out.stdout)
    assert _pm_cj.check_file(_pm_p) == []
for _pm_r in _pm_rows:
    assert _pm_r["rates_source"] in ("declared", "probed")
    assert abs(sum(_pm_r["terms"].values()) - _pm_r["predicted_s"]) \
        <= 1e-6 * _pm_r["predicted_s"]

# (b) the honesty gate: the model agrees with every committed verdict
# it can price (exit 1 + term breakdowns on any drift)
_pm_gr = _pm_sp.run(
    [sys.executable, "-m", "harp_tpu", "predict", "--grade",
     "--repo", _pm_root],
    capture_output=True, text=True, timeout=300, env=_pm_env,
    cwd=_pm_root)
assert _pm_gr.returncode == 0, _pm_gr.stderr[-800:]
_pm_grow = _pm_json.loads(_pm_gr.stdout.strip().splitlines()[-1])
assert _pm_grow["ok"] is True
assert sum(1 for e in _pm_grow["pairs"]
           if e["status"] == "agrees") >= 5

# (d) one wire oracle: planner site costs == model wire term, and the
# Plan rows still fail closed after the re-point
from harp_tpu.plan import planner as _pm_plan
_pm_plan_row = _pm_plan.plan_program(
    "kmeans.fit", _rt_topo.v4_32()).row()
assert all(s["schedule"] == "keep" for s in _pm_plan_row["sites"])
for _pm_sched in _pm_plan.SCHEDULES:
    assert _pm_plan._site_cost(_rt_topo.v4_32(), "psum", _pm_sched,
                               4096) == \
        _pm.wire_cost_s(_rt_topo.v4_32(), "psum", _pm_sched, 4096)

# (e) the pre-sizer reproduces the hand-calibrated tiles offline
assert _pm.presize("kmeans.partials_int8",
                   n=1_000_000, d=300, k=100)["tile"] == 8000
assert _pm.presize("mfsgd.sgd_tile_update",
                   rank=64, n_items=26_744)["tile"] == 256

# and the grading harness itself fails closed under sabotage: a model
# whose dense arm prices like the kernel must flip ok to False
_pm_real_price = _pm_g.price
def _pm_sab(config, row=None, topo=None):
    p = _pm_real_price(config, row, topo)
    if config == "mfsgd":
        return _pm.Price(p.config, p.metric, p.compute_s, 1e-12,
                         p.wire_s, p.overhead_s)
    return p
_pm_g.price = _pm_sab
try:
    assert _pm_g.grade(_pm_root)["ok"] is False
finally:
    _pm_g.price = _pm_real_price

print(f"perfmodel: {len(_pm_rows)} model rows invariant-12-clean, "
      f"grade OK ({sum(1 for e in _pm_grow['pairs'] if e['status'] == 'agrees')}"
      " agreements), wire oracle shared, pre-sizer == hand-calibrated tiles")
print("DRIVE OK round-33")

# --- round 34: the health sentinel (PR 14) ---------------------------------
# The sixth (derived) spine end-to-end, CPU-only: (a) a seeded-ordinal
# chaos sustained serve run fires slo_burn + budget_drift findings whose
# counts reconcile EXACTLY with the row's invariant-9 ledger and the
# ReqTracer outcome counts, and the one exported file (trace + health +
# the stamped bench row) passes check_jsonl invariants 9/11/13 together,
# while the identical healthy control emits zero findings; (b) the skew
# trigger fires only after K consecutive over-threshold supersteps and
# its INLINE plan replays through schedule.apply_rebalance (numpy-checked
# resulting loads); (c) the health CLI summarizes/exits honestly and
# --grade-model emits the invariant-13-clean verdict row the sprint
# script tees; (d) the fail-closed --predicted-top gate is OPEN at HEAD
# (the committed evidence grades confirmed); (e) the driver record is
# bounded under the tail capture in the worst outage case.
import json as _hl_json
import subprocess as _hl_sp
import tempfile as _hl_tmp
import warnings as _hl_w

from harp_tpu import health as _hl
from harp_tpu import schedule as _hl_sched
from harp_tpu.serve.bench import benchmark_sustained as _hl_bs
from harp_tpu.utils import reqtrace as _hl_rt
from harp_tpu.utils import skew as _hl_skew
from harp_tpu.utils import telemetry as _hl_tm
from harp_tpu.utils.metrics import benchmark_json as _hl_bj

_hl_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_hl_env = {**os.environ, "JAX_PLATFORMS": "cpu"}
import check_jsonl as _hl_cj

# (a) chaos run: dispatch event #2 fails (exact ordinal), queue bounded
# at 16 rows under ~flood load -> shedding + one retry-with-restage
with _hl_tm.scope(True):
    with _hl_w.catch_warnings():
        _hl_w.simplefilter("ignore", RuntimeWarning)
        _hl_res = _hl_bs(app="kmeans", n_requests=48, rows_per_request=1,
                         burst_admit=8, ladder=(8,), offered_qps=1e5,
                         state_shape={"k": 4, "d": 8}, max_queue_rows=16,
                         max_retries=2, fault_ordinals=(2,), mesh=mesh)
    assert _hl_res["faults_injected"] == 1
    assert _hl_res["fault_retries"] == 1 and _hl_res["shed_requests"] > 0
    _hl_rows = {r["detector"]: r for r in _hl.monitor.findings()}
    _hl_slo, _hl_bd = _hl_rows["slo_burn"], _hl_rows["budget_drift"]
    for _hl_k, _hl_f in (("offered", "offered_requests"),
                         ("served", "served_requests"),
                         ("shed", "shed_requests"),
                         ("failed", "failed_requests")):
        assert _hl_slo[_hl_k] == _hl_res[_hl_f], (_hl_k, _hl_slo, _hl_res)
    assert _hl_rt.tracer.counts == {"served": _hl_slo["served"],
                                    "shed": _hl_slo["shed"],
                                    "failed": _hl_slo["failed"]}
    assert _hl_bd["violations"] == 1
    assert "h2d_calls used 2 > budget 1" in _hl_bd["worst"]
    assert _hl_res["health_findings"] == 2
    assert _hl_res["health_budget_drift"] == 1
    with _hl_tmp.TemporaryDirectory() as _hl_d:
        _hl_p = os.path.join(_hl_d, "chaos.jsonl")
        _hl_tm.export(_hl_p)
        with open(_hl_p, "a") as _hl_f:
            _hl_f.write(_hl_bj("serve_kmeans_sustained", _hl_res) + "\n")
        assert _hl_cj.check_file(_hl_p, provenance=True) == []
        # (c) the CLI on the same file: actionable findings -> exit 1
        _hl_cli = _hl_sp.run(
            [sys.executable, "-m", "harp_tpu", "health", _hl_p, "--json",
             "--repo", _hl_root],
            capture_output=True, text=True, timeout=300, env=_hl_env,
            cwd=_hl_root)
        assert _hl_cli.returncode == 1, _hl_cli.stderr[-500:]
        _hl_sum = _hl_json.loads(
            _hl_cli.stdout.strip().splitlines()[-1])
        assert _hl_sum["findings"] == 2 and _hl_sum["actionable"] == 2
        assert _hl_sum["worst_severity"] == "page"
# healthy control: same trace shape, degradation knobs off -> clean
with _hl_tm.scope(True):
    _hl_ok = _hl_bs(app="kmeans", n_requests=48, rows_per_request=1,
                    burst_admit=8, ladder=(8,), offered_qps=500.0,
                    state_shape={"k": 4, "d": 8}, mesh=mesh)
    assert _hl_ok["health_findings"] == 0
    assert _hl_ok["health_breaches"] == 0
    assert _hl_ok["health_budget_drift"] == 0
    assert _hl.monitor.findings() == []

# (b) skew trigger -> apply_rebalance, loads numpy-checked
with _hl_tm.scope(True):
    for _hl_i in range(_hl.TRIGGER_SUPERSTEPS):
        _hl_skew.record_partition(
            "files", [10, 1, 0, 1], unit="bytes",
            units=[[("a", 6), ("b", 4)], [("c", 1)], [], [("d", 1)]])
        if _hl_i < _hl.TRIGGER_SUPERSTEPS - 1:
            assert _hl.monitor.findings() == []  # K-1 never fires
    _hl_r = _hl.monitor.findings()[0]
    assert _hl_r["detector"] == "skew_trigger"
    _hl_plan = _hl_r["plan"]
    _hl_new = _hl_sched.apply_rebalance([["a", "b"], ["c"], [], ["d"]],
                                        _hl_plan)
    _hl_sizes = {"a": 6, "b": 4, "c": 1, "d": 1}
    _hl_loads = sorted(sum(_hl_sizes[u] for u in w) for w in _hl_new)
    assert _hl_loads == [1, 1, 4, 6]  # greedy LPT on measured loads
    assert _hl_plan["ratio_after"] < _hl_plan["ratio_before"]

# (c) --grade-model: the one verdict row the sprint tees, checker-clean
_hl_gm = _hl_sp.run(
    [sys.executable, "-m", "harp_tpu", "health", "--grade-model",
     "--repo", _hl_root],
    capture_output=True, text=True, timeout=600, env=_hl_env,
    cwd=_hl_root)
assert _hl_gm.returncode == 0, _hl_gm.stderr[-800:]
_hl_row = _hl_json.loads(_hl_gm.stdout.strip().splitlines()[-1])
assert _hl_row["verdict"] == "confirmed"
assert _hl_cj._check_health_row("t", 1, _hl_row) == []

print(f"health: chaos run {_hl_res['served_requests']}/"
      f"{_hl_res['shed_requests']}/{_hl_res['failed_requests']} "
      "reconciled across ledger+trace+sentinel, control clean, "
      f"skew plan applied (loads {_hl_loads}), grade-model confirmed")
print("DRIVE OK round-34")

# ---------------------------------------------------------------------------
# round 35 — elastic execution (PR 15): the whole loop through the
# PUBLIC surface, numpy-checked.  (a) a skewed corpus fires the PR-14
# trigger, the elastic MF-SGD driver consumes it EXACTLY once and the
# rebalanced per-worker loads match a straight-line numpy LPT over the
# pack grains; (b) the reshard-wire row move equals numpy fancy
# indexing bit-for-bit; (c) an injected permanent worker loss at a
# seeded ordinal shrinks 8 -> 7 and the continued training is
# BIT-identical to a survivors-only run from the same checkpoint;
# (d) the full telemetry export (skew + health + elastic rows) passes
# scripts/check_jsonl.py, and the elastic CLI knob round-trips end to
# end in a subprocess.
# ---------------------------------------------------------------------------
import json as _el_json
import subprocess as _el_sp
import tempfile as _el_tmp

from harp_tpu import health as _el_h
from harp_tpu.elastic import ledger as _el_led
from harp_tpu.elastic.apps import MFSGDElastic as _ElMF
from harp_tpu.elastic.apps import elastic_fit as _el_fit
from harp_tpu.elastic.move import regather_rows as _el_regather
from harp_tpu.elastic.rebalance import wasted_frac as _el_wf
from harp_tpu.models.mfsgd import MFSGDConfig as _ElCfg
from harp_tpu.utils import telemetry as _el_tm
from harp_tpu.utils.checkpoint import CheckpointManager as _ElCkpt
from harp_tpu.utils.fault import FaultInjector as _ElInj

_el_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_el_root, "scripts"))
import check_jsonl as _el_cj  # noqa: E402

_el_rng = np.random.default_rng(0)
_el_users = np.concatenate([_el_rng.integers(0, 2 * (64 // nw), 4000),
                            _el_rng.integers(2 * (64 // nw), 64, 1000)])
_el_rng.shuffle(_el_users)
_el_items = _el_rng.integers(0, 48, _el_users.shape[0])
_el_vals = _el_rng.normal(size=_el_users.shape[0]).astype(np.float32)
_el_cfg = _ElCfg(rank=4, algo="dense", u_tile=8, i_tile=8, entry_cap=64)

with _el_tm.scope(True):
    _el_ad = _ElMF(64, 48, _el_cfg, mesh, 0, users=_el_users,
                   items=_el_items, vals=_el_vals, packs_per_worker=8)
    _el_before = _el_ad.worker_loads().copy()
    assert _el_wf(_el_before) > _el_h.WASTED_FRAC_TRIGGER
    _el_fit(_el_ad, 4)
    # (a) numpy model of the rebalanced loads: greedy LPT (size-desc,
    # argmin-load placement) over the measured pack loads — the exact
    # rule SkewLedger.suggest_rebalance applies
    _el_pl = _el_ad.packs.loads(_el_users)
    _el_lpt = np.zeros(nw)
    for _el_pid in sorted(range(len(_el_pl)),
                          key=lambda p: (-_el_pl[p], p)):
        _el_lpt[int(_el_lpt.argmin())] += _el_pl[_el_pid]
    np.testing.assert_allclose(sorted(_el_ad.worker_loads()),
                               sorted(_el_lpt))
    assert _el_wf(_el_ad.worker_loads()) < _el_h.WASTED_FRAC_TRIGGER
    (_el_reb,) = [r for r in _el_led.ledger.rows
                  if r["event"] == "rebalance"]
    assert _el_reb["wasted_frac_after"] < _el_reb["wasted_frac_before"]
    assert sum(_el_reb["loads_after"]) == sum(_el_reb["loads_before"])
    # the handshake spent the fire: nothing left to consume
    assert _el_h.monitor.consume_skew_trigger(_el_ad.phase) is None

    # (b) reshard-wire row move vs numpy fancy indexing
    _el_x = mesh.shard_array(
        _el_rng.normal(size=(8 * nw, 3)).astype(np.float32), 0)
    _el_rows = _el_rng.integers(-1, 8 * nw, 2 * 8 * nw)
    _el_got = np.asarray(_el_regather(mesh, _el_x, _el_rows))
    _el_ref = np.where((_el_rows >= 0)[:, None],
                       np.asarray(_el_x)[np.maximum(_el_rows, 0)], 0.0)
    np.testing.assert_array_equal(_el_got, _el_ref)

    # (c) permanent loss at seeded dispatch ordinal 2 -> shrink -> the
    # continuation is BIT-identical to survivors-only from the ckpt
    _el_dir = _el_tmp.mkdtemp()
    _el_ck = os.path.join(_el_dir, "ck")
    _el_inj = _ElInj(seed=0, permanent={"dispatch": (2,)},
                     lost_worker=nw - 1)
    _el_ad2 = _ElMF(64, 48, _el_cfg, mesh, 0, users=_el_users,
                    items=_el_items, vals=_el_vals, max_worker_loss=1)
    _el_fit(_el_ad2, 3, _el_ck, ckpt_every=1, fault=_el_inj,
            rebalance=False)
    assert _el_inj.permanent_fired
    assert _el_ad2.mesh.num_workers == nw - 1
    _el_events = [r["event"] for r in _el_led.ledger.rows]
    assert _el_events == ["rebalance", "shrink", "resume"], _el_events
    _el_step, _el_state = _ElCkpt(_el_ck).restore(0)
    _el_surv = mesh.survivors(nw - 1)
    _el_ad3 = _ElMF(64, 48, _el_cfg, _el_surv, 0, users=_el_users,
                    items=_el_items, vals=_el_vals)
    _el_ad3.install(_el_state)
    for _el_i in range(_el_step + 1, 3):
        _el_ad3.train_one()
    np.testing.assert_array_equal(_el_ad2.canonical_state()["W"],
                                  _el_ad3.canonical_state()["W"])
    np.testing.assert_array_equal(_el_ad2.canonical_state()["H"],
                                  _el_ad3.canonical_state()["H"])
    # the comparison adapter's install adds its OWN resume row (it is
    # the same restore path) — the export below carries all four
    assert [r["event"] for r in _el_led.ledger.rows][-1] == "resume"

    # (d) the export passes EVERY checker invariant as one file
    _el_out = os.path.join(_el_dir, "run.jsonl")
    _el_tm.export(_el_out)
_el_errs = _el_cj.check_file(_el_out, provenance=True)
assert _el_errs == [], _el_errs

# CLI round trip in a subprocess (the --elastic knob end to end)
# (the child inherits JAX_PLATFORMS=cpu and the 8-device XLA_FLAGS)
_el_cli = _el_sp.run([sys.executable, "-m", "harp_tpu", "kmeans-stream",
                      "--elastic", "--n", "256", "--d", "4", "--k", "3",
                      "--iters", "2"],
                     capture_output=True, text=True, timeout=600,
                     cwd=_el_root)
assert _el_cli.returncode == 0, _el_cli.stderr[-800:]
_el_row = _el_json.loads(_el_cli.stdout.strip().splitlines()[-1])
assert _el_row["config"] == "kmeans_stream_elastic_cli"
assert _el_row["worker_losses"] == 0 and np.isfinite(_el_row["inertia"])

print(f"elastic: rebalance {round(_el_wf(_el_before), 3)} -> "
      f"{round(_el_wf(_el_ad.worker_loads()), 4)} (numpy LPT match), "
      f"regather bit-exact, loss at ordinal 2 shrank {nw} -> {nw - 1} "
      "bit-identical to survivors-only, export checker-clean, CLI "
      f"inertia {round(_el_row['inertia'], 1)}")
print("DRIVE OK round-35")

# ---------------------------------------------------------------------------
# round-36: wall-attribution observatory (PR 16).  Classifier vs a
# hand-labelled span table, attribute() vs a straight-line numpy model,
# one REAL capture cross-reconciled through check_jsonl invariant 15 and
# the lint's CommGraph byte sheet, profile_drift grading (quiet on
# itself, fires on a forged bound flip), and the newly priced perfmodel
# half (rf/svm/wdamds/subgraph + the serve queueing term).
from harp_tpu.profile import attribution as _pf

# (a) classifier priority: collective names never read as gather/mxu,
# runtime/infra spans land in overhead, the residue is elementwise.
_pf_expect = {
    "all-gather.7": "wire", "all-reduce": "wire",
    "collective-permute.2": "wire",
    "dot_general.1": "mxu", "conv.3": "mxu",
    "convert.9": "elementwise",                # conv(?!ert) guard
    "scatter-add.4": "scatter", "segment_sum": "scatter",
    "gather.5": "gather_dus", "dynamic-update-slice.8": "gather_dus",
    "TfrtCpuExecutable::Execute": "overhead",
    "PjitFunction(fit)": "overhead",
    "fusion.12": "elementwise", "broadcast.2": "elementwise",
}
for _pf_name, _pf_want in _pf_expect.items():
    _pf_got = _pf.classify(_pf_name)
    assert _pf_got == _pf_want, (_pf_name, _pf_got, _pf_want)

# (b) attribute() vs numpy: under-attribution fills overhead exactly;
# over-attribution rescales to the wall and reports the residual;
# device-count normalization divides attributed seconds by N.
_pf_bd = [("dot.1", 0, 0.40), ("fusion.2", 1, 0.20),
          ("all-gather.3", 0, 0.10), ("scatter.4", 1, 0.05),
          ("dynamic-update-slice.5", 0, 0.05)]
_pf_a = _pf.attribute(_pf_bd, 1.0, 1)
assert _pf_a["bound"] == "mxu" and _pf_a["sum_rel_err"] == 0.0
assert abs(sum(_pf_a["terms"].values()) - 1.0) < 1e-5
assert abs(_pf_a["terms"]["overhead_s"] - 0.2) < 1e-5      # 1.0 - 0.8
_pf_o = _pf.attribute(_pf_bd, 0.5, 1)       # 0.8 attributed over 0.5 wall
assert abs(_pf_o["sum_rel_err"] - 0.6) < 1e-6
assert abs(sum(_pf_o["terms"].values()) - 0.5) < 1e-5
_pf_n = _pf.attribute(_pf_bd, 1.0, 2)       # halve per-device seconds
assert abs(sum(_pf_v for _pf_k, _pf_v in _pf_n["terms"].items()
               if _pf_k != "overhead_s") - 0.4) < 1e-5

# (c) one real capture end to end: reconciled, invariant-15 clean, and
# the wire column agrees with an independent CommGraph walk.
_pf_row = _pf.capture("kmeans", reps=2)
assert _pf_row["reconciled"] is True and _pf_row["bound"] in _pf.BUCKETS
import check_jsonl as _pf_cj

_pf_errs = _pf_cj._check_profile_row("drive", 0, _pf_row)
assert _pf_errs == [], _pf_errs
from harp_tpu.analysis import commgraph as _pf_cg
from harp_tpu.analysis.drivers import DRIVERS as _PF_DRV

_pf_fn, _pf_fargs = _PF_DRV["kmeans.fit"]()
assert _pf_row["wire_bytes"] == int(
    _pf_cg.extract("kmeans.fit", _pf_fn, _pf_fargs).amplified_bytes())

# (d) drift grading: the row graded against itself is quiet; moving the
# bound bucket's whole share to another bucket fires a warn finding.
from harp_tpu.health import grade as _pf_hg
from harp_tpu.health import sentinel as _pf_sn

_pf_sn.reset()
_pf_base = {_pf_row["app"]: _pf_row}
assert _pf_hg.grade_profile_row(dict(_pf_row), "/root/repo",
                                committed=_pf_base) is None
_pf_other = "mxu" if _pf_row["bound"] != "mxu" else "wire"
_pf_flip = dict(_pf_row, terms=dict(_pf_row["terms"]),
                bound=_pf_other)
_pf_flip["terms"][_pf_other + "_s"] += \
    _pf_flip["terms"][_pf_row["bound"] + "_s"]
_pf_flip["terms"][_pf_row["bound"] + "_s"] = 0.0
_pf_f = _pf_hg.grade_profile_row(_pf_flip, "/root/repo",
                                 committed=_pf_base)
assert _pf_f is not None and _pf_f["detector"] == "profile_drift"
assert _pf_f["bound_flipped"] is True and _pf_f["severity"] == "warn"
assert _pf_f["share_delta"] > _pf_hg.PROFILE_SHARE_DRIFT
_pf_sn.reset()

# (e) the newly priced half prices: every PR-16 flip candidate plus the
# serve queueing term yields a finite positive predicted wall, and the
# deliberately unpriced kmeans_ingest still raises.
from harp_tpu.perfmodel import model as _pf_pm
from harp_tpu.plan.topology import v4_32 as _pf_v432

_pf_topo = _pf_v432()
for _pf_cfg in ("rf_dense_hist", "svm_x_bf16", "wdamds_delta_bf16",
                "subgraph_csr32", "serve_kmeans_sustained"):
    _pf_price = _pf_pm.price(_pf_cfg, None, _pf_topo)
    _pf_mrow = _pf_pm.model_row(_pf_price, _pf_topo, config=_pf_cfg)
    assert _pf_mrow["predicted_s"] > 0 and np.isfinite(
        _pf_mrow["predicted_s"]), _pf_cfg
try:
    _pf_pm.price("kmeans_ingest", None, _pf_topo)
    raise AssertionError("kmeans_ingest must stay unpriced")
except KeyError:
    pass

print(f"profile: {len(_pf_expect)} span labels classified, attribute() "
      "== numpy (overhead fill / rescale / device split), kmeans "
      f"capture reconciled bound={_pf_row['bound']} "
      f"wire={_pf_row['wire_bytes']} B == CommGraph, drift quiet-on-self "
      f"and fires on flip (delta {_pf_f['share_delta']}), 5 new terms "
      "priced + ingest still refuses")
print("DRIVE OK round-36")

# --------------------------------------------------------------- round 37
# PR 17: the kernelized half.  Attribution re-capture: the rf/svm/wdamds
# profile rows still reconcile (dispatch count, zero in-window compiles,
# CommLedger match) with the new kernels registered.
from harp_tpu.profile import attribution as _k17_attr

for _k17_app in ("rf", "svm", "wdamds"):
    _k17_prow = _k17_attr.capture(_k17_app, reps=2)
    assert _k17_prow["reconciled"] is True, (
        _k17_app, _k17_prow.get("checks"))
    _k17_errs = _pf_cj._check_profile_row("drive", 0, _k17_prow)
    assert _k17_errs == [], (_k17_app, _k17_errs)

print("kernels: rf/svm/wdamds captures reconciled with the kernels "
      "registered")
print("DRIVE OK round-37")

# ---------------------------------------------------------------------------
# round 38 — superstep flightpath (PR 18): one causal training-plane
# timeline across all seven spines, hand-checked.  (a) THE chaos drill
# through the PUBLIC elastic surface — a seeded transient dispatch
# fault, a fired-and-consumed skew rebalance, and a permanent worker
# loss in ONE run — yields a timeline whose span-outcome multiset,
# cause-adjacency (every faulted span's seq carries the injector's own
# mark), elastic mark sequence, and EXACT dispatch-mark==flight-delta
# reconciliation are re-derived by hand from the raw rows; (b) the
# export passes scripts/check_jsonl.py whole-file (invariant 16 on top
# of 13/14), INCLUDING an elastic resume row recorded OUTSIDE any run
# (the round-35 manual-install comparison pattern, on_timeline=False —
# exactly the scenario that caught the first cut of this invariant in
# this drive); (c) the timeline CLI round-trips in a subprocess
# (exit 0, stamped --json row, --perfetto Chrome-Trace JSON with only
# M/X/i phases); (d) zero-cost off: with telemetry disabled the tracer
# stays EMPTY through a full instrumented driver run and kmeans.fit
# returns bit-identical centroids vs the traced run.
# ---------------------------------------------------------------------------
import json as _st_json
import subprocess as _st_sp
import tempfile as _st_tmp

from harp_tpu.elastic import ledger as _st_led
from harp_tpu.elastic.apps import MFSGDElastic as _StMF
from harp_tpu.elastic.apps import elastic_fit as _st_fit
from harp_tpu.models import kmeans as _st_km
from harp_tpu.models.mfsgd import MFSGDConfig as _StCfg
from harp_tpu.utils import steptrace as _st_st
from harp_tpu.utils import telemetry as _st_tm
from harp_tpu.utils.checkpoint import CheckpointManager as _StCkpt
from harp_tpu.utils.fault import FaultInjector as _StInj

_st_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_st_root, "scripts"))
import check_jsonl as _st_cj  # noqa: E402

_st_rng = np.random.default_rng(0)
_st_users = np.concatenate([_st_rng.integers(0, 2 * (64 // nw), 4000),
                            _st_rng.integers(2 * (64 // nw), 64, 1000)])
_st_rng.shuffle(_st_users)
_st_items = _st_rng.integers(0, 48, _st_users.shape[0])
_st_vals = _st_rng.normal(size=_st_users.shape[0]).astype(np.float32)
_st_cfg = _StCfg(rank=4, algo="dense", u_tile=8, i_tile=8, entry_cap=64)
_st_dir = _st_tmp.mkdtemp()
_st_out = os.path.join(_st_dir, "run.jsonl")

with _st_tm.scope(True):
    # (a) transient at dispatch ordinal 5, permanent at 7 — the skewed
    # corpus fires the trigger first, so the narrative is
    # rebalance -> transient+restart -> loss+shrink, one run id
    _st_inj = _StInj(seed=0, fail={"dispatch": (5,)},
                     permanent={"dispatch": (7,)}, lost_worker=nw - 1)
    _st_ad = _StMF(64, 48, _st_cfg, mesh, 0, users=_st_users,
                   items=_st_items, vals=_st_vals, packs_per_worker=8,
                   max_worker_loss=1)
    _st_fit(_st_ad, 6, os.path.join(_st_dir, "ck"), ckpt_every=1,
            fault=_st_inj)
    assert _st_inj.permanent_fired and _st_ad.losses == 1
    _st_ev = [r["event"] for r in _st_led.ledger.rows]
    assert _st_ev == ["rebalance", "resume", "shrink", "resume"], _st_ev
    assert all(r["on_timeline"] for r in _st_led.ledger.rows)
    _st_rows = _st_st.tracer.rows()

    # hand re-derivation from the raw rows: one run, every span
    # terminated, outcome multiset matches the injector script
    (_st_rn,) = [r for r in _st_rows if r["ev"] == "run"]
    _st_sp_rows = [r for r in _st_rows if r["ev"] == "superstep"]
    assert len(_st_sp_rows) == _st_rn["supersteps"]
    _st_oc = {o: sum(1 for s in _st_sp_rows if s["outcome"] == o)
              for o in _st_st.OUTCOMES}
    assert _st_oc == {"completed": 3, "faulted": 2, "rebalanced": 1,
                      "resumed": 2}, _st_oc
    # cause-adjacency: the injector's marks sit on the faulted seqs
    _st_marks = [r for r in _st_rows if r["ev"] == "mark"]
    _st_fm = {m["seq"] for m in _st_marks if m["source"] == "fault"}
    assert _st_fm == {s["seq"] for s in _st_sp_rows
                      if s["outcome"] == "faulted"}
    assert [m["name"] for m in _st_marks if m["source"] == "elastic"] \
        == _st_ev
    assert {"skew_trigger", "consume_skew_trigger"} <= {
        m["name"] for m in _st_marks if m["source"] == "health"}
    # the two-spine dispatch reconciliation, EXACT
    _st_dm = sum(1 for m in _st_marks
                 if (m["source"], m["name"]) == ("flight", "dispatch"))
    assert _st_dm == _st_rn["flight"]["dispatches"]

    # (b) an elastic action OUTSIDE any run: restore the ckpt into a
    # fresh survivors-mesh adapter (the round-35 bit-identity pattern)
    # — its resume row must stamp on_timeline=False and the export must
    # STAY invariant-16 clean
    _st_step, _st_state = _StCkpt(os.path.join(_st_dir, "ck")).restore()
    _st_cmp = _StMF(64, 48, _st_cfg, mesh.survivors(nw - 1), 0,
                    users=_st_users, items=_st_items, vals=_st_vals)
    _st_cmp.install(_st_state)
    assert _st_led.ledger.rows[-1]["event"] == "resume"
    assert _st_led.ledger.rows[-1]["on_timeline"] is False
    _st_tm.export(_st_out)
_st_errs = _st_cj.check_file(_st_out, provenance=True)
assert _st_errs == [], _st_errs

# (c) the CLI in a subprocess: exit 0, stamped JSON row, Perfetto shape
_st_env = {**os.environ, "JAX_PLATFORMS": "cpu"}
_st_pf = os.path.join(_st_dir, "trace.json")
_st_cli = _st_sp.run(
    [sys.executable, "-m", "harp_tpu", "timeline", _st_out, "--json",
     "--perfetto", _st_pf],
    capture_output=True, text=True, timeout=300, env=_st_env,
    cwd=_st_root)
assert _st_cli.returncode == 0, _st_cli.stderr[-800:]
_st_row = _st_json.loads(_st_cli.stdout.strip().splitlines()[-1])
assert _st_row["runs"] == 1 and _st_row["supersteps"] == len(_st_sp_rows)
assert _st_row["unterminated"] == [] and _st_row["dispatch_mismatch"] == []
assert all(k in _st_row for k in ("backend", "date", "commit"))
_st_doc = _st_json.load(open(_st_pf))
assert {e["ph"] for e in _st_doc["traceEvents"]} <= {"M", "X", "i"}
assert any(e["ph"] == "X" and e["dur"] >= 0
           for e in _st_doc["traceEvents"])

# (d) zero-cost off: empty tracer + bit-identical traced/untraced fit
_st_pts = np.random.default_rng(3).normal(size=(32 * nw, 8)) \
    .astype(np.float32)
_st_st.reset()
_st_c0, _st_i0 = _st_km.fit(_st_pts, k=4, iters=3, mesh=mesh, seed=0)
assert _st_st.tracer.rows() == [] and _st_st.tracer._run is None
with _st_tm.scope(True):
    _st_c1, _st_i1 = _st_km.fit(_st_pts, k=4, iters=3, mesh=mesh, seed=0)
    assert _st_st.tracer.rows() != []
np.testing.assert_array_equal(np.asarray(_st_c0), np.asarray(_st_c1))
assert _st_i0 == _st_i1

print(f"steptrace: chaos run {_st_rn['supersteps']} spans {_st_oc} on "
      "one run id, fault marks on the faulted seqs, elastic marks == "
      f"ledger {_st_ev}, dispatch marks == flight ({_st_dm}), "
      "uncovered manual-install resume row exports clean, CLI+Perfetto "
      "round trip, tracer zero-cost off (bit-identical kmeans)")
print("DRIVE OK round-38")

# ---------------------------------------------------------------------------
# round 39 — the memory plane (PR 19).  One instrumented scope drives
# every hook through the PUBLIC surface: (a) shard_array staging +
# a donate_argnums-tracked dispatch (the donated buffer must LEAVE the
# live set) + a checkpoint restore + one passing and one REFUSED
# vmem gate, all inside steptrace supersteps so the peak rides the
# timeline as memory marks; the export must be invariant-17 clean and
# the watermark must match a straight-line python replay of the buffer
# rows; (b) the serve AOT cache persists the memory_analysis()
# footprint as a .mem.json sidecar and a warm load reports the SAME
# exec_hbm_bytes without recompiling; (c) the CLI round-trips the
# export (exit 0, stamped --json row, exit 2 on garbage); (d) zero
# cost off: with telemetry disabled no hook records anything.
# ---------------------------------------------------------------------------
import json as _mr_json
import subprocess as _mr_sp
import tempfile as _mr_tmp

from harp_tpu.ops.kmeans_kernel import vmem_bytes_int8 as _mr_vb
from harp_tpu.serve.cache import ExecutableCache as _MrCache
from harp_tpu.utils import flightrec as _mr_fr
from harp_tpu.utils import memrec as _mr
from harp_tpu.utils import steptrace as _mr_stt
from harp_tpu.utils import telemetry as _mr_tm
from harp_tpu.utils.checkpoint import CheckpointManager as _MrCkpt

_mr_dir = _mr_tmp.mkdtemp()
_mr_out = os.path.join(_mr_dir, "run.jsonl")
_mr_x = np.arange(nw * 8 * 4, dtype=np.float32).reshape(nw * 8, 4)
_mr_step = _mr_fr.track(
    jax.jit(lambda a: a.sum(), donate_argnums=(0,)),
    "drive.mem.step", donate_argnums=(0,))
_mr_pred = _mr_vb(8000, 1024, 128)  # the 2026-08-01 silicon-OOM shape

with _mr_tm.scope(True):
    with _mr_stt.run("drive.mem"):
        with _mr_stt.superstep("drive.mem", 0):
            _mr_xd = mesh.shard_array(_mr_x)          # staged
            _mr_res = float(np.asarray(_mr_step(_mr_xd)))  # donated
            _mr_ck = _MrCkpt(os.path.join(_mr_dir, "ck"))
            _mr_ck.save(1, {"w": np.float32(_mr_res)})
            _mr_ck.restore(1)                         # restored
            _mr.require_vmem_fit("drive.fit", 1 << 20,
                                 budget=14 << 20)     # fits
        with _mr_stt.superstep("drive.mem", 1):
            try:
                _mr.require_vmem_fit("kmeans.partials_int8", _mr_pred,
                                     budget=14 << 20)
                raise AssertionError("over-VMEM config was not refused")
            except MemoryError as e:
                assert str(_mr_pred) in str(e) and "refused before " \
                    "dispatch" in str(e), str(e)
    _mr_rows = list(_mr.ledger._rows)
    _mr_marks = [r for r in _mr_stt.tracer.rows()
                 if r["ev"] == "mark" and r["source"] == "memory"]
    assert _mr_marks and all(m["name"] == "superstep_peak"
                             for m in _mr_marks)
    _mr_tm.export(_mr_out)

# straight-line replay of the buffer rows == every stamped watermark
_mr_live, _mr_peak, _mr_alive = 0, 0, {}
for _mr_r in [r for r in _mr_rows if r["ev"] == "buffer"]:
    if _mr_r["event"] in ("staged", "output"):
        _mr_alive[_mr_r["buf"]] = _mr_r["bytes"]
    elif _mr_r["event"] in ("freed", "donated"):
        _mr_alive.pop(_mr_r["buf"], None)
    # "restored" is a zero-delta provenance row (ckpt state re-enters
    # through its own device_put, already counted) — live unchanged
    _mr_live = sum(_mr_alive.values())
    _mr_peak = max(_mr_peak, _mr_live)
    assert _mr_r["live_bytes"] == _mr_live
    assert _mr_r["peak_bytes"] == _mr_peak
assert _mr_peak >= _mr_x.nbytes
# the donated input is GONE from the live set (runtime HL303 twin)
(_mr_dn,) = [r for r in _mr_rows if r["ev"] == "dispatch"]
assert _mr_dn["donated_bytes"] == _mr_x.nbytes
assert _mr_x.nbytes not in _mr_alive.values()
assert ("restored",) == tuple({r["event"] for r in _mr_rows
                               if str(r.get("label", "")).startswith("ckpt:")})
_mr_errs = _st_cj.check_file(_mr_out, provenance=True)
assert _mr_errs == [], _mr_errs

# (b) AOT cache sidecar: compile writes it, warm load replays it
_mr_cache = _MrCache(_mr_dir, fingerprint="drive39")
_mr_jit = jax.jit(lambda v: v * 2.0)
_mr_args = (jnp.zeros((8, 8), jnp.float32),)
with _mr_tm.scope(True):
    _mr_cache.get_or_compile("drive.prog", _mr_jit, _mr_args)
    (_mr_c,) = [r for r in _mr.ledger._rows if r["ev"] == "executable"]
    assert _mr_c["source"] == "compile" and _mr_c["exec_hbm_bytes"] > 0
assert [f for f in os.listdir(_mr_dir) if f.endswith(".mem.json")]
_mr_fp = _mr_cache.footprint("drive.prog", _mr_args)
assert _mr_fp["argument_bytes"] == 256
with _mr_tm.scope(True):
    _mr_cache.load("drive.prog", _mr_args)
    (_mr_w,) = [r for r in _mr.ledger._rows if r["ev"] == "executable"]
    assert _mr_w["source"] == "cache"
    assert _mr_w["exec_hbm_bytes"] == _mr_c["exec_hbm_bytes"]

# (c) CLI round trip: exit 0 + stamped row matching the replay; exit 2
_mr_env = {**os.environ, "JAX_PLATFORMS": "cpu"}
_mr_cli = _mr_sp.run(
    [sys.executable, "-m", "harp_tpu", "memory", _mr_out, "--json"],
    capture_output=True, text=True, timeout=300, env=_mr_env,
    cwd=_st_root)
assert _mr_cli.returncode == 0, _mr_cli.stderr[-800:]
_mr_row = _mr_json.loads(_mr_cli.stdout.strip().splitlines()[-1])
assert _mr_row["errors"] == [] and _mr_row["peak_hbm_bytes"] == _mr_peak
assert _mr_row["vmem_refusals"] == 1
assert all(k in _mr_row for k in ("backend", "date", "commit"))
_mr_bad = _mr_sp.run(
    [sys.executable, "-m", "harp_tpu", "memory",
     os.path.join(_mr_dir, "nope.jsonl")],
    capture_output=True, text=True, timeout=300, env=_mr_env,
    cwd=_st_root)
assert _mr_bad.returncode == 2, _mr_bad.returncode

# (d) zero-cost off: no hook records anything with telemetry disabled
_mr.reset()
_ = mesh.shard_array(_mr_x)
_ = _mr_step(mesh.shard_array(_mr_x))
assert _mr.ledger._rows == [] and _mr.snapshot()["events"] == 0

print(f"memrec: lifecycle replay == watermark (peak {_mr_peak} B, "
      f"donated {_mr_dn['donated_bytes']} B gone at dispatch), ckpt "
      "restore labeled, over-VMEM refused pre-dispatch naming "
      f"{_mr_pred} B, export invariant-17 clean with "
      f"{len(_mr_marks)} superstep memory mark(s), cache sidecar "
      "compile==warm-load bytes, CLI exit 0/2, zero-cost off")
print("DRIVE OK round-39")

# ---------------------------------------------------------------------------
# round 40 — host-concurrency auditor + thread-ownership twin (PR 20).
# (a) the static layer's ownership map, generated from the thread-root
# graph over the REAL planes, names the watchdog / scheduler workers /
# TCP accept loop as forbidden and leaves the serve dispatcher (the
# designated jax owner) alone; every Layer-5 finding at HEAD is a
# reviewed HL403 allowlist entry and the scoped lint CLI exits 0;
# (b) the runtime twin armed around a REAL socket serve under an
# injected transient dispatch fault: the guard audits live traffic
# (checks > 0), objects to none of it, and the responses still match
# numpy; scheduler workers run under names the static patterns match;
# (c) a thread wearing a forbidden name is caught at a flightrec
# observer site; (d) disarmed, the observer registries and spine
# mutators restore exactly (zero-install contract).
# ---------------------------------------------------------------------------
import fnmatch as _tg_fn
import json as _tg_json
import socket as _tg_sock
import subprocess as _tg_sp
import tempfile as _tg_tmp
import threading as _tg_th

from harp_tpu.analysis import allowlist as _tg_al
from harp_tpu.analysis import threadgraph as _tg
from harp_tpu.schedule import StaticScheduler as _TgSched
from harp_tpu.serve.engines import ENGINES as _TG_ENGINES
from harp_tpu.serve.server import Server as _TgServer
from harp_tpu.serve.transport import TCPFrontEnd as _TgFE
from harp_tpu.utils import flightrec as _tg_fr
from harp_tpu.utils import telemetry as _tg_tm
from harp_tpu.utils import threadguard as _tg_guard
from harp_tpu.utils.fault import FaultInjector as _TgInj

_tg_repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (a) static half: generated map + HEAD findings all reviewed
_tg_omap = _tg.ownership_map(_tg_repo)
_tg_pats = _tg_omap["forbidden_thread_patterns"]
assert "harp-watchdog" in _tg_pats and "harp-serve-tcp" in _tg_pats
assert any(p.startswith("harp-sched-") for p in _tg_pats)
assert not any(_tg_fn.fnmatch("harp-serve-dispatch", p)
               for p in _tg_pats)
assert _tg_omap["spines"]["reqtrace"]["locked"] is True
_tg_vs = _tg.analyze_repo(_tg_repo)
_tg_kept, _tg_sup, _ = _tg_al.apply(_tg_vs, _tg_al.load())
assert _tg_kept == [] and {v.rule for v in _tg_sup} == {"HL403"}
_tg_cli = _tg_sp.run(
    [sys.executable, "-m", "harp_tpu", "lint", "--layer", "threads",
     "--json"], capture_output=True, text=True, cwd=_tg_repo)
assert _tg_cli.returncode == 0, _tg_cli.stdout + _tg_cli.stderr
_tg_row = _tg_json.loads(_tg_cli.stdout.strip().splitlines()[-1])
assert _tg_row["clean"] is True and _tg_row["stale_allowlist"] == 0

# (b) runtime twin armed around a real-socket serve under chaos
_tg_regs = (_tg_fr._READBACK_OBSERVERS, _tg_fr._DISPATCH_OBSERVERS,
            _tg_fr._H2D_OBSERVERS, _tg_fr._CKPT_WRITE_OBSERVERS)
_tg_before = [list(r) for r in _tg_regs]
_tg_orig_h2d = _tg_fr.record_h2d
_tg_rng = np.random.default_rng(40)
with _tg_tm.scope(True):
    _tg_state = _TG_ENGINES["kmeans"].synthetic_state(_tg_rng, k=8, d=16)
    _tg_srv = _TgServer("kmeans", state=_tg_state, mesh=mesh,
                        ladder=(1, 8), cache_dir=_tg_tmp.mkdtemp(),
                        budget_action="warn")
    _tg_srv.startup()
    _tg_inj = _TgInj(seed=0, fail={"dispatch": (2,)})
    with _tg_guard.armed() as _tg_g, _tg_inj.arm():
        _tg_fe = _TgFE(_tg_srv, port=0, max_retries=2).start_in_thread()
        try:
            _tg_s = _tg_sock.create_connection(
                ("127.0.0.1", _tg_fe.port), timeout=60)
            _tg_f = _tg_s.makefile("rw")
            _tg_xs = [_tg_rng.normal(size=(2, 16)).astype(np.float32)
                      for _ in range(6)]
            for _tg_i, _tg_x in enumerate(_tg_xs):
                _tg_f.write(_tg_json.dumps(
                    {"id": _tg_i, "x": _tg_x.tolist()}) + "\n")
            _tg_f.flush()
            _tg_got = [_tg_json.loads(_tg_f.readline()) for _ in range(6)]
            _tg_s.close()
        finally:
            _tg_fe.shutdown()
            _tg_fe.join(60)
        # scheduler workers run under statically-forbidden names
        _tg_names = []
        _TgSched(lambda _x: _tg_names.append(
            _tg_th.current_thread().name), n_threads=2).schedule([1, 2])
        assert all(any(_tg_fn.fnmatch(n, p) for p in _tg_pats)
                   for n in _tg_names)
        # (c) a forbidden name is caught at an observer site
        _tg_box = []

        def _tg_evil():
            try:
                _tg_fr.readback(jnp.zeros(2))
            except _tg_guard.ThreadOwnershipError as e:
                _tg_box.append(e)

        _tg_t = _tg_th.Thread(target=_tg_evil, name="harp-watchdog",
                              daemon=True)
        _tg_t.start()
        _tg_t.join(30)
        assert len(_tg_box) == 1 and "harp-watchdog" in str(_tg_box[0])
    assert _tg_inj.injected["dispatch"] == 1
    assert _tg_fe.runner.fault_retries >= 1
    assert _tg_g.checks > 0
    assert _tg_g.violations == [str(_tg_box[0])]  # ONLY the seeded one
    _tg_cent = _tg_state["centroids"]
    for _tg_r, _tg_x in zip(_tg_got, _tg_xs):
        _tg_ref = np.argmin(((_tg_x[:, None, :] - _tg_cent[None]) ** 2
                             ).sum(-1), 1)
        assert _tg_r["result"] == _tg_ref.tolist()
# (d) zero-install after disarm
assert [list(r) for r in _tg_regs] == _tg_before
assert _tg_fr.record_h2d is _tg_orig_h2d
assert _tg_guard.stats()["active"] is False

print(f"threadguard: map generated ({len(_tg_pats)} forbidden patterns, "
      f"{len(_tg_sup)} reviewed HL403), scoped lint clean, chaos serve "
      f"audited {_tg_g.checks} site crossings with 0 violations "
      f"(retry absorbed {_tg_fe.runner.fault_retries} injected fault), "
      f"forbidden-name readback caught, observers restored exactly")
print("DRIVE OK round-40")

# --- round-41 (PR 35): algo="pallas" keeps its count tables topic-major on
# the device from installation to read-out; every reader still gets [rows, K]
# and a sweep holds the counts of its own chain.  8 workers, the default
# config's path (interpret mode off the chip), public API only.
from harp_tpu.models import lda as _tm_lda

_tm_d, _tm_w = _tm_lda.synthetic_corpus(64, 128, 4, tokens_per_doc=24, seed=2)
_tm_m = _tm_lda.LDA(64, 128, _tm_lda.LDAConfig(
    n_topics=8, d_tile=8, w_tile=8, entry_cap=16), seed=5)
_tm_m.set_tokens(_tm_d, _tm_w)
_tm_ll0 = _tm_m.log_likelihood()
_tm_m.sample_epochs(3)
_tm_m.sample_epoch()
_tm_nwk = np.asarray(_tm_m.Nwk)
assert _tm_m._Nwk.shape == _tm_nwk.shape[::-1]         # stored topic-major
assert np.array_equal(np.asarray(_tm_m._Nwk), _tm_nwk.T)
_tm_doc, _tm_word, _tm_z = _tm_m.token_state()
_tm_want = np.zeros((128, 8), np.float32)
np.add.at(_tm_want, (_tm_word, _tm_z), 1)
assert np.array_equal(_tm_m.word_topic_table(), _tm_want)
assert np.array_equal(np.asarray(_tm_m.Nk), _tm_want.sum(0))
assert np.asarray(_tm_m.Ndk).sum() == _tm_m.n_tokens == len(_tm_d)
assert _tm_m.log_likelihood() > _tm_ll0
_tm_m.Nwk = _tm_nwk                                     # a host table back in
assert np.array_equal(np.asarray(_tm_m.Nwk), _tm_nwk)
print(f"lda topic-major storage: 4 sweeps on {_tm_m.mesh.num_workers} "
      f"workers, tables = the chain's counts, ll {_tm_ll0:.3f} -> "
      f"{_tm_m.log_likelihood():.3f}")
print("DRIVE OK round-41")

# --- round-42 (PR 39): the neighbour sum gathers only the slots its rows
# hold.  The CLI's default graph (100,000 vertices, mean degree 16, padded
# to 64) through the public pair on one worker, at the program's own tiles:
# the plan set_graph makes of the installed rows has a few segments near
# the mean, the five installed arrays are as they were, and the counts are
# those of the program without a plan (every row at the whole 64 slots).
_do_rng = np.random.default_rng(3)
_do_edges = _do_rng.integers(0, 100_000, (800_000, 2))
_do_one = WorkerMesh(jax.devices()[:1])
_do_c = SG.SubgraphCounter(SG.SubgraphConfig(
    template="u5-tree", n_trials=2, trial_chunk=2, max_degree=64, seed=4),
    _do_one)
assert _do_c.set_graph(_do_edges, 100_000) == 0
_do_slots = SG.plan_slots(_do_c.plan)
assert 1 < len(_do_c.plan) <= 8 and _do_c.plan[-1][1] == 100_000
assert all(w % 8 == 0 for _, _, w in _do_c.plan)
assert 1_600_000 <= _do_slots < 0.4 * 64 * 100_000
_do_nbr, _do_msk, *_do_tail = _do_c.installed()
assert _do_nbr.shape == _do_msk.shape == (100_000, 64) and len(_do_tail) == 3
assert (np.asarray(_do_msk).sum(1)
        == np.bincount(_do_edges.ravel(), minlength=100_000)).all()
_do_whole = SG.make_colorful_count_fn(_do_c.tpl, _do_c.k, _do_one,
                                      draw_trials=2)
_do_want = np.asarray(_do_whole(*_do_c.installed(),
                                (_do_c._key, np.int32(0))))
_do_got = _do_c.count_colorings()
np.testing.assert_allclose(_do_got, _do_want, rtol=1e-6)
print(f"subgraph degree order: {len(_do_c.plan)} segments "
      f"{[w for _, _, w in _do_c.plan]}, {_do_slots:,} of 6,400,000 padded "
      f"slots gathered, counts = the whole width's")
print("DRIVE OK round-42")

# --- round-43 (PR 41): the exact tail is summed as rows too.  The CLI's
# powerlaw graph (100,000 vertices, zipf-1.3 sources, padded to 64: a
# third of the entries past 64, one hub of tens of thousands) through the
# public pair on four workers, at the program's own tiles: the tail is
# staged as rows of at most 64 slots in the order of their entries, the
# plan is the widest over the workers, the five installed arrays are the
# flat ones still, and the counts are those of the program without a plan
# to float32 summation order: that program adds the hub's 200,000 entries
# to one float32 row one by one, the rows add 64 at a time and then 3,000
# row sums (they differ by 1.3e-5 here; 6e-8 x the entries is the bound).
_tr_rng = np.random.default_rng(5)
_tr_edges = np.stack([(_tr_rng.zipf(1.3, 800_000) - 1) % 100_000,
                      _tr_rng.integers(0, 100_000, 800_000)], 1)
_tr_mesh = WorkerMesh(jax.devices()[:4])
_tr_c = SG.SubgraphCounter(SG.SubgraphConfig(
    template="u5-tree", n_trials=2, trial_chunk=2, max_degree=64, seed=4),
    _tr_mesh)
_tr_tail = _tr_c.set_graph(_tr_edges, 100_000)
_tr_deg = np.bincount(_tr_edges.ravel(), minlength=100_000)
assert _tr_tail == np.maximum(_tr_deg - 64, 0).sum() > 400_000
_tr_plan = _tr_c.tail_plan
_tr_rows = sum(-(-int(d - 64) // 64) for d in _tr_deg if d > 64)
assert _tr_plan[0][0] == 0 and all(w % 8 == 0 for _, _, w in _tr_plan)
assert _tr_tail <= 4 * SG.plan_slots(_tr_plan)
_tr_t_nbr, _tr_t_own, _tr_t_msk = _tr_c._tail
assert _tr_t_nbr.shape == _tr_t_msk.shape == (4 * _tr_plan[-1][1], 64)
assert int(np.asarray(_tr_t_msk).sum()) == _tr_tail
assert int((np.asarray(_tr_t_msk).sum(1) > 0).sum()) == _tr_rows
_tr_nbr, _tr_msk, *_tr_flat = _tr_c.installed()
assert len(_tr_flat) == 3 and _tr_flat[0].ndim == 1
assert int(np.asarray(_tr_flat[2]).sum()) == _tr_tail
_tr_whole = SG.make_colorful_count_fn(_tr_c.tpl, _tr_c.k, _tr_mesh,
                                      draw_trials=2)
_tr_want = np.asarray(_tr_whole(_tr_nbr, _tr_msk, *_tr_flat,
                                (_tr_c._key, np.int32(0))))
_tr_got = _tr_c.count_colorings()
np.testing.assert_allclose(_tr_got, _tr_want, rtol=1e-4)
print(f"subgraph tail rows: {_tr_tail:,} entries past 64 as {_tr_rows:,} "
      f"rows, {len(_tr_plan)} segments {[w for _, _, w in _tr_plan]}, "
      f"{4 * SG.plan_slots(_tr_plan):,} slots gathered on 4 workers, "
      f"counts = the flat tail's")
print("DRIVE OK round-43")
