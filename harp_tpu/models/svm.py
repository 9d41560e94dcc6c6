"""Parallel SVM — allgather of support vectors, iterate.

Reference parity (SURVEY.md §3.4): Harp's ``edu.iu.svm`` wraps libsvm:
each worker trains on (local shard ∪ current global support vectors),
the support vectors are ``allgather``ed, and the loop repeats until the
SV set stabilizes — an ensemble/cascade scheme that converges to a model
close to the centralized SVM.

TPU-native design: the local solver is a linear SVM trained by batched
sub-gradient descent on the hinge loss (Pegasos-style, jitted, MXU
matmuls).  "Support vectors" = margin violators (y·f(x) < 1), exchanged
by allgather with a fixed-size top-k cap so shapes stay static (the k
closest-to-margin violators stand in for libsvm's SV list).
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from harp_tpu.parallel import collective as C
from harp_tpu.parallel.mesh import WorkerMesh, current_mesh
from harp_tpu.utils.timing import device_sync


@dataclasses.dataclass
class SVMConfig:
    l2: float = 1e-3
    lr: float = 0.1
    inner_steps: int = 200    # pegasos steps per outer round
    outer_rounds: int = 5     # allgather-SV rounds
    sv_per_worker: int = 256  # top-k margin violators exchanged
    # the per-round SV exchange's wire (PR 12: the last per-app wire
    # with no planner byte sheet, with wdamds — ROADMAP item).  The
    # exchange rides collective.reshard blocked(0)→replicated, so
    # "bf16"/"int8" halve/quarter the [nw*k, d] SV rows per round at
    # ONE rounding per exchange (labels/masks ride exact — reshard
    # narrows float leaves only).  The narrow wires are judged on
    # train_acc; default stays exact until a chip run measures them.
    sv_wire: str = "exact"
    # dtype the [n, d] feature matrix is STAGED in (PR 16: the profile
    # pass found the committed svm_cli wall (2026-08-01) bound by that
    # day's host→device staging rate, so halving staged bytes was the
    # model's top-ranked lever — flip candidate svm_x_bf16; whether it
    # still is on the current host is not measured).  Dots promote back to f32, so
    # only the stored feature precision changes; train_acc gates the
    # flip.  Default stays f32 until a chip run measures it.
    x_dtype: str = "f32"
    # inner-solve schedule (PR 17): "xla" = the 2-pass _pegasos scan;
    # "pallas" = the fused single-pass hinge-gradient kernel
    # (ops/svm_kernel.py) — one feature read per step instead of two,
    # composing with x_dtype (a bf16-staged x streams half the tile
    # bytes through the same kernel).  perfmodel.presize picked an
    # 8192-sample tile at the graded 500k×128 shape (2026-08-06,
    # predicted only — NOT yet measured; flip candidate
    # svm_kernel_pallas gates on train_acc).  Dense rows only: the
    # ELL sparse path always solves via XLA.
    algo: str = "xla"

    def __post_init__(self):
        if self.sv_wire not in ("exact", "bf16", "int8"):
            raise ValueError(
                f"sv_wire must be exact|bf16|int8, got {self.sv_wire!r}")
        if self.x_dtype not in ("f32", "bf16"):
            raise ValueError(
                f"x_dtype must be f32|bf16, got {self.x_dtype!r}")
        if self.algo not in ("xla", "pallas"):
            raise ValueError(
                f"algo must be xla|pallas, got {self.algo!r}")


def _pegasos(w, b, x, y, sample_w, cfg: SVMConfig):
    """Batched hinge-loss subgradient descent on (x, y) with weights."""

    def step(carry, t):
        w, b = carry
        margin = y * (x @ w + b)
        viol = (margin < 1.0).astype(jnp.float32) * sample_w
        lr = cfg.lr / (1.0 + 0.01 * t)
        gw = cfg.l2 * w - (viol * y) @ x / jnp.maximum(sample_w.sum(), 1.0)
        gb = -(viol * y).sum() / jnp.maximum(sample_w.sum(), 1.0)
        return (w - lr * gw, b - lr * gb), None

    (w, b), _ = jax.lax.scan(step, (w, b), jnp.arange(cfg.inner_steps))
    return w, b


def _pegasos_pallas(w, b, x, y, sample_w, cfg: SVMConfig):
    """:func:`_pegasos` on the fused Pallas kernel (ops/svm_kernel.py):
    the margin pass and the gradient contraction read each feature tile
    ONCE per step instead of XLA's two passes.  Same update sequence —
    matches the XLA arm to accumulation-order rounding (tests/
    test_svm_kernel.py pins it at rtol 1e-4).  Padding (d → 128-lane
    multiple, n → tile multiple with sw = 0) is invisible: pad features
    start at w = 0 and receive zero gradient, pad samples carry zero
    weight."""
    from harp_tpu.ops import svm_kernel
    from harp_tpu.ops.pallas_compat import interpret_default

    n, d = x.shape
    interp = interpret_default()
    dp = 128 * -(-d // 128)
    xsize = jnp.dtype(x.dtype).itemsize
    tn = svm_kernel.pick_tile(n, d, xsize)
    n_pad = tn * -(-n // tn)
    # transpose ONCE per outer round (x is scan-invariant inside the
    # inner solve); the kernel streams [dp, tn] tiles off this layout
    xT = jnp.pad(x, ((0, n_pad - n), (0, dp - d))).T        # [dp, n_pad]
    yp = jnp.pad(y, (0, n_pad - n))
    swp = jnp.pad(sample_w, (0, n_pad - n))
    denom = jnp.maximum(sample_w.sum(), 1.0)
    cd = jnp.bfloat16 if x.dtype == jnp.bfloat16 else jnp.float32
    wp0 = jnp.pad(w, (0, dp - d))

    def step(carry, t):
        wp, b = carry
        gw, gs = svm_kernel.pegasos_grad(
            wp, b, xT, yp, swp, tn=tn, compute_dtype=cd, interpret=interp)
        lr = cfg.lr / (1.0 + 0.01 * t)
        # identical to _pegasos: gw here is Σ coef·x (un-normalised) and
        # gs = Σ coef = −denom·gb
        wp = wp - lr * (cfg.l2 * wp - gw / denom)
        b = b + lr * gs / denom
        return (wp, b), None

    (wp, b), _ = jax.lax.scan(step, (wp0, b), jnp.arange(cfg.inner_steps))
    return wp[:d], b


def _pegasos_ell(w, b, ids, vals, msk, y, sample_w, cfg: SVMConfig):
    """Hinge subgradient descent on padded-ELL sparse rows.

    ids/vals/msk: [n, width] (see ``csr_to_ell``) — f(x) is a gather-dot,
    the gradient a segment-sum scatter; memory stays O(nnz), never O(n·d).
    """
    d = w.shape[0]

    def step(carry, t):
        w, b = carry
        fx = (vals * jnp.take(w, ids) * msk).sum(1) + b
        margin = y * fx
        viol = (margin < 1.0).astype(jnp.float32) * sample_w
        denom = jnp.maximum(sample_w.sum(), 1.0)
        coef = (viol * y) / denom                     # [n]
        gw_data = jax.ops.segment_sum(
            (coef[:, None] * vals * msk).ravel(), ids.ravel(), num_segments=d)
        lr = cfg.lr / (1.0 + 0.01 * t)
        return (w - lr * (cfg.l2 * w - gw_data), b + lr * coef.sum()), None

    (w, b), _ = jax.lax.scan(step, (w, b), jnp.arange(cfg.inner_steps))
    return w, b


def _make_train_prog(cfg: SVMConfig, d: int, k: int, sparse: bool):
    """Shared outer loop: local solve → top-k margin violators → allgather.

    ``sparse`` switches the row representation: dense [n, d] x vs ELL
    (ids, vals, msk) triples.  The SV exchange gathers rows the same way
    in both (fixed-size top-k keeps shapes static).
    """

    def prog(rows, y, sample_w):
        w = jnp.zeros((d,), jnp.float32)
        b = jnp.float32(0.0)
        nw = jax.lax.axis_size("workers")

        def fwd(rows, w, b):
            if sparse:
                ids, vals, msk = rows
                return (vals * jnp.take(w, ids) * msk).sum(1) + b
            return rows @ w + b

        def take_rows(rows, idx):
            return jax.tree.map(lambda a: a[idx], rows)

        sv_rows = jax.tree.map(
            lambda a: jnp.zeros((nw * k,) + a.shape[1:], a.dtype), rows)
        sv_y = jnp.zeros((nw * k,), jnp.float32)
        sv_m = jnp.zeros((nw * k,), jnp.float32)

        def round_body(carry, _):
            w, b, sv_rows, sv_y, sv_m = carry
            arows = jax.tree.map(
                lambda a, s: jnp.concatenate([a, s], 0), rows, sv_rows)
            ay = jnp.concatenate([y, sv_y], 0)
            am = jnp.concatenate([sample_w, sv_m], 0)
            if sparse:
                w, b = _pegasos_ell(w, b, *arows, ay, am, cfg)
            elif cfg.algo == "pallas":
                w, b = _pegasos_pallas(w, b, arows, ay, am, cfg)
            else:
                w, b = _pegasos(w, b, arows, ay, am, cfg)
            # margin violators of the LOCAL shard → top-k by closeness
            score = jnp.where(sample_w > 0, y * fwd(rows, w, b), jnp.inf)
            _, idx = jax.lax.top_k(-score, k)       # most-violating k
            cand_m = (score[idx] < 1.0).astype(jnp.float32)
            # Harp step: exchange the SV lists — the general reshard
            # verb (blocked→replicated lowers to the same tiled
            # all_gather the old C.allgather call emitted, bit-exact on
            # the exact wire), so cfg.sv_wire can narrow the rows and
            # the planner prices this site off its byte sheet
            # (analysis/drivers.py "svm.train")
            sv_rows, sv_y, sv_m = C.reshard(
                (take_rows(rows, idx), y[idx], cand_m),
                C.ShardSpec.blocked(0), C.ShardSpec.replicated(),
                wire=cfg.sv_wire)
            return (w, b, sv_rows, sv_y, sv_m), None

        (w, b, *_), _ = jax.lax.scan(
            round_body, (w, b, sv_rows, sv_y, sv_m), None,
            length=cfg.outer_rounds)
        # final consensus: average the (identical-input-fed) models — with
        # gathered SVs shared, worker models already agree up to local data;
        # averaging matches Harp's final ensemble vote in expectation
        w = C.allreduce(w, C.Combiner.AVG)
        b = C.allreduce(b, C.Combiner.AVG)
        return w, b

    return prog


def make_train_fn(mesh: WorkerMesh, cfg: SVMConfig, d: int, n_loc: int):
    k = min(cfg.sv_per_worker, n_loc)  # top_k needs k <= local shard size
    prog = _make_train_prog(cfg, d, k, sparse=False)
    return jax.jit(mesh.shard_map(
        prog, in_specs=(mesh.spec(0),) * 3, out_specs=(P(), P()),
    ))


def make_train_fn_ell(mesh: WorkerMesh, cfg: SVMConfig, d: int, n_loc: int):
    k = min(cfg.sv_per_worker, n_loc)
    prog = _make_train_prog(cfg, d, k, sparse=True)
    return jax.jit(mesh.shard_map(
        prog,
        in_specs=((mesh.spec(0),) * 3, mesh.spec(0), mesh.spec(0)),
        out_specs=(P(), P()),
    ))


class SVM:
    """Host driver (the mapCollective residue for edu.iu.svm). Binary, y∈{-1,+1}."""

    def __init__(self, cfg: SVMConfig | None = None, mesh: WorkerMesh | None = None):
        self.mesh = mesh or current_mesh()
        self.cfg = cfg or SVMConfig()
        self.w = None
        self.b = None

    def fit(self, x, y):
        from harp_tpu.models.stats import _shard_rows

        x = np.asarray(x, np.float32)
        y = np.asarray(y, np.float32)
        assert set(np.unique(y)) <= {-1.0, 1.0}, "labels must be ±1"
        if self.cfg.x_dtype == "bf16":
            # cast BEFORE sharding so the staged H2D bytes halve (the
            # point of the knob — the wall is the staging wire, not the
            # MXU); jnp.bfloat16 is a real numpy dtype here
            x = x.astype(jnp.bfloat16)
        # padded rows get y=0 with weight 0: zero hinge gradient, never
        # selected as SVs (their margin is masked to +inf)
        xd, yd, sample_wd = _shard_rows(self.mesh, x, y)
        n_loc = xd.shape[0] // self.mesh.num_workers
        fn = make_train_fn(self.mesh, self.cfg, x.shape[1], n_loc)
        w, b = fn(xd, yd, sample_wd)
        self.w, self.b = np.asarray(w), float(np.asarray(b))
        return self

    def fit_sparse(self, ids, vals, mask, y, n_features: int):
        """Train on padded-ELL sparse rows (``csr_to_ell`` output) —
        memory stays O(nnz) end to end, never densifying [n, d]."""
        from harp_tpu.models.stats import _shard_rows

        y = np.asarray(y, np.float32)
        assert set(np.unique(y)) <= {-1.0, 1.0}, "labels must be ±1"
        idd, vd, md, yd, sample_wd = _shard_rows(self.mesh, ids, vals, mask, y)
        n_loc = yd.shape[0] // self.mesh.num_workers
        fn = make_train_fn_ell(self.mesh, self.cfg, n_features, n_loc)
        w, b = fn((idd, vd, md), yd, sample_wd)
        self.w, self.b = np.asarray(w), float(np.asarray(b))
        return self

    def decision_function(self, x):
        return np.asarray(x, np.float32) @ self.w + self.b

    def predict(self, x):
        return np.sign(self.decision_function(x))

    def accuracy(self, x, y):
        return float((self.predict(x) == np.asarray(y)).mean())


def benchmark(n=500_000, d=128, mesh=None, seed=0, sv_wire="exact",
              x_dtype="f32", algo="xla"):
    rng = np.random.default_rng(seed)
    true_w = rng.normal(size=d).astype(np.float32)
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = np.sign(x @ true_w + 0.1 * rng.normal(size=n)).astype(np.float32)
    model = SVM(SVMConfig(sv_wire=sv_wire, x_dtype=x_dtype, algo=algo),
                mesh=mesh)
    model.fit(x, y)  # warmup: compile at full shape
    t0 = time.perf_counter()
    model.fit(x, y)
    dt = time.perf_counter() - t0
    return {"fit_sec": dt, "samples_per_sec": n / dt,
            "train_acc": model.accuracy(x[:50_000], y[:50_000]),
            "n": n, "d": d, "sv_wire": sv_wire, "x_dtype": x_dtype,
            "algo": algo}


def main(argv=None):
    import argparse

    from harp_tpu.utils.metrics import benchmark_json

    p = argparse.ArgumentParser(description="harp-tpu SVM (edu.iu.svm parity)")
    p.add_argument("--n", type=int, default=500_000)
    p.add_argument("--d", type=int, default=128)
    p.add_argument("--libsvm", default=None, metavar="FILE",
                   help="train on a libsvm-format file (the reference's "
                        "native input format) instead of synthetic data")
    p.add_argument("--zero-based", action="store_true",
                   help="file indices start at 0 (default: 1-based)")
    p.add_argument("--algo", choices=("xla", "pallas"), default="xla",
                   help="inner-solve schedule (pallas = the fused "
                        "hinge-gradient kernel, flip candidate "
                        "svm_kernel_pallas; dense rows only)")
    args = p.parse_args(argv)
    if args.libsvm:
        from harp_tpu.native.datasource import csr_to_ell, load_libsvm

        try:
            labels, indptr, indices, values, nf = load_libsvm(
                args.libsvm, zero_based=args.zero_based)
        except ValueError as e:  # e.g. a 0-based file without --zero-based
            raise SystemExit(str(e))
        classes = np.unique(labels)
        if len(classes) != 2:
            raise SystemExit(
                f"{args.libsvm}: need exactly 2 label values, got "
                f"{classes.tolist()} (binary SVM)")
        y = np.where(labels == classes[1], 1.0, -1.0).astype(np.float32)
        ids, vals, mask = csr_to_ell(indptr, indices, values)
        model = SVM().fit_sparse(ids, vals, mask, y, nf)
        fx = (vals * model.w[ids] * mask).sum(1) + model.b
        acc = float((np.sign(fx) == y).mean())
        print(benchmark_json("svm_fit_cli", {"file": args.libsvm, "n": len(labels), "d": nf,
               "classes": classes.tolist(), "train_acc": acc}))
    else:
        print(benchmark_json("svm_cli",
                             benchmark(args.n, args.d, algo=args.algo)))


if __name__ == "__main__":
    main()
