"""WDA-MDS — weighted multidimensional scaling by SMACOF, allreduce.

Reference parity (SURVEY.md §3.4): Harp's ``edu.iu.wdamds`` implements
WDA-SMACOF (Ruan & Qiu): embed N points in d dimensions from a (weighted)
dissimilarity matrix by iterating the SMACOF majorization
``X ← V⁺ B(X) X``, with the Δ matrix row-partitioned across workers and an
allreduce of the stress and of the updated coordinates every iteration.

TPU-native design: rows of Δ sharded over workers; one iteration is a
jitted program: local distance block [n_loc, N] (matmul-shaped), local
``B(X)·X`` row block, then ``allgather`` of the new coordinate block and
``allreduce`` of the stress.  Unweighted case uses the closed form
``V⁺ = (1/N)(I − 11ᵀ/N)`` folded into the update (standard SMACOF); the
weighted case runs a few CG steps against V, each one allreduce.
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
class MDSConfig:
    dim: int = 2
    iters: int = 50
    eps: float = 1e-9
    # weighted path: CG steps per SMACOF iteration solving V X = B(Z) Z
    # (the reference's DA-SMACOF uses the same inner CG; V is the weight
    # Laplacian, singular along translations — centering handles the null
    # space).  10 matched full solves to ~1e-5 relative on test problems.
    cg_iters: int = 10
    # the per-iteration coordinate exchange's wire (PR 12: last per-app
    # wire with no planner byte sheet, with svm — ROADMAP item).  The
    # unweighted Guttman update's X block exchange rides
    # collective.reshard blocked(0)→replicated; "bf16"/"int8" narrow
    # the [N, dim] payload per iteration at one rounding per hop.
    # UNWEIGHTED path only: the weighted CG solve applies V through its
    # exchanges, and a quantized operator inside CG breaks the residual
    # recurrence — that path stays exact by design.  The narrow wires
    # are judged on final_stress; default stays exact until a chip run
    # measures them.
    coord_wire: str = "exact"
    # dtype the n² dissimilarity matrix is STAGED in (PR 16: the profile
    # pass found the committed wdamds_cli wall (2026-08-01) bound by
    # that day's host→device staging rate, with Δ the dominant staged
    # buffer — flip candidate wdamds_delta_bf16; not re-measured on the
    # current host).  Arithmetic promotes back to f32 (only the
    # stored δ precision changes); final_stress gates the flip.  Default
    # stays f32 until a chip run measures it.
    delta_dtype: str = "f32"
    # Guttman-step schedule (PR 17), UNWEIGHTED path only: "xla" = the
    # reference body (D and ratio round-trip HBM between fusions);
    # "pallas" = the fused distance + B·X row-block kernel
    # (ops/wdamds_kernel.py) — D/ratio never leave VMEM, composing with
    # delta_dtype (a bf16-staged δ streams half the tile bytes).
    # perfmodel.presize picked a 128-row tile at the graded n=4096
    # shape (2026-08-06, predicted only — NOT yet measured; flip
    # candidate wdamds_dist_pallas gates on final_stress).  Falls back
    # to the XLA body when n_pad is not a 128 multiple; the weighted CG
    # path and the final stress pass always run XLA.
    algo: str = "xla"

    def __post_init__(self):
        if self.coord_wire not in ("exact", "bf16", "int8"):
            raise ValueError(f"coord_wire must be exact|bf16|int8, got "
                             f"{self.coord_wire!r}")
        if self.delta_dtype not in ("f32", "bf16"):
            raise ValueError(f"delta_dtype must be f32|bf16, got "
                             f"{self.delta_dtype!r}")
        if self.algo not in ("xla", "pallas"):
            raise ValueError(f"algo must be xla|pallas, got {self.algo!r}")


def smacof_arm(cfg: MDSConfig, n: int, num_workers: int) -> str:
    """The Guttman-step schedule :func:`make_smacof_fn` runs for ``n``
    points: the fused kernel needs the replicated axis (``n`` padded to
    a worker multiple) to be a whole number of lane registers, and hands
    any other shape to the XLA body (bitwise-equivalent in outcome,
    slower in schedule) rather than erroring.  The hand-off warns here
    (once per distinct message) and ``benchmark`` reports the name, so
    it is never silent."""
    n_pad = -(-n // num_workers) * num_workers
    if cfg.algo != "pallas" or n_pad % 128 == 0:
        return cfg.algo
    import warnings

    warnings.warn(f"wdamds: algo='pallas' needs n_pad % 128 == 0, got "
                  f"{n_pad} — running the XLA body",
                  RuntimeWarning, stacklevel=2)
    return "xla"


def make_smacof_fn(mesh: WorkerMesh, cfg: MDSConfig, n_pad: int):
    """One jitted run of SMACOF over the row-sharded Δ (unweighted)."""
    use_pallas = smacof_arm(cfg, n_pad, mesh.num_workers) == "pallas"
    if use_pallas:
        from harp_tpu.ops.pallas_compat import interpret_default

        interp = interpret_default()

    def run(delta_rows, row_mask, X0, n_real):
        # delta_rows: [n_loc, N]; row_mask: [n_loc] (0 for padded rows);
        # X0: [N, d] replicated; n_real: scalar count of live points.
        me0 = jax.lax.axis_index("workers") * delta_rows.shape[0]

        def dist_block(X):
            Xl = jax.lax.dynamic_slice_in_dim(X, me0, delta_rows.shape[0], 0)
            x2 = (Xl ** 2).sum(-1)[:, None]
            y2 = (X ** 2).sum(-1)[None, :]
            d2 = x2 - 2.0 * (Xl @ X.T) + y2
            return jnp.sqrt(jnp.maximum(d2, 0.0)), Xl

        def body(X, _):
            if use_pallas:
                from harp_tpu.ops import wdamds_kernel

                Xl = jax.lax.dynamic_slice_in_dim(
                    X, me0, delta_rows.shape[0], 0)
                Xl_new = wdamds_kernel.smacof_bx(
                    delta_rows, row_mask, Xl, X, n_real, eps=cfg.eps,
                    interpret=interp)
            else:
                D, Xl = dist_block(X)                       # [n_loc, N]
                live = row_mask[:, None] * jnp.where(
                    jnp.arange(n_pad)[None, :] < n_real, 1.0, 0.0)
                # B entries: -δ/d off-diagonal (guarded), diagonal fixes
                # row sum 0
                ratio = jnp.where(
                    D > cfg.eps, delta_rows / jnp.maximum(D, cfg.eps), 0.0)
                ratio = ratio * live
                off = -ratio
                diag_fix = ratio.sum(1)                 # so rows sum to zero
                BX_rows = off @ X + diag_fix[:, None] * Xl  # [n_loc, d]
                # Guttman transform (unweighted): X ← B(X) X / n_real
                Xl_new = BX_rows / jnp.maximum(n_real, 1.0)
            # coordinate exchange via the general reshard verb
            # (blocked→replicated = the same tiled all_gather the old
            # C.allgather emitted, bit-exact on the exact wire) so
            # cfg.coord_wire can narrow it and the planner prices the
            # site (analysis/drivers.py "wdamds.smacof")
            X_new = C.reshard(Xl_new, C.ShardSpec.blocked(0),
                              C.ShardSpec.replicated(),
                              wire=cfg.coord_wire)     # [N, d] everywhere
            return X_new, None

        X, _ = jax.lax.scan(body, X0, None, length=cfg.iters)
        # final stress: Σ_{i<j} (δ − d)²  (counted once via upper mask)
        D, _ = dist_block(X)
        live = row_mask[:, None] * jnp.where(
            jnp.arange(n_pad)[None, :] < n_real, 1.0, 0.0)
        upper = (jnp.arange(n_pad)[None, :] > (me0 + jnp.arange(delta_rows.shape[0]))[:, None])
        se = ((delta_rows - D) ** 2 * live * upper).sum()
        stress = C.allreduce(se)
        return X, stress

    return jax.jit(mesh.shard_map(
        run, in_specs=(mesh.spec(0), mesh.spec(0), P(), P()),
        out_specs=(P(), P()),
    ))


def make_wsmacof_fn(mesh: WorkerMesh, cfg: MDSConfig, n_pad: int):
    """Weighted SMACOF: ``X ← CG-solve(V, B(X) X)`` with the weight
    Laplacian V applied row-sharded (one allgather per CG step) — the
    WDA-SMACOF iteration proper (weights 0 drop a dissimilarity from the
    objective; the unweighted closed form is :func:`make_smacof_fn`)."""

    def run(delta_rows, w_rows, row_mask, X0, n_real):
        me0 = jax.lax.axis_index("workers") * delta_rows.shape[0]
        n_loc = delta_rows.shape[0]

        def live_mask():
            return row_mask[:, None] * jnp.where(
                jnp.arange(n_pad)[None, :] < n_real, 1.0, 0.0)

        def dist_block(X):
            Xl = jax.lax.dynamic_slice_in_dim(X, me0, n_loc, 0)
            x2 = (Xl ** 2).sum(-1)[:, None]
            y2 = (X ** 2).sum(-1)[None, :]
            d2 = x2 - 2.0 * (Xl @ X.T) + y2
            return jnp.sqrt(jnp.maximum(d2, 0.0)), Xl

        def center(X):
            # kill V's translation null space: center over live rows
            m = jnp.where(jnp.arange(n_pad) < n_real, 1.0, 0.0)[:, None]
            return (X - (X * m).sum(0) / jnp.maximum(n_real, 1.0)) * m

        def v_apply(Y, w_live, vdiag):
            # (V Y) rows = vdiag ⊙ Y_local − W_block @ Y, assembled globally
            Yl = jax.lax.dynamic_slice_in_dim(Y, me0, n_loc, 0)
            rows = vdiag[:, None] * Yl - w_live @ Y
            return C.allgather(rows)

        def body(X, _):
            D, Xl = dist_block(X)
            lm = live_mask()
            w_live = w_rows * lm
            vdiag = w_live.sum(1)
            ratio = jnp.where(D > cfg.eps,
                              w_live * delta_rows / jnp.maximum(D, cfg.eps),
                              0.0)
            bz_rows = ratio.sum(1)[:, None] * Xl - ratio @ X
            rhs = center(C.allgather(bz_rows))

            # CG on the replicated [N, dim] system (V is PSD on the
            # centered subspace; all vectors stay replicated, the only
            # distributed op is v_apply's row block + allgather)
            x = center(X)
            r = rhs - v_apply(x, w_live, vdiag)
            p = r
            rs = (r * r).sum()
            rs0 = rs
            rhs_sq = (rhs * rhs).sum()

            def cg_step(st, _):
                x, r, p, rs = st
                # freeze once converged: on the singular system (zero
                # weights enlarge V's null space beyond translations, and
                # can even disconnect the weight graph), iterating past
                # convergence divides f32 noise by f32 noise and explodes.
                # Two guards: a relative one vs the initial residual AND an
                # absolute floor vs |rhs|² (rs0 itself can already be f32
                # noise when the solve starts at convergence); plus a
                # curvature gate — on a direction with ~0/negative p·Vp the
                # step is meaningless, so take alpha = 0 and restart p ← r.
                vp = v_apply(p, w_live, vdiag)
                pvp = (p * vp).sum()
                step_ok = ((rs > 1e-12 * rs0 + 1e-30)
                           & (rs > 1e-10 * rhs_sq + 1e-30)
                           & (pvp > 1e-12 * (p * p).sum()))
                alpha = jnp.where(step_ok,
                                  rs / jnp.maximum(pvp, 1e-30), 0.0)
                x = x + alpha * p
                r = r - alpha * vp
                rs_new = (r * r).sum()
                beta = jnp.where(step_ok,
                                 rs_new / jnp.maximum(rs, 1e-30), 0.0)
                p = r + beta * p
                return (x, r, p, rs_new), None

            (x, _, _, _), _ = jax.lax.scan(
                cg_step, (x, r, p, rs), None, length=cfg.cg_iters)
            return center(x), None

        X, _ = jax.lax.scan(body, X0, None, length=cfg.iters)
        # weighted final stress: Σ_{i<j} w (δ − d)²
        D, _ = dist_block(X)
        lm = live_mask()
        upper = (jnp.arange(n_pad)[None, :]
                 > (me0 + jnp.arange(n_loc))[:, None])
        se = ((delta_rows - D) ** 2 * w_rows * lm * upper).sum()
        return X, C.allreduce(se)

    return jax.jit(mesh.shard_map(
        run, in_specs=(mesh.spec(0), mesh.spec(0), mesh.spec(0), P(), P()),
        out_specs=(P(), P()),
    ))


def mds(delta, cfg: MDSConfig | None = None, mesh: WorkerMesh | None = None,
        seed=0, weights=None):
    """Embed points from dissimilarity matrix delta [n, n] → [n, dim].

    ``weights`` (optional [n, n], symmetric, nonnegative): per-pair
    importance; 0 removes a dissimilarity from the objective (the "W" in
    WDA-MDS — e.g. for missing/unreliable δ entries).  None uses the
    unweighted closed-form V⁺."""
    mesh = mesh or current_mesh()
    cfg = cfg or MDSConfig()
    delta = np.asarray(delta, np.float32)
    n = delta.shape[0]
    nw = mesh.num_workers
    n_pad = -(-n // nw) * nw
    rows = np.zeros((n_pad, n_pad), np.float32)
    rows[:n, :n] = delta
    if cfg.delta_dtype == "bf16":
        # cast BEFORE sharding so the staged H2D bytes halve (the point
        # of the knob); jnp.bfloat16 is a real numpy dtype here, and the
        # in-program arithmetic promotes δ back to f32
        rows = rows.astype(jnp.bfloat16)
    mask = np.zeros(n_pad, np.float32)
    mask[:n] = 1.0
    X0 = np.random.default_rng(seed).normal(size=(n_pad, cfg.dim)).astype(np.float32)

    if weights is None:
        fn = make_smacof_fn(mesh, cfg, n_pad)
        X, stress = fn(mesh.shard_array(rows, 0), mesh.shard_array(mask, 0),
                       jax.device_put(jnp.asarray(X0), mesh.replicated()),
                       jnp.float32(n))
        return np.asarray(X)[:n], float(np.asarray(stress))
    w = np.asarray(weights, np.float32)
    if w.shape != delta.shape:
        raise ValueError(f"weights shape {w.shape} != delta shape {delta.shape}")
    if (w < 0).any():
        raise ValueError("weights must be nonnegative")
    w_rows = np.zeros((n_pad, n_pad), np.float32)
    w_rows[:n, :n] = w
    np.fill_diagonal(w_rows, 0.0)  # self-pairs never contribute
    fn = make_wsmacof_fn(mesh, cfg, n_pad)
    X, stress = fn(mesh.shard_array(rows, 0), mesh.shard_array(w_rows, 0),
                   mesh.shard_array(mask, 0),
                   jax.device_put(jnp.asarray(X0), mesh.replicated()),
                   jnp.float32(n))
    return np.asarray(X)[:n], float(np.asarray(stress))


def benchmark(n=4096, mesh=None, seed=0, coord_wire="exact",
              delta_dtype="f32", algo="xla"):
    rng = np.random.default_rng(seed)
    # 4-D points embedded into dim=3: genuinely LOSSY, so final_stress
    # is bounded away from 0 and the coord_wire flip gate's 2% relative
    # tolerance grades a real number — a perfectly-embeddable benchmark
    # (3-D into 3-D) converges to stress ~0 and a relative quality gate
    # against ~0 refuses every wire unconditionally (vacuous gate)
    pts = rng.normal(size=(n, 4)).astype(np.float32)
    delta = np.sqrt(((pts[:, None] - pts[None]) ** 2).sum(-1))
    cfg = MDSConfig(dim=3, iters=30, coord_wire=coord_wire,
                    delta_dtype=delta_dtype, algo=algo)
    mds(delta, cfg, mesh, seed)  # warmup/compile
    t0 = time.perf_counter()
    X, stress = mds(delta, cfg, mesh, seed)
    dt = time.perf_counter() - t0
    return {"sec_total": dt, "iters_per_sec": cfg.iters / dt,
            "final_stress": stress, "n": n, "coord_wire": coord_wire,
            "delta_dtype": delta_dtype, "algo": algo,
            # the schedule that actually ran (post-fallback)
            "arm": smacof_arm(cfg, n, (mesh or current_mesh()).num_workers)}


def main(argv=None):
    import argparse

    p = argparse.ArgumentParser(description="harp-tpu WDA-MDS (edu.iu.wdamds parity)")
    p.add_argument("--n", type=int, default=4096)
    p.add_argument("--algo", choices=("xla", "pallas"), default="xla",
                   help="Guttman-step schedule (pallas = the fused "
                        "distance + B·X kernel, flip candidate "
                        "wdamds_dist_pallas; unweighted path only)")
    args = p.parse_args(argv)
    from harp_tpu.utils.metrics import benchmark_json

    print(benchmark_json("wdamds_cli", benchmark(args.n, algo=args.algo)))


if __name__ == "__main__":
    main()
