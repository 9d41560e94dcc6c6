"""CCD++ matrix factorization — coordinate descent with column allreduce.

Reference parity (SURVEY.md §3.4): Harp's ``edu.iu.ccd`` implements CCD++
(Yu et al.): rank coordinates get closed-form updates
``w_uf ← Σ_i R̂_ui h_if / (λ + Σ_i h_if²)`` (symmetrically for H), cycling
through coordinates, with the model exchanged through Harp's collective
machinery.

TPU-native design: users (and their ratings) are range-partitioned so each
worker holds **all** ratings of its users; the item factor matrix H is
replicated (items × rank is small).  One coordinate update is then exact:

- W column: per-user segment-sums over local ratings — no communication
  (user data is complete locally);
- H column: per-item partial (num, den) segment-sums over *global* item
  ids, combined with one ``allreduce`` of two [n_items] vectors — the
  TPU translation of Harp's per-coordinate model exchange, exact and
  cheaper than rotating full slices (O(items) on the wire per coordinate
  instead of O(items × rank)).

Per-rating predictions are maintained incrementally across coordinate
updates (the role of CCD++'s explicit residual array), so each epoch costs
O(nnz · rank) like the reference.  The epoch is one jitted SPMD program.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from harp_tpu.parallel import collective as C
from harp_tpu.parallel.mesh import WorkerMesh, current_mesh
from harp_tpu.utils import prng
from harp_tpu.utils.timing import device_sync


@dataclasses.dataclass
class CCDConfig:
    rank: int = 32
    reg: float = 0.1
    sweeps: int = 1  # coordinate cycles per epoch


def _epoch_device_fn(mesh: WorkerMesh, cfg: CCDConfig, n_items: int):
    def epoch(W, H, bu, bi, bv, bm):
        # bu: [B] user ids local to this worker's range; bi: [B] GLOBAL
        # item ids; H replicated [n_items, r].
        u_size = W.shape[0]
        pred = (jnp.take(W, bu, axis=0) * jnp.take(H, bi, axis=0)).sum(-1)

        def coord_body(st, f):
            W, H, pred = st
            wf = jnp.take(W[:, f], bu)          # [B]
            hf = jnp.take(H[:, f], bi)
            rhat = bm * (bv - pred + wf * hf)

            # exact W-column update (all of each user's ratings are local)
            num_u = jax.ops.segment_sum(rhat * hf, bu, num_segments=u_size)
            den_u = jax.ops.segment_sum(bm * hf * hf, bu, num_segments=u_size)
            w_new_col = jnp.where(den_u > 0,
                                  num_u / (cfg.reg + den_u), W[:, f])
            W = W.at[:, f].set(w_new_col)
            wf_new = jnp.take(w_new_col, bu)
            pred = pred + bm * (wf_new - wf) * hf

            # H-column update: partial per-item stats → allreduce (exact)
            rhat = bm * (bv - pred + wf_new * hf)
            num_i = jax.ops.segment_sum(rhat * wf_new, bi, num_segments=n_items)
            den_i = jax.ops.segment_sum(bm * wf_new * wf_new, bi,
                                        num_segments=n_items)
            num_i, den_i = C.allreduce((num_i, den_i))
            h_new_col = jnp.where(den_i > 0,
                                  num_i / (cfg.reg + den_i), H[:, f])
            H = H.at[:, f].set(h_new_col)
            hf_new = jnp.take(h_new_col, bi)
            pred = pred + bm * wf_new * (hf_new - hf)
            return (W, H, pred), None

        coords = jnp.tile(jnp.arange(cfg.rank), cfg.sweeps)
        (W, H, pred), _ = lax.scan(coord_body, (W, H, pred), coords)

        err = bm * (bv - pred)
        se, cnt = C.allreduce(((err * err).sum(), bm.sum()))
        return W, H, se, cnt

    return epoch


_IN_SPECS = lambda mesh: (mesh.spec(0), P(), mesh.spec(0), mesh.spec(0),  # noqa: E731
                          mesh.spec(0), mesh.spec(0))


def make_epoch_fn(mesh: WorkerMesh, cfg: CCDConfig, n_items: int):
    return jax.jit(mesh.shard_map(
        _epoch_device_fn(mesh, cfg, n_items),
        in_specs=_IN_SPECS(mesh),
        out_specs=(mesh.spec(0), P(), P(), P()),
    ))


def make_multi_epoch_fn(mesh: WorkerMesh, cfg: CCDConfig, n_items: int,
                        epochs: int):
    """``epochs`` coordinate-descent epochs as ONE device program — the
    same dispatch amortization as mfsgd/lda (one dispatch and one
    readback per run, not per epoch).  Returns per-epoch
    (se[epochs], cnt[epochs])."""
    inner = _epoch_device_fn(mesh, cfg, n_items)

    def many(W, H, bu, bi, bv, bm):
        def body(carry, _):
            W, H = carry
            W, H, se, cnt = inner(W, H, bu, bi, bv, bm)
            return (W, H), (se, cnt)

        (W, H), (ses, cnts) = lax.scan(body, (W, H), None, length=epochs)
        return W, H, ses, cnts

    return jax.jit(mesh.shard_map(
        many,
        in_specs=_IN_SPECS(mesh),
        out_specs=(mesh.spec(0), P(), P(), P()),
    ))


class CCD:
    """Host driver (the mapCollective residue for edu.iu.ccd)."""

    def __init__(self, n_users, n_items, cfg: CCDConfig | None = None,
                 mesh: WorkerMesh | None = None, seed=0):
        self.mesh = mesh or current_mesh()
        self.cfg = cfg or CCDConfig()
        self.n_users, self.n_items = n_users, n_items
        n = self.mesh.num_workers
        self.u_bound = -(-n_users // n)
        # raw key bits (utils.prng): a fresh seed must not cost a fresh
        # (remote) compile -- CLAUDE.md PRNGKey-specialization trap
        k1, k2 = jax.random.split(jnp.asarray(prng.key_bits(seed)))
        s = 1.0 / np.sqrt(self.cfg.rank)
        self.W = self.mesh.shard_array(np.asarray(
            jax.random.uniform(k1, (self.u_bound * n, self.cfg.rank),
                               jnp.float32, 0, s)), 0)
        self.H = jax.device_put(
            jax.random.uniform(k2, (n_items, self.cfg.rank), jnp.float32, 0, s),
            self.mesh.replicated())
        self._epoch_fn = make_epoch_fn(self.mesh, self.cfg, n_items)
        self._multi_fns: dict = {}
        self._blocks = None

    def set_ratings(self, users, items, vals):
        """Partition by user range; items stay global (H is replicated)."""
        n = self.mesh.num_workers
        users = np.asarray(users); items = np.asarray(items)
        vals = np.asarray(vals, np.float32)
        wid = users // self.u_bound
        order = np.argsort(wid, kind="stable")
        su, si, sv, sw = users[order], items[order], vals[order], wid[order]
        counts = np.bincount(sw, minlength=n)
        B = int(counts.max())
        bu = np.zeros((n, B), np.int32)
        bi = np.zeros((n, B), np.int32)
        bv = np.zeros((n, B), np.float32)
        bm = np.zeros((n, B), np.float32)
        starts = np.zeros(n, np.int64)
        starts[1:] = counts.cumsum()[:-1]
        for w in range(n):
            c = counts[w]
            sl = slice(starts[w], starts[w] + c)
            bu[w, :c] = su[sl] - w * self.u_bound
            bi[w, :c] = si[sl]
            bv[w, :c] = sv[sl]
            bm[w, :c] = 1.0
        self._blocks = tuple(self.mesh.shard_array(a.reshape(n * B) if a.ndim == 2 else a, 0)
                             for a in (bu, bi, bv, bm))
        self._multi_fns.clear()  # compiled executables bind to block shapes

    def train_epoch(self):
        if self._blocks is None:
            raise RuntimeError("call set_ratings() before train_epoch()")
        self.W, self.H, se, cnt = self._epoch_fn(self.W, self.H, *self._blocks)
        return float(np.sqrt(max(device_sync(se), 0.0) /
                             max(device_sync(cnt), 1.0)))

    def compile_epochs(self, epochs: int):
        """AOT-compile the ``epochs``-epoch program WITHOUT training (same
        contract as the mfsgd/lda drivers: benchmark warmup must not
        secretly run extra epochs)."""
        if self._blocks is None:
            raise RuntimeError("call set_ratings() before compile_epochs()")
        fn = self._multi_fns.get(epochs)
        if fn is None:
            jitted = make_multi_epoch_fn(
                self.mesh, self.cfg, self.n_items, epochs)
            fn = self._multi_fns[epochs] = jitted.lower(
                self.W, self.H, *self._blocks).compile()
        return fn

    def train_epochs(self, epochs: int):
        """Run ``epochs`` epochs as one device program; per-epoch RMSEs."""
        fn = self.compile_epochs(epochs)
        self.W, self.H, ses, cnts = fn(self.W, self.H, *self._blocks)
        stats = np.asarray(jnp.stack([ses, cnts]))  # one readback
        return [float(np.sqrt(max(s, 0.0) / max(c, 1.0)))
                for s, c in zip(stats[0], stats[1])]

    def fit(self, epochs: int, ckpt_dir: str | None = None, *,
            ckpt_every: int = 5, max_restarts: int = 3, fault=None):
        """Train with optional checkpoint/resume — the same recovery
        contract as MF-SGD/LDA/MLP ``fit`` (SURVEY.md §6): with
        ``ckpt_dir`` set, a crashed run (or a rerun pointing at the same
        dir) resumes from the latest saved epoch, and a checkpoint from a
        different rank/shape config refuses to restore.  Returns the
        per-epoch RMSEs this call actually ran."""
        from harp_tpu.utils.fault import factor_state_io, fit_epochs

        rmses: list[float] = []
        get_state, set_state = factor_state_io(self, {
            "W": lambda a: self.mesh.shard_array(a, 0),
            # device_put directly (no jnp.asarray detour, which can bake
            # the array into the program as a compile-time literal —
            # CLAUDE.md trap — and H can be hundreds of MB at graded scale)
            "H": lambda a: jax.device_put(a, self.mesh.replicated()),
        })
        fit_epochs(
            lambda: rmses.append(self.train_epoch()),
            get_state, set_state,
            epochs, ckpt_dir, ckpt_every=ckpt_every,
            max_restarts=max_restarts, fault=fault,
            phase="ccd.epochs",
        )
        return rmses


def benchmark(n_users=50_000, n_items=20_000, nnz=2_000_000, rank=32,
              epochs=2, mesh=None, seed=0):
    from harp_tpu.models.mfsgd import synthetic_ratings

    mesh = mesh or current_mesh()
    model = CCD(n_users, n_items, CCDConfig(rank=rank), mesh, seed)
    u, i, v = synthetic_ratings(n_users, n_items, nnz, seed=seed)
    model.set_ratings(u, i, v)
    r0 = model.train_epoch()     # warmup + single-epoch compile
    model.compile_epochs(epochs)  # AOT, off-clock, does NOT train
    t0 = time.perf_counter()
    r = model.train_epochs(epochs)[-1]
    dt = time.perf_counter() - t0
    return {"coord_updates_per_sec": nnz * rank * epochs / dt,
            "sec_per_epoch": dt / epochs, "rmse_first": r0, "rmse_final": r,
            "rank": rank, "nnz": nnz, "num_workers": mesh.num_workers}


def main(argv=None):
    import argparse

    p = argparse.ArgumentParser(description="harp-tpu CCD++ (edu.iu.ccd parity)")
    p.add_argument("--nnz", type=int, default=2_000_000)
    p.add_argument("--rank", type=int, default=32)
    p.add_argument("--epochs", type=int, default=2)
    args = p.parse_args(argv)
    from harp_tpu.utils.metrics import benchmark_json

    print(benchmark_json("ccd_cli", benchmark(
        nnz=args.nnz, rank=args.rank, epochs=args.epochs)))


if __name__ == "__main__":
    main()
