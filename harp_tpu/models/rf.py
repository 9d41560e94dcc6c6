"""Random Forest — graded config #5b: data-parallel ensemble, allgather.

Reference parity (SURVEY.md §3.4): Harp's ``edu.iu.rf`` trains decision
trees on bootstrap samples of each worker's local shard (javaml/weka-style
sequential tree induction), then ``allgather``s the trees so every worker
holds the full forest; prediction is majority vote.

TPU-native design: tree induction is re-formulated as **vectorized
histogram-based level-wise growth** (the XGBoost/LightGBM layout, which is
also how a systolic machine wants it):

- features are quantile-binned once (static [n, f] uint8 bin ids);
- a whole *level* of every tree grows at once: per (tree, node, feature,
  bin, class) label histograms via one-hot matmuls on the MXU, Gini
  impurity from cumulative histogram sums, best (feature, threshold)
  per node by argmin;
- all trees of a worker grow in lockstep via ``vmap`` over the tree axis
  (bootstrap sampling = per-tree example-weight vectors, so "sampling"
  is a weighted histogram, not a gather);
- the forest "allgather" is the same verb apps always use; prediction
  routes every sample down all trees with gather-free arithmetic on the
  dense node arrays.

The per-worker forest shards stay local until ``allgather_forest`` — the
same lifecycle as Harp's local tree lists.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from harp_tpu.ingest import IngestPipeline
from harp_tpu.parallel import collective as C
from harp_tpu.parallel.mesh import WorkerMesh, current_mesh
from harp_tpu.utils import flightrec, telemetry


@dataclasses.dataclass
class RFConfig:
    n_trees: int = 32          # total across workers (Harp: trees per worker × N)
    max_depth: int = 6
    n_bins: int = 32
    n_classes: int = 2
    feature_fraction: float = 1.0  # per-(tree,node) feature subsampling
    # "dense" = one-hot int8 MXU matmul histogram (the default since
    # 2026-07-30 — XLA scatter of small rows runs ~25 GB/s on v5e, see
    # CLAUDE.md); "scatter" = the scatter-add arm kept for the A/B
    # (bit-identical int32 counts, tests/test_rf.py).  PR 16 flip
    # candidate pair: rf_dense_hist vs rf_scatter_hist.  "pallas"
    # (PR 17) = the same dense math as a real kernel with on-chip bin
    # accumulation (ops/rf_kernel.py) — the per-level [n, node·C]
    # one-hot never round-trips HBM; counts stay BIT-identical to
    # "dense".  perfmodel.presize picked a 2048-sample tile at the
    # graded 200k×64 shape (2026-08-06, predicted only — NOT yet
    # measured; flip candidate rf_hist_pallas).  Falls back to "dense"
    # when f·n_bins is not a 128 multiple.
    hist_algo: str = "dense"
    seed: int = 0

    def __post_init__(self):
        if self.hist_algo not in ("dense", "scatter", "pallas"):
            raise ValueError(
                f"hist_algo must be 'dense', 'scatter' or 'pallas', got "
                f"{self.hist_algo!r}")


def hist_arm(cfg: RFConfig, n_features: int) -> str:
    """The histogram formulation :func:`_grow_level` runs: the kernel
    hands feature·bin widths it cannot tile (not a 128 multiple) to the
    dense arm; the hand-off warns here (once per distinct message) and
    ``benchmark`` reports the name, so it is never silent."""
    if cfg.hist_algo != "pallas" or (n_features * cfg.n_bins) % 128 == 0:
        return cfg.hist_algo
    import warnings

    warnings.warn("rf: hist_algo='pallas' needs features·n_bins % 128 == 0, "
                  f"got {n_features}·{cfg.n_bins} — running the dense arm",
                  RuntimeWarning, stacklevel=2)
    return "dense"


def quantile_bins(x, n_bins):
    """Per-feature quantile bin edges [f, n_bins-1] from a sample."""
    qs = np.linspace(0, 1, n_bins + 1)[1:-1]
    return np.quantile(np.asarray(x), qs, axis=0).T.astype(np.float32)


def binize(x, edges):
    """x [n, f] → bin ids [n, f] int32 via the precomputed edges.

    Per-feature searchsorted keeps the transient at [n] (a broadcast
    comparison would materialize [n, f, n_bins-1] — hundreds of MB at
    benchmark scale).
    """
    x = np.asarray(x)
    out = np.empty(x.shape, np.int32)
    for j in range(x.shape[1]):
        out[:, j] = np.searchsorted(edges[j], x[:, j], side="left")
    return out


def binize_chunked(x, edges, chunk_rows=65_536, prefetch=2):
    """:func:`binize` through the shared ingest pipeline (PR 8):
    bit-identical output (per-row searchsorted is row-independent) with
    the work chunked — the read stage hands zero-copy row views and,
    with ``prefetch >= 2``, chunk j+1 bins on a worker thread while
    chunk j's result writes back.  Each chunk's output slice is
    disjoint, so the side-effecting prep stage is thread-safe by
    construction."""
    x = np.asarray(x)
    n = x.shape[0]
    out = np.empty(x.shape, np.int32)
    n_chunks = max(1, -(-n // chunk_rows))

    def read(j):
        lo = j * chunk_rows
        return lo, x[lo:lo + chunk_rows]

    def prep(t):
        lo, blk = t
        out[lo:lo + blk.shape[0]] = binize(blk, edges)

    with IngestPipeline(read, prep, None, depth=max(1, prefetch),
                        tag="rf.binize") as pipe:
        for _ in pipe.stream(n_chunks):
            pass
    return out


def bins_onehot(bins, n_bins):
    """Precompute the flattened bin one-hot BO int8 [n, f*B] — shared by
    every tree and level (bins never change during a fit), so the big
    one-hot is built ONCE instead of per (tree, level, feature).
    Built per feature column to avoid a [n, f, f*B] transient."""
    n, f = bins.shape

    def one_col(bins_f):
        return jax.nn.one_hot(bins_f, n_bins, dtype=jnp.int8)  # [n, B]

    cols = lax.map(one_col, bins.T)                 # [f, n, B]
    return jnp.moveaxis(cols, 0, 1).reshape(n, f * n_bins)


def _grow_level(BO, bins, y, weights, node_id, level, feat_mask, cfg):
    """Grow one level of one tree: returns (split_feat, split_bin,
    new_node_id) for the 2^level nodes of this level.

    BO: [n, f*B] int8 precomputed bin one-hots (see :func:`bins_onehot`);
    y: [n] int32 labels; weights: [n] bootstrap weights (small ints);
    node_id: [n] current node of each sample (within this level's frame);
    feat_mask: [f] 0/1 feature subsample for this tree.

    The full histogram[node, f, bin, class] is ONE int8 matmul: the lhs
    one-hot folds (node, class, weight) into a single [n, nodeC] int8
    matrix (Poisson(1) weights are tiny ints, exact in int8; counts
    accumulate in int32, exact — asserted against a numpy scatter-add
    histogram in tests/test_rf.py).  Compared to the previous per-feature
    f32 outer-product formulation this removes the [n, B*C] transient per
    (tree, level, feature), the fit's dominant HBM traffic by op-level
    accounting (~205 GB/fit at the graded 200k×64 32-tree config vs ~9 GB
    of BO reads).  TPU wall-clock: 8.80 trees/s (1× v5e, 2026-08-01,
    BASELINE.md; the prior formulation measured 7.07 on 2026-07-29).
    """
    n = BO.shape[0]
    C_ = cfg.n_classes
    B = cfg.n_bins
    f = BO.shape[1] // B
    n_nodes = 2 ** level

    arm = hist_arm(cfg, f)
    if arm == "scatter":
        # the 25 GB/s-wall arm (A/B partner of the dense default): one
        # scatter-add of weight w at [node*C + y, feat*B + bin] per
        # (sample, feature) — bit-identical int32 counts by construction
        w = jnp.clip(weights, 0, 127).astype(jnp.int32)
        rows = node_id * C_ + y                          # [n]
        cols = jnp.arange(f, dtype=jnp.int32)[None, :] * B + bins  # [n, f]
        hist = jnp.zeros((n_nodes * C_, f * B), jnp.int32).at[
            jnp.broadcast_to(rows[:, None], cols.shape), cols].add(
            jnp.broadcast_to(w[:, None], cols.shape))
    elif arm == "pallas":
        # the dense arm as a real kernel (ops/rf_kernel.py): same int8
        # MXU products accumulated in int32 on-chip — bit-identical
        # counts, so the Gini/split/route below sees the same numbers.
        # The kernel runs under the tree vmap (batching adds a leading
        # grid dimension).
        from harp_tpu.ops import rf_kernel
        from harp_tpu.ops.pallas_compat import interpret_default

        hist = rf_kernel.hist_bins(
            BO, node_id * C_ + y, jnp.clip(weights, 0, 127).astype(jnp.int32),
            n_nodes * C_, interpret=interpret_default())
    else:
        nc = jax.nn.one_hot(node_id * C_ + y, n_nodes * C_, dtype=jnp.int8)
        nc = nc * jnp.clip(weights, 0, 127).astype(jnp.int8)[:, None]
        hist = lax.dot_general(
            nc, BO, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )                                           # [node*C, f*B]
    hist = hist.reshape(n_nodes, C_, f, B).transpose(0, 2, 3, 1)
    hist = hist.astype(jnp.float32)                 # [n_nodes, f, B, C]

    # left counts for threshold "≤ bin b" = cumsum over bins (exclusive of
    # nothing: splitting at b sends bins ≤ b left)
    left = jnp.cumsum(hist, axis=2)              # [node, f, B, C]
    total = left[:, :, -1:, :]                   # [node, f, 1, C]
    right = total - left

    def gini_side(cnt):  # [.., C] → impurity * size
        sz = cnt.sum(-1)
        p = cnt / jnp.maximum(sz[..., None], 1e-9)
        return sz * (1.0 - (p * p).sum(-1))

    score = gini_side(left) + gini_side(right)   # [node, f, B]
    # forbid: last bin (empty right), masked-out features
    score = score.at[:, :, -1].set(jnp.inf)
    score = jnp.where(feat_mask[None, :, None] > 0, score, jnp.inf)

    flat = score.reshape(n_nodes, f * B)
    best = jnp.argmin(flat, axis=1)
    split_feat = (best // B).astype(jnp.int32)           # [node]
    split_bin = (best % B).astype(jnp.int32)             # [node]

    # route samples: go right if bin > split_bin of their node
    sf = split_feat[node_id]                              # [n]
    sb = split_bin[node_id]
    sample_bin = jnp.take_along_axis(bins, sf[:, None], axis=1)[:, 0]
    go_right = (sample_bin > sb).astype(jnp.int32)
    new_node_id = node_id * 2 + go_right
    return split_feat, split_bin, new_node_id


def _leaf_stats(y_onehot, weights, node_id, n_leaves):
    node_oh = jax.nn.one_hot(node_id, n_leaves, dtype=jnp.float32) * weights[:, None]
    hist = node_oh.T @ y_onehot            # [leaves, C]
    return jnp.argmax(hist, axis=1).astype(jnp.int32)


def make_train_fn(mesh: WorkerMesh, cfg: RFConfig, n_features: int):
    """Compile per-worker forest training (trees_per_worker via vmap)."""

    def train_one_tree(BO, bins, y, y_onehot, key):
        k1, k2 = jax.random.split(key)
        n = bins.shape[0]
        # bootstrap: Poisson(1) weights ≈ sampling with replacement
        weights = jax.random.poisson(k1, 1.0, (n,)).astype(jnp.float32)
        feat_mask = (
            jax.random.uniform(k2, (n_features,)) < cfg.feature_fraction
        ).astype(jnp.float32)
        # never mask every feature out
        feat_mask = jnp.where(feat_mask.sum() > 0, feat_mask,
                              jnp.ones_like(feat_mask))

        node_id = jnp.zeros((n,), jnp.int32)
        feats, bins_out = [], []
        for level in range(cfg.max_depth):
            sf, sb, node_id = _grow_level(
                BO, bins, y, weights, node_id, level, feat_mask, cfg
            )
            feats.append(sf)
            bins_out.append(sb)
        leaves = _leaf_stats(y_onehot, weights, node_id, 2 ** cfg.max_depth)
        # pack level arrays into flat [2^depth - 1] heap order
        return (
            jnp.concatenate(feats),      # node k at offset 2^l - 1 + k
            jnp.concatenate(bins_out),
            leaves,
        )

    def train_shard(bins, y, keys):
        y_onehot = jax.nn.one_hot(y, cfg.n_classes, dtype=jnp.float32)
        BO = bins_onehot(bins, cfg.n_bins)  # shared by all trees/levels
        return jax.vmap(
            lambda k: train_one_tree(BO, bins, y, y_onehot, k))(keys)

    def prog(bins, y, keys):
        feats, thresh, leaves = train_shard(bins, y, keys[0])
        # Harp step: allgather local trees → full forest everywhere
        return C.allgather((feats, thresh, leaves))

    return jax.jit(
        mesh.shard_map(
            prog,
            in_specs=(mesh.spec(0), mesh.spec(0), mesh.spec(0)),
            out_specs=P(),
        )
    )


def predict_forest(forest, bins, max_depth, n_classes):
    """Majority vote over all trees. bins: [n, f] int32 (same binning)."""
    feats, thresh, leaves = forest  # [T, 2^d - 1], [T, 2^d - 1], [T, 2^d]

    def one_tree(tf, tb, tl):
        n = bins.shape[0]
        node = jnp.zeros((n,), jnp.int32)  # level-frame index
        offset = 0
        for level in range(max_depth):
            heap = offset + node
            sf = tf[heap]
            sb = tb[heap]
            sample_bin = jnp.take_along_axis(bins, sf[:, None], axis=1)[:, 0]
            node = node * 2 + (sample_bin > sb).astype(jnp.int32)
            offset += 2 ** level
        return tl[node]  # [n]

    votes = jax.vmap(one_tree)(feats, thresh, leaves)  # [T, n]
    votes_oh = jax.nn.one_hot(votes, n_classes, dtype=jnp.float32)
    return jnp.argmax(votes_oh.sum(0), axis=-1)


class RandomForest:
    """Host driver (the mapCollective residue for edu.iu.rf)."""

    def __init__(self, cfg: RFConfig | None = None, mesh: WorkerMesh | None = None):
        self.mesh = mesh or current_mesh()
        self.cfg = cfg or RFConfig()
        nw = self.mesh.num_workers
        if self.cfg.n_trees % nw:
            raise ValueError(
                f"n_trees={self.cfg.n_trees} must be divisible by {nw} workers")
        self.trees_per_worker = self.cfg.n_trees // nw
        self.forest = None
        self.edges = None
        self._predict_fn = None
        self._train_fn = None

    def fit(self, x, y):
        cfg = self.cfg
        nw = self.mesh.num_workers
        x, y = np.asarray(x, np.float32), np.asarray(y, np.int32)
        if y.max() >= cfg.n_classes or y.min() < 0:
            raise ValueError(
                f"labels must be in [0, {cfg.n_classes}); got range "
                f"[{y.min()}, {y.max()}] — set RFConfig(n_classes=...)")
        n = (x.shape[0] // nw) * nw
        x, y = x[:n], y[:n]
        from harp_tpu.utils import skew, telemetry

        if telemetry.enabled():
            # ingest skew record (utils/skew.py): rows shard evenly by
            # construction (the truncation above), so this pins the
            # balanced baseline the report compares other phases against
            skew.record_partition("rf.partition", np.full(nw, n // nw),
                                  unit="rows", padded_total=n)
        self.edges = quantile_bins(x, cfg.n_bins)
        if self._train_fn is None:
            self._train_fn = make_train_fn(self.mesh, cfg, x.shape[1])
        train = self._train_fn
        from harp_tpu.utils import prng

        keys = np.asarray(
            jax.random.split(jnp.asarray(prng.key_bits(cfg.seed)),
                             nw * self.trees_per_worker)
        ).reshape(nw, self.trees_per_worker, 2)
        # binize + ship through the shared ingest pipeline (PR 8), under
        # the standard warn-mode flight budget: exactly the bins/labels/
        # keys bytes cross the wire and the host half compiles nothing
        with telemetry.budget(compiles=0,
                              h2d_bytes=(x.size * 4 + y.nbytes
                                         + keys.nbytes),
                              action="warn", tag="rf.ingest"):
            bins = binize_chunked(x, self.edges)
            bins_dev = self.mesh.shard_array(bins, 0)
            y_dev = self.mesh.shard_array(y, 0)
            keys_dev = self.mesh.shard_array(keys, 0)
        self.forest = jax.tree.map(np.asarray, train(
            bins_dev, y_dev, keys_dev))
        return self

    def predict(self, x):
        if self.forest is None:
            raise RuntimeError("call fit() before predict()")
        if self._predict_fn is None:
            self._predict_fn = flightrec.track(jax.jit(
                lambda forest, bins: predict_forest(
                    forest, bins, self.cfg.max_depth, self.cfg.n_classes)
            ), "rf.predict")
        # device_put, not jnp.asarray: host bins ride the counted H2D
        # path instead of risking a compile-time literal (HL003)
        bins = jax.device_put(binize(np.asarray(x, np.float32), self.edges))
        return np.asarray(self._predict_fn(
            jax.tree.map(jnp.asarray, self.forest), bins))

    def accuracy(self, x, y):
        return float((self.predict(x) == np.asarray(y)).mean())


def synthetic_classification(n=100_000, f=64, classes=2, seed=0):
    """Axis-aligned-structure task a depth-6 forest can learn."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, f)).astype(np.float32)
    # XOR of two axis-aligned thresholds: exactly representable at depth 2,
    # invisible to any single split (so it actually tests tree growth)
    y = ((x[:, 0] > 0).astype(int) ^ (x[:, 1] > 0.5).astype(int)) % classes
    return x, y.astype(np.int32)


def benchmark(n=200_000, f=64, n_trees=32, max_depth=6, mesh=None, seed=0,
              hist_algo="dense"):
    """Trees/sec + samples/sec (graded config #5b)."""
    mesh = mesh or current_mesh()
    cfg = RFConfig(n_trees=n_trees, max_depth=max_depth, seed=seed,
                   hist_algo=hist_algo)
    x, y = synthetic_classification(n, f, seed=seed)
    model = RandomForest(cfg, mesh)
    model.fit(x, y)  # warmup/compile
    t0 = time.perf_counter()
    model.fit(x, y)
    fit_dt = time.perf_counter() - t0
    t0 = time.perf_counter()
    acc = model.accuracy(x[:20_000], y[:20_000])
    pred_dt = time.perf_counter() - t0
    return {
        "trees_per_sec": n_trees / fit_dt,
        "fit_sec": fit_dt,
        "predict_sec_20k": pred_dt,
        "train_acc": acc,
        "n": n, "features": f, "n_trees": n_trees, "depth": max_depth,
        "num_workers": mesh.num_workers, "hist_algo": hist_algo,
        "hist_arm": hist_arm(cfg, f),  # the formulation that actually ran
    }


def main(argv=None):
    import argparse

    p = argparse.ArgumentParser(description="harp-tpu random forest (edu.iu.rf parity)")
    p.add_argument("--n", type=int, default=200_000)
    p.add_argument("--features", type=int, default=64)
    p.add_argument("--trees", type=int, default=32)
    p.add_argument("--depth", type=int, default=6)
    p.add_argument("--hist-algo", choices=("dense", "scatter", "pallas"),
                   default="dense",
                   help="histogram formulation (pallas = the on-chip "
                        "one-hot kernel, flip candidate rf_hist_pallas; "
                        "bit-identical counts)")
    args = p.parse_args(argv)
    from harp_tpu.utils.metrics import benchmark_json

    print(benchmark_json("rf_cli", benchmark(
        args.n, args.features, args.trees, args.depth,
        hist_algo=args.hist_algo)))


if __name__ == "__main__":
    main()
