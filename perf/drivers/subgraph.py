"""Colour-coding subgraph counting through the program's public pair: a
graph made from the seed, ``SubgraphCounter.set_graph`` once in set-up,
then blocks of ``trial_chunk`` fresh colourings through
``count_colorings``, each one dispatch and one readback."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from harp_tpu.models import subgraph
from harp_tpu.parallel.mesh import WorkerMesh
from perf import graph_like
from perf.reference import subgraph as reference


def _draw(first, second, k) -> dict:
    """Two blocks' colours ``[T, n]`` held to a uniform, independent draw:
    ``z`` is the largest distance, in standard deviations of a fair draw,
    of (i) any colour's count in any colouring from ``n / k`` and (ii)
    the count of vertices on which a colouring agrees with the next of
    its block, or with its place's a block later, from ``n / k``."""
    n = first.shape[1]
    both = jnp.stack([first, second])
    counts = (both[..., None] == jnp.arange(k)).sum(2).reshape(-1)
    kept = (first == second).sum(1)
    agree = jnp.concatenate([kept, (first[:-1] == first[1:]).sum(1)])
    z = jnp.abs(jnp.concatenate([counts, agree]) - n / k) \
        / np.sqrt(n * (1 / k) * (1 - 1 / k))
    return {"z": float(z.max()), "kept": float(kept.sum() / kept.size / n),
            "share_min": float(counts.min() / n),
            "share_max": float(counts.max() / n)}


class Driver:
    def __init__(self, config, traffic, devices, seed, rec):
        if not hasattr(subgraph, "SubgraphCounter"):
            # a program from before the cell: refused at once, before any
            # graph is made (its only entry point re-does the graph on
            # every call and uploads its colourings)
            raise SystemExit(
                "perf: this program has no SubgraphCounter (no graph "
                "installed once, no colourings drawn on the device); the "
                "cell needs the pair")
        self.config, self.traffic, self.rec = config, traffic, rec
        self.seed = int(seed)
        self.mesh = WorkerMesh(devices)
        self.data = dict(config["data"])
        kn = dict(config["knobs"])
        self.chunk = int(traffic["steps"])
        if self.chunk != kn["trial_chunk"] or self.chunk < 2:
            raise SystemExit("perf: a block is one chunk of colourings, two "
                             "or more (the check takes their spread from "
                             "them): the mix's steps and the knob "
                             "trial_chunk differ, or are 1")
        self.cfg = subgraph.SubgraphConfig(
            **kn, n_trials=self.chunk, seed=self.seed)
        self.n = int(self.data["n_vertices"])
        self.rooted: list[np.ndarray] = []   # every block's counts

    # -- set-up -----------------------------------------------------------
    def setup(self):
        with self.rec.span("datagen"):
            self.edges = graph_like.edges(self.data, self.seed)
        with self.rec.span("host_init"):
            self.counter = subgraph.SubgraphCounter(self.cfg, self.mesh)
        with self.rec.span("install"):
            self.counter.set_graph(self.edges, self.n)
        # what check (b) holds the first block to: the colours it will
        # count under, asked of the program before it runs
        with self.rec.span("first_colors"):
            self.first_colors = self.counter.block_colors()

    # -- the window -------------------------------------------------------
    def block(self):
        """One chunk of fresh colourings: one dispatch, one readback of
        ``trial_chunk`` floats."""
        with self.rec.phases("host", {"dispatch": "dispatch",
                                      "readback": "readback"}):
            rooted = self.counter.count_colorings()
        self.rooted.append(np.asarray(rooted, np.float64))
        return (self.n * self.chunk,
                bool(np.isfinite(rooted).all() and (rooted > 0).all()))

    # -- outside the window -------------------------------------------------
    def check(self) -> dict:
        tol = self.config["reference"]
        out = {"correct": True}

        def hold(name, value, limit):
            out[name], out[name + "_limit"] = value, limit
            if not value <= limit:  # a NaN fails too
                out["correct"] = False

        # (a) exact, on what was installed: every vertex's real entries
        # in the padded part and in the tail against the degree sequence
        # the generator states (not the program's arrays)
        cap = self.cfg.max_degree
        degree = graph_like.degree_sequence(self.data)
        nbr, msk, o_nbr, o_row, o_msk = self.counter.installed()
        nw = self.mesh.num_workers
        loc = msk.shape[0] // nw
        in_rows = np.asarray((msk > 0).sum(1, dtype=jnp.int32))[:self.n]
        rows = (o_row.reshape(nw, -1)
                + loc * jnp.arange(nw, dtype=jnp.int32)[:, None]).reshape(-1)
        in_tail = np.asarray(jnp.zeros(msk.shape[0], jnp.int32).at[rows].add(
            (o_msk > 0).astype(jnp.int32)))[:self.n]
        out["entries_padded_part"] = int(in_rows.sum(dtype=np.int64))
        out["entries_tail"] = int(in_tail.sum(dtype=np.int64))
        hold("entries_missing",
             abs(2 * int(self.data["n_edges"])
                 - out["entries_padded_part"] - out["entries_tail"]), 0)
        hold("vertices_split_wrong", int(
            ((in_rows != np.minimum(degree, cap))
             | (in_tail != np.maximum(degree - cap, 0))).sum()), 0)
        # (d) the draw itself (here, while the program holds its graph),
        # by the program's own answer to "which colours does block b
        # use", the one definition the block program draws with: uniform
        # over the colours, a block's colourings apart from each other
        # and from the next block's
        k = self.counter.k
        drawn = _draw(self.first_colors, self.counter.block_colors(1), k)
        out.update(colour_share_min=drawn["share_min"],
                   colour_share_max=drawn["share_max"],
                   colours_kept_share=drawn["kept"])
        hold("draw_z", drawn["z"], tol["draw_z_limit"])
        # the installed graph makes room for the reference's tables
        del nbr, msk, o_nbr, o_row, o_msk, rows
        self.colorings_run = self.counter.colorings_run
        self.counter = None

        # (b) at a fixed early point, from the program the window times:
        # the warm-up block's rooted colourful counts against the plain
        # dynamic program's, colouring by colouring (as many side by side
        # as fill the reference's 128-lane rows)
        tpl = subgraph.TEMPLATES[self.cfg.template]
        src, dst = reference.stage_edges(
            *graph_like.directed(self.edges), self.n)
        per = max(1, reference.LANES >> k)
        want = np.concatenate([reference.rooted_colourful_count(
            tpl, k, self.first_colors[t:t + per].T, src, dst, self.n)
            for t in range(0, self.chunk, per)])
        out["rooted_first"] = self.rooted[0].tolist()
        out["rooted_reference"] = want.tolist()
        hold("counts_rel", float(np.max(
            np.abs(self.rooted[0] - want) / np.maximum(np.abs(want), 1.0))),
            tol["counts_rel_limit"])

        # (c) the later blocks: every count finite and positive, none a
        # repeat of the first block's (a block that drew the first
        # block's colourings again returns its counts again, bit for
        # bit), and the window's mean within so many of its own standard
        # errors of the first block's: independent draws of one
        # estimator, their spread taken from the colourings of each side
        first = self.rooted[0]
        later = np.concatenate(self.rooted[1:]) if len(self.rooted) > 1 \
            else first
        hold("counts_not_positive", int(sum(
            (~(np.isfinite(r) & (r > 0))).sum() for r in self.rooted)), 0)
        hold("blocks_repeating", sum(
            bool((r == first).all()) for r in self.rooted[1:]), 0)
        out["estimate_first"] = reference.estimate(first.mean(), tpl, k)
        out["estimate_window"] = reference.estimate(later.mean(), tpl, k)
        z = out["count_rel_sd"] = 0.0  # counts not positive: held above
        if (first > 0).all() and np.isfinite(later).all():
            within = (((first - first.mean()) ** 2).sum()
                      + ((later - later.mean()) ** 2).sum()) \
                / (len(first) + len(later) - 2)
            out["count_rel_sd"] = float(np.sqrt(within) / first.mean())
            gap = abs(later.mean() - first.mean())
            z = float(gap / np.sqrt(
                within * (1 / len(later) + 1 / len(first)))) if gap else 0.0
        hold("window_mean_z", z, tol["window_mean_z_limit"])
        return out

    def extra(self) -> dict:
        return {"colorings_per_block": self.chunk,
                "colorings_run": self.colorings_run,
                "adjacency_entries": 2 * int(self.data["n_edges"])}
