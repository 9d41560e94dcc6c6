"""LDA-CGS through the program's public pieces: ``LDA.set_tokens`` once
in set-up, then blocks of Gibbs sweeps through ``compile_epochs`` /
``sample_epochs``, tables and chain carried from block to block."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from harp_tpu.models import lda
from harp_tpu.parallel.mesh import WorkerMesh
from perf import corpus
from perf.reference import lda as reference


@functools.partial(jax.jit, static_argnames=("n_topics",))
def _table_differs(table, token_rows, z, row_sums, n_topics):
    """A count table as the program stores it, against the counts rebuilt
    from the chain and against the corpus' own row sums, whole and on the
    device (the 4 GB word-topic table of 1M words and 1k topics fits
    twice; a scatter-add on the chip costs by the update)."""
    got = table.astype(jnp.float32)
    want = reference.counts(token_rows, z, 0, table.shape[0], n_topics)
    return (got != want).sum(), (got.sum(1) != row_sums).sum()


class Driver:
    def __init__(self, config, traffic, devices, seed, rec):
        self.config, self.traffic, self.rec = config, traffic, rec
        self.seed = int(seed)
        self.mesh = WorkerMesh(devices)
        self.data = dict(config["data"])
        self.steps = int(traffic["steps"])
        self.sweeps = 0
        # check (c) is taken where the chain stood after this many sweeps,
        # however many more the window went on to hold
        self.chain_sweeps = int(config["reference"]["chain_sweeps"])
        self.kept = None  # (sweeps, a copy of the chain as it stood then)

    def _model(self):
        d, kn = self.data, self.config["knobs"]
        cfg = lda.LDAConfig(
            n_topics=d["n_topics"], alpha=d["alpha"], beta=d["beta"],
            algo=kn["algo"], d_tile=kn["d_tile"], w_tile=kn["w_tile"],
            entry_cap=kn["entry_cap"], carry_db=kn["carry_db"],
            pallas_exact_gathers=kn["pallas_exact_gathers"],
            ndk_dtype=kn["ndk_dtype"], sampler=kn["sampler"],
            rng_impl=kn["rng_impl"], rotate_chunks=kn["rotate_chunks"],
            rotate_wire=kn["rotate_wire"])
        return lda.LDA(d["n_docs"], d["vocab_size"], cfg, self.mesh,
                       self.seed)

    # -- set-up -----------------------------------------------------------
    def setup(self):
        with self.rec.span("datagen"):
            # the data set pins every shard's bag of words, a shard
            # being one document tile of the layout
            self.corpus = corpus.zipf_corpus(
                self.data, self.seed,
                shard_docs=self.config["knobs"]["d_tile"])
        with self.rec.span("host_init"):
            self.model = self._model()
        with self.rec.span("set_tokens"):
            self.model.set_tokens(*self.corpus)
        with self.rec.span("compile"):
            self.model.compile_epochs(self.steps)
        # the check starts the plain sampler from the same chain: a copy
        # on the device, nothing crosses to the host here
        self.z_initial = jnp.copy(self.model.z_grid)

    # -- the window -------------------------------------------------------
    def block(self):
        """``steps`` sweeps as one program; every token resampled once a
        sweep, tables and chain carried on.  One dispatch, one readback:
        the sweep's count of the tokens it touched."""
        with self.rec.phases("host", {"dispatch": "dispatch",
                                      "readback": "readback"}):
            self.model.sample_epochs(self.steps)
        self.sweeps += self.steps
        if self.kept is None and self.sweeps >= self.chain_sweeps:
            # the next block's program donates z_grid: a copy, on the
            # device, as z_initial is (no compile: setup made that one)
            self.kept = (self.sweeps, jnp.copy(self.model.z_grid))
        touched = self.model.last_work
        return (self.model.n_tokens * self.steps,
                bool(np.isfinite(touched).all()
                     and touched.sum() == self.model.n_tokens))

    # -- outside the window -------------------------------------------------
    def _tokens(self):
        """External ``(doc, word)`` of every token and the slot of its
        topic in a ``z_grid``, through the program's own ``token_state``:
        handed the slots' own numbers for topics, it returns them in the
        order it returns the tokens (the order its sweep visits them)."""
        held = self.model.z_grid
        self.model.z_grid = jnp.arange(held.size, dtype=jnp.int32).reshape(
            held.shape)
        try:
            return self.model.token_state()
        finally:
            self.model.z_grid = held

    def _table_faults(self, table, ids, z, corpus_ids, own, bound):
        """``(entries, row sums)`` of a count table that differ from the
        counts rebuilt from the chain and from the corpus' token counts.
        The program stores a range of ``own`` ids in ``bound`` rows; the
        rest is tile padding and has to stay zero."""
        def stored(ext):
            return ((ext // own) * bound + ext % own).astype(np.int32)

        sums = np.bincount(stored(corpus_ids), minlength=table.shape[0])
        faults = _table_differs(
            table, jnp.asarray(stored(ids)), jnp.asarray(z, jnp.int32),
            jnp.asarray(sums, jnp.float32), self.data["n_topics"])
        entries, row_sums = jax.device_get(faults)
        return int(entries), int(row_sums)

    def check(self) -> dict:
        tol = self.config["reference"]
        d_cfg, m = self.data, self.model
        model_of = {k: d_cfg[k] for k in ("vocab_size", "n_topics", "alpha",
                                          "beta")}
        out = {"correct": True, "sweeps": self.sweeps}

        def hold(name, value, limit):
            out[name], out[name + "_limit"] = value, limit
            if not value <= limit:  # a NaN fails too
                out["correct"] = False

        # (a) exact: the tables the program holds are the counts of the
        # chain it holds, entry for entry; their row sums are the
        # corpus' token counts (the same tokens as went in, each once);
        # N_k is the word-topic table's column sums and sums to n_tokens
        doc, word, slot = self._tokens()
        z0, z = (np.asarray(g).reshape(-1)[slot]
                 for g in (self.z_initial, m.z_grid))
        wbc = m.w_bound // lda.rotate_chunks_resolved(m.cfg)
        bad_d, bad_dsum = self._table_faults(
            m.Ndk, doc, z, self.corpus[0], m.d_own, m.d_bound)
        bad_w, bad_wsum = self._table_faults(
            m.Nwk, word, z, self.corpus[1], m.w_own, wbc)
        hold("count_mismatches", bad_d + bad_w, 0)
        hold("row_sum_mismatches", bad_dsum + bad_wsum, 0)
        hold("nk_mismatches", int((m.Nk != m.Nwk.sum(0)).sum()), 0)
        hold("nk_total_off", abs(float(m.Nk.sum()) - d_cfg["n_tokens"]), 0)
        # (b) tight: the likelihood the program reports, walking its
        # tokens, against the reference's likelihood of the program's own
        # tables: one number where the tables are the chain's counts
        out["ll_program"] = m.log_likelihood()
        out["ll_of_tables"] = reference.log_likelihood(
            m.Ndk, m.Nwk, m.Nk, **model_of)
        hold("ll_tables_rel", abs(out["ll_program"] - out["ll_of_tables"])
             / abs(out["ll_of_tables"]), tol["ll_tables_rtol"])
        # (c) the chain, at a fixed early point: where it stood after
        # ``chain_sweeps`` sweeps (a window that ended sooner: its last),
        # against the plain sampler from the same initial topics, on
        # several keys of its own, after as many sweeps and run for one
        # more.  Its counts are rebuilt from the kept chain.  The band
        # around the keys' mean is a share of what one sweep moves the
        # plain chain's likelihood there: the larger of its last and its
        # next step.  The range of the plain sampler's own keys is
        # printed beside it.  What the window ran after that point is
        # held by (a), (b) and every block's count of touched tokens
        n, z_then = self.kept or (self.sweeps, m.z_grid)
        out["chain_sweeps"] = n
        z_then = np.asarray(z_then).reshape(-1)[slot]
        out["ll_of_chain"] = reference.log_likelihood(
            *reference.tables(doc, word, z_then, d_cfg["n_docs"],
                              d_cfg["vocab_size"], d_cfg["n_topics"]),
            **model_of)
        runs = np.asarray([reference.chain(
            doc, word, z0, n_sweeps=n + 1, n_docs=d_cfg["n_docs"],
            block=int(tol["block"]), seed=self.seed + k, **model_of)[1]
            for k in range(int(tol["plain_keys"]))])
        path = runs.mean(0)  # the likelihood before sweep 1 and after each
        out["ll_initial"], out["ll_plain"] = float(path[0]), float(path[n])
        out["ll_plain_step"] = float(max(path[n] - path[n - 1],
                                         path[n + 1] - path[n]))
        out["ll_plain_key_range"] = float(np.ptp(runs[:, n]))
        hold("ll_chain_abs", abs(out["ll_of_chain"] - out["ll_plain"]),
             tol["chain_step_share"] * out["ll_plain_step"])
        return out

    def extra(self) -> dict:
        return {}
