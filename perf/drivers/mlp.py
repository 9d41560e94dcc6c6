"""The MLP through the program's public pieces: a table made on the
device, ``MLPTrainer.load_resident`` once in set-up, then blocks of
epochs through ``fit_resident``, parameters and optimizer state carried
from block to block."""

from __future__ import annotations

import jax
import numpy as np

from harp_tpu.models import mlp
from harp_tpu.parallel.mesh import WorkerMesh
from perf import mnist_like
from perf.reference import mlp as reference


class Driver:
    def __init__(self, config, traffic, devices, seed, rec):
        if not hasattr(mlp.MLPTrainer, "resident_batch_order"):
            # a program from before the cell: refused at once, before any
            # table is made (its load_resident would pull this one
            # through the host, and nothing says which batches it visits)
            raise SystemExit(
                "perf: this program's MLPTrainer cannot say which batches "
                "an epoch visits (no resident_batch_order); the cell "
                "needs it to hold its first block to plain SGD")
        self.config, self.traffic, self.rec = config, traffic, rec
        self.seed = int(seed)
        self.mesh = WorkerMesh(devices)
        self.data = dict(config["data"])
        self.knobs = dict(config["knobs"])
        self.epochs = int(traffic["steps"])
        self.batch = int(self.knobs["batch_per_worker"]) * len(devices)
        self.last_losses: list[float] = []
        self.steps_per_block = None

    # -- set-up -----------------------------------------------------------
    def setup(self):
        with self.rec.span("datagen"):
            self.x, self.y = mnist_like.table_device(
                self.data, int(self.data["n_per_chip"]), self.seed,
                self.mesh.devices)
        with self.rec.span("host_init"):
            kn = self.knobs
            self.trainer = mlp.MLPTrainer(mlp.MLPConfig(
                sizes=tuple(kn["sizes"]), lr=kn["lr"],
                optimizer=kn["optimizer"],
                half_precision=kn["half_precision"],
                grad_wire=kn["grad_wire"], zero1=kn["zero1"]),
                self.mesh, seed=self.seed)
        with self.rec.span("load_resident"):
            self.trainer.load_resident(self.x, self.y, batch_size=self.batch,
                                       seed=self.seed)
        # what check (c) holds the first block to: where it starts and
        # the batches it will visit (the block itself stays as timed)
        with self.rec.span("first_order"):
            self.first = {
                "start": self.trainer.params,
                "order": self.trainer.resident_batch_order(
                    self.epochs, seed=self.seed)}

    # -- the window -------------------------------------------------------
    def block(self):
        """``steps`` epochs as one program; every used row visited once
        an epoch.  One dispatch, one readback: each epoch's last loss."""
        before = self.trainer.steps_run
        with self.rec.phases("host", {"dispatch": "dispatch",
                                      "readback": "readback"}):
            history = self.trainer.fit_resident(epochs=self.epochs,
                                                seed=self.seed)
        self.steps_per_block = self.trainer.steps_run - before
        self.last_losses.append(history[-1][0])
        if "end" not in self.first:
            # the first block is the warm-up: what it left, and each of
            # its epochs' last loss, are (c)'s to hold
            self.first.update(end=self.trainer.params,
                              losses=[loss for loss, _ in history])
        return (self.steps_per_block * self.batch,
                bool(np.isfinite(history).all()))

    # -- outside the window -------------------------------------------------
    def check(self) -> dict:
        tol = self.config["reference"]
        lr, nw = self.knobs["lr"], self.mesh.num_workers
        out = {"correct": True}

        def hold(name, value, limit):
            out[name], out[name + "_limit"] = value, limit
            if not value <= limit:  # a NaN fails too
                out["correct"] = False

        def worst(got, want):
            """The largest relative L2 distance over the leaves."""
            return max(reference.rel_l2(g, w) for g, w in zip(
                jax.tree.leaves(got), jax.tree.leaves(want)))

        def moved(after, before):
            return jax.tree.map(lambda a, b: np.asarray(a) - np.asarray(b),
                                after, before)

        def whole(tree):
            """Every leaf, as one vector."""
            return np.concatenate([a.ravel() for a in jax.tree.leaves(tree)])

        # the probe: the table's first rows, each label moved on by one
        # class, so that the step's gradient does not vanish where the
        # window's parameters already classify every row
        n = int(tol["probe_rows"])
        px = np.asarray(self.x[:n])
        py = (np.asarray(self.y[:n]) + 1) % self.data["classes"]
        window = self.trainer.params
        # (b) the forward: the program's logits on the probe rows for the
        # parameters the window left, against the reference's at HIGHEST
        # and against the same arithmetic at the precision the
        # configuration states (float32 activations, default dots)
        got = self.trainer.predict(px)
        for name, precision in (("logits_rel", reference.HI),
                                ("logits_as_stated_rel",
                                 reference.AS_STATED)):
            hold(name, reference.rel_l2(got, reference.logits(
                window, px, precision=precision)), tol[name + "_limit"])
        # (a) the step: one train_batch from those parameters; the update
        # it applied, leaf by leaf, against -lr x the reference's gradient
        # at the same two precisions
        self.trainer.train_batch(px, py)
        update = moved(self.trainer.params, window)
        for name, precision in (("step_rel", reference.HI),
                                ("step_as_stated_rel", reference.AS_STATED)):
            _, grads = reference.loss_and_grads(window, px, py,
                                                precision=precision)
            hold(name, worst(update, jax.tree.map(
                lambda g: -lr * np.asarray(g), grads)), tol[name + "_limit"])
        # (c) at a fixed early point, whatever the window held, and from
        # the program the window timed: what the first block (the
        # warm-up: the same compiled epochs, from the initial parameters)
        # moved the parameters by, and the last loss of each of its epochs,
        # against plain SGD over the same batches in the same order.  All
        # parameters as one vector: the last bias's ten numbers end a
        # block 0.007-0.03 from where they began, a near-cancellation
        # whose own relative distance swings 5e-3..2e-2 with the seed
        first = self.first
        plain, losses = reference.sgd(
            first["start"], self.x, self.y, first["order"].reshape(-1), lr,
            batch_per_worker=self.batch // nw, workers=nw)
        hold("block_rel", reference.rel_l2(
            whole(moved(first["end"], first["start"])),
            whole(moved(plain, first["start"]))), tol["block_rel_limit"])
        hold("block_loss_rel", reference.rel_l2(
            first["losses"],
            np.asarray(losses).reshape(first["order"].shape)[:, -1]),
            tol["block_loss_rel_limit"])
        # (d) every block's last loss finite, and the window's parameters
        # no worse over the whole table than those the first block left
        table = reference.bands(self.x, self.y)
        out["loss_window"] = reference.table_loss(window, table)
        out["loss_first_block"] = reference.table_loss(first["end"], table)
        hold("blocks_not_finite",
             int((~np.isfinite(self.last_losses)).sum()), 0)
        hold("loss_window_above_first",
             out["loss_window"] - out["loss_first_block"],
             tol["loss_margin"])
        return out

    def extra(self) -> dict:
        return {"optimizer_steps_per_block": self.steps_per_block}
