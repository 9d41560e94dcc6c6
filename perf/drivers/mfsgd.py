"""MF-SGD through the program's public pieces: ``MFSGD.set_ratings`` once
in set-up, then blocks of epochs through ``compile_epochs`` /
``train_epochs``."""

from __future__ import annotations

import numpy as np

from harp_tpu.models import mfsgd
from harp_tpu.parallel.mesh import WorkerMesh
from harp_tpu.utils import skew, telemetry
from perf import generators
from perf.reference import mfsgd as reference


class Driver:
    def __init__(self, config, traffic, devices, seed, rec):
        self.config, self.traffic, self.rec = config, traffic, rec
        self.seed = int(seed)
        self.mesh = WorkerMesh(devices)
        self.data = dict(config["data"])
        self.epochs = int(traffic["steps"])
        self.first: list[float] | None = None
        self.last: list[float] | None = None

    def _model(self):
        import jax.numpy as jnp

        kn = self.config["knobs"]
        cfg = mfsgd.MFSGDConfig(
            rank=self.data["rank"], lr=kn["lr"], reg=kn["reg"],
            algo=kn["algo"], u_tile=kn["u_tile"], i_tile=kn["i_tile"],
            entry_cap=kn["entry_cap"],
            compute_dtype=jnp.dtype(kn["compute_dtype"]),
            rotate_chunks=kn["rotate_chunks"], rotate_wire=kn["rotate_wire"])
        return mfsgd.MFSGD(self.data["n_users"], self.data["n_items"], cfg,
                           self.mesh, self.seed)

    # -- set-up -----------------------------------------------------------
    def setup(self):
        with self.rec.span("datagen"):
            self.ratings = generators.RATINGS[self.data["pairs"]](
                self.data, self.seed)
        with self.rec.span("host_init"):
            self.model = self._model()
        with self.rec.span("partition"):
            self.model.set_ratings(*self.ratings)
        with self.rec.span("compile"):
            self.model.compile_epochs(self.epochs)
        # the check starts plain SGD from the same factors: keep them on
        # the device (two small copies; nothing crosses to the host here)
        import jax.numpy as jnp

        self.initial = (jnp.copy(self.model.W), jnp.copy(self.model.H))

    # -- the window -------------------------------------------------------
    def block(self):
        """``steps`` epochs as one program; every rating visited once an
        epoch.  The first block ever run is the one the check compares."""
        with self.rec.phases("host", {"dispatch": "dispatch",
                                      "readback": "readback"}):
            rmses = self.model.train_epochs(self.epochs)
        if self.first is None:
            self.first = rmses
        self.last = rmses
        return self.model.nnz * self.epochs, bool(np.isfinite(rmses).all())

    # -- outside the window -------------------------------------------------
    def check(self) -> dict:
        tol = self.config["reference"]
        users, items, vals = self.ratings
        out = {"correct": True}

        def hold(name, value, limit):
            out[name], out[name + "_limit"] = value, limit
            if not value <= limit:  # a NaN fails too
                out["correct"] = False

        # (b) the running RMSE the program reported for its last epoch,
        # against the RMSE of the factors it holds now, over every rating
        W, H = self.model.factors()
        now = reference.rmse(W, H, users, items, vals)
        out["rmse_reported_last"], out["rmse_of_factors"] = self.last[-1], now
        hold("rmse_consistency_rel", abs(self.last[-1] - now) / now,
             tol["rmse_consistency_rtol"])
        # (a) one more block with the program's ledgers on: every rating
        # visited once an epoch
        was = telemetry.enabled()
        telemetry.enable(True)
        try:
            self.model.train_epochs(self.epochs)
            visited = skew.ledger.summary()["mfsgd.epochs"]["total"]
        finally:
            telemetry.enable(was)
        hold("visited_rel", abs(visited - self.model.nnz) / self.model.nnz,
             tol["visited_rtol"])
        # (c) the first epoch, against one epoch of plain SGD from the
        # same initial factors
        held = (self.model.W, self.model.H)
        self.model.W, self.model.H = self.initial
        W0, H0 = self.model.factors()
        self.model.W, self.model.H = held
        _, _, ref = reference.sgd_epoch(
            W0, H0, users, items, vals, self.config["knobs"]["lr"],
            self.config["knobs"]["reg"], int(tol["sgd_batch"]))
        out["rmse_first_epoch"], out["rmse_first_epoch_ref"] = \
            self.first[0], ref
        hold("first_epoch_rel", abs(self.first[0] - ref) / ref,
             tol["first_epoch_rtol"])
        return out

    def extra(self) -> dict:
        return {}
