"""KMeans through the program's public pieces: ``make_fit_fn`` for blocks
on resident points, ``kmeans.fit`` for whole jobs from a host array."""

from __future__ import annotations

import numpy as np

from harp_tpu.models import kmeans
from harp_tpu.parallel.mesh import WorkerMesh
from harp_tpu.utils import flightrec, telemetry
from perf import generators
from perf.reference import kmeans as reference

BLOCK_LABEL = "perf.kmeans.block"


class Driver:
    def __init__(self, config, traffic, devices, seed, rec):
        self.config, self.traffic, self.rec = config, traffic, rec
        self.seed = int(seed)
        self.mesh = WorkerMesh(devices)
        data = config["data"]
        self.n = int(data["n_per_chip"]) * len(devices)
        self.d, self.k = int(data["d"]), int(data["k"])
        self.knobs = dict(config["knobs"])
        self.steps = int(traffic["steps"])
        self.n_jobs = 0
        self.jobs: list[tuple[int, np.ndarray, float]] = []

    # -- set-up -----------------------------------------------------------
    def setup(self):
        if self.traffic["mode"] == "job":
            with self.rec.span("datagen"):
                self.host_points = generators.normal_points_host(
                    self.n, self.d, self.seed)
            return
        import jax
        import jax.numpy as jnp

        with self.rec.span("datagen"):
            self.points = generators.normal_points_device(
                self.n // self.mesh.num_workers, self.d, self.seed,
                self.mesh.devices)
            # placed as the step program returns them, or the second
            # block would compile again for the other sharding
            self.init = jax.device_put(
                jnp.take(self.points, jnp.asarray(_init_rows(
                    self.n, self.k, self.seed)), axis=0),
                self.mesh.replicated())
        self.centroids = self.init
        self.fit_fn = flightrec.track(
            kmeans.make_fit_fn(self.mesh, self._cfg(self.steps)),
            BLOCK_LABEL)

    def _cfg(self, iters: int):
        import jax.numpy as jnp

        kn = self.knobs
        return kmeans.KMeansConfig(
            k=self.k, iters=iters, dtype=jnp.dtype(kn["dtype"]),
            block_points=kn["block_points"], variant=kn["variant"],
            use_pallas=kn["use_pallas"], quantize=kn["quantize"],
            psum_schedule=kn["psum_schedule"])

    # -- the window -------------------------------------------------------
    def block(self):
        """``steps`` Lloyd iterations on the resident points as one
        program, centroids carried on; one dispatch, one readback."""
        with telemetry.ledger.run(BLOCK_LABEL, steps=self.steps):
            with self.rec.span("dispatch"):
                self.centroids, stats = self.fit_fn(self.points,
                                                    self.centroids)
            with self.rec.span("readback"):
                stats = flightrec.readback(stats)
        return self.n * self.steps, bool(np.isfinite(stats).all())

    def job(self):
        """One whole ``kmeans.fit``: host array in, host centroids out."""
        seed = self.seed * 100_003 + self.n_jobs
        self.n_jobs += 1
        with self.rec.phases("host_init", {"h2d": "stage",
                                           "dispatch": "dispatch",
                                           "readback": "readback"}):
            centroids, inertia = self._fit(seed)
        self.jobs.append((seed, centroids, inertia))
        if len(self.jobs) > 2:  # the check wants the first and the last
            del self.jobs[1]
        return self.n * self.steps, bool(np.isfinite(inertia)
                                         and np.isfinite(centroids).all())

    def _fit(self, seed: int):
        import jax.numpy as jnp

        kn = self.knobs
        return kmeans.fit(
            self.host_points, k=self.k, iters=self.steps, mesh=self.mesh,
            seed=seed, dtype=jnp.dtype(kn["dtype"]),
            block_points=kn["block_points"], use_pallas=kn["use_pallas"],
            variant=kn["variant"], quantize=kn["quantize"],
            init=kn["init"], psum_schedule=kn["psum_schedule"])

    # -- outside the window -------------------------------------------------
    def check(self) -> dict:
        """Two comparisons with plain Lloyd from the same start: the
        inertia the program reports after its iterations, and the cost
        its returned centroids achieve against the cost the reference's
        achieve.  Centroids are not compared coordinate by coordinate:
        the configuration file says why."""
        tol = self.config["reference"]
        out = {"correct": True}

        def hold(name, value, limit):
            out[name], out[name + "_limit"] = value, limit
            if not value <= limit:  # a NaN fails too
                out["correct"] = False

        def hold_run(tag, init, iters, got_c, got_inertia):
            want_c, want_inertia = reference.lloyd(bands, init, iters)
            hold(f"inertia_rel{tag}",
                 abs(got_inertia - want_inertia) / abs(want_inertia),
                 tol["inertia_rtol"])
            want_cost = reference.cost(bands, want_c)
            hold(f"cost_rel{tag}",
                 abs(reference.cost(bands, got_c) - want_cost) / want_cost,
                 tol["cost_rtol"])
            out[f"centroid_abs{tag}"] = float(np.abs(got_c - want_c).max())

        if self.traffic["mode"] == "job":
            bands = reference.stage_host(self.host_points)
            for tag, (seed, centroids, inertia) in zip(("_first", "_last"),
                                                       self.jobs):
                init = self.host_points[_init_rows(self.n, self.k, seed)]
                hold_run(tag, init, self.steps, centroids, inertia)
            return out
        bands = reference.stage_device(self.points)
        iters = int(tol["iters"])
        got, stats = kmeans.make_fit_fn(self.mesh, self._cfg(iters))(
            self.points, self.init)
        hold_run("", np.asarray(self.init), iters, np.asarray(got),
                 float(np.asarray(stats)[0, 1]))
        return out

    def extra(self) -> dict:
        return {}


def _init_rows(n: int, k: int, seed: int) -> np.ndarray:
    """The rows ``kmeans.fit(init="random", seed=seed)`` starts from."""
    return np.sort(np.random.default_rng(seed).choice(n, size=k,
                                                      replace=False))
