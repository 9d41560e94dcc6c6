"""A stand-in driver: one tiny jitted program a block."""

import numpy as np


class Driver:
    def __init__(self, config, traffic, devices, seed, rec):
        self.ticks = config["data"]["ticks"] * traffic["steps"]
        self.rec, self.device = rec, devices[0]

    def setup(self):
        import jax
        import jax.numpy as jnp

        self.x = jax.device_put(jnp.ones((8,)), self.device)
        self.fn = jax.jit(lambda x: x * 2.0)

    def block(self):
        with self.rec.span("dispatch"):
            y = self.fn(self.x)
        with self.rec.span("readback"):
            ok = bool(np.isfinite(np.asarray(y)).all())
        return self.ticks, ok

    def check(self):
        return {"correct": True}

    def extra(self):
        return {"answer": 42}
