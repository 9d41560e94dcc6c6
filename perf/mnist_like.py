"""An MNIST-shaped table made on the devices from the seed: every row a
class prototype scaled by ``proto_scale`` plus ``noise`` x N(0, 1), its
label the class drawn, float32 throughout (the recipe of the program's
own ``synthetic_mnist``).  Driven by the ``data`` block of a
configuration file.

What is the data set's and what the run's: the ``classes`` prototypes
are the data set (``proto_seed``, made with numpy: ten rows); ``seed``
draws every label and every pixel's noise.  One band of rows a device,
each from its own key, made as ONE elementwise pass (the prototype of a
row is selected, not gathered), so that no second table-sized buffer is
live while it is made.  The seed is data, so a new seed compiles nothing.
"""

from __future__ import annotations

import numpy as np


def prototypes(data: dict) -> np.ndarray:
    """``[classes, d]`` float32 standard-normal prototypes."""
    return np.random.default_rng(data["proto_seed"]).standard_normal(
        (data["classes"], data["d"])).astype(np.float32)


def table_device(data: dict, n_per_device: int, seed: int, devices):
    """``(x [n, d] float32, y [n] int32)`` with ``n = n_per_device *
    len(devices)``, both row-sharded, one band a device."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    d, classes = int(data["d"]), int(data["classes"])
    scale, noise = float(data["proto_scale"]), float(data["noise"])
    mesh = Mesh(np.asarray(devices), ("w",))
    keys = jax.device_put(
        jax.random.key_data(jax.random.split(jax.random.key(seed),
                                             len(devices))),
        NamedSharding(mesh, P("w")))
    protos = jax.device_put(prototypes(data), NamedSharding(mesh, P()))

    def band(k, protos):
        ky, kx = jax.random.split(jax.random.wrap_key_data(k[0]))
        y = jax.random.randint(ky, (n_per_device,), 0, classes, jnp.int32)
        proto = jnp.zeros((n_per_device, d), jnp.float32)
        for c in range(classes):
            proto = jnp.where((y == c)[:, None], protos[c][None, :], proto)
        return scale * proto + noise * jax.random.normal(
            kx, (n_per_device, d), jnp.float32), y

    return jax.jit(jax.shard_map(
        band, mesh=mesh, in_specs=(P("w"), P()),
        out_specs=(P("w"), P("w"))))(keys, protos)
