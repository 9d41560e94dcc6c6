"""Raw threefry key bits — the PRNGKey-specialization trap, fixed at the
source.

CLAUDE.md trap: ``jax.random.PRNGKey(python_int)`` specializes on
the int — a step function that bakes a fresh seed into its traced program
pays a fresh compile per seed.  The fix is always the
same two lines: build the key's raw uint32[2] bits with numpy (no jax
computation at all), and pass them *as an argument* so the compiled
program is seed-independent.  Before this module each driver open-coded
that (mlp ``fit_resident``, lda ``_advance_keys`` comment); now they all
share one helper whose bit-exactness against ``PRNGKey`` is pinned by
tests/test_prng.py, and whose no-recompile-across-seeds property is
checked by the flight recorder's CompileWatch.
"""

from __future__ import annotations

import numpy as np


def key_bits(seed: int) -> np.ndarray:
    """uint32[2] raw threefry key, bit-identical to
    ``np.asarray(jax.random.PRNGKey(seed))`` — built entirely in numpy so
    a NEW seed never costs a compile.

    In x32 mode (this repo's default) ``PRNGKey`` truncates the seed to
    its low 32 bits and the high word lowers to 0 (``shift_right_logical``
    by 32 on an int32); with ``jax_enable_x64`` the full 64-bit split
    applies.  Negative seeds follow two's complement in both modes,
    matching jax exactly (pinned in tests/test_prng.py).
    """
    import jax

    seed = int(seed)
    if not jax.config.jax_enable_x64:
        return np.array([0, seed & 0xFFFFFFFF], np.uint32)
    return np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                    np.uint32)


def split_keys(seed: int, num: int) -> np.ndarray:
    """[num, 2] uint32 host keys, bit-identical to
    ``np.asarray(jax.random.split(jax.random.PRNGKey(seed), num))``.

    The split program traces on the key *array* (shape-specialized only),
    so it compiles once per ``num`` and is cache-hit for every subsequent
    seed — unlike ``split(PRNGKey(s), num)``, which pays the PRNGKey
    specialization per distinct ``s``.  The result is a host array, ready
    for ``mesh.shard_array`` (the per-worker key pattern lda/rf use).
    """
    import jax
    import jax.numpy as jnp

    return np.asarray(jax.random.split(jnp.asarray(key_bits(seed)), num))
