"""utils/prng — raw key bits: bit-exact vs PRNGKey, no per-seed compiles.

The CLAUDE.md driver-loop trap this pins: ``jax.random.PRNGKey(python_int)``
specializes on the int, so every fresh seed in a hot path paid a fresh
(~140 ms remote) compile.  The helper must be (a) bit-identical to
``PRNGKey``/``split(PRNGKey(...))`` — drivers switched to it mid-history,
so checkpointed RNG chains must resume unchanged — and (b) free of any
compile once the shape-specialized split program is warm.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harp_tpu.utils import flightrec, prng, telemetry

# negative seeds follow two's complement; >32-bit seeds truncate in x32
# mode (the repo default) exactly like PRNGKey does
SEEDS = [0, 1, 42, 7_777_777, 2**31 - 1, -1, -5, 2**40 + 7]


@pytest.mark.parametrize("seed", SEEDS)
def test_key_bits_matches_prngkey(seed):
    assert np.array_equal(prng.key_bits(seed),
                          np.asarray(jax.random.PRNGKey(seed))), seed


@pytest.mark.parametrize("seed", [0, 3, -2, 2**40 + 7])
def test_split_keys_matches_split_of_prngkey(seed):
    want = np.asarray(jax.random.split(jax.random.PRNGKey(seed), 8))
    assert np.array_equal(prng.split_keys(seed, 8), want), seed


def test_key_bits_draws_match_typed_key():
    """normal() from the raw bits equals normal() from jax.random.key —
    the drivers that switched from typed keys (kmeans/mfsgd benchmark
    data generation) produce byte-identical datasets."""
    raw = jax.random.normal(jnp.asarray(prng.key_bits(9)), (16,))
    typed = jax.random.normal(jax.random.key(9), (16,))
    assert np.array_equal(np.asarray(raw), np.asarray(typed))


def test_split_keys_does_not_recompile_across_seeds(mesh):
    """The regression the helper exists for: after one warm call, new
    seeds must be compile-free (CompileWatch counts XLA backend
    compiles)."""
    with telemetry.scope():
        prng.split_keys(123, 8)  # warm: the one shape-keyed compile
        before = flightrec.compile_watch.count
        for seed in range(200, 220):
            prng.split_keys(seed, 8)
        assert flightrec.compile_watch.count == before, \
            "split_keys recompiled on a fresh seed"
