"""Test harness: multi-worker simulation on host CPU.

Harp's test story was "pseudo-distributed Hadoop on localhost — real sockets
over loopback" (SURVEY.md §5).  Our analogue: 8 simulated XLA CPU devices in
one process, so every collective runs through the real shard_map/collective
code path with no mocks.  The environment gets the same two settings, so
every child process a test starts inherits them and stays off any chip.
"""

import os
import sys

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")

# Caches a test run makes go to a per-session scratch directory, not into
# the checkout's .jax_cache.  Set AFTER jax is imported, on purpose: JAX
# reads the variable at import, so this process keeps compiling in memory
# as it always has, chip.setup_compile_cache() sees the variable and
# leaves the config alone, and child processes (which do read it) cache
# here too.
import atexit  # noqa: E402
import shutil  # noqa: E402
import tempfile  # noqa: E402

os.environ["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp(
    prefix="harp_tpu_test_cache_")
atexit.register(shutil.rmtree, os.environ["JAX_COMPILATION_CACHE_DIR"],
                ignore_errors=True)

import pytest  # noqa: E402

from harp_tpu.parallel.mesh import WorkerMesh, set_mesh  # noqa: E402


@pytest.fixture(scope="session")
def mesh() -> WorkerMesh:
    m = WorkerMesh()
    assert m.num_workers == 8, f"expected 8 simulated workers, got {m.num_workers}"
    set_mesh(m)
    return m
