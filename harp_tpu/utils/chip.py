"""The two things every chip entry point does before its first backend
use: place the compile cache, and refuse to run anywhere but on a TPU.

Reference parity (SURVEY.md §2 L8): Harp's launch scripts fixed the
cluster before the job started; a mapper that landed on the wrong host
failed the job, it did not quietly run elsewhere.  JAX, left alone,
drops to the CPU when it finds no accelerator — a benchmark that does
so prints numbers for a machine nobody deploys.

The backend is chosen by the environment only (``JAX_PLATFORMS``); no
code in this repository overrides it except the trace-only CLIs that
say why.  The persistent compile cache lives where
``JAX_COMPILATION_CACHE_DIR`` says, or at ``<checkout>/.jax_cache`` —
a fixed path, because the path is part of the cache key.
"""

from __future__ import annotations

import os
import sys

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def setup_compile_cache() -> str:
    """Place JAX's persistent compile cache; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set this does nothing (JAX reads
    the variable itself); otherwise the cache is ``<checkout>/.jax_cache``.
    Call before the first compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_info() -> dict:
    """``platform`` / ``device_kind`` / ``n_devices`` as JAX reports them
    — the three fields every measured record carries."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "n_devices": len(devs)}


def require_tpu(what: str) -> dict:
    """Exit non-zero unless the default backend is a TPU; returns
    :func:`device_info`.  Called first by the chip entry points, so a
    process that fell back to the CPU stops before any work."""
    info = device_info()
    if info["platform"] != "tpu":
        print(f"{what}: needs a TPU, found platform={info['platform']!r} "
              f"({info['device_kind']}, {info['n_devices']} device(s)) — "
              "refusing to run; the chip is reached through the chip "
              "tool, CPU runs use the --smoke paths and the tests",
              file=sys.stderr, flush=True)
        raise SystemExit(1)
    return info
