"""Reliable device synchronization and iteration timing.

Harp apps timed iterations with wall-clock logs around collective phases
(SURVEY.md §6 "tracing").  On TPU, timing is only honest after forcing
device completion; the sync used here is a device→host readback of a
scalar, which cannot complete before the computation that produces it.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np


class HangWatchdog:
    """Hard-exit instead of hanging the caller forever.

    A device call that never returns is uninterruptible from Python, so
    benchmark entry points arm a daemon timer that ``os._exit``\\ s with a
    diagnostic after ``timeout_s`` (``HARP_BENCH_TIMEOUT``, default 1200).
    ``arm`` may be called repeatedly to restart the clock per phase/config;
    ``on_fire(what)`` runs first so the caller can emit a structured record
    naming the hung phase (stdout lines already flushed are preserved).
    """

    def __init__(self, timeout_s: float | None = None, *, exit_code: int = 3,
                 on_fire: Callable[[str], None] | None = None,
                 _exit: Callable[[int], None] = os._exit):
        if timeout_s is None:
            timeout_s = float(os.environ.get("HARP_BENCH_TIMEOUT", "1200"))
        self.timeout_s = timeout_s
        self.exit_code = exit_code
        self.on_fire = on_fire
        self._exit = _exit
        self._timer: threading.Timer | None = None
        # Timer.cancel() can't stop a _fire already past the waiting stage;
        # the generation check below keeps a just-cancelled timer from
        # emitting a spurious hang record and killing a healthy process.
        self._lock = threading.Lock()
        self._gen = 0

    def arm(self, what: str = "benchmark") -> None:
        with self._lock:
            if self._timer is not None:
                self._timer.cancel()
            self._gen += 1
            t = threading.Timer(self.timeout_s, self._fire, (what, self._gen))
            t.daemon = True
            # stable name so threadguard's ownership map (generated from
            # harplint Layer 5) can forbid jax work on the watchdog timer
            t.name = "harp-watchdog"
            self._timer = t
        t.start()

    def cancel(self) -> None:
        with self._lock:
            if self._timer is not None:
                self._timer.cancel()
                self._timer = None
            self._gen += 1

    def _fire(self, what: str, gen: int) -> None:
        with self._lock:
            if gen != self._gen:
                return  # cancelled or re-armed as we left the waiting stage
        print(f"watchdog: {what} produced no result after "
              f"{self.timeout_s:.0f}s; exiting", file=sys.stderr, flush=True)
        if self.on_fire is not None:
            try:
                self.on_fire(what)
            except Exception:
                pass  # never let the diagnostic path mask the exit
        self._exit(self.exit_code)


def device_sync(x: Any) -> float:
    """Force completion of everything ``x`` depends on; returns a scalar.

    Reduces one leaf to a scalar and reads it back to the host — a readback
    cannot complete before the producing computation has.  Use this, not
    ``block_until_ready``, around benchmark timing.  Each call counts as
    one readback round trip in the flight recorder.
    """
    from harp_tpu.utils import flightrec

    leaf = jax.tree.leaves(x)[0]
    flightrec.record_readback(np.dtype(leaf.dtype).itemsize)
    return float(np.asarray(jnp.ravel(leaf)[0]))


class Timer:
    """Per-iteration timer table, printed like Harp's per-phase logs."""

    def __init__(self):
        self.records: dict[str, list[float]] = {}

    def time(self, name: str, fn, *args, sync: bool = True, **kw):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        if sync:
            device_sync(out)
        self.records.setdefault(name, []).append(time.perf_counter() - t0)
        return out

    def summary(self) -> dict[str, dict[str, float]]:
        return {
            k: {"mean_s": float(np.mean(v)), "total_s": float(np.sum(v)), "n": len(v)}
            for k, v in self.records.items()
        }
