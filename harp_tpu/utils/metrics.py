"""Per-iteration metrics as JSONL — Harp's log4j iteration logs, structured.

Reference parity (SURVEY.md §6): Harp apps print per-iteration wall-clock
lines into container logs; observability is grepping YARN logs.  Here every
iteration appends one JSON object to a file (and mirrors to the Python
logger), so the north-star metrics (iter/sec, updates/sec/chip) are
machine-readable.
"""

from __future__ import annotations

import json
import logging
import time
from typing import Any, IO

import numpy as np

log = logging.getLogger("harp_tpu.metrics")


class MetricsLogger:
    """Use as a context manager (``with MetricsLogger(path) as m: ...``)
    so the file handle closes on any exit path; :meth:`close` is
    idempotent, so drivers that close explicitly (``CollectiveApp.run``'s
    ``finally``) and a surrounding ``with`` can coexist."""

    def __init__(self, path: str | None = None):
        self._fh: IO | None = open(path, "a") if path else None
        self._t0 = time.perf_counter()

    def __enter__(self) -> "MetricsLogger":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()

    def log(self, step: int | None = None, **metrics: Any) -> dict:
        rec = {"t": round(time.perf_counter() - self._t0, 6), **metrics}
        if step is not None:
            rec["step"] = step
        if self._fh:
            self._fh.write(json.dumps(rec) + "\n")
            self._fh.flush()
        log.info("%s", rec)
        return rec

    def close(self):
        if self._fh is not None:
            self._fh.close()
            self._fh = None


_PROVENANCE: dict | None = None


def _provenance() -> dict:
    """backend/date/jax/commit stamp, computed once per process.

    The readers of BENCH_local.jsonl (``perfmodel.grade.latest_tpu_rows``)
    exclude CPU-sim evidence via ``backend == "cpu"`` —
    a config-keyed CLI row WITHOUT the field (e.g. the teed
    `kmeans_stream_cli` 1B record) would pass as TPU evidence, exactly
    the CPU-inversion failure those filters exist for.  Stamping here
    covers every CLI that prints through benchmark_json.
    """
    global _PROVENANCE
    if _PROVENANCE is None:
        import datetime
        import subprocess

        import jax

        try:
            commit = subprocess.run(
                ["git", "rev-parse", "--short", "HEAD"],
                capture_output=True, text=True, timeout=10,
            ).stdout.strip() or None
        except (OSError, subprocess.SubprocessError):
            # TimeoutExpired included: a hung git must not crash
            # benchmark_json at print time and lose an hours-long
            # measurement (ADVICE r5)
            commit = None
        _PROVENANCE = {
            "date": datetime.date.today().isoformat(),
            "backend": jax.default_backend(),
            "n_devices": jax.device_count(),
            "jax": jax.__version__,
            "commit": commit,
        }
    return _PROVENANCE


def benchmark_json(config: str, result: dict) -> str:
    """One JSON line for a CLI benchmark result.

    Every app CLI prints its benchmark dict through this (round 4): CLI
    output gets teed into BENCH_local.jsonl, and a Python dict repr
    there is an unparseable line every JSONL reader must skip.
    numpy scalars coerce to plain Python so json never chokes.  Rows
    carry the provenance fields (backend, date, commit), so
    downstream TPU-evidence filters can classify them.
    """
    def _plain(v: Any):
        if isinstance(v, (np.floating, float)):
            return round(float(v), 4)
        if isinstance(v, np.integer):
            return int(v)
        if isinstance(v, np.ndarray):
            return v.tolist()
        return v

    # provenance first: a measured result key that collides with a stamp
    # field (date/backend/n_devices/...) must win over the ambient stamp
    return json.dumps({"config": config,
                       **_provenance(),
                       **{k: _plain(v) for k, v in result.items()}})
