"""What the chip could do at the least: a work model's operations and
bytes per item against the published peaks.

A configuration names its model under ``work.model``; the model is the
file ``perf/work_models/<model>.py`` with ``per_item(work) -> {"flops",
"bytes", "peak"}`` (exact FLOPs of the dominant matmuls, lower-bound
bytes: inputs read once, outputs written once; ``peak`` names the
compute peak of ``peaks.json`` that applies).  Kept here so that no later
PR can move the yardstick; a new kind of item is a new file.  The
difference from ``harp_tpu/utils/roofline.py``, where the arithmetic
comes from, is the denominator: the benchmark divides by device time
from the trace, never by host wall time.
"""

from __future__ import annotations

import os

from perf import spec

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks_for(device_kind: str) -> dict:
    table = spec.load_json(os.path.join(_HERE, "peaks.json"))
    if device_kind not in table:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}: add it "
            "to perf/peaks.json with its source")
    return table[device_kind]


def least_seconds(work: dict, items: float, device_kind: str) -> dict:
    """Least seconds ONE chip needs for ``items`` items, and the wall
    (``mxu`` or ``hbm``) that sets it."""
    peaks = peaks_for(device_kind)
    per = spec.load_module(os.path.join(
        _HERE, "work_models", work["model"] + ".py")).per_item(work)
    t_mxu = items * per["flops"] / peaks[per["peak"]]
    t_hbm = items * per["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_mxu, t_hbm),
            "wall": "mxu" if t_mxu >= t_hbm else "hbm",
            "mxu_s": t_mxu, "hbm_s": t_hbm}
