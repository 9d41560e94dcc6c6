"""Bytes a job stages (``flightrec`` H2D bytes) over ``stage_s``: the
rate at which the input really arrives, host-side relayout included."""

import os

from perf import spec

_stage_s = spec.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "stage_s.py")).read


def read(run):
    stage = _stage_s(run)
    if not stage or not run.in_window["h2d_bytes"]:
        return None
    return run.in_window["h2d_bytes"] / len(run.block_s) / stage / 1e9
