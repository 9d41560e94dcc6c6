"""Health sentinel — the sixth, *derived* telemetry spine (see
:mod:`harp_tpu.health.sentinel` for the design docstring).

This package import stays light (vocabularies + the sentinel; no jax,
no perfmodel): the skew/flightrec hooks import it lazily on their hot
paths.  The evidence-regression grader (:mod:`harp_tpu.health.grade`)
pulls the perfmodel import cascade, so it is NOT imported here — the
CLI imports it directly.
"""

from harp_tpu.health.sentinel import (  # noqa: F401
    DETECTORS, SEVERITIES, VERDICTS, FAST_BURN_MIN, PAGE_BURN,
    SLO_ERROR_BUDGET, SLOW_BURN_MIN, TRIGGER_SUPERSTEPS,
    WASTED_FRAC_TRIGGER, HealthMonitor, SLOBurn, export_jsonl, monitor,
    reset, summarize_rows)
