"""Predictive performance observatory (PR 13).

An offline cost model over the landed evidence planes — CommGraph byte
sheets (PR 9), topology link rates (PR 11), roofline work models,
kernel-registry shapes, and calibrated flight-recorder deltas — that
prices every config, grades itself against the committed bench rows,
and ranks candidates (``python -m harp_tpu predict --top``).  See
:mod:`harp_tpu.perfmodel.model` for the model and its additive-roofline
rationale, :mod:`harp_tpu.perfmodel.grade` for the self-grading
contract (``grade.grade()`` — the function keeps its module's name, so
the package re-exports it as :func:`grade_evidence`).
"""

from harp_tpu.perfmodel import grade, model  # noqa: F401
from harp_tpu.perfmodel.model import (  # noqa: F401
    BOUNDS, CONFIG_MODELS, FULL_SHAPES, PROGRAM_CONFIGS, RATES_SOURCES,
    Price, model_row, presize, price, price_sheet, rank_candidates,
    wire_cost_s,
)
from harp_tpu.perfmodel.grade import (  # noqa: F401
    DEAD_BAND, FAMILY_PAIRS, MAGNITUDE_TOL, RANK_FLOOR, SWEEPS,
)

grade_evidence = grade.grade
