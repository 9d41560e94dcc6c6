"""The yardstick's generators under the tier-1 floor: the cases of
``perf/tests/test_generators.py`` (the ``id_seed`` / chunk-step cases of
``mfsgd-ml20m-x4-r64`` among them) and of ``perf/tests/test_corpus.py``
(the corpus of ``lda-enwiki-v1m-k1k``), collected here as they are.
``perf/tests`` is not on tier-1's path; a change to a generator that
moves what a seed deals would otherwise pass tier-1 unseen.
``test_perf_harness.py`` and ``test_perf_lda_check.py`` collect the rest
of ``perf/tests`` through the same ``_cases_of``."""

import os
import sys

from perf import spec

_PERF_TESTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "perf", "tests")
# the files there import each other by bare name (``from test_harness
# import ...``, ``import lda_faults``); last on the path, so nothing of
# tests/ is shadowed
if _PERF_TESTS not in sys.path:
    sys.path.append(_PERF_TESTS)


def _cases_of(file_name, *fixtures):
    """The test cases of one file of ``perf/tests``, and the fixtures
    named, for ``globals().update``."""
    module = spec.load_module(os.path.join(_PERF_TESTS, file_name))
    return {name: case for name, case in vars(module).items()
            if name in fixtures
            or (name.startswith("test_") and callable(case))}


globals().update(_cases_of("test_generators.py"))
globals().update(_cases_of("test_corpus.py"))
