"""What ``correct`` compares on ``lda-sweeps``, under the tier-1 floor:
the 18 cases of ``perf/tests/test_lda_check.py`` (the planted faults of
``perf/tests/lda_faults.py`` against checks (a), (b) and (c)), collected
here as they are.  A file of its own: they take minutes, and the driver
deals whole files to its workers."""

from test_perf_generators import _cases_of

globals().update(_cases_of("test_lda_check.py", "checkout"))
