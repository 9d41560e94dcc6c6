"""The yardstick's own suite under the tier-1 floor: the cases of
``perf/tests/test_harness.py`` (every cell rehearsed at a tiny shape, the
shape of the last line, new cells as new files only),
``test_program_telemetry.py`` (the spans, skew records and counters of
the program that the per-layer metrics read), ``test_trace_reduce.py``
(trace -> numbers on the recorded trace) and ``test_scope_reduce.py``
(trace and the program's op map -> time by scope), collected here as they
are.
They are the tests that fail when a program PR renames what a metric
reads.  ``test_perf_lda_check.py`` holds the slow fourth file."""

import jax
import pytest

import mlp_faults
import subgraph_faults
from test_perf_generators import _cases_of

_CACHE_OPTIONS = ("jax_compilation_cache_dir",
                  "jax_persistent_cache_min_compile_time_secs",
                  "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture(autouse=True, scope="module")
def _compile_cache_as_perf_tests_have_it():
    """``tests/conftest.py`` sets ``JAX_COMPILATION_CACHE_DIR`` after jax
    is imported: this process then has no persistent cache, and the
    harness, which sees the variable, places none.  The job cell's
    per-job compiles would each be cold, where under ``perf/tests`` (and
    on the chip) they are cache hits.  So these cases run as they do
    there, the harness placing ``<checkout>/.jax_cache`` itself, and the
    process gets its settings back afterwards."""
    from jax.experimental.compilation_cache import compilation_cache

    before = {name: getattr(jax.config, name) for name in _CACHE_OPTIONS}
    with pytest.MonkeyPatch.context() as patch:
        patch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        yield
    for name, value in before.items():
        jax.config.update(name, value)
    compilation_cache.reset_cache()


_harness = _cases_of("test_harness.py", "checkout")
_telemetry = _cases_of("test_program_telemetry.py", "checkout")
# ``test_harness.py`` keys its toy shapes by driver name and has none for
# the "mlp" and "subgraph" drivers (PRs 36 and 38 may add files under
# perf/ and edit none): both instances of it get the entries before their
# cases run, the one loaded above and the bare ``test_harness`` that
# ``test_program_telemetry.py`` imports its helpers from
for _case in (_harness["test_cell_rehearses_untraced"],
              _telemetry["test_other_cells_print_none_of_the_six"]):
    _toy = _case.__globals__["_tiny"].__globals__["TINY"]
    _toy.setdefault("mlp", mlp_faults.TINY)
    _toy.setdefault("subgraph", subgraph_faults.TINY)
globals().update(_harness)
globals().update(_telemetry)
globals().update(_cases_of("test_trace_reduce.py"))
globals().update(_cases_of("test_scope_reduce.py", "traced_run"))
