"""Executables a job loads again from the persistent compile cache
(``flightrec.observe_compiles`` cache hits) per job: what ``fit``
re-traces, re-lowers and fetches on every call."""


def read(run):
    return run.in_window["cache_hits"] / len(run.block_s)
