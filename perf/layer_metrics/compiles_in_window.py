"""XLA backend compiles inside the window that were not loads from the
persistent cache (``flightrec.observe_compiles``).  Must be 0."""


def read(run):
    c = run.in_window
    return c["compile_events"] - c["cache_hits"]
