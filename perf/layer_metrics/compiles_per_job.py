"""XLA backend compiles per job that were not loads from the persistent
cache (``flightrec.observe_compiles``); 0 once the cache is warm."""


def read(run):
    c = run.in_window
    return (c["compile_events"] - c["cache_hits"]) / len(run.block_s)
