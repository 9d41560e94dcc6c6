"""Dispatches plus blocking readbacks per block or job (``flightrec``
observers): host round trips the app driver makes."""


def read(run):
    c = run.in_window
    return (c["dispatches"] + c["readbacks"]) / len(run.block_s)
