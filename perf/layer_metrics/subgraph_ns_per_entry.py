"""Device busy time of the traced blocks over the adjacency entries they
summed: colourings traced (the driver's count a block) times the
graph's directed entries, whatever share of the slots they are and
however many neighbour sums a colouring takes.  A driver without the
counts reads as nothing."""


def read(run):
    per_block = run.extra.get("colorings_per_block")
    entries = run.extra.get("adjacency_entries")
    t = run.trace
    if not per_block or not entries or not t or not t["busy_s"] \
            or not run.trace_blocks:
        return None
    return 1e9 * t["busy_s"] / (per_block * run.trace_blocks * entries
                                / run.chips)
