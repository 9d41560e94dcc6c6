"""Process start to the first measured block: data from the seed,
staging and partition, compiles or cache loads, the warm-up block."""


def read(run):
    return run.setup_s
