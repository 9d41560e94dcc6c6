"""The least time a chip could take for the traced blocks' items
(``perf/workmodels.py`` over ``perf/peaks.json``) over the device busy
time of those blocks.  The ``info`` line says which wall bounds it."""


def read(run):
    if not run.least or not run.trace or not run.trace["busy_s"]:
        return None
    return 100.0 * run.least["seconds"] / run.trace["busy_s"]
