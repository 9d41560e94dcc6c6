"""The least time a chip could take for the traced blocks' rating
updates over the device time inside the MF-SGD Mosaic call alone."""


def read(run):
    t = run.trace
    if not run.least or not t or not t["class_s"].get("kernel"):
        return None
    return 100.0 * run.least["seconds"] / t["class_s"]["kernel"]
