"""Device busy time in ops that are neither a Mosaic call nor a
collective (device trace): the jitted XLA step programs."""


def read(run):
    t = run.trace
    if not t or not t["busy_s"]:
        return None
    return 100.0 * t["class_s"].get("xla", 0.0) / t["busy_s"]
