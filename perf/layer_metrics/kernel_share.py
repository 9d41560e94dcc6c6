"""Device busy time in Mosaic (Pallas) calls (device trace)."""


def read(run):
    t = run.trace
    if not t or not t["busy_s"]:
        return None
    return 100.0 * t["class_s"].get("kernel", 0.0) / t["busy_s"]
