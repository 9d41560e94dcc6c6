"""Device time in collective ops over the traced window, mean over
chips (device trace).  Includes waiting on the slowest worker."""


def read(run):
    t = run.trace
    if not t or not t["window_s"]:
        return None
    return 100.0 * t["class_s"].get("collective", 0.0) / t["window_s"]
