"""Device busy time of the traced blocks over the optimizer steps they
ran (the trainer's ``steps_run`` counter, read by the driver around every
block): microseconds a step of ``batch_per_worker`` samples.  A program
without the counter reads as nothing."""


def read(run):
    steps = run.extra.get("optimizer_steps_per_block")
    t = run.trace
    if not steps or not t or not t["busy_s"] or not run.trace_blocks:
        return None
    return 1e6 * t["busy_s"] / (steps * run.trace_blocks)
