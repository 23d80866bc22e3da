"""Device busy time per step, from the trace: busy union of the traced
window / steps dispatched in it."""


def read(run):
    n = run.traced_steps()
    return 1e3 * run.trace["busy_s"] / n if run.trace and n else None
