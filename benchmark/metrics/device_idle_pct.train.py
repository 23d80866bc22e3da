"""1 - busy union / traced window, steady steps."""


def read(run):
    t = run.trace
    return 100.0 * (1 - t["busy_s"] / t["window_s"]) if t else None
