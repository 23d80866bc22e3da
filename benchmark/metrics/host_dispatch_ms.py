"""Mean host seconds to enqueue a step (``step_time.dispatch_s``): enqueue
cost, not device time."""


def read(run):
    ev = run.events("step_time")
    return 1e3 * sum(e["dispatch_s"] for e in ev) / len(ev) if ev else None
