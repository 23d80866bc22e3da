"""Mean seconds a step waited for its batch (``step_time.data_wait_s``)."""


def read(run):
    ev = run.events("step_time")
    return 1e3 * sum(e["data_wait_s"] for e in ev) / len(ev) if ev else None
